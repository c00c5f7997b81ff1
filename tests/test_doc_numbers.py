"""README.md, PARITY.md and docs/ state no speed of this system.

A speed is measured by ``benchmarks/run.py`` on the chip, recorded by the
driver in ``PERF_LEDGER.jsonl`` and stated in ``PERF.md``, which cites the
ledger by PR. The ledger is a rolling file the driver rewrites, so no test
pins a document's number to one of its lines; the documents carry no such
number at all and link to ``PERF.md``. What this file refuses, a paragraph
at a time:

- a rate: "<number> preds/s", "tokens/s", "req/s" and their spellings;
- a latency or a cost in time: "<number> ms", "µs", "us";
- a ratio: "2.08x", "~2x faster", "roughly the throughput of one".

One escape: a paragraph (or the paragraph that introduces it, as a list's
lead-in does) that says in words that its figures come from the CPU backend
and are not a speed ("CPU" and "not a speed"): a setting given in
milliseconds, a sample of an output format, a count beside its unit.
"""

import re
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent

DOC_FILES = [REPO / "README.md", REPO / "PARITY.md", *sorted((REPO / "docs").rglob("*.md"))]

# "12,888.09 preds/s", "10,000 predictions/sec", "~1,234 tokens/s", "8 req/s";
# the lookbehind keeps digits glued to words ("ResNet50 preds/s") from matching
_CLAIM = re.compile(
    r"(?<![A-Za-z\d,.])(?P<num>\d[\d,]*(?:\.\d+)?)\s*(?:aggregate\s+)?"
    r"(?:preds|predictions|tokens|tok|reqs?|requests?|rpcs?)\s*(?:/|\s+per\s+)\s*s(?:ec(?:ond)?)?\b",
    re.IGNORECASE,
)

# ratio-shaped perf claims: "2.08x", "10.3×", "~2x", and prose ratios like
# "roughly the throughput of one". Word-boundary design: the x/× must NOT be
# followed by a digit (that's a shape like 224x224) or a letter (that's a
# count like 3×ResNet50).
_RATIO_CLAIM = re.compile(
    r"(?<![\dx×.])(?:~\s*)?\d+(?:\.\d+)?\s*[x×](?![\dx×A-Za-z])"
)
_RATIO_PHRASES = (
    "roughly the throughput of",
    "at the throughput of",
    "for the price of one",
    "models for the price",
    "times faster",
)

# "41.8 ms", "~80 us/request", "2.5-5 µs/round"
_TIME_CLAIM = re.compile(r"(?<![A-Za-z\d,.])\d[\d,]*(?:\.\d+)?\s*(?:ms|µs|us)\b")


def _says_cpu_count(para: str) -> bool:
    low = para.lower()
    return "cpu" in low and "not a speed" in low


def speed_claims(text: str) -> list[str]:
    """Every speed a document states outside the escape, as
    "<what matched>: ...<the paragraph's start>..."."""
    found = []
    paras = re.split(r"\n\s*\n", text)
    for i, para in enumerate(paras):
        if _says_cpu_count(para) or (i and _says_cpu_count(paras[i - 1])):
            continue
        hits = [m.group(0) for rx in (_CLAIM, _RATIO_CLAIM, _TIME_CLAIM) for m in rx.finditer(para)]
        hits += [p for p in _RATIO_PHRASES if p in para.lower()]
        found += [f"'{h.strip()}': ...{' '.join(para.split())[:120]}..." for h in hits]
    return found


@pytest.mark.parametrize("doc", DOC_FILES, ids=lambda p: str(p.relative_to(REPO)))
def test_document_states_no_speed(doc):
    claims = speed_claims(doc.read_text())
    assert not claims, (
        f"{doc.relative_to(REPO)} states a speed; PERF.md is where one is stated, from the ledger:\n"
        + "\n".join(claims)
    )


def test_ratio_claim_regex_shapes():
    """The ratio matcher must hit perf ratios and skip tensor shapes,
    model counts and hex-ish tokens."""
    hits = ["2.08x", "10.3× the per-chip share", "~2x faster", "speedup 1.39x"]
    misses = ["224x224x3 image", "3×ResNet50 combiner", "8x128 tile", "0x1f", "x-npy"]
    for s in hits:
        assert _RATIO_CLAIM.search(s), f"should match: {s}"
    for s in misses:
        assert not _RATIO_CLAIM.search(s), f"should NOT match: {s}"


def test_doc_number_checker_catches_fabrication(tmp_path):
    """The checker itself must refuse a planted speed in each of its three
    shapes, admit the same sentence under the escape, and leave settings
    that are not times alone."""
    doc = tmp_path / "page.md"
    doc.write_text(
        "# A page\n\nThe scheduler serves 1,234 tokens/s on one chip.\n\n"
        "Warm TTFT p50 is 59.7 ms, 2.76x under the cold one.\n\n"
        "`deadline_ms: 50` bounds a request; the ring holds 8,192 frames of a 224x224 input.\n"
    )
    claims = speed_claims(doc.read_text())
    assert [c.split(":")[0] for c in claims] == ["'1,234 tokens/s'", "'2.76x'", "'59.7 ms'"]
    doc.write_text(
        "Counts from a run on the CPU backend, not a speed: 1,234 tokens/s over 40 rounds.\n\n"
        "- the list it introduces: 12 ms\n\n"
        "A paragraph further on: 12 ms.\n"
    )
    assert [c.split(":")[0] for c in speed_claims(doc.read_text())] == ["'12 ms'"]
