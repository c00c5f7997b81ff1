"""The multi-stream residual path's NESTED device scopes, from the same trace,
with the bytes each op moved.

A latent-attention decoder with ``hc_mult`` > 1 (``models/mla_decoder.py``,
``ops/mhc.py``) wraps every attention and feed-forward block in three scopes,
each nested under one of the nine names ``harness/scopes.py`` reads, so the
old readers still see the time: ``qkv/mhc_map`` and ``mlp/mhc_map`` (the sum
of squares, the three products, sigmoid, ``exp``, the Sinkhorn loop and the
frames' canary), ``qkv/mhc_pre`` and ``mlp/mhc_pre`` (the mixture a block
reads), ``attn_out/mhc_post`` and ``mlp/mhc_post`` (the block's output
written back beside the stream mix). This file reads those three names, in
whole dispatches of the step or of the chunk, with the self-time rule of
``harness/scopes.py`` (an op inside the Sinkhorn ``while`` is its own time,
the ``while`` what is left), and beside each op's time the ``bytes_accessed``
its event metadata carries on the TPU (what the compiler's cost analysis says
the op read + wrote). The chunk's dispatches are those of the slice's most
frequent ``(rows, c)`` entry, by the ``decode.dispatch.chunk`` annotation a
module lies in. A program without the names (``hc_mult`` 1, the other
families, the parent of PR 43) gives None.
"""

from __future__ import annotations

import bisect
import functools

from harness import scopes as sc
from harness.trace import TRACE_DIR, WINDOW, _short, newest_xplane

NAMES = ("mhc_map", "mhc_pre", "mhc_post")
STEP_MARK, CHUNK_MARK = "fused_step", "fused_chunk"
BYTES_STAT = "bytes_accessed"
CHUNK_ANNOTATION = sc.ANN_PREFIX + "dispatch.chunk"


def read_events(path: str) -> dict:
    """``scopes.read_scoped``'s events with a fifth number an op: the
    ``bytes_accessed`` of its event metadata, 0 where it carries none."""
    from harness.xplane import read_planes

    events = sc.read_scoped(path)
    for plane in read_planes(path, lambda n: n in events["devices"]):
        meta = plane["metadata"]
        ops = []
        for line in plane["lines"]:
            if line["name"] != "XLA Ops":
                continue
            for mid, start, dur, _stats in line["events"]:
                m = meta.get(mid, {"name": "", "stats": {}})
                ops.append([_short(m["name"]), start, dur, str(m["stats"].get(sc.OP_NAME_STAT, "")),
                            float(m["stats"].get(BYTES_STAT) or 0)])
        events["devices"][plane["name"]]["ops"] = ops
    return events


def nested_key(op_name: str) -> str | None:
    """The innermost of NAMES on an op's path; None for an op under none."""
    return next((p for p in reversed(op_name.rstrip(":").split("/")) if p in NAMES), None)


def _entry_of(events: dict, a: float, b: float) -> tuple | None:
    """The ``(rows, c)`` of the chunk dispatch annotation a module [a, b] lies in."""
    mid = (a + b) / 2
    for e in events["host"]:
        if e[0] == CHUNK_ANNOTATION and e[1] <= mid <= e[1] + e[2]:
            try:
                return int(e[4]["rows"]), int(e[4]["c"])
            except (KeyError, TypeError, ValueError):
                return None
    return None


def by_nested(events: dict, mark: str) -> dict | None:
    """Op self time and bytes inside whole ``mark`` dispatches of the slice,
    by nested key. For the chunk, the dispatches of the slice's most frequent
    entry alone (all of them where no annotation names an entry). None where
    the slice holds no such dispatch or no op of it carries one of the
    ``mhc_*`` names. Keys: ``dispatches``, ``entry`` ((rows, c) or None),
    ``by`` {key: s}, ``bytes`` {key: bytes} (absent ops' bytes count 0)."""
    win = [e for e in events["host"] if e[0] == WINDOW]
    if not win:
        return None
    t0, t1 = win[0][1], win[0][1] + win[0][2]
    dispatches, by, moved, entry = 0, {}, {}, None
    for plane in sorted(events["devices"]):
        dev = events["devices"][plane]
        mods = sorted((s, s + d) for name, s, d in dev["modules"] if mark in name and t0 <= s and s + d <= t1)
        if mark == CHUNK_MARK and mods:
            named = [_entry_of(events, a, b) for a, b in mods]
            seen = [n for n in named if n is not None]
            if seen:
                entry = max(sorted(set(seen)), key=seen.count)
                mods = [m for m, n in zip(mods, named) if n == entry]
        begins = [a for a, _ in mods]
        inside: list[list] = [[] for _ in mods]
        nbytes: list[dict] = [{} for _ in mods]
        for op in dev["ops"]:
            label, s, d, op_name = op[:4]
            i = bisect.bisect_right(begins, s) - 1
            if i >= 0 and s + d <= mods[i][1] + 1e-9:
                inside[i].append((s, -d, d, op_name, label))
                key = nested_key(op_name)
                if key is not None and len(op) > 4 and label.split(" ", 1)[0] != "while":  # a loop's bytes are its body's ops'
                    nbytes[i][key] = nbytes[i].get(key, 0.0) + op[4]
        if not any(inside):
            continue
        dispatches += len(mods)
        for ops, nb in zip(inside, nbytes):
            for _start, own, op_name, _label in sc._self_times(ops):
                key = nested_key(op_name)
                if key is not None:
                    by[key] = by.get(key, 0.0) + own
            for key, v in nb.items():
                moved[key] = moved.get(key, 0.0) + v
    if not dispatches or not by:
        return None
    return {"dispatches": dispatches, "entry": entry, "by": by, "bytes": moved}


@functools.lru_cache(maxsize=1)
def _of_file(path: str) -> dict:
    events = read_events(path)
    return {"step": by_nested(events, STEP_MARK), "chunk": by_nested(events, CHUNK_MARK)}


def of_run(o: dict, program: str) -> dict | None:
    """``by_nested`` of this run's ``program`` ("step" / "chunk"); None on
    an untraced run or where the names were not found."""
    if not o.get("trace"):
        return None
    return _of_file(newest_xplane(TRACE_DIR))[program]


def nested_ms(o: dict, program: str) -> float | None:
    """Device time per ``program`` dispatch in ops under the three names, ms."""
    r = of_run(o, program)
    return 1e3 * sum(r["by"].values()) / r["dispatches"] if r else None


def blocks(o: dict) -> int:
    """Blocks a dispatch wraps: an attention and a feed-forward block a layer."""
    return 2 * int(o["geometry"]["layers"])


def streams(o: dict) -> int:
    return int(o["config"].get("hc_mult", 1))
