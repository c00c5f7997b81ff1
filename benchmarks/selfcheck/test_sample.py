"""The sampler's two metrics: ``sample_device_ms`` reads the ``sample`` scope
of the kept chip trace; ``sampled_rounds_pct`` reads the frames' own count:
None where the program has none (PR 35's parent), 0 where every round was
greedy, else the share of rounds in which a row drew."""

import json
import os

import pytest
from conftest import BENCH, ROOT

from harness import cells
from harness import scopes as sc


def _reader(name):
    """The metric's reader as run.py loads it."""
    return cells.load_module(ROOT, cells.load_bench(ROOT), "layer_metrics", name).read


def test_sample_device_ms_reads_the_sample_scope_of_the_kept_trace():
    with open(os.path.join(BENCH, "harness", "fixtures", "trace_scoped.json")) as f:
        kept = json.load(f)
    events, want = sc.expanded(kept["events"]), kept["expected"]["step"]
    bare = {"devices": {p: {"ops": [o[:3] + [""] for o in d["ops"]], "modules": d["modules"]}
                        for p, d in events["devices"].items()}, "host": events["host"], "op_name_stat": None}
    o = {"trace": {"families": {}}}
    real = sc.newest_xplane, sc.read_scoped
    try:
        # the ops under the scope as recorded, and the compiler's waits for them (0.12 us)
        ms = 1e3 * want["by_scope"]["sample"] / want["dispatches"]
        for ev, rel in ((events, 1e-3), (bare, None)):
            sc._of_file.cache_clear()
            sc.newest_xplane, sc.read_scoped = (lambda d: "x"), (lambda p, ev=ev: ev)
            got = _reader("sample_device_ms")(o)
            # the recorded step's sampler: a vocabulary sort for 16 greedy rows, 0.98 ms
            assert got is None if rel is None else ms <= got == pytest.approx(ms, rel=rel) and 0.9 < got < 1.1
        assert _reader("sample_device_ms")({"trace": None}) is None  # an untraced run
    finally:
        sc.newest_xplane, sc.read_scoped = real
        sc._of_file.cache_clear()


class Old:
    """A frame of a program without the counter."""

    tokens = 16


class Frame(Old):
    def __init__(self, rows, topk=0):
        self.sample_rows, self.sample_topk_rows = rows, topk


@pytest.mark.parametrize("frames, want", [
    (None, None),
    ([], None),
    ([Old(), Old()], None),
    ([Frame(0), Frame(0), Frame(0)], 0.0),  # every request greedy
    ([Frame(0), Frame(3), Frame(16, 4), Frame(0)], 50.0),
    ([Frame(64, 64)] * 5, 100.0),
], ids=["no_frames", "empty", "no_counter", "all_greedy", "half", "every_round"])
def test_sampled_rounds_pct(frames, want):
    got = _reader("sampled_rounds_pct")({"frames": frames})
    assert got is None if want is None else got == pytest.approx(want)
