"""``chunk_rows_held_pct`` reads the frames' own counters: None where the
program has none (PR 44's parent), else the slots left for a later round over
the slots that had a chunk to run, summed over the window's rounds that ran a
chunk dispatch."""

import json
import os

import pytest
from conftest import BENCH, ROOT
from harness import cells


class Old:
    """A chunk round's frame of a program without the counter."""

    def __init__(self, rows, live):
        self.chunk_rows, self.chunk_rows_live = rows, live


class Frame(Old):
    def __init__(self, rows, live, held):
        super().__init__(rows, live)
        self.chunk_rows_held = held


@pytest.mark.parametrize("frames, want", [
    (None, None),
    ([], None),
    ([Old(64, 5), Old(2, 1)], None),
    ([Frame(0, 0, 0), Frame(0, 0, 0)], None),  # step-only rounds
    ([Frame(2, 1, 0), Frame(0, 0, 0), Frame(4, 3, 0)], 0.0),
    ([Frame(4, 4, 2), Frame(2, 2, 0), Frame(2, 2, 0), Frame(0, 0, 0)], 100 * 2 / 10),  # a wave of six: 4, then 2
    ([Old(2, 1), Frame(2, 1, 0)], None),
], ids=["no_frames", "empty", "no_counter", "no_chunk_round", "none_held", "a_wave_of_six", "mixed"])
def test_chunk_rows_held_pct(frames, want):
    bench = cells.load_bench(ROOT)
    got = cells.load_module(ROOT, bench, "layer_metrics", "chunk_rows_held_pct").read({"frames": frames})
    assert got is None if want is None else got == pytest.approx(want)


def test_the_metric_is_listed_for_the_cells_that_report_what_it_moves():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(m for m in bench["per_layer"] if m["name"] == "chunk_rows_held_pct")
    moved = next(m for m in bench["end_to_end"] if m["name"] == entry["moves"])
    assert entry["moves"] == "tokens_per_s" and entry["workloads"] == moved["workloads"]
    assert (entry["source"], entry["better"], entry["layer"]) == ("program_counter", "lower", "decode scheduler")
    assert os.path.exists(os.path.join(BENCH, "layer_metrics", "chunk_rows_held_pct.py"))
