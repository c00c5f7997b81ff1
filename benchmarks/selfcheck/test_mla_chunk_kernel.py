"""``mla_chunk_kernel_pct`` reads the frames' own counters: None where the
program has none (PR 45's parent), else the prefilling rows whose attention
ran in the chunk kernel over the prefilling rows, summed over the window's
rounds that ran a chunk dispatch."""

import json
import os

import pytest
from conftest import BENCH, ROOT
from harness import cells


class Old:
    """A round's frame of a program without the counter."""

    def __init__(self, live):
        self.chunk_rows, self.chunk_rows_live = 2 * ((live + 1) // 2), live


class Frame(Old):
    def __init__(self, live, kernel):
        super().__init__(live)
        self.chunk_rows_kernel = kernel


@pytest.mark.parametrize("frames, want", [
    (None, None),
    ([], None),
    ([Old(2), Old(1)], None),
    ([Frame(0, 0), Frame(0, 0)], None),  # step-only rounds
    ([Frame(2, 2), Frame(0, 0), Frame(3, 3), Frame(1, 1)], 100.0),
    ([Frame(2, 0), Frame(4, 0)], 0.0),  # the walk ran: the CPU backend, a mesh, an untileable plane
    ([Frame(2, 2), Frame(4, 0), Frame(2, 2)], 50.0),  # one entry of the ladder kept the walk
    ([Old(1), Frame(1, 1)], None),
], ids=["no_frames", "empty", "no_counter", "no_chunk_round", "every_row", "the_walk", "one_entry_walks", "mixed"])
def test_mla_chunk_kernel_pct(frames, want):
    bench = cells.load_bench(ROOT)
    got = cells.load_module(ROOT, bench, "layer_metrics", "mla_chunk_kernel_pct").read({"frames": frames})
    assert got is None if want is None else got == pytest.approx(want)


def test_the_metric_is_listed_for_the_latent_cells_alone():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = [m["name"] for m in bench["per_layer"]]
    assert names.index("mla_chunk_kernel_pct") > names.index("chunk_rows_held_pct")  # appended after PR 44's last
    entry = bench["per_layer"][names.index("mla_chunk_kernel_pct")]
    chunk_ms = next(m for m in bench["per_layer"] if m["name"] == "mla_chunk_device_ms")
    assert entry["workloads"] == chunk_ms["workloads"] == ["a.x-k1.doc-qa-closed-64", "xing4.0-29b-a4b.agent-context-closed-64"]
    assert (entry["source"], entry["better"], entry["layer"], entry["moves"], entry["unit"]) == (
        "program_counter", "higher", "kernels", "itl_p95_ms", "%")
    assert os.path.exists(os.path.join(BENCH, "layer_metrics", "mla_chunk_kernel_pct.py"))
