"""``gqa_chunk_kernel_pct`` reads the frames' own counters: None where the
program has none, 0 where every chunk gathers (PR 52's parent), else the
prefilling rows whose attention ran in ops/gqa_decode.py's chunk kernel over
the prefilling rows, summed over the window's rounds that ran a chunk
dispatch. Its ``BENCHMARK.json`` entry is found by NAME."""

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
    ([Frame(4, 4), Frame(0, 0), Frame(3, 3), Frame(1, 1)], 100.0),
    ([Frame(2, 0), Frame(4, 0)], 0.0),  # the gather ran: the parent, the CPU backend, the int8 pool, a mesh
    ([Frame(2, 2), Frame(4, 0), Frame(2, 2)], 50.0),  # one entry of the ladder kept the gather
    ([Old(1), Frame(1, 1)], None),
], ids=["no_frames", "empty", "no_counter", "no_chunk_round", "every_row", "the_gather", "one_entry_gathers", "mixed"])
def test_gqa_chunk_kernel_pct(frames, want):
    bench = cells.load_bench(ROOT)
    got = cells.load_module(ROOT, bench, "layer_metrics", "gqa_chunk_kernel_pct").read({"frames": frames})
    assert got is None if want is None else got == pytest.approx(want)


def test_the_metric_is_listed_by_name_for_the_grouped_query_cells_alone():
    """The entry is looked up by its NAME, and its cells are named here: the
    five whose families read a two-plane grouped-query pool, each of which
    exists and reports the end-to-end metric it moves; the latent cells'
    twin keeps its own two."""
    bench = cells.load_bench(ROOT)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert len(by_name) == len(bench["per_layer"])
    entry = by_name["gqa_chunk_kernel_pct"]
    assert entry == {
        "name": "gqa_chunk_kernel_pct", "unit": "%", "better": "higher", "source": "program_counter",
        "layer": "kernels", "moves": "itl_p95_ms",
        "workloads": [
            "mellum2-12b-a2.5b.repo-context-closed", "granite-4.0-h-micro.chat-closed-64",
            "lfm2-24b-a2b.agent-context-closed-64", "laguna-s-2.1.repo-session-closed-64",
            "nemotron-3-nano-30b-a3b.agent-context-closed-64",
        ],
    }
    cells_by_name = {w["name"]: w for w in bench["workloads"]}
    families = {"mellum2-12b-a2.5b", "granite-4.0-h-micro", "lfm2-24b-a2b", "laguna-s-2.1", "nemotron-3-nano-30b-a3b"}
    assert {cells_by_name[w]["config"] for w in entry["workloads"]} == families
    moved = next(m for m in bench["end_to_end"] if m["name"] == entry["moves"])
    assert all(w in moved.get("workloads", entry["workloads"]) for w in entry["workloads"])
    assert not set(entry["workloads"]) & set(by_name["mla_chunk_kernel_pct"]["workloads"])
    assert os.path.exists(os.path.join(BENCH, "layer_metrics", "gqa_chunk_kernel_pct.py"))
