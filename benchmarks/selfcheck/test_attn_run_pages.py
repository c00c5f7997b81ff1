"""What PR 48 adds to the benchmark, checked without the program: the
``attn_run_pages_pct`` reader over frame fixtures with and without the two
counters, and its entry. Names are pinned, positions are not."""

import os
import types

from conftest import BENCH, ROOT

from harness import cells

NAME = "attn_run_pages_pct"
CELLS = {"mellum2-12b-a2.5b.repo-context-closed", "granite-4.0-h-micro.chat-closed-64",
         "lfm2-24b-a2b.agent-context-closed-64", "laguna-s-2.1.repo-session-closed-64"}


def _frame(**kw):
    return types.SimpleNamespace(mode="plain", busy_ns=(0, 1), **kw)


def _read(frames):
    bench = cells.load_bench(ROOT)
    return cells.load_module(ROOT, bench, "layer_metrics", NAME).read({"frames": frames})


def test_the_share_is_run_pages_over_pages_read_over_the_rounds_that_stepped():
    stepped = [_frame(attn_pages_read=100, attn_pages_table=464, attn_run_pages=80),
               _frame(attn_pages_read=300, attn_pages_table=464, attn_run_pages=120)]
    chunk_only = _frame(attn_pages_read=0, attn_pages_table=0, attn_run_pages=0)  # no step ran: nothing was read
    assert _read(stepped + [chunk_only]) == 100.0 * 200 / 400
    # the step gathers (the CPU backend, a pool Mosaic cannot tile, a family's parent): every page read, none in a run
    assert _read([_frame(attn_pages_read=464, attn_pages_table=464, attn_run_pages=0)]) == 0.0


def test_a_program_that_counts_neither_gives_none():
    assert _read([]) is None and _read(None) is None
    assert _read([chunk for chunk in [_frame(attn_pages_read=0, attn_pages_table=0, attn_run_pages=0)]]) is None
    assert _read([_frame()]) is None  # frames without the fields: the parent of PR 29
    assert _read([_frame(attn_pages_read=464, attn_pages_table=464)]) is None  # pages read but no run count: PR 42's parent


def test_the_entry_lists_the_four_cells_whose_step_can_take_the_kernel():
    bench = cells.load_bench(ROOT)
    entry = next(m for m in bench["per_layer"] if m["name"] == NAME)
    assert set(entry["workloads"]) == CELLS and len(entry["workloads"]) == 4
    assert (entry["unit"], entry["better"], entry["source"], entry["layer"], entry["moves"]) == (
        "%", "higher", "program_counter", "kernels", "itl_p95_ms")
    assert os.path.exists(os.path.join(BENCH, "layer_metrics", NAME + ".py"))
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for cell in CELLS:  # each reports the end-to-end metric the share should move
        assert "workloads" not in e2e["itl_p95_ms"] or cell in e2e["itl_p95_ms"]["workloads"]
    for cell in bench["workloads"]:
        listed = NAME in {m["name"] for m in cells.cell_metrics(bench, cell, "per_layer")}
        assert listed == (cell["name"] in CELLS)
