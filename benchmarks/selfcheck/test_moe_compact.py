"""What PR 49 adds to the benchmark, checked without the program: the
``moe_compact_pct`` reader over frame fixtures with and without the two
counters, and its entry. Names are pinned, positions are not."""

import os
import types

from conftest import BENCH, ROOT

from harness import cells

NAME = "moe_compact_pct"
CELLS = {"laguna-s-2.1.repo-session-closed-64", "lfm2-24b-a2b.agent-context-closed-64",
         "xing4.0-29b-a4b.agent-context-closed-64"}


def _frame(**kw):
    return types.SimpleNamespace(mode="plain", busy_ns=(0, 1), **kw)


def _read(frames):
    bench = cells.load_bench(ROOT)
    return cells.load_module(ROOT, bench, "layer_metrics", NAME).read({"frames": frames})


def test_the_share_is_compact_calls_over_grouped_calls_over_the_rounds_that_ran_a_wide_chunk():
    wide = [_frame(moe_grouped_calls=7, moe_compact_calls=7), _frame(moe_grouped_calls=7, moe_compact_calls=7)]
    step_only = _frame(moe_grouped_calls=0, moe_compact_calls=0)  # 64 rows: the masked form, nothing to compact
    assert _read(wide + [step_only]) == 100.0
    # one layer's routing overflowed the capacity in one round: that call ran a second block
    assert _read(wide + [_frame(moe_grouped_calls=7, moe_compact_calls=6)]) == 100.0 * 20 / 21
    assert _read([_frame(moe_grouped_calls=14, moe_compact_calls=0)]) == 0.0


def test_no_grouped_call_over_a_share_and_a_program_that_does_not_count_give_none():
    assert _read([]) is None and _read(None) is None
    assert _read([_frame()]) is None  # frames without the fields: the parent of PR 49
    # a configuration that holds all its experts (and a window of narrow chunks) counts none of either
    assert _read([_frame(moe_grouped_calls=0, moe_compact_calls=0)] * 3) is None


def test_the_entry_lists_the_three_cells_whose_chunks_run_the_grouped_form_over_a_share():
    bench = cells.load_bench(ROOT)
    entry = next(m for m in bench["per_layer"] if m["name"] == NAME)
    assert set(entry["workloads"]) == CELLS and len(entry["workloads"]) == 3
    assert (entry["unit"], entry["better"], entry["source"], entry["layer"], entry["moves"]) == (
        "%", "higher", "program_counter", "kernels", "tokens_per_s")
    assert os.path.exists(os.path.join(BENCH, "layer_metrics", NAME + ".py"))
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for cell in CELLS:  # each reports the end-to-end metric the share should move
        assert cell in e2e["tokens_per_s"]["workloads"]
    for cell in bench["workloads"]:
        listed = NAME in {m["name"] for m in cells.cell_metrics(bench, cell, "per_layer")}
        assert listed == (cell["name"] in CELLS)
