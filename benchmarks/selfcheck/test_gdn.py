"""What PR 57 adds to the benchmark, checked without the program: the
``qwen3-next-80b-a3b`` configuration's file against the catalog's row, the
cell's place on the traffic file three configurations already ride, the
delta rule's and the step's operation and byte counts, the frames' and the
nested scopes' readers (by hand, and on a piece of a recorded chip trace,
``harness/fixtures/trace_gdn.json``). Names are pinned, positions are not."""

import json
import os
import types

import pytest
from conftest import BENCH, ROOT

from harness import cells, opsbytes_gdn, scopes, scopes_gdn, scopes_moe, scopes_ssm_moe, scopes_win

CONFIG = "qwen3-next-80b-a3b"
CELL = CONFIG + ".agent-context-closed-64"
NEW = ("gdn_device_ms", "gdn_chunk_device_ms", "gdn_scan_roofline", "gdn_chunk_roofline", "step_roofline.gdn_moe",
       "moe_held_device_ms.gdn_moe", "moe_held_chunk_device_ms.gdn_moe", "moe_held_hit_pct.gdn_moe",
       "shared_expert_device_ms.gdn_moe")
READ_AS_THEY_STAND = ("ssm_state_restore_pct", "attn_run_pages_pct", "gqa_chunk_kernel_pct", "moe_compact_pct",
                      "chunk_rows_held_pct", "step_device_ms", "chunk_device_ms", "kv_gather_device_ms", "attn_device_ms")
GEOMETRY = {"hidden": 2048, "layers": 24, "ffn": 512, "vocab": 18992}
SCAN = {"gdn_layers": 18, "key_heads": 16, "value_heads": 32, "key_dim": 128, "value_dim": 128, "conv": 4}


@pytest.fixture(scope="module")
def found():
    return cells.resolve(ROOT, CELL)


def _o(found, **kw):
    return {"config": found["config"], "geometry": GEOMETRY, "traffic": found["traffic"], **kw}


def test_configuration_file_is_the_catalogs_row_but_for_the_three_cuts(found):
    c = found["config"]
    assert c["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert c["published"] == {"num_hidden_layers": 48, "num_experts": 512, "vocab_size": 151936}
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"]) == (24, 32, 18992)
    # the floors of a cut: whole periods, 8 experts or more, an eighth of the vocabulary
    assert c["num_hidden_layers"] % c["full_attention_interval"] == 0 and c["num_hidden_layers"] >= 24
    assert c["num_experts"] >= 8 and c["vocab_size"] * 8 >= c["published"]["vocab_size"]
    assert (c["share"]["chips"], c["share"]["stages"], c["share"]["experts_held"], c["share"]["first_expert"]) == (16, 2, 32, 0)
    entry = next(e for e in found["bench"]["configs"] if e["name"] == CONFIG)
    assert entry["reduced"] == c["reduced"] and entry["source"] == c["source"] and entry["file"].endswith(CONFIG + ".json")
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
        assert c["source"] == row["source_url"]
        assert {k for k in row["config"] if c[k] != row["config"][k]} == set(c["reduced"])  # no width touched
        assert all(row["config"][k] == v for k, v in c["published"].items())
    unit = {p["name"]: p["value"] for p in c["deployment"]["spec"]["predictors"][0]["graph"]["parameters"]}
    assert unit["model"] == "hybrid_decoder"  # one family: the third, in its third shape
    ints = {"hidden": "hidden_size", "layers": "num_hidden_layers", "heads": "num_attention_heads",
            "kv_heads": "num_key_value_heads", "head_dim": "head_dim", "gdn_key_heads": "linear_num_key_heads",
            "gdn_value_heads": "linear_num_value_heads", "gdn_key_dim": "linear_key_head_dim",
            "gdn_value_dim": "linear_value_head_dim", "ssm_conv": "linear_conv_kernel_dim", "ffn": "moe_intermediate_size",
            "shared_ffn": "shared_expert_intermediate_size", "experts_held": "num_experts",
            "experts_per_tok": "num_experts_per_tok", "vocab": "vocab_size", "max_len": "max_position_embeddings"}
    assert {k: int(unit[k]) for k in ints} == {k: c[v] for k, v in ints.items()}
    assert {k: int(unit[k]) for k in GEOMETRY} == GEOMETRY  # what harness/deploy.py reads
    assert int(unit["experts"]) == c["published"]["num_experts"]  # the router keeps its width
    every = c["full_attention_interval"]
    assert unit["attn_layers"] == "".join("G" if (i + 1) % every == 0 else "D" for i in range(24))  # a tree before PR 57 refuses it
    assert (float(unit["rope_theta"]), float(unit["rotary"]), float(unit["rms_eps"]), unit["untied"]) == (
        c["rope_theta"], c["partial_rotary_factor"], c["rms_norm_eps"], "true")
    assert c["tie_word_embeddings"] is False and c["norm_topk_prob"] is True and c["mlp_only_layers"] == []
    assert [float(unit[k]) for k in ("embedding_multiplier", "residual_multiplier", "logits_scaling")] == [1.0] * 3
    assert float(unit["attention_multiplier"]) == c["head_dim"] ** -0.5
    tpu = c["deployment"]["spec"]["predictors"][0]["tpu"]
    assert set(tpu) == {"max_batch", "batch_buckets", "dtype", "decode_slots", "decode_prefix_slots",
                        "decode_prefill_chunk", "decode_kv_page_size", "decode_kv_pages"}  # no new key
    per_slot = -(-(int(unit["seq"]) + int(unit["max_new_tokens"])) // tpu["decode_kv_page_size"])
    shared = found["traffic"]["shared_prefix_len"] // tpu["decode_kv_page_size"]
    assert (per_slot, shared) == (144, 64)
    assert tpu["decode_kv_pages"] >= shared + tpu["decode_slots"] * per_slot + 1
    assert tpu["dtype"] == unit["param_dtype"] == "bfloat16" and c["reference"]["n_head"] == c["num_attention_heads"]
    assert int(unit["seq"]) == found["traffic"]["prompt_len"] and tpu["decode_slots"] == found["traffic"]["clients"]
    assert any("interleaved by key head" in d for d in c["departures"])  # the stored order is said
    for key in ("stands_for", "assumed", "departures", "sizing"):
        assert c[key]


def test_the_cell_rides_the_traffic_file_three_configurations_ride(found):
    bench = found["bench"]
    mine = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert mine["traffic"] == "agent-context-closed-64" and mine["chips"] == 1 and mine["config"] == CONFIG
    assert "16x" in mine["why"]  # attention and the mixers see sixteen times their share: said where the driver reads it
    riders = {w["config"] for w in bench["workloads"] if w["traffic"] == mine["traffic"]}
    assert {"lfm2-24b-a2b", "xing4.0-29b-a4b", "nemotron-3-nano-30b-a3b", CONFIG} <= riders
    assert not os.path.exists(os.path.join(BENCH, "traffic", CELL + ".json"))  # no traffic file of its own
    assert os.path.exists(os.path.join(BENCH, "reference", CONFIG + ".py"))


def test_new_metrics_list_only_the_new_cell_and_the_lists_that_read_it_hold_it_once(found):
    bench = found["bench"]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL] and by_name[name]["layer"] == "kernels"
        assert by_name[name]["moves"] == "itl_p95_ms"
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", name + ".py"))
    for name in READ_AS_THEY_STAND:
        assert by_name[name]["workloads"].count(CELL) == 1, name
    for name in ("ssm_device_ms", "ssm_chunk_device_ms", "ssm_scan_roofline.ssm_moe", "step_roofline.ssm_moe",
                 "moe_held_device_ms.ssm_moe", "shared_expert_device_ms.ssm_moe", "conv_device_ms", "mla_device_ms",
                 "step_roofline"):
        assert CELL not in by_name[name]["workloads"], name  # other scopes, counts or key names
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL in e2e["tokens_per_s"]["workloads"] and CELL in e2e["itl_p95_ms"]["workloads"]
    assert CELL not in e2e["itl_p95_closed_ms"]["workloads"]  # its bound is the first cell's


# ------------------------------------------------------- counts and readers


def test_the_sizes_come_from_this_configurations_keys(found):
    p = scopes_gdn.published(_o(found))
    assert p == {"hidden": 2048, "vocab": 18992, "gdn_layers": 18, "attn_layers": 6, "expert_layers": 24, "heads": 16,
                 "kv_heads": 2, "head_dim": 256, "key_heads": 16, "value_heads": 32, "key_dim": 128, "value_dim": 128,
                 "conv": 4, "ffn": 512, "shared_ffn": 512, "experts": 512, "held": 32, "per_tok": 10}
    assert scopes_gdn.scan_sizes(p) == SCAN
    other = cells.resolve(ROOT, "nemotron-3-nano-30b-a3b.agent-context-closed-64")
    assert scopes_gdn.published({"config": other["config"], "geometry": GEOMETRY}) is None  # another shape's keys
    assert scopes_ssm_moe.published(_o(found)) is None  # and the second shape's readers do not take this one


def test_the_issues_bytes_come_out_of_the_counts(found):
    flops, nbytes = opsbytes_gdn.gdn_scan(rows=64, **SCAN)
    state, width = 32 * 128 * 128, 2 * 2048 + 4096
    # a row's state read once and written once, its conv cache written, q | k | v read: ISSUE 57's 4.83 GB of state
    assert nbytes == 64 * 18 * (2 * state + 4 * width) * 4 and 64 * 18 * 2 * state * 4 == pytest.approx(4.83e9, rel=0.01)
    assert flops == 7.0 * state * 64 * 18 and flops / 197e12 < nbytes / 819e9  # the bytes bind
    p = scopes_gdn.published(_o(found))
    sizes = {k: v for k, v in p.items() if k not in ("held", "per_tok")}
    hit, picks = 24 * 32, 64 * 10 * 24 / 16
    flops, nbytes = opsbytes_gdn.gdn_moe_step(**sizes, rows=64, ctx_tokens=64 * 2176, experts_hit=hit, local_picks=picks)
    # ISSUE 57's reckoning: 4.83 (state) + 5.03 (expert layers) + 1.54 (mixers) + 1.7 (K/V) + 0.08 (head) = 13.2 GB a step
    assert nbytes == pytest.approx(13.2e9, rel=0.04)
    assert opsbytes_gdn.least_seconds("TPU v5 lite", flops, nbytes) == pytest.approx(nbytes / 819e9)
    fewer = opsbytes_gdn.gdn_moe_step(**sizes, rows=64, ctx_tokens=64 * 2176, experts_hit=hit - 1, local_picks=picks)[1]
    assert nbytes - fewer == 3 * 2048 * 512 * 2  # an expert is gate, up and down, and only where a row hit it
    one_row = opsbytes_gdn.gdn_moe_step(**sizes, rows=1, ctx_tokens=2176, experts_hit=24 * 10 / 16, local_picks=15)[1]
    assert one_row < nbytes / 4  # the state and the K/V rows follow the rows that generate
    # a (4, 256) chunk dispatch: four rows' states once, 1,024 tokens' q | k | v and outputs; the recurrence's own products
    c_flops, c_bytes = opsbytes_gdn.gdn_chunk(rows=4, tokens=1024, **SCAN)
    assert c_bytes == 18 * (4 * (2 * state + 3 * width) * 4 + 1024 * (width + 4096) * 4)
    assert c_flops == 7.0 * state * 1024 * 18 and c_flops / 197e12 < c_bytes / 819e9


def _frame(counts=(), mode="plain", busy=(1000, 1000, 0, 0, 0), **kw):
    named = dict(zip(scopes_ssm_moe.COUNTED, counts))
    return types.SimpleNamespace(step_counts=tuple(counts), mode=mode, busy_ns=busy,
                                 ssm_rows=named.get("ssm_rows", 0), moe_rows=named.get("moe_rows", 0), **kw)


def test_the_readers_take_the_frames_own_counts_and_never_raise(found):
    a, b = (60, 500, 8, 900, 0, 0, 60, 36), (64, 560, 9, 980, 0, 0, 64, 40)
    frames = [_frame(a), _frame(b), _frame(b, mode="spec"), _frame(b, busy=(1000, 0, 0, 0, 0)), _frame(())]
    hit = cells.load_module(ROOT, found["bench"], "layer_metrics", "moe_held_hit_pct.gdn_moe")
    assert hit.read(_o(found, frames=frames)) == pytest.approx(100.0 * 530 / (24 * 32))
    assert hit.read(_o(found, frames=[])) is None
    chunks = [types.SimpleNamespace(chunk_rows=4, chunk_rows_live=3, chunk_c=256),
              types.SimpleNamespace(chunk_rows=2, chunk_rows_live=1, chunk_c=256), types.SimpleNamespace(chunk_rows=0)]
    assert scopes_gdn.chunk_entry_means({"frames": chunks}) == {"rows": 2.0, "tokens": 512.0}
    assert scopes_gdn.chunk_entry_means({"frames": []}) is None and scopes_gdn.chunk_entry_means({}) is None
    other = cells.resolve(ROOT, "nemotron-3-nano-30b-a3b.agent-context-closed-64")
    for name in NEW:  # an untraced run, a program that counts nothing, another configuration's keys: None, never a raise
        reader = cells.load_module(ROOT, found["bench"], "layer_metrics", name)
        empty = dict(frames=[], trace=None, requests=[], device={"kind": "TPU v5 lite"})
        assert reader.read(_o(found, **empty)) is None
        assert reader.read({"config": other["config"], "geometry": GEOMETRY, "traffic": other["traffic"], **empty}) is None


# ------------------------------------------------------------ nested scopes


def _events():
    j, c = "jit(_fused_step)/jit(main)/", "jit(_fused_chunk)/jit(main)/"
    ops = [("fusion", 0.10, 0.02, j + "qkv/gdn_in/dot_general:"), ("fusion", 0.12, 0.01, j + "attn/gdn_conv/mul:"),
           ("fusion", 0.13, 0.05, j + "attn/gdn_scan/reduce:"), ("fusion", 0.18, 0.01, j + "attn_out/gdn_norm/mul:"),
           ("fusion", 0.19, 0.01, j + "attn_out/gdn_out/dot_general:"), ("fusion", 0.20, 0.004, j + "qkv/rope/mul:"),
           ("fusion", 0.21, 0.003, j + "attn_out/attn_gate/logistic:"), ("fusion", 0.22, 0.01, j + "mlp/shared_expert/dot_general:"),
           ("fusion", 0.23, 0.03, j + "mlp/moe_experts/dot_general:"), ("fusion", 0.27, 0.01, j + "attn/dot_general:"),
           ("while", 0.50, 0.10, c + "attn/gdn_scan/while:"), ("fusion", 0.52, 0.04, c + "attn/gdn_scan/while/body/dot_general:"),
           ("fusion", 0.61, 0.02, c + "attn/gdn_scan/triangular:"), ("fusion", 0.64, 0.03, c + "mlp/moe_experts/custom-call:")]
    mods = [("jit__fused_step", 0.10, 0.30), ("jit__fused_chunk", 0.50, 0.20), ("jit__fused_step", 0.95, 0.30)]
    return {"devices": {"/device:TPU:0": {"ops": [list(o) for o in ops], "modules": [list(m) for m in mods]}},
            "host": [[scopes.WINDOW, 0.0, 1.0, "", {}]], "op_name_stat": "tf_op"}


def test_the_nested_reader_takes_the_delta_rules_names_and_leaves_the_others():
    assert scopes_gdn.nested_key("jit(_fused_step)/jit(main)/attn/gdn_scan/reduce:") == "gdn_scan"
    assert scopes_gdn.nested_key("jit(_fused_chunk)/jit(main)/attn/gdn_scan/while/body/dot_general:") == "gdn_scan"
    assert scopes_gdn.nested_key("jit(_fused_step)/jit(main)/attn_out/attn_gate/logistic:") == "attn_gate"
    assert scopes_gdn.nested_key("jit(_fused_step)/jit(main)/attn/ssm_scan/mul:") is None
    step = scopes_gdn.by_nested(_events(), scopes_gdn.STEP_MARK)
    assert step["dispatches"] == 1 and step["by"] == pytest.approx(
        {"gdn_in": 0.02, "gdn_conv": 0.01, "gdn_scan": 0.05, "gdn_norm": 0.01, "gdn_out": 0.01, "attn_gate": 0.003})
    chunk = scopes_gdn.by_nested(_events(), scopes_gdn.CHUNK_MARK)
    assert chunk == {"dispatches": 1, "by": pytest.approx({"gdn_scan": 0.12})}  # the loop's self time and its body's
    assert scopes_moe.by_nested(_events(), scopes_moe.STEP_MARK)["by"] == pytest.approx({"moe_experts": 0.03, "rope": 0.004})
    assert scopes_win.by_nested(_events(), scopes_win.STEP_MARK)["by"] == pytest.approx({"shared_expert": 0.01})
    old = scopes.step_by_scope(_events())  # the nine scopes' readers hold all of it
    assert scopes.scoped_s(old, "attn") == pytest.approx(0.07) and scopes.scoped_s(old, "mlp") == pytest.approx(0.04)
    assert scopes_gdn.nested_ms({"trace": None}, "step", "gdn_scan") is None
    assert scopes_gdn.moe_ms({"trace": None, "config": {}, "geometry": GEOMETRY}, "step") is None


def test_recorded_chip_trace_reads_the_delta_rule_beside_the_expert_layers(found):
    """A piece of the new cell's traced run (my chip run, PR 57): the step
    carries the delta rule's five names, the gated attention's gate, the
    expert layer's four and the shared expert's; their time lies inside what
    the nine scopes' readers give ``attn``, ``mlp``, ``qkv`` and
    ``attn_out``; the step's and the delta rule's shares of their rooflines
    from this piece's own time stay under 100 with every slot generating and
    every held expert counted as hit."""
    with open(os.path.join(BENCH, "harness", "fixtures", "trace_gdn.json")) as f:
        events = scopes.expanded(json.load(f))
    gdn = scopes_gdn.by_nested(events, scopes_gdn.STEP_MARK)
    moe = scopes_moe.by_nested(events, scopes_moe.STEP_MARK)
    shared = scopes_win.by_nested(events, scopes_win.STEP_MARK)
    assert gdn and set(gdn["by"]) == set(scopes_gdn.GDN) and gdn["dispatches"] >= 1
    assert moe and set(scopes_moe.MOE) <= set(moe["by"]) and shared and set(shared["by"]) == {"shared_expert"}
    old = scopes.step_by_scope(events)
    n = old["dispatches"]
    assert sum(moe["by"][k] for k in scopes_moe.MOE) + shared["by"]["shared_expert"] <= scopes.scoped_s(old, "mlp") * moe["dispatches"] / n + 1e-9
    assert gdn["by"]["gdn_scan"] + gdn["by"]["gdn_conv"] <= scopes.scoped_s(old, "attn") * gdn["dispatches"] / n + 1e-9
    flops, nbytes = opsbytes_gdn.gdn_scan(rows=64, **SCAN)
    assert 0 < 100.0 * opsbytes_gdn.least_seconds("TPU v5 lite", flops, nbytes) / (gdn["by"]["gdn_scan"] / gdn["dispatches"]) <= 100.0
    p = scopes_gdn.published(_o(found))
    sizes = {k: v for k, v in p.items() if k not in ("held", "per_tok")}
    flops, nbytes = opsbytes_gdn.gdn_moe_step(**sizes, rows=64, ctx_tokens=64 * 2176, experts_hit=24 * 32, local_picks=960)
    assert 0 < 100.0 * opsbytes_gdn.least_seconds("TPU v5 lite", flops, nbytes) / (old["module_s"] / n) <= 100.0
    chunk = scopes_gdn.by_nested(events, scopes_gdn.CHUNK_MARK)
    assert chunk is None or "gdn_scan" in chunk["by"]
