"""What PR 51 adds to the benchmark, checked without the program: the
``nemotron-3-nano-30b-a3b`` configuration's file against the catalog's row,
the cell's place on the traffic file two configurations already ride, the
step's operation and byte counts at the PUBLISHED expert width, the frames'
and the nested scopes' readers (by hand, and on a piece of a recorded chip
trace, ``harness/fixtures/trace_ssm_moe.json``). Names are pinned, positions
are not."""

import json
import os
import types

import pytest
from conftest import BENCH, ROOT

from harness import cells, opsbytes_ssm_moe, scopes, scopes_moe, scopes_ssm, scopes_ssm_moe, scopes_win

CELL = "nemotron-3-nano-30b-a3b.agent-context-closed-64"
NEW = ("step_roofline.ssm_moe", "ssm_scan_roofline.ssm_moe", "moe_held_device_ms.ssm_moe",
       "moe_held_chunk_device_ms.ssm_moe", "moe_held_hit_pct.ssm_moe", "shared_expert_device_ms.ssm_moe")
READ_AS_THEY_STAND = ("ssm_device_ms", "ssm_chunk_device_ms", "ssm_state_restore_pct", "attn_run_pages_pct",
                      "moe_compact_pct", "chunk_rows_held_pct", "step_device_ms", "chunk_device_ms", "kv_gather_device_ms")
GEOMETRY = {"hidden": 2688, "layers": 27, "ffn": 1856, "vocab": 16384}
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*"


@pytest.fixture(scope="module")
def found():
    return cells.resolve(ROOT, CELL)


def _o(found, **kw):
    return {"config": found["config"], "geometry": GEOMETRY, "traffic": found["traffic"], **kw}


def test_configuration_file_is_the_catalogs_row_but_for_the_three_cuts(found):
    c = found["config"]
    assert c["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert c["published"] == {"num_hidden_layers": 52, "n_routed_experts": 128, "vocab_size": 131072}
    assert (c["num_hidden_layers"], c["n_routed_experts"], c["vocab_size"]) == (27, 16, 16384)
    # the floors of a cut: 8 experts or more, an eighth of the vocabulary, every kind of layer near its published ratio
    assert c["n_routed_experts"] >= 8 and c["vocab_size"] * 8 >= c["published"]["vocab_size"]
    kept, whole = c["hybrid_override_pattern"][:27], c["hybrid_override_pattern"]
    assert kept == PATTERN and len(whole) == 52 and set(whole) == {"M", "E", "*"}
    assert [kept.count(k) for k in "ME*"] == [12, 11, 4] and [whole.count(k) for k in "ME*"] == [23, 23, 6]
    assert (c["share"]["chips"], c["share"]["stages"], c["share"]["experts_held"], c["share"]["first_expert"]) == (8, 2, 16, 0)
    entry = next(e for e in found["bench"]["configs"] if e["name"] == "nemotron-3-nano-30b-a3b")
    assert entry["reduced"] == c["reduced"] and entry["source"] == c["source"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
        assert c["source"] == row["source_url"]
        assert {k for k in row["config"] if c[k] != row["config"][k]} == set(c["reduced"])  # no width touched
        assert all(row["config"][k] == v for k, v in c["published"].items())
    unit = {p["name"]: p["value"] for p in c["deployment"]["spec"]["predictors"][0]["graph"]["parameters"]}
    assert unit["model"] == "hybrid_decoder"  # one family: the third, with more parameters
    ints = {"hidden": "hidden_size", "layers": "num_hidden_layers", "heads": "num_attention_heads",
            "kv_heads": "num_key_value_heads", "head_dim": "head_dim", "ssm_heads": "mamba_num_heads",
            "ssm_head_dim": "mamba_head_dim", "ssm_state": "ssm_state_size", "ssm_conv": "conv_kernel",
            "ssm_groups": "n_groups", "ffn": "moe_intermediate_size", "shared_ffn": "moe_shared_expert_intermediate_size",
            "experts_held": "n_routed_experts", "experts_per_tok": "num_experts_per_tok", "vocab": "vocab_size",
            "max_len": "max_position_embeddings"}
    assert {k: int(unit[k]) for k in ints} == {k: c[v] for k, v in ints.items()}
    assert {k: int(unit[k]) for k in GEOMETRY} == GEOMETRY  # what harness/deploy.py reads
    assert int(unit["experts"]) == c["published"]["n_routed_experts"]  # the router keeps its width
    assert unit["attn_layers"] == PATTERN  # the pattern where a tree before PR 51 parses integers: it refuses at once
    assert (float(unit["routed_scale"]), float(unit["rms_eps"]), unit["untied"]) == (
        c["routed_scaling_factor"], c["layer_norm_epsilon"], "true")
    assert c["tie_word_embeddings"] is False and c["mlp_hidden_act"] == "relu2" and (c["n_group"], c["topk_group"]) == (1, 1)
    # no multipliers: ones, and the score scale 1 / sqrt(head)
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
    assert any("1920" in d and "1856" in d for d in c["departures"])  # the store is said, and that no key is it
    for key in ("stands_for", "assumed", "departures", "sizing"):
        assert c[key]


def test_the_cell_rides_the_traffic_file_two_configurations_ride(found):
    bench = found["bench"]
    mine = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert mine["traffic"] == "agent-context-closed-64" and mine["chips"] == 1 and mine["config"] == "nemotron-3-nano-30b-a3b"
    riders = {w["config"] for w in bench["workloads"] if w["traffic"] == mine["traffic"]}
    assert {"lfm2-24b-a2b", "xing4.0-29b-a4b", "nemotron-3-nano-30b-a3b"} <= riders
    assert not os.path.exists(os.path.join(BENCH, "traffic", CELL + ".json"))  # no traffic file of its own


def test_new_metrics_list_only_the_new_cell_and_the_lists_that_read_it_hold_it_once(found):
    bench = found["bench"]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL] and by_name[name]["layer"] == "kernels"
        assert by_name[name]["moves"] == "itl_p95_ms"
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", name + ".py"))
    for name in READ_AS_THEY_STAND:
        assert by_name[name]["workloads"].count(CELL) == 1, name
    for name in ("conv_device_ms", "step_roofline.conv", "moe_held_chunk_device_ms", "moe_held_hit_pct.conv",
                 "ssm_scan_roofline", "step_roofline.ssm", "shared_expert_device_ms", "moe_held_device_ms",
                 "moe_held_device_ms.moe", "step_roofline.moe_held", "mla_device_ms", "step_roofline"):
        assert CELL not in by_name[name]["workloads"], name  # other families' scopes, counts or key names
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL in e2e["tokens_per_s"]["workloads"] and CELL in e2e["itl_p95_ms"]["workloads"]
    assert CELL not in e2e["itl_p95_closed_ms"]["workloads"]  # its bound is the first cell's


# ------------------------------------------------------- counts and readers


def test_the_sizes_come_from_this_configurations_keys(found):
    p = scopes_ssm_moe.published(_o(found))
    assert p == {"hidden": 2688, "vocab": 16384, "ssm_layers": 12, "attn_layers": 4, "expert_layers": 11, "heads": 32,
                 "kv_heads": 2, "head_dim": 128, "ssm_heads": 64, "ssm_head_dim": 64, "ssm_state": 128, "ssm_conv": 4,
                 "ssm_groups": 8, "ffn": 1856, "shared_ffn": 3712, "experts": 128, "held": 16, "per_tok": 6}
    other = cells.resolve(ROOT, "granite-4.0-h-micro.chat-closed-64")
    assert scopes_ssm_moe.published({"config": other["config"], "geometry": GEOMETRY}) is None  # another shape's keys


def test_the_issues_bytes_come_out_of_the_counts(found):
    p = scopes_ssm_moe.published(_o(found))
    scan = {k: p[k] for k in ("ssm_layers", "ssm_heads", "ssm_head_dim", "ssm_state", "ssm_conv", "ssm_groups")}
    flops, nbytes = opsbytes_ssm_moe.ssm_scan(rows=64, **scan)
    state, conv = 64 * 64 * 128, 3 * (4096 + 2 * 8 * 128)
    assert nbytes == 64 * 12 * (2 * state + conv) * 4 and conv == 3 * 6144  # eight groups' conv width, not one's 4352
    assert flops == 5.0 * state * 64 * 12 and flops / 197e12 < nbytes / 819e9  # the bytes bind
    sizes = {k: v for k, v in p.items() if k not in ("held", "per_tok")}
    hit, picks = 11 * 16, 64 * 6 * 11 / 8
    flops, nbytes = opsbytes_ssm_moe.ssm_moe_step(**sizes, rows=64, ctx_tokens=64 * 2117, experts_hit=hit, local_picks=picks)
    # ISSUE 51's reckoning: 12 x (0.078 + 0.278) + 11 x 0.36 + 0.75 + 0.09 = 9.1 GB a step with every held expert hit
    assert nbytes == pytest.approx(9.1e9, rel=0.03)
    assert opsbytes_ssm_moe.least_seconds("TPU v5 lite", flops, nbytes) == pytest.approx(nbytes / 819e9)
    # an expert counts 2 x 2688 x 1856 numbers whatever is stored (1920), and only where a row hit it
    fewer = opsbytes_ssm_moe.ssm_moe_step(**sizes, rows=64, ctx_tokens=64 * 2117, experts_hit=hit - 1, local_picks=picks)[1]
    assert nbytes - fewer == 2 * 2688 * 1856 * 2
    one_row = opsbytes_ssm_moe.ssm_moe_step(**sizes, rows=1, ctx_tokens=2117, experts_hit=11 * 6 / 8, local_picks=66 / 8)[1]
    assert one_row < nbytes / 4  # the state and the K/V rows follow the rows that generate


def _frame(counts=(), mode="plain", busy=(1000, 1000, 0, 0, 0), **kw):
    named = dict(zip(scopes_ssm_moe.COUNTED, counts))
    return types.SimpleNamespace(step_counts=tuple(counts), mode=mode, busy_ns=busy,
                                 ssm_rows=named.get("ssm_rows", 0), moe_rows=named.get("moe_rows", 0), **kw)


def test_step_means_take_the_step_dispatchs_own_counts(found):
    a, b = (60, 170, 80, 500, 0, 0, 60, 36), (64, 176, 90, 540, 0, 0, 64, 40)
    frames = [_frame(a), _frame(b), _frame(b, mode="spec"), _frame(b, busy=(1000, 0, 0, 0, 0)),
              _frame((4, 2, 0)), _frame(())]  # a speculative round, a round without a step, other families' counts
    m = scopes_ssm_moe.step_means({"frames": frames})
    assert m == {"rows": 62.0, "experts_hit": 173.0, "load_max": 85.0, "local_picks": 520.0}
    assert scopes_ssm_moe.step_means({"frames": frames[2:]}) is None and scopes_ssm_moe.step_means({}) is None
    hit = cells.load_module(ROOT, found["bench"], "layer_metrics", "moe_held_hit_pct.ssm_moe")
    assert hit.read(_o(found, frames=frames)) == pytest.approx(100.0 * 173 / (11 * 16))
    assert hit.read(_o(found, frames=[])) is None
    for name in NEW:  # an untraced run, a program that counts nothing: None, never a raise
        reader = cells.load_module(ROOT, found["bench"], "layer_metrics", name)
        assert reader.read(_o(found, frames=[], trace=None, requests=[], device={"kind": "TPU v5 lite"})) is None


# ------------------------------------------------------------ nested scopes


def _events():
    j, c = "jit(_fused_step)/jit(main)/", "jit(_fused_chunk)/jit(main)/"
    ops = [("fusion", 0.10, 0.02, j + "qkv/ssm_in/dot_general:"), ("fusion", 0.12, 0.05, j + "attn/ssm_scan/mul:"),
           ("fusion", 0.17, 0.01, j + "mlp/shared_expert/dot_general:"), ("fusion", 0.18, 0.004, j + "mlp/moe_router/top_k:"),
           ("fusion", 0.19, 0.03, j + "mlp/moe_experts/dot_general:"), ("fusion", 0.22, 0.002, j + "mlp/moe_combine/add:"),
           ("fusion", 0.23, 0.01, j + "attn/dot_general:"),
           ("while", 0.50, 0.10, c + "mlp/while:"), ("fusion", 0.51, 0.03, c + "mlp/while/body/moe_experts/custom-call:"),
           ("fusion", 0.55, 0.02, c + "mlp/while/body/moe_combine/dot_general:"),
           ("fusion", 0.61, 0.04, c + "mlp/shared_expert/dot_general:"), ("fusion", 0.65, 0.03, c + "attn/ssm_scan/exp:")]
    mods = [("jit__fused_step", 0.10, 0.30), ("jit__fused_chunk", 0.50, 0.20), ("jit__fused_step", 0.95, 0.30)]
    return {"devices": {"/device:TPU:0": {"ops": [list(o) for o in ops], "modules": [list(m) for m in mods]}},
            "host": [[scopes.WINDOW, 0.0, 1.0, "", {}]], "op_name_stat": "tf_op"}


def test_the_three_readers_split_one_step_between_them():
    """The expert layer's names, the shared expert's and the mixer's are
    three files' to read; none needs another family's scopes beside them,
    and the compact form's products are found inside its loop."""
    moe = scopes_moe.by_nested(_events(), scopes_moe.STEP_MARK)
    assert moe["dispatches"] == 1 and moe["by"] == pytest.approx({"moe_router": 0.004, "moe_experts": 0.03, "moe_combine": 0.002})
    shared = scopes_win.by_nested(_events(), scopes_win.STEP_MARK)
    assert shared["by"] == pytest.approx({"shared_expert": 0.01})
    ssm = scopes_ssm.by_nested(_events(), scopes_ssm.STEP_MARK)
    assert ssm["by"] == pytest.approx({"ssm_in": 0.02, "ssm_scan": 0.05})
    chunk = scopes_moe.by_nested(_events(), scopes_moe.CHUNK_MARK)
    assert chunk["by"] == pytest.approx({"moe_experts": 0.03, "moe_combine": 0.02})  # the loop's own 0.05 has no name
    old = scopes.step_by_scope(_events())  # the nine scopes' readers hold all of it
    assert scopes.scoped_s(old, "mlp") == pytest.approx(0.046) and scopes.scoped_s(old, "attn") == pytest.approx(0.06)
    assert scopes_ssm_moe.moe_ms({"trace": None, "config": {}, "geometry": GEOMETRY}, "step") is None


def test_recorded_chip_trace_reads_the_expert_layers_beside_the_mixers(found):
    """A piece of the new cell's traced run (my chip run, PR 51): the step
    carries the mixer's five names, the expert layer's four and the shared
    expert's, their time lies inside what the nine scopes' readers give
    ``mlp``, ``attn``, ``qkv`` and ``attn_out``, and the step's and the
    recurrence's shares of their rooflines from this piece's own time stay
    under 100 with every held expert counted as hit."""
    with open(os.path.join(BENCH, "harness", "fixtures", "trace_ssm_moe.json")) as f:
        events = scopes.expanded(json.load(f))
    ssm = scopes_ssm.by_nested(events, scopes_ssm.STEP_MARK)
    moe = scopes_moe.by_nested(events, scopes_moe.STEP_MARK)
    shared = scopes_win.by_nested(events, scopes_win.STEP_MARK)
    assert ssm and set(ssm["by"]) == set(scopes_ssm.SSM) and ssm["dispatches"] >= 1
    assert moe and set(moe["by"]) == set(scopes_moe.MOE) and shared and set(shared["by"]) == {"shared_expert"}
    old = scopes.step_by_scope(events)
    n = old["dispatches"]
    assert sum(moe["by"].values()) + shared["by"]["shared_expert"] <= scopes.scoped_s(old, "mlp") * moe["dispatches"] / n + 1e-9
    assert ssm["by"]["ssm_scan"] + ssm["by"]["ssm_conv"] <= scopes.scoped_s(old, "attn") * ssm["dispatches"] / n + 1e-9
    p = scopes_ssm_moe.published(_o(found))
    scan = {k: p[k] for k in ("ssm_layers", "ssm_heads", "ssm_head_dim", "ssm_state", "ssm_conv", "ssm_groups")}
    flops, nbytes = opsbytes_ssm_moe.ssm_scan(rows=64, **scan)
    assert 0 < 100.0 * opsbytes_ssm_moe.least_seconds("TPU v5 lite", flops, nbytes) / (ssm["by"]["ssm_scan"] / ssm["dispatches"]) <= 100.0
    sizes = {k: v for k, v in p.items() if k not in ("held", "per_tok")}
    flops, nbytes = opsbytes_ssm_moe.ssm_moe_step(**sizes, rows=64, ctx_tokens=64 * 2117, experts_hit=176, local_picks=528)
    assert 0 < 100.0 * opsbytes_ssm_moe.least_seconds("TPU v5 lite", flops, nbytes) / (old["module_s"] / n) <= 100.0
    chunk = scopes_moe.by_nested(events, scopes_moe.CHUNK_MARK)
    assert chunk is None or set(chunk["by"]) <= set(scopes_moe.MOE)
