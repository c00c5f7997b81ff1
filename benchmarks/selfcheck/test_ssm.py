"""What PR 34 adds to the benchmark, checked without the program: the
``chat-closed-64`` mix, the ``granite-4.0-h-micro`` configuration's file, the
hybrid family's nested-scope reduction (on a piece of a recorded chip trace,
``harness/fixtures/trace_ssm.json``) and the step's operation and byte counts."""

import json
import os
import types

import pytest
from conftest import BENCH, ROOT

from harness import cells, opsbytes_ssm, scopes, scopes_ssm, traffic

CELL = "granite-4.0-h-micro.chat-closed-64"
SIZES = dict(hidden=2048, layers=40, attn_layers=4, ffn=8192, vocab=100352, heads=32, kv_heads=8, head_dim=64,
             ssm_heads=64, ssm_head_dim=64, ssm_state=128, ssm_conv=4)
SCAN = {k: SIZES[k] for k in ("ssm_heads", "ssm_head_dim", "ssm_state", "ssm_conv")}


@pytest.fixture(scope="module")
def found():
    return cells.resolve(ROOT, CELL)


@pytest.mark.parametrize("seeds", [(1, 2), (7, 2800000123)])
def test_two_seeds_offer_the_same_work(found, seeds):
    a, b = (traffic.build_plan(found["traffic"], seed=s, seconds=51) for s in seeds)
    assert traffic.offered_work(a) == traffic.offered_work(b) and a == b  # the seed draws the ids alone
    first = a["clients"][0][0]
    assert (first["prompt_len"], first["prefix_len"], first["cache_prefix"], first["prefix"]) == (576, 512, 512, 0)
    ids_a, ids_b = (traffic.token_ids(s, 100352, first) for s in seeds)
    assert ids_a != ids_b and max(ids_a) > 98304  # drawn from the whole vocabulary
    other = dict(first, uid=first["uid"] + 1)
    assert traffic.token_ids(seeds[0], 100352, other)[:512] == ids_a[:512]  # one system prompt
    assert traffic.token_ids(seeds[0], 100352, other)[512:] != ids_a[512:]


def test_the_mix_is_64_lanes_over_a_log_uniform_table(found):
    t = found["traffic"]
    table = t["output_table"]
    assert len(table) == 32 and table == sorted(table) and 64 <= table[0] and table[-1] <= 256
    assert table == [int(round(64 * 4 ** ((i + 0.5) / 32))) for i in range(32)]
    assert (table[15] + table[16]) / 2 == 128 and 138 <= sum(table) / 32 <= 139  # median 128, mean 138
    assert (t["generator"], t["protocol"], t["clients"], t["cycle_from"], t["ramp_s"]) == ("closed", "sse", 64, 1, 8.0)
    assert len(t["lanes"]) == 64 and "prefixes" not in t
    for first, a, b in t["lanes"]:  # one small + large pair of the table, the first request cut short
        assert table.index(a) + table.index(b) == 31 and 4 <= first <= 256
    assert sorted(x for lane in t["lanes"] for x in lane[1:]) == sorted(table * 4)  # every point four times
    assert len({lane[0] for lane in t["lanes"]}) >= 60  # completions spread from the first seconds


def test_configuration_file_is_the_published_config_with_no_cut(found):
    c = found["config"]
    assert c["reduced"] == [] and c["num_hidden_layers"] == 40 == len(c["layer_types"])
    assert [i for i, k in enumerate(c["layer_types"]) if k == "attention"] == [5, 15, 25, 35]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "granite-4.0-h-micro")
        assert c["source"] == row["source_url"]
        assert {k: c[k] for k in row["config"]} == row["config"]  # every key, no width touched
    unit = {p["name"]: p["value"] for p in c["deployment"]["spec"]["predictors"][0]["graph"]["parameters"]}
    assert (int(unit["hidden"]), int(unit["layers"]), int(unit["ffn"]), int(unit["vocab"]), int(unit["heads"]),
            int(unit["kv_heads"]), int(unit["ssm_heads"]), int(unit["ssm_head_dim"]), int(unit["ssm_state"]),
            int(unit["ssm_conv"]), int(unit["max_len"])) == (
        c["hidden_size"], c["num_hidden_layers"], c["shared_intermediate_size"], c["vocab_size"],
        c["num_attention_heads"], c["num_key_value_heads"], c["mamba_n_heads"], c["mamba_d_head"],
        c["mamba_d_state"], c["mamba_d_conv"], c["max_position_embeddings"])
    assert int(unit["head_dim"]) * c["num_attention_heads"] == c["hidden_size"]
    assert c["mamba_n_heads"] * c["mamba_d_head"] == c["mamba_expand"] * c["hidden_size"]
    assert (float(unit["embedding_multiplier"]), float(unit["residual_multiplier"]),
            float(unit["attention_multiplier"]), float(unit["logits_scaling"]), float(unit["rms_eps"])) == (
        c["embedding_multiplier"], c["residual_multiplier"], c["attention_multiplier"], c["logits_scaling"],
        c["rms_norm_eps"])
    assert unit["attn_layers"] == "5,15,25,35" and c["reference"]["n_head"] == 32
    tpu = c["deployment"]["spec"]["predictors"][0]["tpu"]
    per_slot = -(-(int(unit["seq"]) + int(unit["max_new_tokens"])) // tpu["decode_kv_page_size"])
    assert tpu["decode_kv_pages"] >= tpu["decode_slots"] * per_slot + 512 // 16 + 1
    assert tpu["dtype"] == unit["param_dtype"] == "bfloat16" and c["mamba_chunk_size"] == tpu["decode_prefill_chunk"]
    assert int(unit["seq"]) == found["traffic"]["prompt_len"] and tpu["decode_slots"] == found["traffic"]["clients"]
    assert max(max(lane) for lane in found["traffic"]["lanes"]) <= int(unit["max_new_tokens"])


def test_new_metrics_list_only_the_new_cell(found):
    by_name = {m["name"]: m for m in found["bench"]["per_layer"]}
    for name in ("ssm_device_ms", "ssm_chunk_device_ms", "ssm_scan_roofline", "step_roofline.ssm"):
        assert by_name[name]["workloads"] == [CELL] and by_name[name]["layer"] == "kernels"
    assert by_name["ssm_state_restore_pct"]["workloads"] == [CELL]
    assert by_name["ssm_state_restore_pct"]["layer"] == "state cache"
    for name in ("step_roofline", "step_roofline.moe", "attn_pages_read_pct", "moe_device_ms"):
        assert CELL not in by_name[name]["workloads"]  # the other families' counts
    for name in ("step_scoped_pct", "prefix_saved_pct", "dense_device_ms", "attn_device_ms", "hbm_peak_gb"):
        assert CELL in by_name[name]["workloads"]
    assert [w["name"] for w in found["bench"]["workloads"]][-1] == CELL  # appended, nothing moved


# ------------------------------------------------------------ nested scopes


def _events():
    j = "jit(_fused_step)/jit(main)/"
    ops = [("fusion", 0.10, 0.02, j + "qkv/ssm_in/dot_general:"), ("fusion", 0.12, 0.01, j + "attn/ssm_conv/add:"),
           ("fusion", 0.13, 0.05, j + "attn/ssm_scan/scatter:"), ("fusion", 0.18, 0.01, j + "attn_out/ssm_norm/mul:"),
           ("fusion", 0.19, 0.02, j + "attn_out/ssm_out/dot_general:"), ("fusion", 0.21, 0.03, j + "attn/dot_general:"),
           ("while", 0.50, 0.10, "jit(_fused_chunk)/jit(main)/attn/ssm_scan/while:"),
           ("fusion", 0.52, 0.04, "jit(_fused_chunk)/jit(main)/attn/ssm_scan/while/body/exp:")]  # nested: self time
    mods = [("jit__fused_step", 0.10, 0.30), ("jit__fused_chunk", 0.50, 0.20), ("jit__fused_step", 0.95, 0.30)]
    return {"devices": {"/device:TPU:0": {"ops": [list(o) for o in ops], "modules": [list(m) for m in mods]}},
            "host": [[scopes.WINDOW, 0.0, 1.0, "", {}]], "op_name_stat": "tf_op"}


def test_nested_keys_and_reduction():
    k = scopes_ssm.nested_key
    assert k("jit(_fused_step)/jit(main)/attn/ssm_scan/scatter:") == "ssm_scan"
    assert k("jit(_fused_chunk)/jit(main)/attn/ssm_scan/while/body/exp:") == "ssm_scan"
    assert k("jit(_fused_step)/jit(main)/attn/dot_general:") is None and k("") is None  # an attention layer's
    step = scopes_ssm.by_nested(_events(), scopes_ssm.STEP_MARK)
    assert step["dispatches"] == 1  # the second step is cut by the slice's edge
    assert step["by"] == pytest.approx(
        {"ssm_in": 0.02, "ssm_conv": 0.01, "ssm_scan": 0.05, "ssm_norm": 0.01, "ssm_out": 0.02}
    )
    chunk = scopes_ssm.by_nested(_events(), scopes_ssm.CHUNK_MARK)
    assert chunk == {"dispatches": 1, "by": pytest.approx({"ssm_scan": 0.10})}
    plain = _events()
    for o in plain["devices"]["/device:TPU:0"]["ops"]:
        o[3] = o[3].replace("ssm_", "x_")
    assert scopes_ssm.by_nested(plain, scopes_ssm.STEP_MARK) is None  # a program without the names: the parent
    assert scopes_ssm.nested_ms({"trace": None}, "step", "ssm_scan") is None


def test_recorded_chip_trace_reads_the_mixers_time_inside_the_old_scopes():
    """A piece of the new cell's traced run (my chip run, PR 34): the nested
    names are found, their time lies inside what the nine scopes' readers
    give ``attn``, ``qkv`` and ``attn_out``, and the recurrence is the
    larger part of the mixer."""
    with open(os.path.join(BENCH, "harness", "fixtures", "trace_ssm.json")) as f:
        events = scopes.expanded(json.load(f))
    step = scopes_ssm.by_nested(events, scopes_ssm.STEP_MARK)
    assert step and step["dispatches"] >= 1 and set(step["by"]) == set(scopes_ssm.SSM)
    old = scopes.step_by_scope(events)
    per = lambda key: step["by"][key] / step["dispatches"]  # noqa: E731
    assert per("ssm_conv") + per("ssm_scan") <= scopes.scoped_s(old, "attn") / old["dispatches"] + 1e-9
    assert per("ssm_in") <= scopes.scoped_s(old, "qkv") / old["dispatches"] + 1e-9
    assert per("ssm_scan") > per("ssm_conv")
    # the share of the roofline from this piece's own time and 64 generating rows stays under 100
    flops, nbytes = opsbytes_ssm.ssm_scan(rows=64, ssm_layers=36, **SCAN)
    assert 0 < 100.0 * opsbytes_ssm.least_seconds("TPU v5 lite", flops, nbytes) / per("ssm_scan") <= 100.0


def _frame(rows=0, restores=0, admitted=0, chunk_ns=0, mode="plain"):
    return types.SimpleNamespace(ssm_rows=rows, state_restores=restores, admitted=admitted, mode=mode,
                                 busy_ns=(chunk_ns, 1000, 0, 0, 0))


def test_counts_read_step_only_rounds_and_a_program_without_them_gives_none():
    parent = types.SimpleNamespace(mode="plain", busy_ns=(0, 1000, 0, 0, 0), admitted=2)  # no field: the parent
    o = {"frames": [_frame(64), _frame(60), _frame(66, 1, 1, chunk_ns=5), _frame(63, 2, 2), parent]}
    assert scopes_ssm.step_rows(o) == pytest.approx((64 + 60 + 63) / 3)
    assert scopes_ssm.restore_share(o) == 1.0
    assert scopes_ssm.restore_share({"frames": [_frame(64, 1, 2)]}) == 0.5  # a cold admission shows
    assert scopes_ssm.step_rows({"frames": [parent]}) is None and scopes_ssm.restore_share({"frames": [parent]}) is None


# ------------------------------------------------------- operations and bytes


def test_the_issues_bytes_come_out_of_the_count():
    flops, nbytes = opsbytes_ssm.ssm_scan(rows=64, ssm_layers=36, **SCAN)
    assert nbytes == pytest.approx(9.7e9, rel=0.02)  # ISSUE 34: 64 x 75.5 MB x 2
    assert nbytes / 819e9 > flops / 197e12  # the bytes bind
    flops, nbytes = opsbytes_ssm.hybrid_decoder_step(**SIZES, rows=64, ctx_tokens=64 * 700)
    assert nbytes == pytest.approx(9.7e9 + 6.38e9 + 0.2e9, rel=0.03)  # state + every weight once + conv and K/V rows
    assert nbytes / 819e9 > flops / 197e12
    # a slot that does not generate costs no state bytes
    less = opsbytes_ssm.hybrid_decoder_step(**SIZES, rows=32, ctx_tokens=32 * 700)[1]
    assert nbytes - less == pytest.approx(32 * 36 * (2 * 64 * 64 * 128 + 2 * 3 * 4352) * 4 + 32 * 2048 * 2
                                          + 2 * 512 * 4 * (32 * 700 + 32) * 2)
    weights = 36 * 25.85e6 + 4 * 10.49e6 + 40 * 50.33e6 + 205.5e6  # the configuration's sizing
    assert opsbytes_ssm.hybrid_decoder_step(**SIZES, rows=0, ctx_tokens=0)[1] == pytest.approx(2 * weights, rel=0.002)


def test_a_share_computed_from_the_counts_cannot_pass_100_at_the_least_time():
    flops, nbytes = opsbytes_ssm.ssm_scan(rows=64, ssm_layers=36, **SCAN)
    least = opsbytes_ssm.least_seconds("TPU v5 lite", flops, nbytes)
    assert 100.0 * least / (nbytes / 819e9) == pytest.approx(100.0)
    with pytest.raises(KeyError):
        opsbytes_ssm.least_seconds("cpu", flops, nbytes)  # a device without published peaks is an error
