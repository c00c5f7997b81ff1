"""What PR 28 adds to the benchmark, checked without the program: the
``repo-context-closed`` mix, the ``mellum2-12b-a2.5b`` configuration's file,
the nested-scope reduction and the sparse-expert step's counts."""

import json
import os
import types

import pytest
from conftest import BENCH, ROOT

from harness import cells, opsbytes_moe, scopes_moe, traffic
from harness.scopes import WINDOW

CELL = "mellum2-12b-a2.5b.repo-context-closed"


@pytest.fixture(scope="module")
def found():
    return cells.resolve(ROOT, CELL)


@pytest.mark.parametrize("seeds", [(1, 2), (7, 2800000123)])
def test_two_seeds_offer_the_same_work(found, seeds):
    a, b = (traffic.build_plan(found["traffic"], seed=s, seconds=51) for s in seeds)
    assert traffic.offered_work(a) == traffic.offered_work(b)
    assert a == b  # client c runs lane c whatever the seed (PR 31's refusal round): the seed draws the ids alone
    first = a["clients"][0][0]
    assert (first["prompt_len"], first["prefix_len"], first["cache_prefix"], first["prefix"]) == (3136, 3072, 3072, 0)
    ids_a, ids_b = (traffic.token_ids(s, 98304, first) for s in seeds)
    assert ids_a != ids_b and max(ids_a) > 50257  # drawn from the whole vocabulary
    other = dict(first, uid=first["uid"] + 1)
    assert traffic.token_ids(seeds[0], 98304, other)[:3072] == ids_a[:3072]  # one shared prefix family
    assert traffic.token_ids(seeds[0], 98304, other)[3072:] != ids_a[3072:]


def test_the_mix_is_batch_unshareds_lanes_and_table(found):
    with open(os.path.join(BENCH, "traffic", "batch-unshared.json")) as f:
        base = json.load(f)
    t = found["traffic"]
    assert (t["output_table"], t["lanes"], t["cycle_from"], t["clients"], t["ramp_s"]) == (
        base["output_table"], base["lanes"], 1, 16, 4.0)
    assert t["generator"] == "closed" and t["protocol"] == "sse" and "prefixes" not in t


def test_configuration_file_is_the_published_config_cut_in_depth_only(found):
    c = found["config"]
    want = {"hidden_size": 2304, "num_attention_heads": 32, "num_key_value_heads": 4, "head_dim": 128,
            "num_experts": 64, "num_experts_per_tok": 8, "moe_intermediate_size": 896, "sliding_window": 1024,
            "vocab_size": 98304, "intermediate_size": 7168, "num_hidden_layers": 12, "rms_norm_eps": 1e-6,
            "max_position_embeddings": 131072, "norm_topk_prob": True, "tie_word_embeddings": False}
    assert {k: c[k] for k in want} == want
    assert c["reduced"] == ["num_hidden_layers"] and c["published"]["num_hidden_layers"] == 28 == len(c["layer_types"])
    assert c["layer_types"][:4] == ["sliding_attention"] * 3 + ["full_attention"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Mellum2-12B-A2.5B-Instruct")
        assert c["source"] == row["source_url"]
        assert {k: c[k] for k in row["config"] if k != "num_hidden_layers"} == {
            k: v for k, v in row["config"].items() if k != "num_hidden_layers"}
    unit = {p["name"]: p["value"] for p in c["deployment"]["spec"]["predictors"][0]["graph"]["parameters"]}
    yarn = c["rope_parameters"]["full_attention"]
    assert (int(unit["hidden"]), int(unit["layers"]), int(unit["ffn"]), int(unit["vocab"]), int(unit["heads"]),
            int(unit["kv_heads"]), int(unit["head_dim"]), int(unit["experts"]), int(unit["experts_per_tok"]),
            int(unit["window"]), int(unit["max_len"])) == (
        c["hidden_size"], c["num_hidden_layers"], c["moe_intermediate_size"], c["vocab_size"],
        c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"], c["num_experts"],
        c["num_experts_per_tok"], c["sliding_window"], c["max_position_embeddings"])
    assert (float(unit["rope_theta"]), float(unit["yarn_factor"]), int(unit["yarn_original"]),
            float(unit["yarn_beta_fast"]), float(unit["yarn_beta_slow"]), float(unit["yarn_attention_factor"])) == (
        yarn["rope_theta"], yarn["factor"], yarn["original_max_position_embeddings"], yarn["beta_fast"],
        yarn["beta_slow"], yarn["attention_factor"])
    assert c["layer_types"][int(unit["period"]) - 1] == "full_attention" and c["reference"]["n_head"] == 32
    tpu = c["deployment"]["spec"]["predictors"][0]["tpu"]
    per_slot = -(-(int(unit["seq"]) + int(unit["max_new_tokens"])) // tpu["decode_kv_page_size"])
    assert tpu["decode_kv_pages"] >= tpu["decode_slots"] * per_slot + tpu["decode_prefix_ctx"] // 16 + 1
    assert tpu["dtype"] == unit["param_dtype"] == "bfloat16"
    assert int(unit["seq"]) == found["traffic"]["prompt_len"] and tpu["decode_prefix_ctx"] == found["traffic"]["cache_prefix"]


def test_new_metrics_list_only_the_new_cell_and_step_roofline_leaves_it_out(found):
    by_name = {m["name"]: m for m in found["bench"]["per_layer"]}
    for name in ("moe_device_ms", "moe_chunk_device_ms", "moe_experts_roofline", "step_roofline.moe",
                 "moe_experts_hit_pct", "moe_load_max_over_mean", "attn_full_share_pct"):
        assert by_name[name]["workloads"] == [CELL] and by_name[name]["layer"] == "kernels"
    assert CELL not in by_name["step_roofline"]["workloads"]  # its count is the GPT-2 block's
    assert CELL in by_name["step_scoped_pct"]["workloads"] and CELL in by_name["prefix_saved_pct"]["workloads"]


# ------------------------------------------------------------ nested scopes


def _events():
    j = "jit(_fused_step)/jit(main)/"
    ops = [("fusion", 0.10, 0.02, j + "qkv/dot_general:"), ("fusion", 0.12, 0.01, j + "qkv/rope/mul:"),
           ("fusion", 0.13, 0.03, j + "win/kv_gather/gather:"), ("fusion", 0.16, 0.02, j + "win/attn/dot_general:"),
           ("fusion", 0.18, 0.04, j + "full/kv_gather/gather:"), ("fusion", 0.22, 0.01, j + "full/attn/softmax:"),
           ("fusion", 0.23, 0.01, j + "mlp/moe_router/top_k:"), ("fusion", 0.24, 0.01, j + "mlp/moe_dispatch/sort:"),
           ("while", 0.25, 0.10, j + "mlp/moe_experts/ragged_dot:"),
           ("fusion", 0.26, 0.04, j + "mlp/moe_experts/ragged_dot:"),  # nested in the while: self time
           ("fusion", 0.35, 0.02, j + "mlp/moe_combine/reduce_sum:"), ("copy", 0.37, 0.01, ""),
           ("fusion", 0.50, 0.10, "jit(_fused_chunk)/jit(main)/mlp/moe_experts/ragged_dot:"),
           ("fusion", 0.60, 0.05, "jit(_fused_chunk)/jit(main)/mlp/rms:")]
    mods = [("jit__fused_step", 0.10, 0.30), ("jit__fused_chunk", 0.50, 0.20), ("jit__fused_step", 0.95, 0.30)]
    return {"devices": {"/device:TPU:0": {"ops": [list(o) for o in ops], "modules": [list(m) for m in mods]}},
            "host": [[WINDOW, 0.0, 1.0, "", {}]], "op_name_stat": "tf_op"}


def test_nested_keys_by_path():
    k = scopes_moe.nested_key
    assert k("jit(_fused_step)/jit(main)/mlp/moe_experts/ragged_dot:") == "moe_experts"
    assert k("jit(_fused_step)/jit(main)/win/kv_gather/gather:") == "win/kv_gather"
    assert k("jit(_fused_step)/jit(main)/full/attn/while/body/dot_general:") == "full/attn"
    assert k("jit(_fused_step)/jit(main)/qkv/rope/mul:") == "rope"
    assert k("jit(_fused_step)/jit(main)/attn/dot_general:") is None and k("") is None  # the GPT-2 family's


def test_nested_reduction_takes_whole_dispatches_and_self_time():
    step = scopes_moe.by_nested(_events(), scopes_moe.STEP_MARK)
    assert step["dispatches"] == 1  # the second step is cut by the slice's edge
    assert step["by"] == pytest.approx({
        "rope": 0.01, "win/kv_gather": 0.03, "win/attn": 0.02, "full/kv_gather": 0.04, "full/attn": 0.01,
        "moe_router": 0.01, "moe_dispatch": 0.01, "moe_experts": 0.10, "moe_combine": 0.02})
    chunk = scopes_moe.by_nested(_events(), scopes_moe.CHUNK_MARK)
    assert chunk == {"dispatches": 1, "by": pytest.approx({"moe_experts": 0.10})}
    plain = _events()
    for o in plain["devices"]["/device:TPU:0"]["ops"]:
        o[3] = o[3].replace("win/", "").replace("full/", "").replace("moe_", "x_").replace("rope/", "")
    assert scopes_moe.by_nested(plain, scopes_moe.STEP_MARK) is None  # a program without the names


def _frame(rows, hit, load, chunk_ns=0, mode="plain"):
    return types.SimpleNamespace(moe_rows=rows, moe_experts_hit=hit, moe_load_max=load, mode=mode,
                                 busy_ns=(chunk_ns, 1000, 0, 0, 0))


def test_counts_read_step_only_rounds_and_a_program_without_them_gives_none():
    o = {"frames": [_frame(16, 672, 60), _frame(14, 600, 48), _frame(80, 760, 300, chunk_ns=5),
                    types.SimpleNamespace(mode="plain", busy_ns=(0, 1000, 0, 0, 0))]}  # the parent's frame: no field
    assert scopes_moe.step_means(o) == {"rows": 15.0, "experts_hit": 636.0, "load_max": 54.0}
    assert scopes_moe.step_means({"frames": o["frames"][3:]}) is None
    assert scopes_moe.nested_ms({"trace": None}, "step", "moe_experts") is None


# ------------------------------------------------------- operations and bytes


SIZES = dict(hidden=2304, layers=12, ffn=896, vocab=98304, heads=32, kv_heads=4, head_dim=128, experts=64,
             per_tok=8, window=1024, full_layers=3)


def test_the_issues_bytes_come_out_of_the_count():
    flops, nbytes = opsbytes_moe.expert_products(hidden=2304, ffn=896, experts_hit=56 * 12, rows=16, layers=12, per_tok=8)
    assert nbytes == pytest.approx(8.32e9, rel=0.01) and flops == 2 * 3 * 2304 * 896 * 16 * 8 * 12
    flops, nbytes = opsbytes_moe.moe_decoder_step(**SIZES, experts_hit=56 * 12, rows=16, ctx_tokens=16 * 3200)
    assert nbytes == pytest.approx(9.9e9, rel=0.03)  # ISSUE 28: ~10.0 GB, ~12 ms at 819 GB/s
    assert nbytes / 819e9 > flops / 197e12  # bytes bind
    # a sliding layer reads its window, not the context: a third of the keys at three windows
    full = opsbytes_moe.moe_decoder_step(**{**SIZES, "full_layers": 12}, experts_hit=0, rows=16, ctx_tokens=16 * 3200)[1]
    none = opsbytes_moe.moe_decoder_step(**{**SIZES, "full_layers": 0}, experts_hit=0, rows=16, ctx_tokens=16 * 3200)[1]
    assert (full - none) == pytest.approx(2 * 2 * 512 * 12 * 16 * (3200 - 1024))


def test_a_share_computed_from_the_counts_cannot_pass_100_at_the_least_time():
    """At the roofline itself (time = bytes / peak) the share reads 100."""
    flops, nbytes = opsbytes_moe.expert_products(hidden=2304, ffn=896, experts_hit=672, rows=16, layers=12, per_tok=8)
    least = opsbytes_moe.least_seconds("TPU v5 lite", flops, nbytes)
    assert 100.0 * least / (nbytes / 819e9) == pytest.approx(100.0)
    with pytest.raises(KeyError):
        opsbytes_moe.least_seconds("cpu", flops, nbytes)  # a device without published peaks is an error
