"""What PR 37 adds to the benchmark, checked without the program: the
``doc-qa-closed-64`` mix, the ``a.x-k1`` configuration's file against the
catalog's row, the latent-attention family's nested-scope reduction (on a
piece of a recorded chip trace, ``harness/fixtures/trace_mla.json``) and the
step's operation and byte counts."""

import json
import os
import types

import pytest
from conftest import BENCH, ROOT

from harness import cells, opsbytes_mla, scopes, scopes_mla, traffic

CELL = "a.x-k1.doc-qa-closed-64"
SIZES = dict(hidden=7168, layers=7, ffn=2048, vocab=20480, heads=64, q_rank=1536, kv_rank=512, nope=128, rope=64,
             v_dim=128, dense_layers=1, dense_ffn=18432, experts=192, held=12, per_tok=8)
ATTN = {k: SIZES[k] for k in ("layers", "heads", "kv_rank", "rope", "nope", "v_dim")}
NEW = ("mla_device_ms", "mla_chunk_device_ms", "mla_decode_roofline", "step_roofline.mla", "shared_expert_device_ms",
       "moe_local_pick_pct", "moe_held_hit_pct", "moe_held_device_ms")


@pytest.fixture(scope="module")
def found():
    return cells.resolve(ROOT, CELL)


@pytest.mark.parametrize("seeds", [(1, 2), (7, 2900000123)])
def test_two_seeds_offer_the_same_work_from_the_vocabulary_rows_held(found, seeds):
    a, b = (traffic.build_plan(found["traffic"], seed=s, seconds=51) for s in seeds)
    assert traffic.offered_work(a) == traffic.offered_work(b) and a == b  # the seed draws the ids alone
    first = a["clients"][0][0]
    assert (first["prompt_len"], first["prefix_len"], first["cache_prefix"], first["prefix"]) == (8256, 8192, 8192, 0)
    ids_a, ids_b = (traffic.token_ids(s, 20480, first) for s in seeds)
    assert ids_a != ids_b and 20000 < max(ids_a) < 20480  # drawn from the whole slice, and from nothing past it
    other = dict(first, uid=first["uid"] + 1)
    assert traffic.token_ids(seeds[0], 20480, other)[:8192] == ids_a[:8192]  # one document
    assert traffic.token_ids(seeds[0], 20480, other)[8192:] != ids_a[8192:]  # its own question


def test_the_mix_is_the_chat_mixes_lanes_over_one_long_document(found):
    t = found["traffic"]
    with open(os.path.join(BENCH, "traffic", "chat-closed-64.json")) as f:
        chat = json.load(f)
    assert t["lanes"] == chat["lanes"] and t["output_table"] == chat["output_table"]
    assert (t["generator"], t["protocol"], t["clients"], t["cycle_from"], t["ramp_s"]) == ("closed", "sse", 64, 1, 8.0)
    assert (t["prompt_len"], t["shared_prefix_len"], t["cache_prefix"]) == (8256, 8192, 8192) and "prefixes" not in t
    assert sum(t["output_table"]) / 32 == pytest.approx(138.5, abs=0.1)


def test_configuration_file_is_the_catalogs_row_but_for_the_three_cuts(found):
    c = found["config"]
    assert c["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert c["published"] == {"num_hidden_layers": 61, "n_routed_experts": 192, "vocab_size": 163840}
    assert (c["num_hidden_layers"], c["n_routed_experts"], c["vocab_size"]) == (7, 12, 20480)
    # the floors of a cut: the dense layer + at least four that follow, 8 experts or more, an eighth of the vocabulary
    assert c["num_hidden_layers"] - c["first_k_dense_replace"] >= 4 and c["n_routed_experts"] >= 8
    assert c["vocab_size"] * 8 == c["published"]["vocab_size"] and c["published"]["n_routed_experts"] == 16 * 12
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "A.X-K1")
        assert c["source"] == row["source_url"]
        differs = {k for k in row["config"] if c[k] != row["config"][k]}
        assert differs == set(c["reduced"])  # every other key as published, rope_scaling whole: no width touched
        assert {k: row["config"][k] for k in c["reduced"]} == c["published"]
    unit = {p["name"]: p["value"] for p in c["deployment"]["spec"]["predictors"][0]["graph"]["parameters"]}
    assert unit["model"] == "mla_decoder"
    ints = {"hidden": "hidden_size", "layers": "num_hidden_layers", "heads": "num_attention_heads",
            "q_rank": "q_lora_rank", "kv_rank": "kv_lora_rank", "nope_dim": "qk_nope_head_dim",
            "rope_dim": "qk_rope_head_dim", "v_dim": "v_head_dim", "dense_layers": "first_k_dense_replace",
            "dense_ffn": "intermediate_size", "ffn": "moe_intermediate_size", "experts_held": "n_routed_experts",
            "experts_per_tok": "num_experts_per_tok", "n_group": "n_group", "topk_group": "topk_group",
            "vocab": "vocab_size", "max_len": "max_position_embeddings"}
    assert {k: int(unit[k]) for k in ints} == {k: c[v] for k, v in ints.items()}
    assert int(unit["experts"]) == c["published"]["n_routed_experts"]  # the router keeps its width
    assert int(unit["first_expert"]) == c["share"]["first_expert"] == 0 and c["share"]["chips"] == 16
    rs = c["rope_scaling"]
    assert (float(unit["routed_scale"]), float(unit["rope_theta"]), float(unit["yarn_factor"]),
            int(unit["yarn_original"]), float(unit["yarn_beta_fast"]), float(unit["yarn_beta_slow"]),
            float(unit["mscale_all_dim"]), float(unit["rms_eps"])) == (
        c["routed_scaling_factor"], c["rope_theta"], rs["factor"], rs["original_max_position_embeddings"],
        rs["beta_fast"], rs["beta_slow"], rs["mscale_all_dim"], c["rms_norm_eps"])
    assert rs["mscale"] == rs["mscale_all_dim"] and c["scoring_func"] == "sigmoid" and c["n_shared_experts"] == 1
    tpu = c["deployment"]["spec"]["predictors"][0]["tpu"]
    assert set(tpu) == {"max_batch", "batch_buckets", "dtype", "decode_slots", "decode_prefix_slots",
                        "decode_prefill_chunk", "decode_kv_page_size", "decode_kv_pages"}  # no new key
    per_slot = -(-(int(unit["seq"]) + int(unit["max_new_tokens"])) // tpu["decode_kv_page_size"])
    shared = found["traffic"]["shared_prefix_len"] // tpu["decode_kv_page_size"]
    assert (per_slot, shared) == (532, 512)
    # the pinned document, every slot's own pages, the priming request's whole context, the junk page
    assert tpu["decode_kv_pages"] >= shared + tpu["decode_slots"] * (per_slot - shared) + per_slot + 1
    assert tpu["dtype"] == unit["param_dtype"] == "bfloat16" and c["reference"]["n_head"] == 64
    assert int(unit["seq"]) == found["traffic"]["prompt_len"] and tpu["decode_slots"] == found["traffic"]["clients"]
    assert max(max(lane) for lane in found["traffic"]["lanes"]) <= int(unit["max_new_tokens"])


def test_new_metrics_list_only_the_new_cell_and_the_older_cells_keep_their_places(found):
    bench = found["bench"]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL] and by_name[name]["layer"] == "kernels"
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", name + ".py"))
    for name in ("step_roofline", "step_roofline.moe", "step_roofline.ssm", "moe_device_ms", "moe_experts_roofline",
                 "attn_pages_read_pct", "ssm_device_ms", "kv_gather_device_ms"):
        assert CELL not in by_name[name]["workloads"]  # the other families' counts and names
    for name in ("step_scoped_pct", "prefix_saved_pct", "dense_device_ms", "attn_device_ms", "hbm_peak_gb",
                 "kv_write_device_ms", "step_device_ms", "chunk_device_ms", "device_idle_pct.gen", "recompiles.gen"):
        assert by_name[name]["workloads"][-1] == CELL  # appended
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert e2e["tokens_per_s"]["workloads"][-1] == CELL and e2e["itl_p95_ms"]["workloads"][-1] == CELL
    assert CELL not in e2e["itl_p95_closed_ms"]["workloads"]
    names = [w["name"] for w in bench["workloads"]]
    assert names[:4] == ["gpt2-large.batch-unshared", "gpt2-large.sysprompt-open",
                         "mellum2-12b-a2.5b.repo-context-closed", "granite-4.0-h-micro.chat-closed-64"]
    assert names.index(CELL) == 4  # after the four it found, wherever later cells go


# ------------------------------------------------------------ nested scopes


def _events():
    j, c = "jit(_fused_step)/jit(main)/", "jit(_fused_chunk)/jit(main)/"
    ops = [("fusion", 0.10, 0.01, j + "qkv/mla_q/dot_general:"), ("fusion", 0.11, 0.01, j + "qkv/mla_kv/dot_general:"),
           ("fusion", 0.12, 0.01, j + "qkv/rope/mul:"), ("fusion", 0.13, 0.02, j + "attn/mla_absorb/dot_general:"),
           ("while", 0.15, 0.10, j + "attn/mla_core/while:"),
           ("fusion", 0.16, 0.04, j + "attn/mla_core/while/body/gather:"),  # nested in the while: self time
           ("fusion", 0.25, 0.01, j + "attn/mla_absorb/dot_general:"),
           ("fusion", 0.26, 0.03, j + "mlp/moe_experts/dot_general:"), ("fusion", 0.29, 0.01, j + "mlp/shared_expert/dot_general:"),
           ("fusion", 0.30, 0.02, j + "mlp/dense/dot_general:"), ("fusion", 0.32, 0.02, j + "attn_out/dot_general:"),
           ("while", 0.50, 0.10, c + "attn/cond/branch_1_fun/mla_core/while:"),
           ("fusion", 0.52, 0.06, c + "attn/cond/branch_1_fun/mla_core/while/body/mla_expand/dot_general:")]
    mods = [("jit__fused_step", 0.10, 0.30), ("jit__fused_chunk", 0.50, 0.20), ("jit__fused_step", 0.95, 0.30)]
    return {"devices": {"/device:TPU:0": {"ops": [list(o) for o in ops], "modules": [list(m) for m in mods]}},
            "host": [[scopes.WINDOW, 0.0, 1.0, "", {}]], "op_name_stat": "tf_op"}


def test_nested_keys_take_the_innermost_name_and_reduce_by_self_time():
    k = scopes_mla.nested_key
    assert k("jit(_fused_step)/jit(main)/attn/mla_core/while/body/gather:") == "mla_core"
    assert k("jit(_fused_chunk)/jit(main)/attn/cond/branch_1_fun/mla_core/while/body/mla_expand/dot_general:") == "mla_expand"
    assert k("jit(_fused_step)/jit(main)/mlp/moe_router/top_k:") == "moe_router"
    assert k("jit(_fused_step)/jit(main)/attn_out/dot_general:") is None and k("") is None
    step = scopes_mla.by_nested(_events(), scopes_mla.STEP_MARK)
    assert step["dispatches"] == 1  # the second step is cut by the slice's edge
    assert step["by"] == pytest.approx({"mla_q": 0.01, "mla_kv": 0.01, "rope": 0.01, "mla_absorb": 0.03, "mla_core": 0.10,
                                        "moe_experts": 0.03, "shared_expert": 0.01, "dense": 0.02})
    chunk = scopes_mla.by_nested(_events(), scopes_mla.CHUNK_MARK)
    assert chunk == {"dispatches": 1, "by": pytest.approx({"mla_core": 0.04, "mla_expand": 0.06})}
    other = _events()  # the sparse-expert family has the moe_* names and none of these: not this reader's program
    for o in other["devices"]["/device:TPU:0"]["ops"]:
        o[3] = o[3].replace("mla_", "x_")
    assert scopes_mla.by_nested(other, scopes_mla.STEP_MARK) is None
    assert scopes_mla.nested_ms({"trace": None}, "step", "mla_core") is None


def test_recorded_chip_trace_reads_latent_attention_inside_the_old_scopes():
    """A piece of the new cell's traced run (my chip run, PR 37): the nested
    names are found, their time lies inside what the nine scopes' readers give
    ``attn``, ``qkv`` and ``mlp``, the walk over the latent rows is the larger
    part of attention, and the share of the roofline from this piece's own
    time stays under 100."""
    with open(os.path.join(BENCH, "harness", "fixtures", "trace_mla.json")) as f:
        events = scopes.expanded(json.load(f))
    step = scopes_mla.by_nested(events, scopes_mla.STEP_MARK)
    assert step and step["dispatches"] >= 1
    assert {"mla_q", "mla_kv", "rope", "mla_absorb", "mla_core", "shared_expert", "dense", "moe_experts"} <= set(step["by"])
    assert "mla_expand" not in step["by"]  # no expansion in a step
    old = scopes.step_by_scope(events)
    per = lambda *keys: sum(step["by"][key] for key in keys) / step["dispatches"]  # noqa: E731
    assert per("mla_absorb", "mla_core") <= scopes.scoped_s(old, "attn") / old["dispatches"] + 1e-9
    assert per("mla_q", "mla_kv", "rope") <= scopes.scoped_s(old, "qkv") / old["dispatches"] + 1e-9
    assert per("moe_experts", "shared_expert", "dense") <= scopes.scoped_s(old, "mlp") / old["dispatches"] + 1e-9
    assert per("mla_core") > per("mla_absorb")
    flops, nbytes = opsbytes_mla.mla_decode(ctx_rows=64 * 8400, rows=64, **ATTN)
    assert 0 < 100.0 * opsbytes_mla.least_seconds("TPU v5 lite", flops, nbytes) / per("mla_core", "mla_absorb") <= 100.0
    chunk = scopes_mla.by_nested(events, scopes_mla.CHUNK_MARK)
    assert chunk is None or "mla_core" in chunk["by"]


def _frame(ctx=0, rows=0, hit=0, load=0, local=0, chunk_ns=0, mode="plain"):
    return types.SimpleNamespace(mla_ctx_rows=ctx, moe_rows=rows, moe_experts_hit=hit, moe_load_max=load,
                                 moe_local_picks=local, mode=mode, busy_ns=(chunk_ns, 1000, 0, 0, 0))


def test_counts_read_step_only_rounds_and_a_program_without_them_gives_none():
    parent = types.SimpleNamespace(mode="plain", busy_ns=(0, 1000, 0, 0, 0), moe_rows=16)  # no mla field: another family
    o = {"frames": [_frame(500000, 64, 66, 30, 190), _frame(540000, 64, 68, 34, 194),
                    _frame(600000, 66, 70, 40, 250, chunk_ns=5), parent],
         "config": cells.resolve(ROOT, CELL)["config"], "geometry": {"hidden": 7168, "layers": 7, "ffn": 2048, "vocab": 20480}}
    m = scopes_mla.step_means(o)
    assert m == {"rows": 64, "ctx_rows": 520000, "experts_hit": 67, "load_max": 32, "local_picks": 192}
    assert scopes_mla.step_means({"frames": [parent]}) is None
    assert scopes_mla.published(o) == SIZES
    readers = {n: cells.load_module(ROOT, cells.load_bench(ROOT), "layer_metrics", n) for n in NEW}
    assert readers["moe_local_pick_pct"].read(o) == pytest.approx(100 * 192 / (64 * 8 * 6))  # 6.25: 12 of 192
    assert readers["moe_held_hit_pct"].read(o) == pytest.approx(100 * 67 / 72)
    for name, r in readers.items():  # a program without the counters or the scopes: nothing, and no error
        assert r.read({"frames": [parent], "trace": None, "config": o["config"], "geometry": o["geometry"]}) is None, name


# ------------------------------------------------------- operations and bytes


def test_the_issues_bytes_and_intensity_come_out_of_the_count():
    ctx = 64 * 8400
    flops, nbytes = opsbytes_mla.mla_decode(ctx_rows=ctx, rows=64, **ATTN)
    assert nbytes == pytest.approx(4.33e9 + 7 * 16.8e6, rel=0.01)  # ISSUE 37: 64 x 8.4k x 1152 B x 7, + kv_b a layer
    assert flops / nbytes == pytest.approx(121, rel=0.03)  # 64 heads share a row: 2 x 64 x 1088 FLOP over 1152 B
    assert nbytes / 819e9 > flops / 197e12  # memory-bound, by half
    flops, nbytes = opsbytes_mla.mla_decoder_step(**SIZES, rows=64, ctx_rows=ctx, experts_hit=67, local_picks=192)
    weights = 7 * 101.1e6 + 396.4e6 + 6 * (44.04e6 + 1.38e6) + 146.8e6 + 67 * 44.04e6  # layers, dense MLP, shared + router, head, experts hit
    assert nbytes == pytest.approx(2 * weights + 4.33e9 + 64 * 7168 * 2 + 7 * 64 * 576 * 2, rel=0.005)
    assert opsbytes_mla.least_seconds("TPU v5 lite", flops, nbytes) == pytest.approx(nbytes / 819e9)
    assert nbytes / 819e9 == pytest.approx(16.2e-3, rel=0.03)  # the step's least time at the cell's shapes
    # an expert that is held and not hit is not read; an absent pick costs nothing
    fewer = opsbytes_mla.mla_decoder_step(**SIZES, rows=64, ctx_rows=ctx, experts_hit=60, local_picks=192)[1]
    assert nbytes - fewer == 7 * 3 * 7168 * 2048 * 2
    # a slot that does not generate reads no latent rows
    less = opsbytes_mla.mla_decoder_step(**SIZES, rows=32, ctx_rows=ctx // 2, experts_hit=67, local_picks=96)[1]
    assert nbytes - less == pytest.approx(7 * (ctx // 2) * 576 * 2 + 32 * 7168 * 2 + 7 * 32 * 576 * 2)


def test_a_share_computed_from_the_counts_cannot_pass_100_at_the_least_time():
    flops, nbytes = opsbytes_mla.mla_decode(ctx_rows=64 * 8400, rows=64, **ATTN)
    least = opsbytes_mla.least_seconds("TPU v5 lite", flops, nbytes)
    assert 100.0 * least / (nbytes / 819e9) == pytest.approx(100.0)
    with pytest.raises(KeyError):
        opsbytes_mla.least_seconds("cpu", flops, nbytes)  # a device without published peaks is an error
