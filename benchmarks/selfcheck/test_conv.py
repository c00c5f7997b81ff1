"""What PR 41 adds to the benchmark, checked without the program: the
``agent-context-closed-64`` mix, the ``lfm2-24b-a2b`` configuration's file
against the catalog's row, the short-convolution family's nested-scope
reduction (on a piece of a recorded chip trace,
``harness/fixtures/trace_conv.json``) and the step's operation and byte
counts."""

import json
import os
import types

import pytest
from conftest import BENCH, ROOT

from harness import cells, opsbytes_conv, scopes, scopes_conv, traffic

CELL = "lfm2-24b-a2b.agent-context-closed-64"
SIZES = dict(hidden=2048, layers=40, ffn=1536, vocab=65536, attn_layers=10, heads=32, kv_heads=8, head_dim=64, taps=3,
             dense_layers=2, dense_ffn=11776, experts=64, held=8, per_tok=4)
NEW = ("conv_device_ms", "conv_chunk_device_ms", "conv_mix_roofline", "step_roofline.conv", "moe_held_chunk_device_ms",
       "moe_held_hit_pct.conv", "moe_local_pick_pct.conv")
GEOMETRY = {"hidden": 2048, "layers": 40, "ffn": 1536, "vocab": 65536}


@pytest.fixture(scope="module")
def found():
    return cells.resolve(ROOT, CELL)


@pytest.mark.parametrize("seeds", [(1, 2), (7, 2900000123)])
def test_two_seeds_offer_the_same_work_over_one_shared_prompt(found, seeds):
    a, b = (traffic.build_plan(found["traffic"], seed=s, seconds=51) for s in seeds)
    assert traffic.offered_work(a) == traffic.offered_work(b) and a == b  # the seed draws the ids alone
    first = a["clients"][0][0]
    assert (first["prompt_len"], first["prefix_len"], first["cache_prefix"], first["prefix"]) == (2048, 1024, 1024, 0)
    ids_a, ids_b = (traffic.token_ids(s, 65536, first) for s in seeds)
    assert ids_a != ids_b and 65000 < max(ids_a) < 65536  # drawn from the whole vocabulary
    other = dict(first, uid=first["uid"] + 1)
    assert traffic.token_ids(seeds[0], 65536, other)[:1024] == ids_a[:1024]  # one system prompt
    assert traffic.token_ids(seeds[0], 65536, other)[1024:] != ids_a[1024:]  # its own kilobyte of context


def test_the_mix_is_the_chat_mixes_lanes_behind_a_shared_prompt_and_a_long_unique_tail(found):
    t = found["traffic"]
    with open(os.path.join(BENCH, "traffic", "chat-closed-64.json")) as f:
        chat = json.load(f)
    assert t["lanes"] == chat["lanes"] and t["output_table"] == chat["output_table"]
    assert (t["generator"], t["protocol"], t["clients"], t["cycle_from"], t["ramp_s"]) == ("closed", "sse", 64, 1, 8.0)
    assert (t["prompt_len"], t["shared_prefix_len"], t["cache_prefix"]) == (2048, 1024, 1024) and "prefixes" not in t


def test_configuration_file_is_the_catalogs_row_but_for_the_experts_held(found):
    c = found["config"]
    assert c["reduced"] == ["num_experts"] and c["published"] == {"num_experts": 64} and c["num_experts"] == 8
    # the floors of a cut: every layer here, 8 experts or more, the whole vocabulary
    assert c["num_hidden_layers"] == len(c["layer_types"]) == 40 and c["share"]["layers_here"] == "all 40"
    assert c["share"] == {"chips": 8, "first_expert": 0, "experts_held": 8, "layers_here": "all 40", "vocab_rows": 65536}
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "LFM2-24B-A2B")
        assert c["source"] == row["source_url"]
        assert {k for k in row["config"] if c[k] != row["config"][k]} == {"num_experts"}  # no width touched
        assert row["config"]["num_experts"] == c["published"]["num_experts"]
    unit = {p["name"]: p["value"] for p in c["deployment"]["spec"]["predictors"][0]["graph"]["parameters"]}
    assert unit["model"] == "conv_decoder"
    ints = {"hidden": "hidden_size", "layers": "num_hidden_layers", "heads": "num_attention_heads",
            "kv_heads": "num_key_value_heads", "conv_taps": "conv_L_cache", "dense_layers": "num_dense_layers",
            "dense_ffn": "intermediate_size", "ffn": "moe_intermediate_size", "experts_held": "num_experts",
            "experts_per_tok": "num_experts_per_tok", "vocab": "vocab_size", "max_len": "max_position_embeddings"}
    assert {k: int(unit[k]) for k in ints} == {k: c[v] for k, v in ints.items()}
    assert int(unit["experts"]) == c["published"]["num_experts"]  # the router keeps its width
    assert int(unit["head_dim"]) * c["num_attention_heads"] == c["hidden_size"]
    assert [int(i) for i in unit["attn_layers"].split(",")] == [i for i, k in enumerate(c["layer_types"]) if k == "full_attention"]
    assert int(unit["first_expert"]) == c["share"]["first_expert"]
    assert (float(unit["routed_scale"]), float(unit["rope_theta"]), float(unit["rms_eps"])) == (
        c["routed_scaling_factor"], c["rope_parameters"]["rope_theta"], c["norm_eps"])
    assert c["use_expert_bias"] and c["norm_topk_prob"] and not c["conv_bias"]
    tpu = c["deployment"]["spec"]["predictors"][0]["tpu"]
    assert set(tpu) == {"max_batch", "batch_buckets", "dtype", "decode_slots", "decode_prefix_slots",
                        "decode_prefill_chunk", "decode_kv_page_size", "decode_kv_pages"}  # no new key
    per_slot = -(-(int(unit["seq"]) + int(unit["max_new_tokens"])) // tpu["decode_kv_page_size"])
    shared = found["traffic"]["shared_prefix_len"] // tpu["decode_kv_page_size"]
    assert (per_slot, shared) == (144, 64)
    # the pinned prompt, every slot's own pages, the priming request's whole context, the junk page
    assert tpu["decode_kv_pages"] >= shared + tpu["decode_slots"] * (per_slot - shared) + per_slot + 1
    assert tpu["dtype"] == unit["param_dtype"] == "bfloat16" and c["reference"]["n_head"] == 32
    assert int(unit["seq"]) == found["traffic"]["prompt_len"] and tpu["decode_slots"] == found["traffic"]["clients"]
    assert max(max(lane) for lane in found["traffic"]["lanes"]) <= int(unit["max_new_tokens"])


def test_new_metrics_list_only_the_new_cell_and_every_older_list_only_grew(found):
    bench = found["bench"]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL] and by_name[name]["layer"] == "kernels"
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", name + ".py"))
    for name in ("step_roofline", "step_roofline.moe", "step_roofline.ssm", "step_roofline.mla", "moe_device_ms",
                 "moe_experts_roofline", "attn_pages_read_pct", "ssm_device_ms", "mla_device_ms", "moe_held_device_ms",
                 "moe_held_hit_pct", "moe_local_pick_pct"):
        assert CELL not in by_name[name]["workloads"]  # the other families' counts and names
    for name in ("step_scoped_pct", "prefix_saved_pct", "dense_device_ms", "attn_device_ms", "hbm_peak_gb",
                 "kv_write_device_ms", "kv_gather_device_ms", "step_device_ms", "chunk_device_ms",
                 "device_idle_pct.gen", "recompiles.gen", "ssm_state_restore_pct"):
        assert by_name[name]["workloads"].count(CELL) == 1  # appended, wherever later cells go
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL in e2e["tokens_per_s"]["workloads"] and CELL in e2e["itl_p95_ms"]["workloads"]
    assert CELL not in e2e["itl_p95_closed_ms"]["workloads"]
    names = [w["name"] for w in bench["workloads"]]
    assert names.index(CELL) == 5  # after the five it found, wherever later cells go


# ------------------------------------------------------------ nested scopes


def _events():
    j, c = "jit(_fused_step)/jit(main)/", "jit(_fused_chunk)/jit(main)/"
    ops = [("fusion", 0.10, 0.01, j + "qkv/conv_in/dot_general:"), ("fusion", 0.11, 0.02, j + "attn/conv_mix/mul:"),
           ("slice-done bf16[2048,2048]", 0.135, 0.004, ""),  # the wait for conv_out's weights: counted with it
           ("copy f32[8]", 0.145, 0.002, ""),  # a plain copy without a name is work nobody owns
           ("copy-done bf16[8]", 0.165, 0.003, ""),  # a wait before an op under one of the nine alone: nobody's here
           ("fusion", 0.13, 0.005, j + "attn/conv_mix/scatter:"), ("fusion", 0.14, 0.005, j + "attn_out/conv_out/dot_general:"),
           ("fusion", 0.15, 0.01, j + "qkv/qk_norm/rsqrt:"), ("fusion", 0.16, 0.005, j + "qkv/rope/mul:"),
           ("fusion", 0.17, 0.03, j + "attn/dot_general:"), ("fusion", 0.20, 0.02, j + "mlp/dense/dot_general:"),
           ("fusion", 0.22, 0.01, j + "mlp/moe_router/top_k:"), ("fusion", 0.23, 0.04, j + "mlp/moe_experts/dot_general:"),
           ("fusion", 0.27, 0.01, j + "mlp/moe_combine/add:"),
           ("fusion", 0.50, 0.05, c + "attn/conv_mix/mul:"), ("while", 0.55, 0.10, c + "mlp/moe_experts/while:"),
           ("fusion", 0.56, 0.06, c + "mlp/moe_experts/while/body/moe_dispatch/sort:")]  # nested in the while: self time
    mods = [("jit__fused_step", 0.10, 0.30), ("jit__fused_chunk", 0.50, 0.20), ("jit__fused_step", 0.95, 0.30)]
    return {"devices": {"/device:TPU:0": {"ops": [list(o) for o in ops], "modules": [list(m) for m in mods]}},
            "host": [[scopes.WINDOW, 0.0, 1.0, "", {}]], "op_name_stat": "tf_op"}


def test_nested_keys_take_the_innermost_name_and_reduce_by_self_time():
    k = scopes_conv.nested_key
    assert k("jit(_fused_step)/jit(main)/attn/conv_mix/scatter:") == "conv_mix"
    assert k("jit(_fused_chunk)/jit(main)/mlp/moe_experts/while/body/moe_dispatch/sort:") == "moe_dispatch"
    assert k("jit(_fused_step)/jit(main)/attn/dot_general:") is None and k("") is None
    step = scopes_conv.by_nested(_events(), scopes_conv.STEP_MARK)
    assert step["dispatches"] == 1  # the second step is cut by the slice's edge
    assert step["by"] == pytest.approx({"conv_in": 0.01, "conv_mix": 0.025, "conv_out": 0.009, "qk_norm": 0.01, "rope": 0.005,
                                        "dense": 0.02, "moe_router": 0.01, "moe_experts": 0.04, "moe_combine": 0.01})
    chunk = scopes_conv.by_nested(_events(), scopes_conv.CHUNK_MARK)
    assert chunk == {"dispatches": 1, "by": pytest.approx({"conv_mix": 0.05, "moe_experts": 0.04, "moe_dispatch": 0.06})}
    other = _events()  # the latent family has the moe_* names and none of these: not this reader's program
    for o in other["devices"]["/device:TPU:0"]["ops"]:
        o[3] = o[3].replace("conv_", "x_")
    assert scopes_conv.by_nested(other, scopes_conv.STEP_MARK) is None
    assert scopes_conv.nested_ms({"trace": None}, "step", "conv_mix") is None


def test_recorded_chip_trace_reads_the_conv_operator_inside_the_old_scopes():
    """A piece of the new cell's traced run (my chip run, PR 41): the nested
    names are found in step and chunk, their time lies inside what the nine
    scopes' readers give ``qkv``, ``attn``, ``attn_out`` and ``mlp``, and the
    operator's share of its roofline from this piece's own time stays under
    100."""
    with open(os.path.join(BENCH, "harness", "fixtures", "trace_conv.json")) as f:
        events = scopes.expanded(json.load(f))
    step = scopes_conv.by_nested(events, scopes_conv.STEP_MARK)
    assert step and step["dispatches"] >= 1
    assert {"conv_in", "conv_mix", "conv_out", "qk_norm", "rope", "dense", "moe_router", "moe_experts"} <= set(step["by"])
    old = scopes.step_by_scope(events)
    per = lambda *keys: sum(step["by"][key] for key in keys) / step["dispatches"]  # noqa: E731
    assert per("conv_in", "qk_norm", "rope") <= scopes.scoped_s(old, "qkv") / old["dispatches"] + 1e-9
    assert per("conv_mix") <= scopes.scoped_s(old, "attn") / old["dispatches"] + 1e-9
    assert per("conv_out") <= scopes.scoped_s(old, "attn_out") / old["dispatches"] + 1e-9
    assert per("dense", "moe_router", "moe_experts") <= scopes.scoped_s(old, "mlp") / old["dispatches"] + 1e-9
    flops, nbytes = opsbytes_conv.conv_mix(hidden=2048, conv_layers=30, taps=3, rows=64)
    assert 0 < 100.0 * opsbytes_conv.least_seconds("TPU v5 lite", flops, nbytes) / per(*scopes_conv.CONV) <= 100.0
    chunk = scopes_conv.by_nested(events, scopes_conv.CHUNK_MARK)
    assert chunk is None or "conv_mix" in chunk["by"]


def _frame(conv=0, rows=0, hit=0, load=0, local=0, chunk_ns=0, mode="plain"):
    return types.SimpleNamespace(conv_rows=conv, moe_rows=rows, moe_experts_hit=hit, moe_load_max=load,
                                 moe_local_picks=local, mode=mode, busy_ns=(chunk_ns, 1000, 0, 0, 0))


def test_counts_read_step_only_rounds_and_a_program_without_them_gives_none(found):
    parent = types.SimpleNamespace(mode="plain", busy_ns=(0, 1000, 0, 0, 0), moe_rows=16)  # no conv field: another family
    o = {"frames": [_frame(60, 60, 296, 300, 1100), _frame(64, 64, 300, 340, 1300),
                    _frame(66, 578, 304, 900, 9000, chunk_ns=5), parent],
         "config": found["config"], "geometry": GEOMETRY}
    m = scopes_conv.step_means(o)
    assert m == {"rows": 62, "experts_hit": 298, "load_max": 320, "local_picks": 1200}
    assert scopes_conv.step_means({"frames": [parent]}) is None
    assert scopes_conv.published(o) == SIZES
    readers = {n: cells.load_module(ROOT, cells.load_bench(ROOT), "layer_metrics", n) for n in NEW}
    assert readers["moe_local_pick_pct.conv"].read(o) == pytest.approx(100 * 1200 / (62 * 4 * 38))  # 12.7: 8 of 64
    assert readers["moe_held_hit_pct.conv"].read(o) == pytest.approx(100 * 298 / (38 * 8))
    for name, r in readers.items():  # a program without the counters or the scopes: nothing, and no error
        assert r.read({"frames": [parent], "trace": None, "config": o["config"], "geometry": o["geometry"]}) is None, name


# ------------------------------------------------------- operations and bytes


def test_the_issues_bytes_come_out_of_the_count():
    flops, nbytes = opsbytes_conv.conv_mix(hidden=2048, conv_layers=30, taps=3, rows=64)
    assert nbytes == pytest.approx(30 * (33.57e6 + 2 * 64 * 2 * 2048 * 4), rel=0.001)  # ISSUE 41: 30 x 33.6 MB + the rows' state
    assert flops / nbytes == pytest.approx(64.0, rel=0.08)  # 64 rows x 2 FLOP a weight of 2 bytes: the bytes bind (240 a byte)
    assert opsbytes_conv.least_seconds("TPU v5 lite", flops, nbytes) == pytest.approx(nbytes / 819e9)
    ctx = 64 * 2100
    flops, nbytes = opsbytes_conv.conv_decoder_step(**SIZES, rows=64, ctx_tokens=ctx, experts_hit=38 * 8, local_picks=38 * 32)
    weights = 30 * 16.78e6 + 10 * 10.49e6 + 2 * 72.35e6 + 38 * 0.131e6 + 134.2e6 + 38 * 8 * 9.437e6  # ISSUE 41's table: 3,761 M
    assert weights == pytest.approx(3761e6, rel=0.002)
    kv = ctx * 20480 + 64 * 20480
    assert nbytes == pytest.approx(2 * weights + kv + 30 * 2 * 64 * 2 * 2048 * 4 + 64 * 2048 * 2, rel=0.003)
    assert opsbytes_conv.least_seconds("TPU v5 lite", flops, nbytes) == pytest.approx(nbytes / 819e9)
    assert nbytes / 819e9 == pytest.approx(12.6e-3, rel=0.03)  # ISSUE 41: >= 12.6 ms at 819 GB/s
    # an expert that is held and not hit is not read; an absent pick costs nothing
    fewer = opsbytes_conv.conv_decoder_step(**SIZES, rows=64, ctx_tokens=ctx, experts_hit=38 * 8 - 5, local_picks=38 * 32)[1]
    assert nbytes - fewer == 5 * 3 * 2048 * 1536 * 2
    # a slot that does not generate reads no K/V rows and advances no state
    less = opsbytes_conv.conv_decoder_step(**SIZES, rows=32, ctx_tokens=ctx // 2, experts_hit=38 * 8, local_picks=38 * 16)[1]
    assert nbytes - less == pytest.approx((ctx // 2 + 32) * 20480 + 30 * 2 * 32 * 2 * 2048 * 4 + 32 * 2048 * 2)


def test_a_share_computed_from_the_counts_cannot_pass_100_at_the_least_time():
    flops, nbytes = opsbytes_conv.conv_mix(hidden=2048, conv_layers=30, taps=3, rows=64)
    least = opsbytes_conv.least_seconds("TPU v5 lite", flops, nbytes)
    assert 100.0 * least / (nbytes / 819e9) == pytest.approx(100.0)
    with pytest.raises(KeyError):
        opsbytes_conv.least_seconds("cpu", flops, nbytes)  # a device without published peaks is an error
