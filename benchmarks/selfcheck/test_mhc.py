"""What PR 43 adds to the benchmark, checked without the program: the
``xing4.0-29b-a4b`` configuration's file against the catalog's row, the
multi-stream residual path's nested-scope reduction with its byte counts (by
hand, and on a piece of a recorded chip trace,
``harness/fixtures/trace_mhc.json``), the stream traffic's operation and byte
counts and the five readers. Names are pinned, positions are not."""

import json
import os
import types

import pytest
from conftest import BENCH, ROOT

from harness import cells, opsbytes_mhc, scopes, scopes_mhc, scopes_mla

CELL = "xing4.0-29b-a4b.agent-context-closed-64"
NEW = ("mhc_device_ms", "mhc_chunk_device_ms", "mhc_mix_roofline", "mhc_chunk_state_passes", "mhc_resid_ppm")
LATENT = ("mla_device_ms", "mla_chunk_device_ms", "mla_decode_roofline", "step_roofline.mla", "shared_expert_device_ms",
          "moe_local_pick_pct", "moe_held_hit_pct", "moe_held_device_ms", "mla_run_pages_pct")
GEOMETRY = {"hidden": 3584, "layers": 20, "ffn": 1024, "vocab": 16384}


@pytest.fixture(scope="module")
def found():
    return cells.resolve(ROOT, CELL)


def test_configuration_file_is_the_catalogs_row_but_for_the_three_cuts(found):
    c = found["config"]
    assert c["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert c["published"] == {"num_hidden_layers": 40, "n_routed_experts": 64, "vocab_size": 131072}
    assert (c["num_hidden_layers"], c["n_routed_experts"], c["vocab_size"]) == (20, 8, 16384)
    # the floors of a cut: the dense layers + 4 or more of what follows, 8 experts or more, an eighth of the vocabulary
    assert c["num_hidden_layers"] - c["first_k_dense_replace"] >= 4 and c["vocab_size"] * 8 >= c["published"]["vocab_size"]
    assert (c["share"]["chips"], c["share"]["stages"], c["share"]["experts_held"], c["share"]["first_expert"]) == (8, 2, 8, 0)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Xing4.0-29B-A4B")
        assert c["source"] == row["source_url"]
        assert {k for k in row["config"] if c[k] != row["config"][k]} == set(c["reduced"])  # no width touched
        assert all(row["config"][k] == v for k, v in c["published"].items())
    unit = {p["name"]: p["value"] for p in c["deployment"]["spec"]["predictors"][0]["graph"]["parameters"]}
    assert unit["model"] == "mla_decoder"  # one family: the fourth, with more parameters
    ints = {"hidden": "hidden_size", "layers": "num_hidden_layers", "heads": "num_attention_heads", "q_rank": "q_lora_rank",
            "kv_rank": "kv_lora_rank", "nope_dim": "qk_nope_head_dim", "rope_dim": "qk_rope_head_dim", "v_dim": "v_head_dim",
            "dense_layers": "first_k_dense_replace", "dense_ffn": "intermediate_size", "ffn": "moe_intermediate_size",
            "experts_held": "n_routed_experts", "experts_per_tok": "num_experts_per_tok", "vocab": "vocab_size",
            "max_len": "max_position_embeddings", "hc_mult": "hc_mult", "hc_sinkhorn_iters": "hc_sinkhorn_iters"}
    assert {k: int(unit[k]) for k in ints} == {k: c[v] for k, v in ints.items()}
    assert int(unit["experts"]) == c["published"]["n_routed_experts"]  # the router keeps its width
    assert (float(unit["hc_eps"]), float(unit["hc_res_clamp"])) == (c["hc_eps"], c["mhc_h_res_clamp_max"])
    assert c["mhc_h_res_clamp_min"] == -c["mhc_h_res_clamp_max"]
    rs = c["rope_scaling"]
    assert (float(unit["routed_scale"]), float(unit["yarn_factor"]), int(unit["yarn_original"])) == (
        c["routed_scaling_factor"], rs["factor"], rs["original_max_position_embeddings"])
    # noaux_tc without groups: the biased gate, and "no groups" said so that a tree without it refuses at once
    assert c["topk_method"] == "noaux_tc" and (c["n_group"], c["topk_group"]) == (1, 1)
    assert (unit["gate_bias"], unit["n_group"], unit["topk_group"]) == ("true", "0", "0")
    tpu = c["deployment"]["spec"]["predictors"][0]["tpu"]
    assert set(tpu) == {"max_batch", "batch_buckets", "dtype", "decode_slots", "decode_prefix_slots",
                        "decode_prefill_chunk", "decode_kv_page_size", "decode_kv_pages"}  # no new key
    per_slot = -(-(int(unit["seq"]) + int(unit["max_new_tokens"])) // tpu["decode_kv_page_size"])
    shared = found["traffic"]["shared_prefix_len"] // tpu["decode_kv_page_size"]
    assert (per_slot, shared) == (144, 64)
    assert tpu["decode_kv_pages"] >= shared + tpu["decode_slots"] * (per_slot - shared) + per_slot + 1
    assert tpu["dtype"] == unit["param_dtype"] == "bfloat16" and c["reference"]["n_head"] == c["num_attention_heads"]
    assert int(unit["seq"]) == found["traffic"]["prompt_len"] and tpu["decode_slots"] == found["traffic"]["clients"]
    assert any("multi-token-prediction" in d for d in c["departures"])


def test_the_cell_rides_the_traffic_file_the_benchmark_has(found):
    bench = found["bench"]
    mine = next(w for w in bench["workloads"] if w["name"] == CELL)
    other = next(w for w in bench["workloads"] if w["name"] == "lfm2-24b-a2b.agent-context-closed-64")
    assert mine["traffic"] == other["traffic"] == "agent-context-closed-64" and mine["chips"] == 1
    assert sum(w["traffic"] == mine["traffic"] for w in bench["workloads"]) == 2  # one mix, two configurations


def test_new_metrics_list_only_the_new_cell_and_the_latent_familys_lists_grew_by_it(found):
    bench = found["bench"]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL] and by_name[name]["layer"] == "kernels"
        assert by_name[name]["moves"] == "itl_p95_ms"
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", name + ".py"))
    k1 = "a.x-k1.doc-qa-closed-64"
    for m in bench["per_layer"]:
        if k1 in m["workloads"]:  # the same family, scopes and closed loop: every list that holds the fifth cell
            assert m["workloads"].count(CELL) == 1, m["name"]
    for name in LATENT:
        assert set(by_name[name]["workloads"]) == {k1, CELL}
    for name in ("kv_gather_device_ms", "moe_device_ms", "conv_device_ms", "ssm_device_ms", "step_roofline", "moe_held_hit_pct.conv"):
        assert CELL not in by_name[name]["workloads"]  # the other families' scopes and counts
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL in e2e["tokens_per_s"]["workloads"] and CELL in e2e["itl_p95_ms"]["workloads"]
    assert CELL not in e2e["itl_p95_closed_ms"]["workloads"]  # its bound is the first cell's


# ------------------------------------------------------------ nested scopes


def _events():
    j, c = "jit(_fused_step)/jit(main)/", "jit(_fused_chunk)/jit(main)/"
    ops = [("fusion", 0.10, 0.010, j + "qkv/mhc_map/dot_general:", 4e6),
           ("while", 0.11, 0.020, j + "qkv/mhc_map/while:", 9e9),  # a loop's bytes are its body's ops'
           ("fusion", 0.112, 0.004, j + "qkv/mhc_map/while/body/div:", 1e3),
           ("fusion", 0.118, 0.004, j + "qkv/mhc_map/while/body/reduce_sum:", 1e3),
           ("fusion", 0.13, 0.005, j + "qkv/mhc_pre/add:", 5e6), ("fusion", 0.135, 0.03, j + "qkv/mla_q/dot_general:", 7e6),
           ("fusion", 0.17, 0.02, j + "attn/mla_core/custom-call:", 0.0), ("fusion", 0.19, 0.006, j + "attn_out/mhc_post/add:", 9e6),
           ("fusion", 0.20, 0.01, j + "mlp/mhc_map/rsqrt:", 4e6), ("fusion", 0.21, 0.004, j + "mlp/mhc_pre/add:", 5e6),
           ("fusion", 0.22, 0.04, j + "mlp/moe_experts/dot_general:", 8e7), ("fusion", 0.26, 0.006, j + "mlp/mhc_post/add:", 9e6),
           ("fusion", 0.50, 0.05, c + "mlp/mhc_post/add:", 6e8), ("fusion", 0.55, 0.02, c + "qkv/mhc_pre/add:", 3e8),
           ("fusion", 0.70, 0.09, c + "mlp/mhc_post/add:", 5e9)]  # the other entry's dispatch: left out
    mods = [("jit__fused_step", 0.10, 0.30), ("jit__fused_chunk", 0.50, 0.10), ("jit__fused_chunk", 0.70, 0.10),
            ("jit__fused_chunk", 0.82, 0.10), ("jit__fused_step", 0.95, 0.30)]
    ann = scopes_mhc.CHUNK_ANNOTATION
    host = [[scopes.WINDOW, 0.0, 1.0, "", {}], [ann, 0.49, 0.12, "t", {"rows": "4", "c": "256"}],
            [ann, 0.69, 0.12, "t", {"rows": "64", "c": "256"}], [ann, 0.81, 0.12, "t", {"rows": "4", "c": "256"}]]
    return {"devices": {"/device:TPU:0": {"ops": [list(o) for o in ops], "modules": [list(m) for m in mods]}},
            "host": host, "op_name_stat": "tf_op"}


def test_nested_keys_reduce_by_self_time_and_count_bytes_once():
    k = scopes_mhc.nested_key
    assert k("jit(_fused_step)/jit(main)/qkv/mhc_map/while/body/div:") == "mhc_map"
    assert k("jit(_fused_step)/jit(main)/attn_out/mhc_post/add:") == "mhc_post"
    assert k("jit(_fused_step)/jit(main)/qkv/mla_q/dot_general:") is None and k("") is None
    step = scopes_mhc.by_nested(_events(), scopes_mhc.STEP_MARK)
    assert step["dispatches"] == 1 and step["entry"] is None  # the second step is cut by the slice's edge
    assert step["by"] == pytest.approx({"mhc_map": 0.04, "mhc_pre": 0.009, "mhc_post": 0.012})
    assert step["bytes"] == pytest.approx({"mhc_map": 8e6 + 2e3, "mhc_pre": 1e7, "mhc_post": 1.8e7})
    # the nine scopes' readers hold the operator's time where its scopes are nested
    old = scopes.step_by_scope(_events_plain())
    assert scopes.scoped_s(old, "qkv") == pytest.approx(0.03 + 0.005 + 0.03)
    assert scopes.scoped_s(old, "attn_out") == pytest.approx(0.006) and scopes.scoped_s(old, "attn") == pytest.approx(0.02)
    chunk = scopes_mhc.by_nested(_events(), scopes_mhc.CHUNK_MARK)
    assert chunk["entry"] == (4, 256) and chunk["dispatches"] == 2  # the usual entry's dispatches alone
    assert chunk["by"] == pytest.approx({"mhc_post": 0.05, "mhc_pre": 0.02})
    assert chunk["bytes"] == pytest.approx({"mhc_post": 6e8, "mhc_pre": 3e8})
    other = _events()  # a program without the names: hc_mult 1, the other families, the parent
    for o in other["devices"]["/device:TPU:0"]["ops"]:
        o[3] = o[3].replace("mhc_", "x_")
    assert scopes_mhc.by_nested(other, scopes_mhc.STEP_MARK) is None
    assert scopes_mhc.nested_ms({"trace": None}, "step") is None and scopes_mhc.of_run({}, "chunk") is None
    # the latent family's reader is the attention's alone
    mla = scopes_mla.by_nested(_events_plain(), scopes_mla.STEP_MARK)
    assert set(mla["by"]) == {"mla_q", "mla_core", "moe_experts"}


def _events_plain():
    e = _events()
    for d in e["devices"].values():
        d["ops"] = [o[:4] for o in d["ops"]]
    return e


def test_recorded_chip_trace_reads_the_operator_inside_the_old_scopes():
    """A piece of the new cell's traced run (my chip run, PR 43): the three
    names are found in the step, their time lies inside what the nine scopes'
    readers give ``qkv``, ``attn_out`` and ``mlp``, the attention's reader is
    untouched by them, and the stream traffic's share of its roofline from
    this piece's own time stays under 100."""
    with open(os.path.join(BENCH, "harness", "fixtures", "trace_mhc.json")) as f:
        kept = json.load(f)
    events = scopes.expanded(kept)
    names = kept["names"]
    moved = {(names[a], names[b]): v for a, b, v in kept["op_bytes"]}
    for d in events["devices"].values():
        d["ops"] = [o + [moved.get((o[0], o[3]), 0.0)] for o in d["ops"]]
    step = scopes_mhc.by_nested(events, scopes_mhc.STEP_MARK)
    assert step and step["dispatches"] >= 1 and set(step["by"]) == set(scopes_mhc.NAMES)
    plain = scopes.expanded(kept)
    old = scopes.step_by_scope(plain)
    per = sum(step["by"].values()) / step["dispatches"]
    assert 0 < per <= (scopes.scoped_s(old, "qkv") + scopes.scoped_s(old, "attn_out") + scopes.scoped_s(old, "mlp")) / old["dispatches"]
    mla = scopes_mla.by_nested(plain, scopes_mla.STEP_MARK)
    assert mla and not set(mla["by"]) & set(scopes_mhc.NAMES)
    flops, nbytes = opsbytes_mhc.mhc_mix(rows=64, streams=4, hidden=3584, blocks=40)
    assert 0 < 100.0 * opsbytes_mhc.least_seconds("TPU v5 lite", flops, nbytes) / per <= 100.0
    assert sum(step["bytes"].values()) > 0  # the trace's ops carry their byte counts


# ------------------------------------------------------- counts and readers


def _frame(rows=0, ctx=0, resid=None, chunk_ns=0):
    f = types.SimpleNamespace(moe_rows=rows, mla_ctx_rows=ctx, moe_experts_hit=100, moe_load_max=9, moe_local_picks=500,
                              mode="plain", busy_ns=(chunk_ns, 1000, 0, 0, 0))
    if resid is not None:
        f.mhc_resid_ppm = resid
    return f


def test_the_issues_bytes_come_out_of_the_count():
    flops, nbytes = opsbytes_mhc.mhc_mix(rows=64, streams=4, hidden=3584, blocks=40)
    assert nbytes == 40 * 64 * (2 * 4 * 3584 + 2 * 3584) * 2  # ISSUE 43: blocks x rows x (2 x 4C + 2C) x 2 bytes
    assert nbytes == pytest.approx(183.5e6, rel=0.001) and flops / nbytes == pytest.approx(12.4, rel=0.02)
    assert opsbytes_mhc.least_seconds("TPU v5 lite", flops, nbytes) == pytest.approx(nbytes / 819e9)  # the bytes bind
    assert opsbytes_mhc.state_bytes(rows=1024, streams=4, hidden=3584, blocks=40) == 40 * 1024 * 28672
    with pytest.raises(KeyError):
        opsbytes_mhc.least_seconds("cpu", flops, nbytes)  # a device without published peaks is an error


def test_readers_read_the_counts_and_give_none_without_them(found, monkeypatch):
    o = {"frames": [_frame(60, 120000, 2), _frame(64, 130000, 5), _frame(64, 130000, 40, chunk_ns=5), _frame(64, 130000)],
         "config": found["config"], "geometry": GEOMETRY, "device": {"kind": "TPU v5 lite"}, "trace": {"families": {}}}
    readers = {n: cells.load_module(ROOT, cells.load_bench(ROOT), "layer_metrics", n) for n in NEW}
    assert readers["mhc_resid_ppm"].read(o) == 5.0  # the largest of the step-only rounds: a chunk round's sum stays out
    step = {"dispatches": 2, "entry": None, "by": {"mhc_map": 0.003, "mhc_pre": 0.0004, "mhc_post": 0.0006}, "bytes": {}}
    chunk = {"dispatches": 3, "entry": (4, 256), "by": {"mhc_map": 0.006, "mhc_post": 0.009},
             "bytes": {"mhc_map": 3 * 1.2e9, "mhc_post": 3 * 3.5e9}}
    monkeypatch.setattr(scopes_mhc, "newest_xplane", lambda d: "a traced run's file")
    monkeypatch.setattr(scopes_mhc, "_of_file", lambda path: {"step": step, "chunk": chunk})
    assert readers["mhc_device_ms"].read(o) == pytest.approx(2.0)
    assert readers["mhc_chunk_device_ms"].read(o) == pytest.approx(5.0)
    rows = (60 + 64 + 64) / 3  # the frames' generating rows, step-only rounds (the last frame has no field and still counts)
    _, nbytes = opsbytes_mhc.mhc_mix(rows=rows, streams=4, hidden=3584, blocks=40)
    assert readers["mhc_mix_roofline"].read(o) == pytest.approx(100 * (nbytes / 819e9) / 2.0e-3)
    assert readers["mhc_chunk_state_passes"].read(o) == pytest.approx(4.7e9 / (40 * 1024 * 28672))
    parent = {"frames": [_frame(64, 130000)], "trace": None, "config": found["config"], "geometry": GEOMETRY,
              "device": {"kind": "TPU v5 lite"}}
    for name, r in readers.items():  # a program without the counter or the scopes: nothing, and no error
        assert r.read(parent) is None, name
