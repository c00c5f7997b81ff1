"""What PR 47 adds to the benchmark, checked without the program: the
``laguna-s-2.1`` configuration's file against the catalog's row, the new
nested scopes' reduction (by hand, and on a piece of a recorded chip trace,
``harness/fixtures/trace_window.json``), the held-share step's operation and
byte counts and the nine readers. Names are pinned, positions are not."""

import json
import os
import types

import pytest
from conftest import BENCH, ROOT

from harness import cells, opsbytes_moe_held, scopes, scopes_moe, scopes_win

CELL = "laguna-s-2.1.repo-session-closed-64"
OLDER = "mellum2-12b-a2.5b.repo-context-closed"
BOTH = ("kv_window_released_pct", "kv_window_live_peak_pct", "attn_win_device_ms", "attn_full_device_ms",
        "attn_full_chunk_device_ms")
MINE = ("attn_gate_device_ms", "moe_held_device_ms.moe", "moe_held_hit_pct.moe", "step_roofline.moe_held")
GEOMETRY = {"hidden": 3072, "layers": 8, "ffn": 1024, "vocab": 12544}


@pytest.fixture(scope="module")
def found():
    return cells.resolve(ROOT, CELL)


def test_configuration_file_is_the_catalogs_row_but_for_the_three_cuts(found):
    c = found["config"]
    assert c["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert c["published"] == {"num_hidden_layers": 48, "num_experts": 256, "vocab_size": 100352}
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"]) == (8, 32, 12544)
    # the floors of a cut: the dense layer + 4 or more of what follows, 8 experts or more, an eighth of the vocabulary
    assert c["num_hidden_layers"] - len(c["mlp_only_layers"]) >= 4 and c["vocab_size"] * 8 >= c["published"]["vocab_size"]
    assert (c["share"]["chips"], c["share"]["experts_held"], c["share"]["first_expert"]) == (8, 32, 0)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Laguna-S-2.1")
        assert c["source"] == row["source_url"]
        assert {k for k in row["config"] if c[k] != row["config"][k]} == set(c["reduced"])  # no width touched, no list cut
        assert all(row["config"][k] == v for k, v in c["published"].items())
    unit = {p["name"]: p["value"] for p in c["deployment"]["spec"]["predictors"][0]["graph"]["parameters"]}
    assert unit["model"] == "moe_decoder"  # one family: the second, with more parameters
    ints = {"hidden": "hidden_size", "layers": "num_hidden_layers", "kv_heads": "num_key_value_heads", "head_dim": "head_dim",
            "dense_ffn": "intermediate_size", "ffn": "moe_intermediate_size", "experts_held": "num_experts",
            "experts_per_tok": "num_experts_per_tok", "vocab": "vocab_size", "max_len": "max_position_embeddings",
            "window": "sliding_window"}
    assert {k: int(unit[k]) for k in ints} == {k: c[v] for k, v in ints.items()}
    assert int(unit["experts"]) == c["published"]["num_experts"]  # the router keeps its width
    n = c["num_hidden_layers"]
    heads = c["num_attention_heads_per_layer"][:n]
    kinds = c["layer_types"][:n]
    # two head counts by layer kind, said so that a tree without them refuses the value at once ("48,72" is no int)
    assert unit["heads"] == "48,72" == ",".join(str(h) for h in sorted(set(heads)))
    assert [h == 48 for h in heads] == [k == "full_attention" for k in kinds] == [i % 4 == 0 for i in range(n)]
    assert (unit["full_first"], unit["period"], unit["attn_gate"], unit["shared_expert"]) == ("true", "4", "true", "true")
    assert c["gating"] == "per-head" and c["mlp_only_layers"] == [0] and int(unit["dense_layers"]) == 1
    assert c["shared_expert_intermediate_size"] == c["moe_intermediate_size"]  # the shared expert is one more expert
    full, win = c["rope_parameters"]["full_attention"], c["rope_parameters"]["sliding_attention"]
    assert (float(unit["rope_theta"]), float(unit["rope_theta_window"]), float(unit["rotary_full"])) == (
        full["rope_theta"], win["rope_theta"], full["partial_rotary_factor"])
    assert (float(unit["yarn_factor"]), int(unit["yarn_original"]), float(unit["yarn_attention_factor"])) == (
        full["factor"], full["original_max_position_embeddings"], full["attention_factor"])
    assert float(unit["routed_scale"]) == c["moe_routed_scaling_factor"] and c["norm_topk_prob"] is True
    tpu = c["deployment"]["spec"]["predictors"][0]["tpu"]
    assert set(tpu) == {"max_batch", "batch_buckets", "dtype", "decode_slots", "decode_prefix_slots",
                        "decode_prefill_chunk", "decode_kv_page_size", "decode_kv_pages"}  # no key for the second page kind
    per_slot = -(-(int(unit["seq"]) + int(unit["max_new_tokens"])) // tpu["decode_kv_page_size"])
    shared = found["traffic"]["shared_prefix_len"] // tpu["decode_kv_page_size"]
    assert (per_slot, shared) == (464, 256)
    assert tpu["decode_kv_pages"] >= shared + tpu["decode_slots"] * (per_slot - shared) + 1  # the full kind: every slot admits
    assert tpu["dtype"] == unit["param_dtype"] == "bfloat16" and c["reference"]["n_head"] == c["num_attention_heads"]
    assert int(unit["seq"]) == found["traffic"]["prompt_len"] and tpu["decode_slots"] == found["traffic"]["clients"]


def test_the_traffic_is_the_issues_mix(found):
    t = found["traffic"]
    assert (t["generator"], t["protocol"], t["clients"], t["prompt_len"], t["shared_prefix_len"], t["cache_prefix"],
            t["ramp_s"], t["cycle_from"]) == ("closed", "sse", 64, 7168, 4096, 4096, 16.0, 1)
    with open(os.path.join(BENCH, "traffic", "chat-closed-64.json")) as f:
        chat = json.load(f)
    assert t["output_table"] == chat["output_table"] and t["lanes"] == chat["lanes"]
    assert (t["prompt_len"] - t["shared_prefix_len"]) % 256 == 0  # whole chunks of the unique tail
    assert min(t["output_table"]) > 64 and max(t["output_table"]) < 256


def test_new_metrics_list_the_new_cell_and_the_older_window_cell_where_they_read_it(found):
    bench = found["bench"]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in BOTH:
        assert set(by_name[name]["workloads"]) == {OLDER, CELL}
    for name in MINE:
        assert by_name[name]["workloads"] == [CELL]
    for name in BOTH + MINE:
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", name + ".py"))
        assert by_name[name]["layer"] == ("KV pool" if name.startswith("kv_") else "kernels")
        assert by_name[name]["moves"] == ("tokens_per_s" if name.startswith("kv_") else "itl_p95_ms")
    for m in bench["per_layer"]:  # every list the older window cell is on, but the four whose count is another block's
        if OLDER in m["workloads"] and m["name"] not in ("moe_experts_roofline", "step_roofline.moe", "moe_experts_hit_pct",
                                                         "moe_load_max_over_mean"):
            assert m["workloads"].count(CELL) == 1, m["name"]
    for name in ("moe_experts_roofline", "step_roofline.moe", "moe_experts_hit_pct", "moe_load_max_over_mean",
                 "shared_expert_device_ms", "moe_held_device_ms", "mla_device_ms", "conv_device_ms", "step_roofline"):
        assert CELL not in by_name[name]["workloads"]
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL in e2e["tokens_per_s"]["workloads"] and CELL in e2e["itl_p95_ms"]["workloads"]
    assert CELL not in e2e["itl_p95_closed_ms"]["workloads"]  # its bound is the first cell's


# ------------------------------------------------------------ nested scopes


def _events():
    j, c = "jit(_fused_step)/jit(main)/", "jit(_fused_chunk)/jit(main)/"
    ops = [("fusion", 0.10, 0.010, j + "qkv/dot_general:"), ("fusion", 0.11, 0.004, j + "qkv/rope/mul:"),
           ("fusion", 0.12, 0.030, j + "full/kv_gather/gather:"), ("fusion", 0.15, 0.020, j + "full/attn/dot_general:"),
           ("fusion", 0.17, 0.003, j + "win/kv_gather/gather:"), ("fusion", 0.173, 0.005, j + "win/attn/dot_general:"),
           ("fusion", 0.18, 0.002, j + "attn_out/gate/dot_general:"), ("fusion", 0.182, 0.001, j + "attn_out/gate/logistic:"),
           ("fusion", 0.185, 0.008, j + "attn_out/dot_general:"), ("fusion", 0.20, 0.006, j + "mlp/dense/dot_general:"),
           ("fusion", 0.21, 0.004, j + "mlp/shared_expert/dot_general:"), ("fusion", 0.22, 0.04, j + "mlp/moe_experts/dot_general:"),
           ("fusion", 0.26, 0.001, j + "mlp/moe_router/gate/dot_general:"),  # a "gate" that is not attn_out's
           ("fusion", 0.50, 0.05, c + "full/attn/dot_general:"), ("fusion", 0.55, 0.01, c + "attn_out/gate/mul:")]
    mods = [("jit__fused_step", 0.10, 0.30), ("jit__fused_chunk", 0.50, 0.10), ("jit__fused_step", 0.95, 0.30)]
    return {"devices": {"/device:TPU:0": {"ops": [list(o) for o in ops], "modules": [list(m) for m in mods]}},
            "host": [[scopes.WINDOW, 0.0, 1.0, "", {}]], "op_name_stat": "tf_op"}


def test_nested_keys_reduce_by_self_time_inside_the_old_scopes():
    k = scopes_win.nested_key
    assert k("jit(_fused_step)/jit(main)/attn_out/gate/logistic:") == "gate"
    assert k("jit(_fused_step)/jit(main)/mlp/shared_expert/dot_general:") == "shared_expert"
    assert k("jit(_fused_step)/jit(main)/mlp/dense/dot_general:") == "dense"
    assert k("jit(_fused_step)/jit(main)/mlp/moe_router/gate/dot_general:") is None and k("") is None
    assert k("jit(_fused_step)/jit(main)/attn_out/dot_general:") is None
    step = scopes_win.by_nested(_events(), scopes_win.STEP_MARK)
    assert step["dispatches"] == 1  # the second step is cut by the slice's edge
    assert step["by"] == pytest.approx({"gate": 0.003, "shared_expert": 0.004, "dense": 0.006})
    chunk = scopes_win.by_nested(_events(), scopes_win.CHUNK_MARK)
    assert chunk["dispatches"] == 1 and chunk["by"] == pytest.approx({"gate": 0.01})
    # the nine scopes' readers hold the new scopes' time, and the family's own reader splits the layer kinds
    old = scopes.step_by_scope(_events())
    assert scopes.scoped_s(old, "attn_out") == pytest.approx(0.011) and scopes.scoped_s(old, "mlp") == pytest.approx(0.051)
    moe = scopes_moe.by_nested(_events(), scopes_moe.STEP_MARK)
    assert moe["by"]["full/kv_gather"] + moe["by"]["full/attn"] == pytest.approx(0.05)
    assert moe["by"]["win/kv_gather"] + moe["by"]["win/attn"] == pytest.approx(0.008)
    other = _events()  # a program without the names: the other families, the parent
    for o in other["devices"]["/device:TPU:0"]["ops"]:
        o[3] = o[3].replace("/gate/", "/x/").replace("shared_expert", "x").replace("/dense/", "/x/")
    assert scopes_win.by_nested(other, scopes_win.STEP_MARK) is None
    assert scopes_win.nested_ms({"trace": None}, "step", "gate") is None


def test_recorded_chip_trace_reads_the_gate_inside_attn_out():
    """A piece of the new cell's traced run (my chip run, PR 47): the gate, the
    shared expert and the dense layer are found in the step, their time lies
    inside what the nine scopes' readers give ``attn_out`` and ``mlp``, and
    the family's reader finds both layer kinds."""
    path = os.path.join(BENCH, "harness", "fixtures", "trace_window.json")
    if not os.path.exists(path):
        pytest.skip("the fixture is cut from a chip run")
    with open(path) as f:
        events = scopes.expanded(json.load(f))
    step = scopes_win.by_nested(events, scopes_win.STEP_MARK)
    assert step and step["dispatches"] >= 1 and set(step["by"]) == set(scopes_win.NAMES)
    old = scopes.step_by_scope(events)
    assert 0 < step["by"]["gate"] <= scopes.scoped_s(old, "attn_out")
    assert 0 < step["by"]["shared_expert"] + step["by"]["dense"] <= scopes.scoped_s(old, "mlp")
    moe = scopes_moe.by_nested(events, scopes_moe.STEP_MARK)
    assert {"full/kv_gather", "full/attn", "win/kv_gather", "win/attn", "moe_experts"} <= set(moe["by"])
    assert moe["by"]["full/attn"] > moe["by"]["win/attn"]  # two layers over 7k keys against six over 512


# ------------------------------------------------------- counts and readers


def _frame(rows=64, hit=200, picks=80, live=3000, written=40, released=38, chunk_ns=0, window=True, step=None):
    f = types.SimpleNamespace(moe_rows=rows, moe_experts_hit=hit, moe_load_max=30, moe_local_picks=picks, mode="plain",
                              busy_ns=(chunk_ns, 1000, 0, 0, 0))
    if step is not None:
        f.step_counts = step  # the step dispatch's own counts, beside the round's sums
    if window:
        f.kv_win_live, f.kv_win_written, f.kv_win_released = live, written, released
    return f


def test_the_issues_bytes_come_out_of_the_count(found):
    o = {"config": found["config"], "geometry": GEOMETRY}
    p = scopes_win.held_share(o)
    assert (p["layers"], p["dense_layers"], p["held"], p["experts"], p["per_tok"], p["window"]) == (8, 1, 32, 256, 10, 512)
    assert p["heads_by_layer"] == [48, 72, 72, 72, 48, 72, 72, 72] and p["full_by_layer"] == [True, False, False, False] * 2
    ctx = 64 * 7300.0
    flops, nbytes = opsbytes_moe_held.moe_held_step(**p, experts_hit=7 * 32, local_picks=64 * 10 * 7 / 8, rows=64, ctx_tokens=ctx)
    # ISSUE 47's arithmetic: 5.69 GB of weights less the embedding's other rows, every held expert hit
    weights = 2.843e9 - 12544 * 3072 + 64 * 3072
    kv = 2 * 1024 * (2 * ctx + 6 * 64 * 512 + 8 * 64)
    assert nbytes == pytest.approx(2 * (weights + kv), rel=0.002)
    assert 2 * 1024 * 2 * ctx * 2 == pytest.approx(3.83e9, rel=0.01)  # "3.7 GB of full-layer rows"
    assert 2 * 1024 * 6 * 64 * 512 * 2 == pytest.approx(0.805e9, rel=0.01)  # "0.8 GB of window rows"
    assert opsbytes_moe_held.least_seconds("TPU v5 lite", flops, nbytes) == pytest.approx(nbytes / 819e9)  # the bytes bind
    assert nbytes / 819e9 == pytest.approx(12.4e-3, rel=0.03)  # "12 ms at 819 GB/s"
    fewer, _ = opsbytes_moe_held.moe_held_step(**p, experts_hit=100, local_picks=10, rows=64, ctx_tokens=ctx), None
    assert fewer[1] == pytest.approx(nbytes - 2 * (7 * 32 - 100) * 3 * 3072 * 1024)  # an expert without a row is not read
    with pytest.raises(KeyError):
        opsbytes_moe_held.least_seconds("cpu", flops, nbytes)  # a device without published peaks is an error
    older = cells.resolve(ROOT, OLDER)  # a configuration that holds all its experts is not this count's
    assert scopes_win.held_share({"config": older["config"], "geometry": {"hidden": 2304, "layers": 12, "ffn": 896, "vocab": 98304}}) is None


def test_readers_read_the_counts_and_give_none_without_them(found, monkeypatch):
    o = {"frames": [_frame(), _frame(live=3100, written=10, released=12), _frame(chunk_ns=5, hit=999),
                    _frame(rows=1088, hit=424, picks=1400, chunk_ns=5, written=0, released=0, step=(64, 200, 30, 80))],
         "config": found["config"], "geometry": GEOMETRY, "traffic": found["traffic"], "device": {"kind": "TPU v5 lite"},
         "trace": {"families": {"step": {"mean_s": 0.040}}}, "requests": [{"gen_len": 100}, {"gen_len": 200}], "after": {}}
    readers = {n: cells.load_module(ROOT, cells.load_bench(ROOT), "layer_metrics", n) for n in BOTH + MINE}
    assert readers["kv_window_released_pct"].read(o) == pytest.approx(100 * (38 + 12 + 38) / (40 + 10 + 40))  # + 0 / 0
    assert readers["kv_window_live_peak_pct"].read(o) == pytest.approx(100 * 3100 / (64 * 49 + 2 * 33 + 1))
    # step-only rounds' sums, and the step's own counts of a round that also ran a chunk; a chunk round without them: left out
    assert readers["moe_held_hit_pct.moe"].read(o) == pytest.approx(100 * 200 / (7 * 32))
    assert scopes_win.step_means(o) == {"rows": 64, "experts_hit": 200, "load_max": 30, "local_picks": 80}
    assert scopes_win.step_means({"frames": [_frame(chunk_ns=5)]}) is None  # every round rides a chunk, a tree before PR 47
    win = {"dispatches": 2, "by": {"gate": 0.0006, "shared_expert": 0.002, "dense": 0.001}}
    moe = {"dispatches": 2, "by": {"full/kv_gather": 0.02, "full/attn": 0.03, "win/kv_gather": 0.004, "win/attn": 0.006,
                                   "moe_router": 0.001, "moe_dispatch": 0.001, "moe_experts": 0.01, "moe_combine": 0.002}}
    chunk = {"dispatches": 4, "by": {"full/kv_gather": 0.04, "full/attn": 0.08}}
    monkeypatch.setattr(scopes_win, "newest_xplane", lambda d: "a traced run's file")
    monkeypatch.setattr(scopes_moe, "newest_xplane", lambda d: "a traced run's file")
    monkeypatch.setattr(scopes_win, "_of_file", lambda path: {"step": win, "chunk": None})
    monkeypatch.setattr(scopes_moe, "_of_file", lambda path: {"step": moe, "chunk": chunk})
    assert readers["attn_gate_device_ms"].read(o) == pytest.approx(0.3)
    assert readers["attn_full_device_ms"].read(o) == pytest.approx(25.0)
    assert readers["attn_win_device_ms"].read(o) == pytest.approx(5.0)
    assert readers["attn_full_chunk_device_ms"].read(o) == pytest.approx(30.0)
    assert readers["moe_held_device_ms.moe"].read(o) == pytest.approx(7.0)
    p = scopes_win.held_share(o)
    _, nbytes = opsbytes_moe_held.moe_held_step(**p, experts_hit=200, local_picks=80, rows=64, ctx_tokens=64 * (7168 + 75.0))
    assert readers["step_roofline.moe_held"].read(o) == pytest.approx(100 * (nbytes / 819e9) / 0.040)
    assert readers["step_roofline.moe_held"].read(o) < 100
    parent = {**o, "frames": [_frame(window=False)], "trace": None}  # a tree without the window kind, an untraced run
    for name, r in readers.items():
        if name != "moe_held_hit_pct.moe":  # the frames' own count needs neither
            assert r.read(parent) is None, name
    older = cells.resolve(ROOT, OLDER)  # all experts held: the held-share readers leave their metric out
    o2 = {**o, "config": older["config"], "geometry": {"hidden": 2304, "layers": 12, "ffn": 896, "vocab": 98304}}
    for name in ("moe_held_device_ms.moe", "moe_held_hit_pct.moe", "step_roofline.moe_held"):
        assert readers[name].read(o2) is None, name
