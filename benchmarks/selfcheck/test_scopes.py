import json
import os

import pytest
from conftest import BENCH

from harness import scopes as sc
from harness import trace as tr

W = sc.WINDOW


def _events(ops, modules, host):
    return {
        "devices": {"/device:TPU:0": {"ops": [list(o) for o in ops], "modules": [list(m) for m in modules]}},
        "host": [[W, 0.0, 1.0, "", {}]] + [list(h) + ["t", {}] for h in host],
        "op_name_stat": "tf_op",
    }


def _one_round():
    """Device busy [0.10, 0.40) and [0.45, 0.65); a round that names most of
    what lies between, and one that names nothing."""
    ops = [("fusion", 0.10, 0.05, "jit(_fused_step)/jit(main)/qkv/dot_general:"),
           ("copy-done f32[3840]", 0.15, 0.10, ""),
           ("fusion", 0.25, 0.05, "jit(_fused_step)/jit(main)/kv_write/scatter:"),
           ("while", 0.30, 0.10, "jit(_fused_step)/jit(main)/sample/while:"),
           ("fusion", 0.32, 0.04, "jit(_fused_step)/jit(main)/sample/sort:"),
           ("fusion", 0.45, 0.20, "jit(_fused_chunk)/jit(main)/attn/dot_general:")]
    modules = [("jit__fused_step", 0.10, 0.30), ("jit__fused_chunk", 0.45, 0.20)]
    host = [("decode.round", 0.05, 0.50), ("decode.round", 0.55, 0.50),
            ("decode.phase.admit", 0.05, 0.03),
            ("decode.dispatch.step", 0.08, 0.33), ("decode.enqueue.step", 0.085, 0.01),
            ("decode.readback.step", 0.10, 0.305),
            ("decode.phase.sampling", 0.41, 0.02), ("decode.phase.emit_slo", 0.415, 0.005),
            ("decode.dispatch.chunk", 0.43, 0.24), ("decode.enqueue.chunk", 0.435, 0.014),
            ("decode.readback.chunk", 0.449, 0.21),
            ("decode.sse_write", 0.70, 0.01), ("decode.idle_wait", 0.80, 0.10)]
    return _events(ops, modules, host)


def test_every_idle_instant_goes_to_one_state_by_precedence():
    idle = sc.idle_by_state(_one_round())
    s = idle["by_state"]
    assert idle["idle_s"] == pytest.approx(0.45) and sum(s.values()) == pytest.approx(idle["idle_s"])
    # [0.08, 0.085) is in the dispatch before its enqueue starts: the hand-off out
    assert s[sc.ENQUEUE] == pytest.approx(0.005 + 0.010 + 0.005 + 0.014)
    # the readback's own idle, and the dispatch's rest after it: the hand-off back
    assert s[sc.READBACK] == pytest.approx(0.005 + 0.005 + 0.005 + 0.001 + 0.009 + 0.011)
    assert s[sc.PHASE] == pytest.approx(0.03 + 0.02)
    assert idle["by_phase"] == pytest.approx({"admit": 0.03, "sampling": 0.015, "emit_slo": 0.005})  # innermost
    assert s[sc.SSE_WRITE] == pytest.approx(0.01) and s[sc.IDLE_WAIT] == pytest.approx(0.10)
    # [0, 0.05) lies before the first round's start: outside the span the rounds cover
    assert idle["span_s"] == pytest.approx(0.95) and s[sc.NONE] == pytest.approx(0.0)
    assert s[sc.ROUND_ONLY] == pytest.approx(0.45 - 0.034 - 0.036 - 0.05 - 0.01 - 0.10)
    assert idle["named_share"] == pytest.approx(1 - s[sc.ROUND_ONLY] / 0.45)
    assert idle["rounds"] == pytest.approx(0.5 / 0.5 + 0.45 / 0.5)  # the second is cut by the slice's end


def test_idle_is_what_the_first_reduction_calls_idle():
    ev = _one_round()
    ev["host"][1][1:3] = [0.0, 0.55]  # the rounds span the whole slice
    plain = {"devices": {p: {"ops": [o[:3] for o in d["ops"]], "modules": d["modules"]}
                         for p, d in ev["devices"].items()},
             "host": [[W, 0.0, 1.0]], "lines": {}}
    r = tr.reduce_trace(plain)
    assert sc.idle_by_state(ev)["idle_s"] == pytest.approx(r["idle_share"] * r["window_s"])


def test_a_clipped_round_counts_by_its_share():
    ev = _events([("x", 0.0, 1.0, "")], [], [("decode.round", -0.3, 0.5), ("decode.round", 0.2, 0.4),
                                            ("decode.round", 0.6, 0.8)])
    # 0.2/0.5 + 1 + 0.4/0.8 rounds over a slice they cover entirely
    assert sc.idle_by_state(ev)["rounds"] == pytest.approx(0.4 + 1.0 + 0.5)
    ev = _events([("x", 0.0, 0.6, "")], [], [("decode.round", 0.5, 0.25), ("decode.round", 0.75, 0.25),
                                            ("decode.phase.admit", 0.1, 0.1)])
    # a session that began mid-round: the sweep covers what the rounds span, and no more
    idle = sc.idle_by_state(ev)
    assert idle["rounds"] == pytest.approx(2.0) and idle["span_s"] == pytest.approx(0.5)
    assert idle["idle_s"] == pytest.approx(0.4) and idle["by_state"][sc.ROUND_ONLY] == pytest.approx(0.4)
    assert sc.idle_by_state(_events([("x", 0.0, 1.0, "")], [], [("decode.phase.admit", 0.1, 0.1)])) is None


def test_op_time_by_scope_counts_self_time_and_whole_dispatches():
    step = sc.step_by_scope(_one_round())
    assert step["dispatches"] == 1 and step["module_s"] == pytest.approx(0.30)
    # the while holds its body's sort on the same line: 0.10 in all, not 0.14
    assert step["by_scope"] == pytest.approx({"qkv": 0.05, "kv_write": 0.05, "sample": 0.10})
    # the 0.10 of the copy-done before the scatter: an async wait without an op_name, counted
    # under the scope of the op it is for
    assert step["waits_by_scope"] == pytest.approx({"kv_write": 0.10})
    assert sc.scoped_s(step, "kv_write") == pytest.approx(0.15) and sc.scoped_s(step) == pytest.approx(0.30)
    assert step["unscoped_s"] == pytest.approx(0.0) and step["unscoped_ops"] == {} == step["unscoped_by_next"]
    assert sc.scoped_s(step) + step["unscoped_s"] == pytest.approx(step["op_s"])
    assert "attn" not in step["by_scope"]  # the chunk's op is not a step's
    ev = _one_round()
    ev["devices"]["/device:TPU:0"]["modules"][0] = ("jit__fused_step", -0.05, 0.45)  # cut by the slice's edge
    assert sc.step_by_scope(ev) is None


def test_an_async_wait_goes_to_the_next_scoped_op_and_a_plain_copy_does_not():
    ops = [("fusion", 0.10, 0.02, "jit(_fused_step)/jit(main)/attn_out/dot_general:"),
           ("slice-done f32[320,5120]", 0.12, 0.03, ""),  # the prefetch of the mlp's weights
           ("custom-call f32[1280,5120]", 0.15, 0.01, ""),  # joins the prefetched quarters
           ("copy f32[720,16,20,64]", 0.16, 0.04, ""),  # a layout copy is work, not a wait
           ("fusion", 0.20, 0.05, "jit(_fused_step)/jit(main)/mlp/dot_general:"),
           ("custom-call f32[16,20,64]", 0.25, 0.02, "jit(_fused_step)/jit(main)/attn/paged_attention:"),
           ("slice-done f32[256,1280]", 0.27, 0.03, "")]  # nothing scoped follows it in the dispatch
    step = sc.step_by_scope(_events(ops, [("jit__fused_step", 0.10, 0.20)], []))
    assert step["by_scope"] == pytest.approx({"attn_out": 0.02, "mlp": 0.05, "attn": 0.02})  # a named custom call keeps its name
    assert step["waits_by_scope"] == pytest.approx({"mlp": 0.04})
    assert step["unscoped_ops"] == pytest.approx({"copy f32[720,16,20,64]": 0.04, "slice-done f32[256,1280]": 0.03})
    assert step["unscoped_by_next"] == pytest.approx({"mlp": 0.04, sc.NONE: 0.03})
    assert sc.scoped_s(step) + step["unscoped_s"] == pytest.approx(step["op_s"]) and step["op_s"] == pytest.approx(0.20)
    ms = sc.per_dispatch_ms(step)
    assert ms["by_scope_ms"]["mlp"] == pytest.approx(90.0) and ms["unscoped_ms"] == pytest.approx(70.0)
    # the chunk's twin: the same rule under the other mark
    chunk = sc.step_by_scope(_events(ops, [("jit__fused_chunk", 0.10, 0.20)], []), sc.CHUNK_MARK)
    assert chunk["waits_by_scope"] == pytest.approx({"mlp": 0.04})


def test_what_a_program_lacks_reads_none_not_zero():
    ev = _one_round()
    bare = {"devices": {p: {"ops": [o[:3] + [""] for o in d["ops"]], "modules": d["modules"]}
                        for p, d in ev["devices"].items()},
            "host": [[W, 0.0, 1.0, "", {}], ["bench:dispatch_chunk", 0.43, 0.24, "t", {}]], "op_name_stat": None}
    r = sc.reduce_scoped(bare)
    assert r["idle"] is None
    assert r["step"]["by_scope"] == {} and r["step"]["unscoped_s"] == pytest.approx(r["step"]["op_s"])
    o = {"trace": {"families": {}}}
    sc._of_file.cache_clear()
    real = sc.newest_xplane, sc.read_scoped
    sc.newest_xplane, sc.read_scoped = (lambda d: "x"), (lambda p: bare)
    try:
        assert sc.idle_ms_per_round(o, sc.ENQUEUE) is None
        assert sc.step_scope_ms(o, "kv_write", "pool_restack") is None
        for name in ("idle_named_pct", "step_scoped_pct", "idle_phases_ms", "kv_gather_device_ms"):
            import importlib.util

            spec = importlib.util.spec_from_file_location("m", os.path.join(BENCH, "layer_metrics", name + ".py"))
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            assert mod.read(o) is None, name
        assert sc.of_run({"trace": None}) is None  # an untraced run
    finally:
        sc.newest_xplane, sc.read_scoped = real
        sc._of_file.cache_clear()


def test_frame_counters_read_none_on_frames_without_them():
    import importlib.util

    class Old:
        admitted = 2

    class New(Old):
        admit_wait_ns, prefill_ns, first_tokens = 30_000_000, 280_000_000, 2

    for name, want in (("ttft_queue_ms", 15.0), ("ttft_prefill_ms", 140.0)):
        spec = importlib.util.spec_from_file_location("m", os.path.join(BENCH, "layer_metrics", name + ".py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert mod.read({"frames": [Old(), Old()]}) is None
        assert mod.read({"frames": [New(), New()]}) == pytest.approx(want)


def test_the_kept_form_round_trips():
    ev = _one_round()
    back = sc.expanded(sc.trimmed(ev, 0.95))  # from the first round's start, 0.05, to the slice's end
    a, b = sc.reduce_scoped(ev), sc.reduce_scoped(back)
    assert a["idle"]["by_state"] == pytest.approx(b["idle"]["by_state"])
    assert a["step"]["by_scope"] == pytest.approx(b["step"]["by_scope"])


FIXTURE = os.path.join(BENCH, "harness", "fixtures", "trace_scoped.json")


def test_the_recorded_chip_trace_splits_as_recorded():
    """0.3 s of a traced gpt2-large.batch-unshared run on one v5e (PR 26) with
    the program's own annotations and each op's op_name, as ``scopes.trimmed``
    wrote it; the expected numbers are that file's own reduction when kept."""
    with open(FIXTURE) as f:
        kept = json.load(f)
    events = sc.expanded(kept["events"])
    r = sc.reduce_scoped(events)
    idle, step, want = r["idle"], r["step"], kept["expected"]
    # the states partition the idle time ...
    assert sum(idle["by_state"].values()) == pytest.approx(idle["idle_s"], rel=1e-9)
    assert idle["idle_s"] == pytest.approx(want["idle_s"], rel=1e-6)
    for state, v in want["by_state"].items():
        assert idle["by_state"][state] == pytest.approx(v, rel=1e-6, abs=1e-12), state
    # ... and so the four per-round metrics and the unnamed rest sum to idle / rounds
    per_round = lambda *st: 1e3 * sum(idle["by_state"][s] for s in st) / idle["rounds"]  # noqa: E731
    four = (per_round(sc.ENQUEUE), per_round(sc.READBACK), per_round(sc.PHASE),
            per_round(sc.SSE_WRITE, sc.ROUND_ONLY))
    rest = per_round(sc.IDLE_WAIT, sc.NONE)
    assert sum(four) + rest == pytest.approx(1e3 * idle["idle_s"] / idle["rounds"], rel=1e-9)
    assert idle["rounds"] == pytest.approx(want["rounds"], rel=1e-6)
    assert idle["named_share"] == pytest.approx(want["named_share"], rel=1e-6)
    # the same idle as the first reduction reads off the same events
    # the piece starts at a round's start: up to the end of its last round, the same idle as
    # the first reduction reads off the same events
    plain = {"devices": {p: {"ops": [o[:3] for o in d["ops"]], "modules": d["modules"]}
                         for p, d in events["devices"].items()},
             "host": [[W, 0.0, idle["span_s"]]], "lines": {}}
    first = tr.reduce_trace(plain)
    assert idle["idle_s"] == pytest.approx(first["idle_share"] * first["window_s"], rel=1e-6)
    # scoped + unscoped = the step dispatches' op time, which their module time bounds
    assert step["dispatches"] == want["step"]["dispatches"]
    assert sc.scoped_s(step) + step["unscoped_s"] == pytest.approx(step["op_s"], rel=1e-9)
    assert 0 < sum(step["waits_by_scope"].values()) < want["step"]["unscoped_s"]  # the waits leave the unscoped time
    assert step["unscoped_s"] + sum(step["waits_by_scope"].values()) == pytest.approx(want["step"]["unscoped_s"], rel=1e-6)
    assert step["op_s"] <= step["module_s"] * (1 + 1e-6)
    for scope, v in want["step"]["by_scope"].items():
        assert step["by_scope"][scope] == pytest.approx(v, rel=1e-6), scope
    assert set(step["by_scope"]) <= set(sc.SCOPES) and kept["events"]["op_name_stat"] == "tf_op"
