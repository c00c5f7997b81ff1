import json
import os

import pytest
from conftest import BENCH

from harness import trace as tr


def _events(ops, modules=(), host=(), window=(0.0, 1.0)):
    return {
        "devices": {"/device:TPU:0": {"ops": [list(o) for o in ops], "modules": [list(m) for m in modules]}},
        "host": [[tr.WINDOW, window[0], window[1] - window[0]]] + [list(h) for h in host],
        "lines": {},
    }


def test_busy_is_the_union_not_the_sum():
    ev = _events(ops=[("a.1", 0.10, 0.20), ("b.2", 0.20, 0.20), ("c.3", 0.60, 0.10), ("d.4", 0.95, 0.20)])
    r = tr.reduce_trace(ev)
    assert r["busy_s"] == pytest.approx(0.30 + 0.10 + 0.05)  # overlap once, the last one clipped
    assert r["idle_share"] == pytest.approx(0.55)
    assert r["window_s"] == pytest.approx(1.0)


def test_busy_is_averaged_over_chips():
    ev = _events(ops=[("a", 0.0, 0.5)])
    ev["devices"]["/device:TPU:1"] = {"ops": [["a", 0.0, 0.25]], "modules": []}
    r = tr.reduce_trace(ev)
    assert r["devices"] == 2 and r["busy_s"] == pytest.approx(0.375)


def test_family_time_counts_whole_dispatches_only():
    mods = [("jit__fused_step(1)", 0.10, 0.14), ("jit__fused_step(1)", 0.30, 0.16),
            ("jit__fused_chunk(2)", 0.50, 0.20), ("jit__fused_step(1)", 0.95, 0.14)]
    ev = _events(ops=[("x", 0.1, 0.8)], modules=mods)
    fam = tr.reduce_trace(ev, {"step": "fused_step", "chunk": "fused_chunk"})["families"]
    assert fam["step"]["dispatches"] == 2 and fam["step"]["mean_s"] == pytest.approx(0.15)
    assert fam["chunk"]["dispatches"] == 1 and fam["chunk"]["mean_s"] == pytest.approx(0.20)


def test_idle_gaps_are_named_by_the_innermost_host_annotation():
    host = [(tr.HOST_PREFIX + "round", 0.0, 1.0), (tr.HOST_PREFIX + "host_in_sampling", 0.38, 0.06)]
    ev = _events(ops=[("a", 0.0, 0.35), ("b", 0.45, 0.55)], host=host)
    gaps = tr.reduce_trace(ev)["idle_gaps"]
    assert gaps[0][0] == "host_in_sampling" and gaps[0][1] == pytest.approx(0.10)
    ev = _events(ops=[("a", 0.0, 0.35), ("b", 0.45, 0.55)])
    assert tr.reduce_trace(ev)["idle_gaps"][0][0] == "no_annotation"


def test_labels_merge_instances_and_keep_the_shape():
    assert tr._short("fusion.12") == "fusion"
    assert tr._short("jit__fused_step(1234567)") == "jit__fused_step"
    hlo = "%copy.369 = f32[1,720,20,16,64]{4,2,3,1,0:T(8,128)} copy(f32[1,720,20,16,64]{4,3,2,1,0} %gte.2)"
    assert tr._short(hlo) == "copy f32[1,720,20,16,64]"
    assert tr._short("%convert_reduce_fusion.30 = (f32[32,128]{1,0}, bf16[32,128,768]{2,1,0}) fusion(...)") == (
        "convert_reduce_fusion f32[32,128]")
    ev = _events(ops=[("fusion", 0.0, 0.1), ("fusion", 0.2, 0.1), ("copy", 0.4, 0.05)])
    ops = dict(tr.reduce_trace(ev)["device_ops"])
    assert ops["fusion"] == pytest.approx(0.2) and ops["copy"] == pytest.approx(0.05)


FIXTURE = os.path.join(BENCH, "harness", "fixtures", "trace_small.json")


def test_the_recorded_chip_trace_reduces_to_its_recorded_numbers():
    """0.3 s of a traced gpt2-large.batch-unshared run on one v5e (PR 25),
    as ``trace.trimmed`` wrote it; the expected numbers were read off that
    run's own reduction and are held to it here."""
    with open(FIXTURE) as f:
        kept = json.load(f)
    events = tr.expanded(kept["events"])
    r = tr.reduce_trace(events, kept["families"])
    want = kept["expected"]
    assert r["window_s"] == pytest.approx(want["window_s"])
    assert r["busy_s"] == pytest.approx(want["busy_s"], rel=1e-6)
    assert r["idle_share"] == pytest.approx(want["idle_share"], rel=1e-6)
    for f, v in want["families"].items():
        assert r["families"][f]["dispatches"] == v["dispatches"]
        assert r["families"][f]["mean_s"] == pytest.approx(v["mean_s"], rel=1e-6)
    assert 0.0 < r["busy_s"] <= r["window_s"]
    # busy by the union can never pass the sum of the operations' own times
    total = sum(d for dev in events["devices"].values() for _, _, d in dev["ops"])
    assert r["busy_s"] <= total + 1e-9
