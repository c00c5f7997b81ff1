"""harness/dispatches.py: the join of host to device by dispatch, the split
of the device's idle at its own events, and device time per ladder entry,
on hand-made events and on a recorded trace, without a chip."""

import copy
import json
import os
from types import SimpleNamespace

import pytest
from conftest import BENCH

from harness import dispatches as dp
from harness import scopes as sc

W = sc.WINDOW
LOOP, POOL = "/host:CPU#3", "/host:CPU#7"


def _events(modules, host):
    return {
        "devices": {"/device:TPU:0": {"ops": [], "modules": [list(m) for m in modules]}},
        "host": [[W, 0.0, 1.0, "", {}]] + [list(h) for h in host],
        "op_name_stat": "tf_op",
    }


def _d(family, seq, rnd, a, b, thread=LOOP, **kw):
    stats = {"seq": str(seq), "round": str(rnd), **{k: str(v) for k, v in kw.items()}}
    return [f"decode.dispatch.{family}", a, b - a, thread, stats]


def _rt(kind, family, seq, a, b, thread=POOL):
    return [f"decode.{kind}.{family}", a, b - a, thread, {"seq": str(seq)}]


def _two_rounds():
    """Two rounds; five dispatches: a (2,64) chunk and a step in the first, a
    (2,256) and a (2,64) chunk in the second and a (2,256) chunk that the
    slice's end cuts (the window ends at 1.0)."""
    modules = [("jit__fused_chunk", 0.10, 0.06), ("jit__fused_step", 0.25, 0.15),
               ("jit__fused_chunk", 0.55, 0.13), ("jit__fused_chunk", 0.74, 0.04),
               ("jit__fused_chunk", 0.93, 0.09)]
    host = [["decode.round", 0.05, 0.45, LOOP, {"round": "0", "t_ns": "1"}],
            ["decode.round", 0.50, 0.60, LOOP, {"round": "1", "t_ns": "2"}],
            _d("chunk", 1, 0, 0.06, 0.20, rows=2, c=64, live=1),
            _rt("enqueue", "chunk", 1, 0.07, 0.09), _rt("readback", "chunk", 1, 0.09, 0.18),
            _d("step", 2, 0, 0.22, 0.45, rows=16, live=16),
            _rt("enqueue", "step", 2, 0.225, 0.235, LOOP), _rt("readback", "step", 2, 0.24, 0.43),
            ["decode.phase.sampling", 0.45, 0.04, LOOP, {}],
            _d("chunk", 3, 1, 0.52, 0.70, rows=2, c=256, live=2),
            _rt("enqueue", "chunk", 3, 0.53, 0.545), _rt("readback", "chunk", 3, 0.545, 0.69),
            _d("chunk", 4, 1, 0.72, 0.80, rows=2, c=64, live=1),
            _rt("enqueue", "chunk", 4, 0.725, 0.735), _rt("readback", "chunk", 4, 0.735, 0.79),
            ["decode.ingress", 0.785, 0.03, LOOP, {}],
            _d("chunk", 5, 1, 0.90, 1.05, rows=2, c=256, live=2),
            _rt("enqueue", "chunk", 5, 0.91, 0.92), _rt("readback", "chunk", 5, 0.92, 1.04)]
    return _events(modules, host)


def test_the_three_legs_sum_to_the_idle_that_idle_by_state_counts():
    ev = _two_rounds()
    r, old = dp.by_dispatch(ev), sc.idle_by_state(ev)
    legs = r["legs"]
    assert r["idle_s"] == pytest.approx(old["idle_s"]) and r["rounds"] == pytest.approx(old["rounds"])
    assert r["span_s"] == pytest.approx(0.95) and r["rounds"] == pytest.approx(1 + 0.5 / 0.6)
    assert legs["launch"] + legs["return"] + legs["between"] == pytest.approx(old["idle_s"])
    # before each dispatch's module starts; the cut one's idle counts, its figures do not
    assert legs["launch"] == pytest.approx(0.04 + 0.03 + 0.03 + 0.02 + 0.03)
    assert legs["return"] == pytest.approx(0.04 + 0.05 + 0.02 + 0.02)
    assert legs["between"] == pytest.approx(0.01 + 0.02 + 0.05 + 0.02 + 0.02 + 0.10)
    # launch + return is what idle_by_state calls enqueue + readback, hand-offs included
    assert legs["launch"] + legs["return"] == pytest.approx(old["by_state"][sc.ENQUEUE] + old["by_state"][sc.READBACK])
    # no thread in the runtime: before the first enqueue event, after the last readback event
    assert legs["hop"] == pytest.approx((0.01 + 0.02) + (0.005 + 0.02) + (0.01 + 0.01) + (0.005 + 0.01) + 0.01)
    assert r["hop_in_ingress_s"] == pytest.approx(0.01)  # [0.79, 0.80) of the fourth's return: the ingress began at 0.785


def test_the_split_follows_the_device_not_where_the_enqueue_annotation_ends():
    """The race: the jitted call returns before or after the module starts.
    ``idle_by_state`` moves that time between its two states; the legs stay."""
    ev = _two_rounds()
    late = copy.deepcopy(ev)
    enq = next(e for e in late["host"] if e[0] == "decode.enqueue.chunk" and e[4]["seq"] == "3")
    rdb = next(e for e in late["host"] if e[0] == "decode.readback.chunk" and e[4]["seq"] == "3")
    enq[2] = 0.56 - enq[1]  # the call returns after the module's start (0.55), not 5 ms before
    rdb[1], rdb[2] = 0.56, 0.69 - 0.56
    a, b = sc.idle_by_state(ev)["by_state"], sc.idle_by_state(late)["by_state"]
    assert b[sc.ENQUEUE] - a[sc.ENQUEUE] == pytest.approx(0.005) == a[sc.READBACK] - b[sc.READBACK]
    x, y = dp.by_dispatch(ev), dp.by_dispatch(late)
    assert x["legs"] == pytest.approx(y["legs"])
    assert [d["launch_s"] for d in x["dispatches"]] == pytest.approx([d["launch_s"] for d in y["dispatches"]])


def test_a_skew_between_the_two_clocks_moves_the_legs_and_not_the_join():
    """The device plane's clock lies off the host's by another amount in each
    profiler session: a module may "start" before the call that launched it
    was entered. Its middle stays in its span, so every dispatch keeps its
    module, its entry and its device time; launch and return trade the skew;
    their sum stays."""
    ev = _two_rounds()
    early = copy.deepcopy(ev)
    for m in early["devices"]["/device:TPU:0"]["modules"]:
        m[1] -= 0.035  # the step's module now starts at 0.215, its span at 0.22
    x, y = dp.by_dispatch(ev), dp.by_dispatch(early)
    assert [(d["seq"], d["modules"], d["device_s"]) for d in y["dispatches"]] == [
        (d["seq"], d["modules"], pytest.approx(d["device_s"])) for d in x["dispatches"]]
    assert {k: (v["n"], pytest.approx(v["device_s"])) for k, v in y["entries"].items()} == {
        k: (v["n"], v["device_s"]) for k, v in x["entries"].items()}
    step = {d["seq"]: d for d in y["dispatches"]}[2]
    assert step["launch_s"] == 0.0 and step["call_to_device_s"] == pytest.approx(0.215 - 0.225)  # negative: the skew shows
    assert y["legs"]["launch"] < x["legs"]["launch"] and y["legs"]["return"] > x["legs"]["return"]
    assert y["legs"]["launch"] + y["legs"]["return"] + y["legs"]["between"] == pytest.approx(sc.idle_by_state(early)["idle_s"])


def test_a_dispatch_cut_by_the_slices_edge_is_left_out_not_counted_short():
    r = dp.by_dispatch(_two_rounds())
    assert [d["seq"] for d in r["dispatches"]] == [1, 2, 3, 4]  # 5 runs past the window's end
    assert r["entries"][(2, 256)] == {"n": 1, "device_s": pytest.approx(0.13)}  # not 2, not 0.13 + 0.07
    d = {x["seq"]: x for x in r["dispatches"]}
    assert d[1]["family"] == "chunk" and (d[1]["rows"], d[1]["c"], d[1]["live"], d[1]["round"]) == (2, 64, 1, 0)
    assert d[2]["family"] == "step" and (d[2]["rows"], d[2]["c"], d[2]["live"]) == (16, None, 16)
    assert d[2]["device_s"] == pytest.approx(0.15) and d[2]["wall_s"] == pytest.approx(0.23)
    assert (d[3]["launch_s"], d[3]["return_s"]) == pytest.approx((0.03, 0.02))
    # one cut at the slice's START is left out the same way
    ev = _two_rounds()
    ev["host"][0][1:3] = [0.08, 0.92]  # the window opens inside the first dispatch
    r = dp.by_dispatch(ev)
    assert [d["seq"] for d in r["dispatches"]] == [2, 3, 4] and r["entries"][(2, 64)]["n"] == 1
    assert r["legs"]["launch"] + r["legs"]["return"] + r["legs"]["between"] == pytest.approx(
        sc.idle_by_state(ev)["idle_s"])


def test_device_time_of_the_most_frequent_entry():
    r = dp.by_dispatch(_two_rounds())
    assert dp.top_entry(r["entries"]) == (2, 64)
    e = r["entries"][(2, 64)]
    assert e["n"] == 2 and e["device_s"] / e["n"] == pytest.approx(0.05)  # 0.06 and 0.04; the (2,256) read 0.13
    # between equals, the ladder's cheaper entry
    assert dp.top_entry({(2, 256): {"n": 3}, (2, 64): {"n": 3}, (4, 256): {"n": 1}}) == (2, 64)
    assert dp.top_entry({}) is None
    n = r["rounds"]
    assert r["annotations_per_round"]["decode.dispatch.chunk"] == pytest.approx(4 / n)
    assert r["annotations_per_round"]["decode.round"] == pytest.approx(2 / n)


def test_a_program_without_the_stats_reads_none_never_zero():
    ev = _two_rounds()
    for e in ev["host"]:
        if e[0].startswith("decode.dispatch."):
            e[4] = {}
    assert dp.by_dispatch(ev) is None and sc.idle_by_state(ev) is not None  # the parent: the old readers still read
    assert dp.by_dispatch(_events([("jit__fused_step", 0.1, 0.1)], [_d("step", 1, 0, 0.05, 0.3, rows=16, live=1)])) is None
    o = {"trace": None, "frames": []}
    assert dp.leg_ms_per_round(o, "launch") is None and dp.chunk_entry_ms(o) is None
    assert dp.offentry_wall_pct(o) is None and dp.ingress_ms(o) is None
    old = [SimpleNamespace(chunk_rows=2, busy_ns=(5, 0, 0, 0, 0))]  # frames of the parent: no chunk_c, no ingress_*
    assert dp.frame_entries({"frames": old}) is None and dp.ingress_ms({"frames": old}) is None


def test_an_enqueue_only_dispatch_is_all_launch():
    """A draft prefill is enqueued and not read back: its module starts after
    its span has ended, and the next dispatch's span does not take it."""
    ev = _events([("jit__draft", 0.22, 0.05), ("jit__fused_step", 0.32, 0.10)],
                 [["decode.round", 0.0, 1.0, LOOP, {"round": "0"}],
                  _d("draft", 1, 0, 0.10, 0.20), _rt("enqueue", "draft", 1, 0.10, 0.20, LOOP),
                  _d("step", 2, 0, 0.30, 0.50, rows=2, live=1), _rt("enqueue", "step", 2, 0.30, 0.31, LOOP),
                  _rt("readback", "step", 2, 0.31, 0.45)])
    r = dp.by_dispatch(ev)
    d = {x["seq"]: x for x in r["dispatches"]}
    assert d[1]["modules"] == 0 and d[1]["launch_s"] == pytest.approx(0.10) and d[1]["return_s"] == 0.0
    assert d[2]["modules"] == 1 and d[2]["launch_s"] == pytest.approx(0.02) and d[2]["return_s"] == pytest.approx(0.08)
    assert r["legs"]["hop"] == pytest.approx(0.05)  # the step's return after its readback ended; the draft's has none


def test_what_the_frames_say_of_entries_and_ingress():
    def frame(rows=0, c=0, wall=0, n=0, ns=0):
        return SimpleNamespace(chunk_rows=rows, chunk_c=c, busy_ns=(wall, 9, 0, 0, 0), ingress_requests=n, ingress_ns=ns)

    fs = [frame(2, 64, 20_000_000)] * 6 + [frame(64, 256, 110_000_000), frame(2, 256, 30_000_000)] + [frame()] * 5
    o = {"frames": fs + [frame(n=2, ns=3_000_000), frame(n=1, ns=1_500_000)]}
    assert dp.frame_entries(o) == {(2, 64): {"n": 6, "wall_s": pytest.approx(0.12)},
                                   (64, 256): {"n": 1, "wall_s": pytest.approx(0.11)},
                                   (2, 256): {"n": 1, "wall_s": pytest.approx(0.03)}}
    assert dp.offentry_wall_pct(o) == pytest.approx(100 * 0.14 / 0.26)
    assert dp.ingress_ms(o) == pytest.approx(1.5)
    assert dp.offentry_wall_pct({"frames": [frame()]}) is None and dp.ingress_ms({"frames": [frame()]}) is None
    assert dp.offentry_wall_pct({"frames": [frame(2, 64, 5)]}) == 0.0  # one entry: nothing off it


def test_what_the_session_costs_a_round_is_read_before_inside_and_after_it(monkeypatch):
    """``decode.round``'s ``round`` stat names the frames that committed
    inside the traced slice; the step-only rounds of the window are then
    read in three groups, and an untraced window's in one."""
    ev = _two_rounds()
    assert dp.traced_rounds(ev) == {0}  # round 1 runs past the window's end
    ev["host"][0][2] = 1.2
    assert dp.traced_rounds(ev) == {0, 1}

    def frame(seq, step=10_000_000, chunk=0, mode="plain"):
        return SimpleNamespace(seq=seq, mode=mode, busy_ns=(chunk, step, 0, 0, 0), gap_ns=1_000_000)

    frames = ([frame(i) for i in range(4)] + [frame(4, chunk=5)] + [frame(5, step=11_000_000), frame(6, step=13_000_000)]
              + [frame(7, mode="chain")] + [frame(i, step=15_000_000) for i in (8, 9)])
    monkeypatch.setattr(dp, "_of_file", lambda path: (None, {5, 6}))
    monkeypatch.setattr(dp, "newest_xplane", lambda d: "x")
    got = dp.tracing_on_cost({"trace": {"busy_s": 1.0}, "frames": frames})["step_round_ms"]
    want = {"before": (4, 11.0, 11.0, 10.0), "inside": (2, 13.0, 14.0, 12.0), "after": (2, 16.0, 16.0, 15.0),
            "after_1": (1, 16.0, 16.0, 15.0), "after_2": (1, 16.0, 16.0, 15.0)}  # neither the chunk round nor the speculative one
    assert got == {k: {"n": n, "mean": pytest.approx(m), "median": pytest.approx(md), "busy_mean": pytest.approx(b),
                       "gap_mean": pytest.approx(1.0)} for k, (n, m, md, b) in want.items()}
    assert dp.tracing_on_cost({"trace": None, "frames": frames}) is None
    assert dp.tracing_on_cost({"trace": {"busy_s": 1.0}, "frames": frames[:6]}) is None  # no round after it


# --------------------------------------------------------- a recorded trace

FIXTURE = os.path.join(BENCH, "harness", "fixtures", "trace_dispatches.json")


@pytest.fixture(scope="module")
def kept():
    with open(FIXTURE) as f:
        return json.load(f)


def test_the_recorded_trace_reduces_to_what_was_recorded(kept):
    ev = sc.expanded(kept["events"])
    r, old, want = dp.by_dispatch(ev), sc.idle_by_state(ev), kept["expected"]
    assert r["rounds"] == pytest.approx(want["rounds"]) == pytest.approx(old["rounds"])
    assert r["idle_s"] == pytest.approx(want["idle_s"]) == pytest.approx(old["idle_s"])
    for leg in dp.LEGS:
        assert r["legs"][leg] == pytest.approx(want["legs"][leg], abs=1e-9), leg
    assert sum(r["legs"][k] for k in ("launch", "return", "between")) == pytest.approx(old["idle_s"])
    assert r["legs"]["hop"] <= r["legs"]["launch"] + r["legs"]["return"]
    assert list(dp.top_entry(r["entries"])) == want["entry"]
    e = r["entries"][tuple(want["entry"])]
    assert e["n"] == want["entry_n"] and e["device_s"] / e["n"] == pytest.approx(want["entry_device_s"])
    assert len(r["dispatches"]) == want["whole_dispatches"]
    seqs = [d["seq"] for d in r["dispatches"]]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    assert all(d["modules"] >= 1 for d in r["dispatches"] if d["family"] in ("chunk", "step"))


def test_the_seven_metrics_have_their_readers():
    from conftest import ROOT

    from harness import cells

    bench = cells.load_bench(ROOT)
    names = {"idle_launch_ms", "idle_return_ms", "idle_between_ms", "idle_hop_ms", "chunk_entry_device_ms",
             "chunk_offentry_wall_pct", "ingress_ms.gen"}
    mine = [m for m in bench["per_layer"] if m["name"] in names]
    assert len(mine) == 7 and [m["name"] for m in bench["per_layer"][-7:]] == [m["name"] for m in mine]
    every = [w["name"] for w in bench["workloads"] if w["config"] != "bert"]
    for m in mine:
        assert m["moves"] == "itl_p95_ms" and m["better"] == "lower" and m["workloads"] == every[:5]
        reader = cells.load_module(ROOT, bench, "layer_metrics", m["name"])
        assert reader.read({"trace": None, "frames": [], "cell": "x", "t0": 0.0}) is None
