"""harness/ready.py: the launch and return legs as durations, the planes'
offset as a number, the join by order and the idle gaps by name, on hand-made
events and on a recorded trace, without a chip."""

import copy
import json
import os
from types import SimpleNamespace

import pytest
from conftest import BENCH, ROOT

from harness import cells
from harness import dispatches as dp
from harness import ready as rd
from harness import scopes as sc

W = sc.WINDOW
LOOP, POOL = "/host:CPU#3", "/host:CPU#7"
PLANE = "/device:TPU:0"


def _span(kind, family, seq, rnd, a, b, thread=POOL, **kw):
    stats = {"seq": str(seq), "round": str(rnd), **{k: str(v) for k, v in kw.items()}}
    return [f"decode.{kind}.{family}", a, b - a, thread, stats]


def _dispatch(family, seq, rnd, a, b, enq, mark, **kw):
    """A dispatch's four annotations: its span on the loop, the jitted call,
    the blocking read and, inside that, the copy out from ``mark``."""
    return [_span("dispatch", family, seq, rnd, a, b, LOOP, **kw), _span("enqueue", family, seq, rnd, a + 0.01, enq),
            _span("readback", family, seq, rnd, enq, b - 0.01), _span("copyout", family, seq, rnd, mark, b - 0.015)]


def _two_rounds():
    """Two whole rounds and one the slice's end cuts; each module ends 5 ms
    before its dispatch's mark (the runtime's completion latency: the planes
    share a clock here); 220 ms with nothing on the device before the last."""
    modules = [["jit__fused_chunk", 0.10, 0.06], ["jit__fused_step", 0.25, 0.15],
               ["jit__fused_chunk", 0.55, 0.13], ["jit__fused_step", 0.74, 0.04],
               ["jit__fused_chunk", 1.00, 0.03]]
    host = [[W, 0.0, 1.0, "", {}],
            ["decode.round", 0.05, 0.45, LOOP, {"round": "0", "t_ns": "1"}],
            ["decode.round", 0.50, 0.48, LOOP, {"round": "1", "t_ns": "2"}],
            ["decode.round", 0.98, 0.12, LOOP, {"round": "2", "t_ns": "3"}],
            *_dispatch("chunk", 1, 0, 0.06, 0.20, 0.09, 0.165, rows=2, c=64, live=1),
            *_dispatch("step", 2, 0, 0.22, 0.45, 0.24, 0.405, rows=16, live=16),
            *_dispatch("chunk", 3, 1, 0.52, 0.70, 0.545, 0.685, rows=2, c=256, live=2),
            *_dispatch("step", 4, 1, 0.72, 0.80, 0.735, 0.785, rows=16, live=16),
            ["decode.phase.emit_slo", 0.80, 0.17, LOOP, {}],
            ["decode.gc2", 0.82, 0.13, LOOP, {}],
            *_dispatch("chunk", 5, 2, 0.99, 1.05, 1.0, 1.035, rows=2, c=64, live=1)]
    return {"devices": {PLANE: {"ops": [], "modules": modules}}, "host": host, "op_name_stat": "tf_op"}


def _skewed(ev, by: float):
    """The device plane's clock ``by`` seconds ahead of the host plane's."""
    out = copy.deepcopy(ev)
    for dev in out["devices"].values():
        for e in dev["modules"] + dev["ops"]:
            e[1] += by
    return out


def _legs(ev):
    j = rd.joined(ev)
    n = len(j["rounds"])
    return (sum(d["launch_s"] for d in j["dispatches"]) / n, sum(d["rdy_s"] for d in j["dispatches"]) / n,
            [d["offset_s"] for d in j["dispatches"]])


def test_the_two_legs_are_durations_and_the_offset_is_a_number():
    j = rd.joined(_two_rounds())
    assert j["rounds"] == {0, 1} and [d["seq"] for d in j["dispatches"]] == [1, 2, 3, 4]  # 5 is the cut round's
    d = {x["seq"]: x for x in j["dispatches"]}
    # wall less the part after the mark less the module: 140 - 35 - 60
    assert (d[1]["wall_s"], d[1]["rdy_s"], d[1]["device_s"], d[1]["launch_s"]) == pytest.approx((0.14, 0.035, 0.06, 0.045))
    assert (d[2]["rdy_s"], d[2]["launch_s"]) == pytest.approx((0.045, 0.035))
    assert (d[4]["rdy_s"], d[4]["device_s"], d[4]["launch_s"]) == pytest.approx((0.015, 0.04, 0.025))
    assert all(x["by"] == "order" and x["modules"] == 1 for x in j["dispatches"]) and j["disagree"] == 0
    launch, back, offs = _legs(_two_rounds())
    assert launch == pytest.approx(0.14 / 2) and back == pytest.approx(0.11 / 2)
    assert offs == pytest.approx([-0.005] * 4)  # device end - mark: the latency alone


@pytest.mark.parametrize("planted", [-0.02, 0.0013, 0.035])
def test_an_offset_between_the_planes_moves_neither_leg_and_is_returned(planted):
    """What differs between two profiler sessions: the old legs trade it, the
    new ones do not see it, and ``plane_offset_ms`` reads it."""
    ev, skew = _two_rounds(), _skewed(_two_rounds(), planted)
    assert _legs(skew)[:2] == pytest.approx(_legs(ev)[:2])
    assert _legs(skew)[2] == pytest.approx([planted - 0.005] * 4)
    old, moved = dp.by_dispatch(ev)["legs"], dp.by_dispatch(skew)["legs"]
    assert (moved["launch"] - old["launch"]) * planted > 0 > (moved["return"] - old["return"]) * planted


def test_the_join_by_order_holds_where_a_modules_middle_falls_outside_its_span():
    """45 ms of skew put the short step's middle before its span began: the
    midpoint rule finds it no module; by order it is still the second step."""
    skew = _skewed(_two_rounds(), -0.045)
    lost = {d["seq"]: d for d in dp.by_dispatch(skew)["dispatches"]}[4]
    assert lost["modules"] == 0 and lost["device_s"] == 0.0
    j = rd.joined(skew)
    kept = {d["seq"]: d for d in j["dispatches"]}[4]
    assert kept["modules"] == 1 and kept["device_s"] == pytest.approx(0.04) and j["disagree"] == 1
    assert _legs(skew)[:2] == pytest.approx(_legs(_two_rounds())[:2])
    # a session begins mid-stream: modules whose dispatch spans are not in the trace shift nothing
    early = _two_rounds()
    early["devices"][PLANE]["modules"][:0] = [["jit__fused_chunk", -0.30, 0.06], ["jit__fused_step", -0.20, 0.15]]
    assert _legs(early)[:2] == pytest.approx(_legs(_two_rounds())[:2])


def test_a_gap_over_20_ms_is_listed_with_the_dispatches_on_either_side():
    ev = _two_rounds()
    idle = rd.idle_gaps(ev, rd.joined(ev))
    assert idle["max_s"] == pytest.approx(0.22) and [round(g["ms"]) for g in idle["gaps"]] == [220, 150, 90, 60, 50]
    g = idle["gaps"][0]
    assert g["before"] == [4, "step"] and g["after"] == [5, "chunk"] and g["round"] == 1
    assert g["before_marked"] and g["mark_into_gap_ms"] == pytest.approx(5.0)
    # what the program was in: the collection's name is there, the round's own is not
    assert g["covered_ms"]["gc2"] == pytest.approx(130.0) and g["covered_ms"]["phase.emit_slo"] == pytest.approx(170.0)
    assert g["covered_ms"]["dispatch.step"] == pytest.approx(20.0) and "round" not in g["covered_ms"]
    assert idle["gaps"][-1]["before"] is None and not idle["gaps"][-1]["before_marked"]  # before the slice's first dispatch
    assert rd.idle_gaps(ev, rd.joined(ev), floor_ms=200.0)["gaps"] == [g]
    # the device went idle and the read was not woken for 120 ms: the earlier dispatch had NOT marked
    late = copy.deepcopy(ev)
    mark = next(e for e in late["host"] if e[0] == "decode.copyout.step" and e[4]["seq"] == "4")
    mark[1], mark[2] = 0.90, 0.005
    g = rd.idle_gaps(late, rd.joined(late))["gaps"][0]
    assert not g["before_marked"] and g["mark_into_gap_ms"] == pytest.approx(120.0)


def _frame(seq, rdy=(0, 0, 0, 0, 0), busy=(0, 10_000_000, 0, 0, 0), gap=1_000_000, **kw):
    return SimpleNamespace(seq=seq, rdy_ns=rdy, busy_ns=busy, gap_ns=gap, active=16, queued=0, mode="plain", **kw)


def test_dispatch_return_ms_reads_the_frames_alone(monkeypatch):
    """A ``program_counter``: frames and no trace. In a traced run the frames
    after the slice are left out, and the line has the window's three parts."""
    frames = [_frame(i, rdy=(1_000_000, 2_000_000, 0, 0, 0), busy=(8_000_000, 10_000_000, 0, 0, 0)) for i in range(6)]
    frames += [_frame(i, rdy=(0, 4_000_000, 0, 0, 0)) for i in range(6, 10)]  # after the slice: slower
    o = {"trace": None, "frames": frames}
    assert rd.return_ms_per_round(o) == pytest.approx((6 * 3.0 + 4 * 4.0) / 10)
    assert rd.window_parts(o) == {"all": {"rounds": 10, "return_ms": pytest.approx(3.4),
                                          "rest_of_wall_ms": pytest.approx((6 * 15.0 + 4 * 6.0) / 10), "gap_ms": pytest.approx(1.0)}}
    assert rd.launch_ms_per_round(o) is None and rd.offset_ms(o) is None and rd.gap_max_ms(o) is None
    monkeypatch.setattr(rd, "_of_file", lambda path: {"join": None, "idle": None, "slice_rounds": {2, 3, 4, 5}, "read_s": 0.0})
    monkeypatch.setattr(rd, "newest_xplane", lambda d: "x")
    traced = {"trace": {"busy_s": 1.0}, "frames": frames}
    assert rd.return_ms_per_round(traced) == pytest.approx(3.0)  # frames 0..5
    parts = rd.window_parts(traced)
    assert [parts[k]["rounds"] for k in ("before", "inside", "after")] == [2, 4, 4]
    assert parts["after"]["return_ms"] == pytest.approx(4.0) and parts["inside"]["rest_of_wall_ms"] == pytest.approx(15.0)
    # the parent's frames have no slot: None, never 0
    old = [SimpleNamespace(seq=0, busy_ns=(0, 5, 0, 0, 0), gap_ns=1)]
    assert rd.return_ms_per_round({"trace": None, "frames": old}) is None and rd.window_parts({"frames": old}) is None
    assert rd.return_ms_per_round({"trace": None, "frames": []}) is None


def test_a_program_without_the_mark_reads_none_never_zero():
    ev = _two_rounds()
    ev["host"] = [e for e in ev["host"] if not e[0].startswith("decode.copyout.")]
    assert rd.joined(ev) is None and dp.by_dispatch(ev) is not None  # the parent: the old readers still read
    reduced = rd.reduce_ready(ev)
    assert reduced["join"] is None and reduced["idle"]["max_s"] == pytest.approx(0.22) and reduced["slice_rounds"] == {0, 1}
    no_rounds = {"devices": {PLANE: {"ops": [], "modules": []}}, "host": [[W, 0.0, 1.0, "", {}]], "op_name_stat": None}
    assert rd.reduce_ready(no_rounds) == {"join": None, "idle": None, "slice_rounds": set()}


def test_the_run_says_one_ready_line(monkeypatch, capsys):
    ev = _two_rounds()
    monkeypatch.setattr(rd, "_of_file", lambda path: {**rd.reduce_ready(ev), "read_s": 0.0})
    monkeypatch.setattr(rd, "newest_xplane", lambda d: "x")
    monkeypatch.setattr(dp, "of_run", lambda o: None)
    frames = [_frame(0, rdy=(35_000_000, 45_000_000, 0, 0, 0)), _frame(1, rdy=(15_000_000, 15_000_000, 0, 0, 0)), _frame(2)]
    o = {"cell": "x", "t0": 53.0, "trace": {"busy_s": 1.0}, "frames": frames}
    rd.say(o)
    rd.say(o)  # once a window
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == 1
    line = json.loads(out[0])
    assert line["phase"] == "ready" and line["window"]["inside"]["rounds"] == 2 and line["window"]["after"]["rounds"] == 1
    t = line["trace"]
    assert (t["launch_ms_a_round"], t["return_ms_a_round"]) == pytest.approx((70.0, 55.0))
    assert t["return_ms_a_round_by_frames"] == pytest.approx(55.0)  # the frames' slot and the trace's span say the same
    assert t["offset_ms_device_end_less_mark"]["median"] == pytest.approx(-5.0) and t["joined_by"] == ["order"]
    assert t["per_dispatch_ms_median"]["step"]["n"] == 2
    assert line["idle"]["gap_max_ms"] == pytest.approx(220.0)
    assert line["idle"]["gaps"][0]["frame"] == {"active": 16, "queued": 0, "mode": "plain"}
    assert rd.launch_ms_per_round(o) == pytest.approx(70.0) and rd.offset_ms(o) == pytest.approx(5.0)
    assert rd.gap_max_ms(o) == pytest.approx(220.0)


# --------------------------------------------------------- a recorded trace

FIXTURE = os.path.join(BENCH, "harness", "fixtures", "trace_ready.json")


@pytest.fixture(scope="module")
def kept():
    with open(FIXTURE) as f:
        return json.load(f)


def test_the_recorded_trace_reduces_to_what_was_recorded(kept):
    ev = sc.expanded(kept["events"])
    want = kept["expected"]
    j = rd.joined(ev)
    assert sorted(j["rounds"]) == want["rounds"] and len(j["dispatches"]) == want["dispatches"]
    launch, back, offs = _legs(ev)
    assert (launch, back) == pytest.approx((want["launch_s_a_round"], want["return_s_a_round"]), abs=1e-9)
    assert sorted(offs)[len(offs) // 2] == pytest.approx(want["offset_s_median"], abs=1e-9)
    assert {d["by"] for d in j["dispatches"]} == {"order"} and j["disagree"] == 0
    assert all(d["modules"] == 1 and 0 < d["rdy_s"] < d["wall_s"] and d["launch_s"] > 0 for d in j["dispatches"])
    assert rd.idle_gaps(ev, j)["max_s"] == pytest.approx(want["gap_max_s"], abs=1e-9)
    # the old split of the same events, for the record: the legs' sum is the same idle
    old = dp.by_dispatch(ev)
    assert 1e3 * (old["legs"]["launch"] + old["legs"]["return"]) / old["rounds"] == pytest.approx(
        want["idle_launch_plus_return_ms_a_round"], abs=1e-6)


@pytest.mark.parametrize("planted_ms", [-1.5, 0.8, 2.0])
def test_an_offset_planted_in_the_recorded_trace_is_returned_and_moves_neither_leg(kept, planted_ms):
    """A session whose device plane lies ``planted_ms`` further off: the new
    legs stay to the nanosecond, the offset reads it, the old legs trade it."""
    ev = sc.expanded(kept["events"])
    skew = _skewed(ev, planted_ms / 1e3)
    launch, back, offs = _legs(ev)
    launch2, back2, offs2 = _legs(skew)
    assert (launch2, back2) == pytest.approx((launch, back), abs=1e-9)
    assert sorted(offs2)[len(offs2) // 2] - sorted(offs)[len(offs) // 2] == pytest.approx(planted_ms / 1e3, abs=1e-9)
    old, moved = dp.by_dispatch(ev), dp.by_dispatch(skew)
    per_round = 1e3 * (moved["legs"]["launch"] - old["legs"]["launch"]) / old["rounds"]
    assert per_round * planted_ms > 0 and abs(per_round) > 0.5 * abs(planted_ms)  # a dispatch or more a round trades it


def test_the_four_metrics_have_their_readers():
    """Each entry is found by NAME, wherever later entries put it."""
    bench = cells.load_bench(ROOT)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    every = [w["name"] for w in bench["workloads"] if w["config"] != "bert"]
    sources = {"dispatch_return_ms": "program_counter", "dispatch_launch_ms": "device_trace",
               "plane_offset_ms": "device_trace", "idle_gap_max_ms": "device_trace"}
    for name, source in sources.items():
        m = by_name[name]
        assert m == {"name": name, "unit": "ms", "better": "lower", "source": source, "layer": "decode scheduler",
                     "moves": "itl_p95_ms", "workloads": m["workloads"]}
        assert set(every[:9]) <= set(m["workloads"])
        reader = cells.load_module(ROOT, bench, "layer_metrics", name)
        assert reader.read({"trace": None, "frames": [], "cell": "x", "t0": 0.0}) is None
    # the one that needs no trace reads with none
    reader = cells.load_module(ROOT, bench, "layer_metrics", "dispatch_return_ms")
    assert reader.read({"trace": None, "frames": [_frame(0, rdy=(0, 3_000_000, 0, 0, 0))], "cell": "y", "t0": 1.0}) == pytest.approx(3.0)
