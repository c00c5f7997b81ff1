"""A dispatch says when its result was ready: the host's launch and return
legs as DURATIONS, the two planes' clock offset as a number, and the device's
longest idle gaps named by what the program was in.

``harness/dispatches.py`` splits the device's idle inside a dispatch at the
device's module events and found (PERF.md section 6, PR 39) that the two legs
trade a millisecond or two between profiler sessions: the device plane's
clock lies off the host plane's by another amount in each. Since PR 53 the
program marks, inside every blocking read, the moment it LEARNED that the
result was ready (``serving/decode_scheduler.py`` ``_Dispatch._collect``): the
FlightFrame slot ``rdy_ns`` (per family, the part of ``rdb_ns`` after the
mark) and the annotation ``decode.copyout.<family>`` nested in
``decode.readback.<family>``. From them, with no difference of two clocks:

- ``return_ms_per_round`` (``dispatch_return_ms``, a ``program_counter``):
  the frames' summed ``rdy_ns`` a round, over the window's frames up to the
  end of the profiler's slice (the rounds after it run 2 ms a dispatch
  slower: PERF.md section 7, PR 39 (b)); the whole window untraced. Frames
  alone: no trace is read for it but to know where the slice ended.
- ``launch_ms_per_round`` (``dispatch_launch_ms``): over the dispatches of
  the slice's whole rounds, a dispatch's wall (its ``decode.dispatch.*``
  span, host clock) less its return part (its ``decode.copyout.*`` start to
  the span's end, host clock) less the device time of its modules (device
  clock), summed, a round.
- ``offset_ms`` (``plane_offset_ms``): the median over those dispatches of
  \\|device plane's end of the dispatch's last module - host plane's start of
  its ``decode.copyout.*``\\|: the offset between the planes' clocks in this
  session less the runtime's completion latency. Signed (device end - mark)
  it is the correction to the same run's old legs: ``dispatch_return_ms`` =
  ``idle_return_ms`` + dispatches a round x the signed offset.
- ``gap_max_ms`` (``idle_gap_max_ms``): the device's longest single idle
  interval in the span the rounds cover; ``gaps`` lists every one over
  ``GAP_MS`` with the dispatches on either side, whether the earlier one had
  marked, the program's own ``decode.*`` spans that cover it and the frame's
  ``active`` / ``queued``.

**The join is BY ORDER.** The n-th dispatch by ``seq`` of a family owns the
n-th module of that family's program on the device's line, which runs them in
the order they were enqueued: what ``dispatches.py``'s docstring says a
two-deep queue needs, and what no offset can move. Its midpoint rule only
seeds the alignment (a session begins mid-stream: the first modules' spans
are not in the trace) at the slice's first whole dispatch of the family. A
family without a program mark (a speculative pair, the copy ladder: in no
cell) keeps the midpoint rule.

A program without the mark (the parent of PR 53) gives None for everything
here, never 0. Times are seconds on the trace's clocks. Checked without a
chip on ``fixtures/trace_ready.json`` (``selfcheck/test_ready.py``).
"""

from __future__ import annotations

import bisect
import functools
import json
import statistics
import time

from harness import dispatches as dp
from harness import scopes as sc
from harness.trace import TRACE_DIR, WINDOW, _clip, _union, newest_xplane

PRE = sc.ANN_PREFIX
GAP_MS = 20.0  # an idle interval of the device longer than this is listed by name


def _seq_events(ann: list, kind: str) -> list:
    """The ``decode.<kind>.<family>`` events that carry a ``seq``, by it."""
    out = [e for e in ann if e[0].startswith(PRE + kind + ".") and dp._int(e[4], "seq") is not None]
    return sorted(out, key=lambda e: dp._int(e[4], "seq"))


def joined(events: dict) -> dict | None:
    """Every dispatch of the slice's whole rounds with its modules, joined by
    order. None where the trace holds no whole ``decode.round`` with a
    ``round`` stat, no dispatch with a ``seq`` or no ``decode.copyout.*`` (the
    parent of PR 53). Keys: ``rounds`` (the whole rounds' indices),
    ``dispatches`` [{"family", "seq", "round", "wall_s", "device_s", "modules",
    "rdy_s" (its copyout's start to its span's end | 0), "launch_s" (wall -
    rdy - device), "offset_s" (device end - its copyout's start | None), "by"
    ("order" | "middle")}], ``disagree`` (whole dispatches whose module by
    order is not the one the midpoint rule finds)."""
    rounds = dp.traced_rounds(events)
    if not rounds or not events["devices"]:
        return None
    win = next(e for e in events["host"] if e[0] == WINDOW)
    w0, w1 = win[1], win[1] + win[2]
    ann = [e for e in events["host"] if e[0].startswith(PRE)]
    spans = _seq_events(ann, "dispatch")
    ready = {dp._int(e[4], "seq"): e[1] for e in _seq_events(ann, "copyout")}
    if not spans or not ready:
        return None
    plane = sorted(events["devices"])[0]
    mods = sorted((s, s + d, name) for name, s, d in events["devices"][plane]["modules"])
    mids = [(m[0] + m[1]) / 2 for m in mods]

    def by_middle(a: float, b: float) -> list:
        return mods[bisect.bisect_left(mids, a):bisect.bisect_left(mids, b)]

    own: dict[int, tuple] = {}  # seq -> (its modules, how they were found)
    disagree = 0
    for family in sorted({e[0][len(PRE + "dispatch."):] for e in spans}):
        mine = [e for e in spans if e[0] == PRE + "dispatch." + family]
        mark = dp.MARKS.get(family)
        fam_mods = [m for m in mods if mark and mark in m[2]]
        shift = None
        for i, e in enumerate(mine):  # the seed: the first whole dispatch that holds a module's middle
            if w0 <= e[1] and e[1] + e[2] <= w1:
                held = [m for m in by_middle(e[1], e[1] + e[2]) if mark and mark in m[2]]
                if held:
                    shift = fam_mods.index(held[0]) - i
                    break
        for i, e in enumerate(mine):
            seq = dp._int(e[4], "seq")
            if shift is None:
                own[seq] = (by_middle(e[1], e[1] + e[2]), "middle")
                continue
            j = i + shift
            own[seq] = ([fam_mods[j]] if 0 <= j < len(fam_mods) else [], "order")
            if w0 <= e[1] and e[1] + e[2] <= w1:
                disagree += own[seq][0] != [m for m in by_middle(e[1], e[1] + e[2]) if mark in m[2]]
    table = []
    for name, a, d, _thread, stats in spans:
        seq, rnd = dp._int(stats, "seq"), dp._int(stats, "round")
        if rnd not in rounds or not (w0 <= a and a + d <= w1):
            continue
        ms, how = own[seq]
        dev_end = max((m[1] for m in ms), default=None)
        mark = ready.get(seq)
        rdy = a + d - mark if mark is not None else 0.0
        device = sum(m[1] - m[0] for m in ms)
        table.append({
            "family": name[len(PRE + "dispatch."):], "seq": seq, "round": rnd,
            "wall_s": d, "device_s": device, "modules": len(ms), "rdy_s": rdy, "launch_s": d - rdy - device,
            "offset_s": dev_end - mark if mark is not None and dev_end is not None else None, "by": how,
        })
    return {"rounds": rounds, "dispatches": table, "disagree": disagree}


def idle_gaps(events: dict, join: dict | None, floor_ms: float = GAP_MS) -> dict | None:
    """{"max_s", "gaps": [...]} over the span the rounds cover: the device's
    idle intervals (the complement of the union of its op events, as
    ``idle_by_state`` takes them; the longest over the device planes) and, for
    each over ``floor_ms``: ``ms``, ``at_s`` (from the span's start),
    ``before`` / ``after`` (``seq`` and family of the dispatch whose span began
    last before the gap did, and of the next), ``before_marked`` (that
    dispatch's ``decode.copyout.*`` had begun when the gap did, within the
    planes' offset: the device's result was known and the stall lies after the
    mark; false where the device went idle and the read was not woken, or the
    gap lies in the launch of ``before``), ``mark_into_gap_ms``, ``covered_ms``
    {the program's own ``decode.*`` span names: ms of the gap under one} and
    ``round`` (the earlier dispatch's). None where no ``decode.round`` spans."""
    span = dp._round_span(events)
    if span is None:
        return None
    t0, t1, _ = span
    gaps = []
    for plane in sorted(events["devices"]):
        dev = events["devices"][plane]
        busy = _union([(a, b) for _, a, b in _clip([e[:3] for e in (dev["ops"] or dev["modules"])], t0, t1)])
        gaps += sc._subtract([(t0, t1)], busy)
    if not gaps:
        return {"max_s": 0.0, "gaps": []}
    ann = [e for e in events["host"] if e[0].startswith(PRE)]
    spans = sorted(_seq_events(ann, "dispatch"), key=lambda e: e[1])
    starts = [e[1] for e in spans]
    ready = {dp._int(e[4], "seq"): e[1] for e in _seq_events(ann, "copyout")}
    offs = [abs(d["offset_s"]) for d in (join or {}).get("dispatches", []) if d["offset_s"] is not None]
    tol = 0.002 + (statistics.median(offs) if offs else 0.0)
    listed = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1]):
        if 1e3 * (b - a) <= floor_ms:
            break
        i = bisect.bisect_right(starts, a)
        before, after = (spans[i - 1] if i else None), (spans[i] if i < len(spans) else None)
        mark = ready.get(dp._int(before[4], "seq")) if before else None
        covered: dict[str, float] = {}
        for name, s, d, _thread, _stats in ann:
            lap = min(s + d, b) - max(s, a)
            if lap > 0 and name != PRE + "round":
                covered[name[len(PRE):]] = covered.get(name[len(PRE):], 0.0) + 1e3 * lap
        listed.append({
            "ms": 1e3 * (b - a), "at_s": a - t0,
            "before": before and [dp._int(before[4], "seq"), before[0][len(PRE + "dispatch."):]],
            "after": after and [dp._int(after[4], "seq"), after[0][len(PRE + "dispatch."):]],
            "before_marked": mark is not None and mark <= a + tol,
            "mark_into_gap_ms": None if mark is None else 1e3 * (mark - a),
            "covered_ms": {k: round(v, 2) for k, v in sorted(covered.items(), key=lambda kv: -kv[1]) if v >= 0.5},
            "round": before and dp._int(before[4], "round"),
        })
    return {"max_s": max(b - a for a, b in gaps), "gaps": listed}


def reduce_ready(events: dict) -> dict:
    join = joined(events)
    return {"join": join, "idle": idle_gaps(events, join), "slice_rounds": dp.traced_rounds(events)}


# ----------------------------------------------------- what the readers call


@functools.lru_cache(maxsize=1)
def _of_file(path: str) -> dict:
    t = time.monotonic()
    out = reduce_ready(sc.read_scoped(path))
    out["read_s"] = time.monotonic() - t
    return out


def of_run(o: dict) -> dict | None:
    """This run's reduction, read once for its readers; None untraced."""
    if not o.get("trace"):
        return None
    return _of_file(newest_xplane(TRACE_DIR))


def _marked(o: dict) -> list | None:
    """The window's frames, if they carry the slot (None on the parent)."""
    fs = o.get("frames") or []
    return fs if fs and hasattr(fs[0], "rdy_ns") else None


def _legs(fs: list) -> dict | None:
    """Of frames: rounds, and a round's return leg, the rest of its dispatch
    wall (launch + device: ``busy_ns - rdy_ns``) and its host gap, ms."""
    if not fs:
        return None
    n = len(fs)
    rdy, busy = sum(sum(f.rdy_ns) for f in fs), sum(sum(f.busy_ns) for f in fs)
    return {"rounds": n, "return_ms": rdy / n / 1e6, "rest_of_wall_ms": (busy - rdy) / n / 1e6,
            "gap_ms": sum(f.gap_ns for f in fs) / n / 1e6}


def window_parts(o: dict) -> dict | None:
    """The window's frames before, in and after the profiler's slice, each as
    ``_legs`` reads it; the whole window under "all". None on the parent."""
    fs = _marked(o)
    if fs is None:
        return None
    out = {"all": _legs(fs)}
    inside = (of_run(o) or {}).get("slice_rounds")
    if inside:
        lo, hi = min(inside), max(inside)
        out.update(before=_legs([f for f in fs if f.seq < lo]), inside=_legs([f for f in fs if lo <= f.seq <= hi]),
                   after=_legs([f for f in fs if f.seq > hi]))
    return out


def return_ms_per_round(o: dict) -> float | None:
    fs = _marked(o)
    if fs is None:
        return None
    inside = (of_run(o) or {}).get("slice_rounds")
    if inside:
        fs = [f for f in fs if f.seq <= max(inside)]
    legs = _legs(fs)
    return legs and legs["return_ms"]


def launch_ms_per_round(o: dict) -> float | None:
    j = (of_run(o) or {}).get("join")
    if not j or not j["dispatches"]:
        return None
    return 1e3 * sum(d["launch_s"] for d in j["dispatches"]) / len(j["rounds"])


def _offsets(o: dict) -> list:
    j = (of_run(o) or {}).get("join")
    return [d["offset_s"] for d in j["dispatches"] if d["offset_s"] is not None] if j else []


def offset_ms(o: dict) -> float | None:
    offs = _offsets(o)
    return 1e3 * statistics.median(abs(x) for x in offs) if offs else None


def gap_max_ms(o: dict) -> float | None:
    r = of_run(o)
    if not r or not r["join"] or not r["idle"]:
        return None  # the parent reads None for all four alike
    return 1e3 * r["idle"]["max_s"]


# ------------------------------------------------------- the run's own line

_said: set = set()


def _spread(xs: list) -> dict:
    xs = sorted(xs)
    q = statistics.quantiles(xs, n=4) if len(xs) > 1 else [xs[0]] * 3
    return {"n": len(xs), "min": xs[0], "q1": q[0], "median": q[1], "q3": q[2], "max": xs[-1]}


def say(o: dict) -> None:
    """One earlier line of the run, ``{"phase": "ready"}``, once a window, by
    whichever of the four readers runs first; nothing on the parent. The
    window's three parts by the frames; the slice by the trace: both legs a
    round, the signed offset (device end - mark) and its spread, a dispatch's
    medians by family, the old legs of the same run beside what the offset
    makes of them, and every idle gap over ``GAP_MS``."""
    key = (o.get("cell"), o.get("t0"))
    if key in _said:
        return
    _said.add(key)
    line: dict = {"phase": "ready"}
    frames = {f.seq: f for f in o.get("frames") or []}
    parts = window_parts(o)
    if parts:
        line["window"] = parts
    r = of_run(o)
    j = r and r["join"]
    if j and j["dispatches"]:
        n, ds = len(j["rounds"]), j["dispatches"]
        offs = _offsets(o)
        signed = 1e3 * statistics.median(offs) if offs else None
        fams: dict[str, list] = {}
        for d in ds:
            fams.setdefault(d["family"], []).append(d)
        old = dp.of_run(o)
        old_legs = old and {k: 1e3 * old["legs"][k] / old["rounds"] for k in ("launch", "return")}
        line["trace"] = {
            "rounds": n, "dispatches": len(ds), "dispatches_a_round": len(ds) / n, "read_s": r["read_s"],
            "launch_ms_a_round": 1e3 * sum(d["launch_s"] for d in ds) / n,
            "return_ms_a_round": 1e3 * sum(d["rdy_s"] for d in ds) / n,
            "return_ms_a_round_by_frames": (
                sum(sum(frames[i].rdy_ns) for i in j["rounds"] if i in frames) / 1e6 / n if parts else None),
            "offset_ms_device_end_less_mark": offs and {k: v if k == "n" else 1e3 * v for k, v in _spread(offs).items()},
            "joined_by": sorted({d["by"] for d in ds}), "order_and_middle_disagree": j["disagree"],
            "per_dispatch_ms_median": {
                f: {"n": len(x), **{k: 1e3 * statistics.median(d[k + "_s"] for d in x)
                                    for k in ("wall", "device", "rdy", "launch")}}
                for f, x in sorted(fams.items())},
            # the same run's old legs, and what the measured offset makes of them
            "idle_legs_ms_a_round": old_legs,
            "idle_legs_corrected_ms_a_round": old_legs and signed is not None and {
                "launch": old_legs["launch"] - signed * len(offs) / n, "return": old_legs["return"] + signed * len(offs) / n},
        }
    idle = r and r["idle"]
    if j and idle:
        for g in idle["gaps"]:
            f = frames.get(g["round"])
            g["frame"] = f and {"active": f.active, "queued": f.queued, "mode": f.mode}
        line["idle"] = {"gap_max_ms": 1e3 * idle["max_s"], "over_ms": GAP_MS, "gaps": idle["gaps"]}
    if len(line) > 1:
        print(json.dumps(line), flush=True)
