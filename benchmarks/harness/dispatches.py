"""Host joined to device BY DISPATCH: the device's idle split where the
device's own events fall, and device time per chunk-ladder entry.

``harness/scopes.py`` ``idle_by_state`` gives an idle instant to ``enqueue``
or ``readback`` by which HOST annotation covers it, and the two numbers trade
a millisecond between runs with nothing changed (PERF.md section 6, PR 32,
which took it for a race between the jitted call's return and the module's
start). This file reads the same ``.xplane.pb`` through
``scopes.read_scoped`` and splits the same idle at the device's own events,
which share the annotations' clock: a split that no annotation's end can
move. What it found on the chip (PERF.md section 6, PR 39): the call returns
1-2.6 ms AFTER the module starts (``start_to_call_return_s``), so both splits
read the same to 0.01 ms a round, and the millisecond moves with the offset
between the device plane's clock and the host plane's, which is another in
each profiler session, in every dispatch alike. Across sessions compare ``launch + return``,
``hop``, ``between`` and a dispatch's wall less its device time.

**A dispatch's modules.** Since PR 39 every ``decode.dispatch.<family>``
annotation carries ``seq`` (the scheduler's dispatch serial), ``round`` (its
FlightFrame's index) and, for a chunk, its ``chunk_buckets`` entry ``rows`` /
``c`` and its ``live`` rows. A dispatch's modules are the "XLA Modules"
events whose MIDDLE lies inside its span: the loop awaits each dispatch, so a
module starts and ends inside the span that launched it and no other
program's can. The middle and not the start, because the device plane's
clock lies up to a millisecond or two off the host plane's, differently in
each profiler session (PERF.md section 6, PR 39: in one session two steps'
modules "started" 0.02 ms BEFORE the call that launched them was entered);
a module of 7 ms or more keeps its middle in its span through that, where
its start may fall before it. A later two-deep dispatch queue breaks the rule
either way (a span then holds its predecessor's module); it must join by
ORDER instead: the n-th dispatch by ``seq`` that launches a family's program
owns the n-th module of that family on the device's line, which runs them in
the order they were enqueued.

**The legs.** Over the part of the slice that ``decode.round`` spans, exactly
as ``idle_by_state`` takes it (so both see the same idle), every idle instant
of the device goes to ONE of:

- ``launch``: inside a dispatch span, before its last module ends (idle
  there is before its first module starts: hand-off to the executor, the
  jitted call, argument transfer, the runtime's launch; between two modules
  of one dispatch it is the second's launch). A span with no module of its
  own (an enqueue-only draft prefill) is all launch;
- ``return``: inside a dispatch span, after its last module ends (device to
  host, the blocked read's return, the hop back to the loop);
- ``between``: outside every dispatch span (phases, ``sse_write``, the
  loop's other tasks);
- and, a sub-account of the first two, ``hop``: the part of ``launch`` before
  any thread has entered the dispatch's ``decode.enqueue.*`` plus the part of
  ``return`` after its last ``decode.enqueue.*`` / ``decode.readback.*`` has
  ended. No thread is in the runtime then: the time is asyncio's and the
  GIL's. ``hop_in_ingress`` is the part of it under a ``decode.ingress``.

``launch + return + between`` is ``idle_by_state``'s ``idle_s``. Idle is
counted over every dispatch span, cut or whole; the PER-DISPATCH figures
(counts, device time per entry) take whole dispatches only: one cut by the
slice's edge is left out, not counted short.

A program without the stats (the parent of PR 39) gives None for everything
here, never 0. Times are seconds on the trace's clock. Checked without a chip
on ``fixtures/trace_dispatches.json`` (``selfcheck/test_dispatches.py``).
"""

from __future__ import annotations

import bisect
import functools
import json

from harness import scopes as sc
from harness.trace import TRACE_DIR, WINDOW, _clip, _union, newest_xplane

PRE = sc.ANN_PREFIX
LEGS = ("launch", "return", "between", "hop")
MARKS = {"chunk": sc.CHUNK_MARK, "step": sc.STEP_MARK}


def _round_span(events: dict) -> tuple | None:
    """(t0, t1, rounds) as ``idle_by_state`` takes them."""
    win = [e for e in events["host"] if e[0] == WINDOW]
    whole = [e for e in events["host"] if e[0] == PRE + "round" and e[2] > 0]
    if not win or not events["devices"]:
        return None
    w0, w1 = win[0][1], win[0][1] + win[0][2]
    inside = [(a, b) for _, a, b in _clip([e[:3] for e in whole], w0, w1)]
    if not inside:
        return None
    t0, t1 = min(a for a, _ in inside), max(b for _, b in inside)
    rounds = sum((min(e[1] + e[2], t1) - max(e[1], t0)) / e[2] for e in whole
                 if min(e[1] + e[2], t1) > max(e[1], t0))
    return t0, t1, rounds


def _int(stats: dict, key: str) -> int | None:
    try:
        return int(stats[key])
    except (KeyError, TypeError, ValueError):
        return None


def by_dispatch(events: dict) -> dict | None:
    """The join and the split. None where the trace holds no ``decode.round``
    or no dispatch annotation with a ``seq``. Keys: ``span_s``, ``rounds``,
    ``idle_s``, ``legs`` {leg: s} (mean over device planes),
    ``hop_in_ingress_s``, ``dispatches`` (the whole ones, by ``seq``:
    {"family", "seq", "round", "rows", "c", "live", "wall_s", "device_s",
    "modules", "launch_s", "return_s", "call_to_device_s",
    "device_to_read_s", "start_to_call_return_s"}; ``device_s`` is the
    family's own program's modules, the legs are the first plane's; the last
    three are enqueue entry to first module start, last module end to
    readback return, unclipped (host clock against device clock: neither can
    truly be negative, so one that reads so, or their minima, bound the two
    planes' clock offset in this session) and first module start to the enqueue annotation's end (above
    0: the jitted call returned after the device had begun, so
    ``idle_by_state`` splits the dispatch's idle where the legs do), ``entries``
    {(rows, c): {"n", "device_s"}} of the chunk dispatches and
    ``annotations_per_round`` {name: events that began in the span a round}."""
    span = _round_span(events)
    if span is None:
        return None
    t0, t1, rounds = span
    ann = [e for e in events["host"] if e[0].startswith(PRE)]
    spans = sorted((e for e in ann if e[0].startswith(PRE + "dispatch.") and _int(e[4], "seq") is not None),
                   key=lambda e: e[1])
    if not spans:
        return None
    # the runtime's side of a dispatch: its enqueue and readback events, on whatever thread
    runtime = sorted((e[1], e[1] + e[2], e[0].startswith(PRE + "enqueue.")) for e in ann
                     if e[0].startswith((PRE + "enqueue.", PRE + "readback.")))
    runtime_starts = [r[0] for r in runtime]
    ingress = _union([(a, b) for _, a, b in _clip([e[:3] for e in ann if e[0] == PRE + "ingress"], t0, t1)])
    covered = _union([(a, b) for _, a, b in _clip([e[:3] for e in spans], t0, t1)])

    legs = dict.fromkeys(LEGS, 0.0)
    hop_in_ingress = idle_total = 0.0
    table: dict[int, dict] = {}
    planes = sorted(events["devices"])
    for pi, plane in enumerate(planes):
        dev = events["devices"][plane]
        busy = _union([(a, b) for _, a, b in _clip([e[:3] for e in (dev["ops"] or dev["modules"])], t0, t1)])
        rest = sc._subtract([(t0, t1)], busy)
        idle_total += sc._length(rest)
        mods = sorted((s, s + d, name) for name, s, d in dev["modules"])
        mod_mids = [(m[0] + m[1]) / 2 for m in mods]  # sorted like the starts: a device line runs one module at a time
        launch, back, hop = [], [], []
        for name, a, d, _thread, stats in spans:
            b = a + d
            mine = mods[bisect.bisect_left(mod_mids, a):bisect.bisect_left(mod_mids, b)]
            first = min(max(mine[0][0], a), b) if mine else b
            last = max(min(max((m[1] for m in mine), default=b), b), first)
            rt = runtime[bisect.bisect_left(runtime_starts, a):bisect.bisect_left(runtime_starts, b)]
            entered = min((r[0] for r in rt if r[2]), default=first)
            left = max([r[1] for r in rt] + [last])
            called = max([r[1] for r in rt if r[2]] + [entered])
            launch.append((a, last))
            back.append((last, b))
            hop += [(a, entered), (left, b)]
            if pi == 0 and t0 <= a and b <= t1:
                family = name[len(PRE + "dispatch."):]
                own = [m for m in mine if MARKS.get(family, "") in m[2]]
                table[_int(stats, "seq")] = {
                    "family": family, "seq": _int(stats, "seq"), "round": _int(stats, "round"),
                    "rows": _int(stats, "rows"), "c": _int(stats, "c"), "live": _int(stats, "live"),
                    "wall_s": d, "device_s": sum(m[1] - m[0] for m in own), "modules": len(mine),
                    "launch_s": sc._length(sc._intersect(rest, [(a, last)])),
                    "return_s": sc._length(sc._intersect(rest, [(last, b)])),
                    # the two clocks against each other: the device cannot start before the
                    # call is entered nor end after the read returned
                    "call_to_device_s": (mine[0][0] if mine else b) - entered,
                    "device_to_read_s": left - max((m[1] for m in mine), default=b),
                    "start_to_call_return_s": called - first,
                }
        launch = _union([iv for iv in launch if iv[1] > iv[0]])
        back = sc._subtract(_union([iv for iv in back if iv[1] > iv[0]]), launch)
        hop = sc._intersect(_union([iv for iv in hop if iv[1] > iv[0]]), _union(launch + back))
        legs["launch"] += sc._length(sc._intersect(rest, launch))
        legs["return"] += sc._length(sc._intersect(rest, back))
        legs["between"] += sc._length(sc._subtract(rest, covered))
        idle_hop = sc._intersect(rest, hop)
        legs["hop"] += sc._length(idle_hop)
        hop_in_ingress += sc._length(sc._intersect(idle_hop, ingress))
    n = len(planes)
    entries: dict[tuple, dict] = {}
    for d in table.values():
        if d["family"] == "chunk" and d["rows"] is not None and d["c"] is not None:
            e = entries.setdefault((d["rows"], d["c"]), {"n": 0, "device_s": 0.0})
            e["n"] += 1
            e["device_s"] += d["device_s"]
    counts: dict[str, int] = {}
    for e in ann:
        if t0 <= e[1] < t1:
            counts[e[0]] = counts.get(e[0], 0) + 1
    return {
        "span_s": t1 - t0, "rounds": rounds, "idle_s": idle_total / n,
        "legs": {k: v / n for k, v in legs.items()}, "hop_in_ingress_s": hop_in_ingress / n,
        "dispatches": [table[k] for k in sorted(table)], "entries": entries,
        "annotations_per_round": {k: v / rounds for k, v in sorted(counts.items())} if rounds else {},
    }


def traced_rounds(events: dict) -> set:
    """The ``round`` stats of the ``decode.round`` events that lie whole
    inside the slice: the frames that committed while the profiler ran."""
    win = [e for e in events["host"] if e[0] == WINDOW]
    if not win:
        return set()
    w0, w1 = win[0][1], win[0][1] + win[0][2]
    return {r for r in (_int(e[4], "round") for e in events["host"]
                        if e[0] == PRE + "round" and e[2] > 0 and w0 <= e[1] and e[1] + e[2] <= w1) if r is not None}


def top_entry(entries: dict) -> tuple | None:
    """The most frequent ``(rows, c)`` of {entry: {"n", ...}}; between equals
    the cheaper (the ladder's order)."""
    return min(entries, key=lambda k: (-entries[k]["n"], k)) if entries else None


# ----------------------------------------------------- what the readers call


@functools.lru_cache(maxsize=1)
def _of_file(path: str) -> tuple:
    events = sc.read_scoped(path)
    return by_dispatch(events), traced_rounds(events)


def of_run(o: dict) -> dict | None:
    """This run's join, read once for all its readers; None on an untraced
    run or a program without the stats."""
    if not o.get("trace"):
        return None
    return _of_file(newest_xplane(TRACE_DIR))[0]


def _step_round_ms(f) -> tuple | None:
    """(wall, dispatch wall, host gap) of a frame, ms on its own clock, if
    its round was a plain one that ran a step and no chunk."""
    if f.mode == "plain" and f.busy_ns[0] == 0 and f.busy_ns[1] > 0:
        busy = sum(f.busy_ns)
        return (busy + f.gap_ns) / 1e6, busy / 1e6, f.gap_ns / 1e6
    return None


def _mean_median(rounds: list) -> dict:
    """Of ``_step_round_ms`` tuples: the wall's mean and median, and the
    means of its two parts."""
    wall, busy, gap = zip(*rounds)
    n = len(wall)
    return {"n": n, "mean": sum(wall) / n, "median": sorted(wall)[n // 2], "busy_mean": sum(busy) / n, "gap_mean": sum(gap) / n}


def tracing_on_cost(o: dict) -> dict | None:
    """What the profiler session costs a round, inside one run: the wall of
    the window's step-only rounds (``_step_round_ms``) that committed BEFORE
    the traced slice, INSIDE it and AFTER it, the last in thirds by order (a
    cost that fades is the export's, one that stays is a mode the session
    left on). Needs only ``decode.round``'s ``round`` stat (PR 26), so the
    parent reads too. An untraced run's figure for the whole window is on
    its ``frames`` line (``step_round_ms``)."""
    if not o.get("trace"):
        return None
    inside = _of_file(newest_xplane(TRACE_DIR))[1]
    if not inside:
        return None
    lo, hi = min(inside), max(inside)
    groups: dict[str, list] = {"before": [], "inside": [], "after": []}
    for f in o.get("frames") or []:
        ms = _step_round_ms(f)
        if ms is not None and (f.seq in inside or not lo <= f.seq <= hi):
            groups["inside" if f.seq in inside else "before" if f.seq < lo else "after"].append(ms)
    if not all(groups.values()):
        return None
    after = groups["after"]
    third = max(len(after) // 3, 1)
    groups.update({f"after_{i + 1}": after[i * third:(i + 1) * third if i < 2 else None] for i in range(3)})
    return {"step_round_ms": {k: _mean_median(v) for k, v in groups.items() if v}}


def leg_ms_per_round(o: dict, leg: str) -> float | None:
    r = of_run(o)
    if not r or not r["rounds"]:
        return None
    return 1e3 * r["legs"][leg] / r["rounds"]


def chunk_entry_ms(o: dict) -> float | None:
    """Mean device time of the chunk dispatches of the slice's most frequent
    ``(rows, c)`` entry, ms."""
    r = of_run(o)
    top = r and top_entry(r["entries"])
    if not top:
        return None
    e = r["entries"][top]
    return 1e3 * e["device_s"] / e["n"]


# ------------------------------------------ what the frames say, untraced too


def frame_entries(o: dict) -> dict | None:
    """{(chunk_rows, chunk_c): {"n", "wall_s"}} over the window's rounds that
    ran a chunk dispatch: the wall is the frame's ``busy_ns[chunk]``, host
    call to readback return. None on a program whose frames carry no
    ``chunk_c`` (the parent of PR 39) or in a window without a chunk round."""
    out: dict[tuple, dict] = {}
    for f in o.get("frames") or []:
        c = getattr(f, "chunk_c", None)
        if c is None:
            return None
        if f.chunk_rows:
            e = out.setdefault((f.chunk_rows, c), {"n": 0, "wall_s": 0.0})
            e["n"] += 1
            e["wall_s"] += f.busy_ns[0] / 1e9
    return out or None


def offentry_wall_pct(o: dict) -> float | None:
    entries = frame_entries(o)
    if not entries:
        return None
    top = top_entry(entries)
    wall = sum(e["wall_s"] for e in entries.values())
    return 100.0 * (wall - entries[top]["wall_s"]) / wall if wall > 0 else None


def ingress_ms(o: dict) -> float | None:
    """FlightFrame ``ingress_ns`` over ``ingress_requests``, the window's
    frames; None on a program without the fields or with no marked submit."""
    fs = o.get("frames") or []
    if not fs or not hasattr(fs[0], "ingress_requests"):
        return None
    n = sum(f.ingress_requests for f in fs)
    return sum(f.ingress_ns for f in fs) / n / 1e6 if n else None


# ------------------------------------------------------- the run's own line

_said: set = set()


def say(o: dict) -> None:
    """One earlier line of the run (JSON, before the result): which entry
    ``chunk_entry_device_ms`` and ``chunk_offentry_wall_pct`` read, every
    entry beside it, the legs beside ``idle_by_state``'s split of the same
    idle, dispatches and annotations a round. Printed once a window, by
    whichever of the two readers runs first; nothing on the parent."""
    key = (o.get("cell"), o.get("t0"))
    if key in _said:
        return
    _said.add(key)
    line: dict = {"phase": "dispatches"}
    cost = tracing_on_cost(o)
    if cost:
        line["tracing"] = cost
    r = of_run(o)
    if r:
        n = r["rounds"]
        fams: dict[str, list] = {}
        for d in r["dispatches"]:
            fams.setdefault(d["family"], []).append(d)
        old = (sc.of_run(o) or {}).get("idle")
        line["trace"] = {
            "rounds": n, "span_s": r["span_s"], "idle_ms_a_round": 1e3 * r["idle_s"] / n,
            "legs_ms_a_round": {k: 1e3 * v / n for k, v in r["legs"].items()},
            "hop_in_ingress_ms_a_round": 1e3 * r["hop_in_ingress_s"] / n,
            "idle_by_state_ms_a_round": old and {
                "idle": 1e3 * old["idle_s"] / old["rounds"],
                **{s: 1e3 * old["by_state"][s] / old["rounds"] for s in (sc.ENQUEUE, sc.READBACK)}},
            "entry": list(top_entry(r["entries"]) or ()),
            "entries": [[*k, e["n"], 1e3 * e["device_s"] / e["n"]] for k, e in sorted(r["entries"].items())],
            "per_dispatch_ms": {
                f: {"n": len(ds), "a_round": len(ds) / n,
                    **{k: 1e3 * sum(d[k + "_s"] for d in ds) / len(ds) for k in ("wall", "device", "launch", "return")},
                    **{k + "_min_med": [1e3 * min(d[k + "_s"] for d in ds), 1e3 * sorted(d[k + "_s"] for d in ds)[len(ds) // 2]]
                       for k in ("launch", "return", "call_to_device", "device_to_read", "start_to_call_return")}}
                for f, ds in sorted(fams.items())},
            "annotations_a_round": {k[len(PRE):]: round(v, 2) for k, v in r["annotations_per_round"].items()},
        }
    entries = frame_entries(o)
    if entries:
        line["frames"] = {
            "entry": list(top_entry(entries)), "chunk_rounds": sum(e["n"] for e in entries.values()),
            "chunk_wall_s": sum(e["wall_s"] for e in entries.values()),
            "entries": [[*k, e["n"], 1e3 * e["wall_s"] / e["n"]] for k, e in sorted(entries.items())],
            "frames_in_window": len(o.get("frames") or []),
            "step_round_ms": _mean_median(ms) if (ms := [m for m in map(_step_round_ms, o.get("frames") or []) if m is not None]) else None,
            "rounds_by_counters": o["after"]["rounds"] - o["before"]["rounds"] if "rounds" in o.get("after", {}) else None,
        }
    if len(line) > 1:
        print(json.dumps(line), flush=True)
