"""From a profiler trace to NAMED time: what the host was in while the device
idled, and which part of the model a fused step's device time went to.

``harness/trace.py`` reduces a trace to busy/idle and per-program time; it
keeps no op stats and no threads. This file reads the same ``.xplane.pb``
again, once per run, for what the program itself writes into it (PR 26):

- its host annotations, all named ``decode.*`` (``telemetry/flight.py``:
  round, phase.<name>, dispatch/enqueue/readback.<family>, idle_wait,
  sse_write), each with the thread that wrote it;
- its device scopes (``models/decoder.py`` ``PAGED_SCOPES``): an op event's
  METADATA carries the HLO ``op_name`` of its instruction in the stat
  ``tf_op`` (``harness/xplane.py`` reads it; ``jax.profiler.ProfileData``
  does not show it), and the scope is a component of that path
  (``jit(_fused_step)/jit(main)/kv_write/scatter:``).

Two reductions, both checked on a recorded chip trace without a chip
(``fixtures/trace_scoped.json``, ``selfcheck/test_scopes.py``):

- ``idle_by_state``: the slice's idle intervals (as ``reduce_trace`` takes
  them: the complement of the union of device op intervals), over the part
  of the slice that ``decode.round`` annotations span, swept against the
  host states. Each idle instant goes to ONE state, by overlap and by
  precedence: ``enqueue`` > ``readback`` > innermost ``phase`` >
  ``sse_write`` > ``idle_wait`` > inside a ``dispatch`` but in none of those
  (the hand-off: to ``enqueue`` before the dispatch's first enqueue or
  readback event starts, to ``readback`` after) > ``round`` only > none;
- ``step``: op self time inside whole ``fused_step`` dispatches, by scope.

A program without the annotations or the scopes (the parent of PR 26, or a
compile cache that handed back executables without metadata) gives None for
what it lacks, never 0. Times are seconds on the trace's clock.
"""

from __future__ import annotations

import bisect
import functools

from harness.trace import TRACE_DIR, WINDOW, _clip, _short, _union, newest_xplane

ANN_PREFIX = "decode."
OP_NAME_STAT = "tf_op"  # the event-metadata stat that carries an instruction's HLO op_name
SCOPES = ("embed", "qkv", "kv_write", "pool_restack", "kv_gather", "attn", "attn_out", "mlp",
          "lm_head", "sample")
STEP_MARK, CHUNK_MARK = "fused_step", "fused_chunk"
# states that name idle time; ROUND_ONLY and NONE are what idle_named_pct leaves out
ENQUEUE, READBACK, PHASE, SSE_WRITE, IDLE_WAIT, ROUND_ONLY, NONE = (
    "enqueue", "readback", "phase", "sse_write", "idle_wait", "round_only", "none")


def read_scoped(path: str) -> dict:
    """{"devices": {plane: {"ops": [[label, start_s, dur_s, op_name]], "modules":
    [[name, start_s, dur_s]]}}, "host": [[name, start_s, dur_s, thread, stats]],
    "op_name_stat": "tf_op" (None where no op carried it)};
    ``label`` is ``trace._short`` of the op's HLO line, ``op_name`` "" where
    the op has none (copies the compiler adds carry none), ``thread`` is
    "<plane>#<line index>", ``stats`` the annotation's own (``decode.round``'s
    ``round`` and ``t_ns``)."""
    from harness.xplane import read_planes

    out: dict = {"devices": {}, "host": [], "op_name_stat": None}
    planes = read_planes(path, lambda n: n.startswith("/host:") or (n.startswith("/device:") and "CPU" not in n))
    for plane in planes:
        meta = plane["metadata"]
        for li, line in enumerate(plane["lines"]):
            if plane["name"].startswith("/device:"):
                if line["name"] not in ("XLA Ops", "XLA Modules"):
                    continue
                dev = out["devices"].setdefault(plane["name"], {"ops": [], "modules": []})
                for mid, start, dur, _stats in line["events"]:
                    m = meta.get(mid, {"name": "", "stats": {}})
                    if line["name"] == "XLA Modules":
                        dev["modules"].append([_short(m["name"]), start, dur])
                        continue
                    op_name = str(m["stats"].get(OP_NAME_STAT, ""))
                    if op_name:
                        out["op_name_stat"] = OP_NAME_STAT
                    dev["ops"].append([_short(m["name"]), start, dur, op_name])
            else:
                thread = f"{plane['name']}#{li}"
                for mid, start, dur, stats in line["events"]:
                    name = meta.get(mid, {"name": ""})["name"]
                    if name.startswith(ANN_PREFIX) or name == WINDOW:
                        out["host"].append([name, start, dur, thread, {k: str(v) for k, v in stats.items()}])
    return out


def _is_wait(label: str) -> bool:
    """An async wait the compiler adds: the start or the end of its own
    prefetch (``slice-start`` / ``slice-done`` of a layer's weights into VMEM,
    ``copy-start`` / ``copy-done``) or the ``custom-call`` that joins the
    pieces. A plain ``copy`` is work (a layout change), not a wait."""
    kind = label.split(" ", 1)[0]
    return kind.endswith(("-start", "-done")) or kind == "custom-call"


def _scope_of(op_name: str) -> str | None:
    """The program scope an HLO op_name lies under: the first component of
    its path that is one of SCOPES."""
    for part in op_name.rstrip(":").split("/"):
        if part in SCOPES:
            return part
    return None


# ------------------------------------------------------------ interval sets


def _intersect(xs: list, ys: list) -> list:
    """Intersection of two sorted lists of disjoint (a, b) intervals."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            out.append((a, b))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def _subtract(xs: list, ys: list) -> list:
    """xs minus ys, both sorted lists of disjoint intervals."""
    out, j = [], 0
    for a, b in xs:
        while j < len(ys) and ys[j][1] <= a:
            j += 1
        k, cur = j, a
        while k < len(ys) and ys[k][0] < b:
            if ys[k][0] > cur:
                out.append((cur, ys[k][0]))
            cur = max(cur, ys[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


def _length(xs: list) -> float:
    return sum(b - a for a, b in xs)


# ---------------------------------------------------------------- reductions


def idle_by_state(events: dict) -> dict | None:
    """The slice's idle time given to host states. None where the trace
    holds no ``decode.round`` annotation. The sweep runs over the part of
    the slice that the round annotations SPAN, first start to last end: an
    annotation that began before the profiler session did is not in the
    trace, so the round the session began in (and the one it ended in) has
    no ``decode.round``, and counting its time as unnamed would measure the
    session's edges, not the program. Keys: ``window_s``, ``span_s``,
    ``idle_s`` (in the span, mean over device planes), ``rounds``
    (``decode.round`` events, one cut by the slice's edge counted by its
    share), ``by_state`` {state: s}, ``by_phase`` {phase: s} (innermost),
    ``named_share``."""
    win = [e for e in events["host"] if e[0] == WINDOW]
    ann = [e for e in events["host"] if e[0].startswith(ANN_PREFIX)]
    whole = [e for e in ann if e[0] == ANN_PREFIX + "round" and e[2] > 0]
    if not win or not events["devices"]:
        return None
    w0, w1 = win[0][1], win[0][1] + win[0][2]
    inside = [(a, b) for _, a, b in _clip([e[:3] for e in whole], w0, w1)]
    if not inside:
        return None
    t0, t1 = min(a for a, _ in inside), max(b for _, b in inside)

    def spans(prefix: str) -> list:
        return [(a, b) for _, a, b in _clip([e[:3] for e in ann if e[0].startswith(prefix)], t0, t1)]

    enq, rdb = _union(spans(ANN_PREFIX + "enqueue.")), _union(spans(ANN_PREFIX + "readback."))
    phases = sorted(((b - a, a, b, n[len(ANN_PREFIX + "phase."):])
                     for n, a, b in _clip([e[:3] for e in ann if e[0].startswith(ANN_PREFIX + "phase.")], t0, t1)))
    hand_out, hand_back = [], []
    execs = sorted(spans(ANN_PREFIX + "enqueue.") + spans(ANN_PREFIX + "readback."))
    starts = [a for a, _ in execs]
    for a, b in spans(ANN_PREFIX + "dispatch."):
        i = bisect.bisect_left(starts, a)
        first = starts[i] if i < len(starts) and starts[i] < b else b
        hand_out.append((a, first))
        hand_back.append((first, b))
    layers = [
        (ENQUEUE, enq), (READBACK, rdb),
        (PHASE, _union([(a, b) for _, a, b, _ in phases])),
        (SSE_WRITE, _union(spans(ANN_PREFIX + "sse_write"))),
        (IDLE_WAIT, _union(spans(ANN_PREFIX + "idle_wait"))),
        (ENQUEUE, _union(hand_out)), (READBACK, _union(hand_back)),
        (ROUND_ONLY, _union(spans(ANN_PREFIX + "round"))),
    ]
    by_state = {s: 0.0 for s in (ENQUEUE, READBACK, PHASE, SSE_WRITE, IDLE_WAIT, ROUND_ONLY, NONE)}
    by_phase: dict[str, float] = {}
    idle_total = 0.0
    planes = sorted(events["devices"])
    for plane in planes:
        dev = events["devices"][plane]
        source = dev["ops"] or dev["modules"]
        busy = _union([(a, b) for _, a, b in _clip([e[:3] for e in source], t0, t1)])
        rest = _subtract([(t0, t1)], busy)
        idle_total += _length(rest)
        for state, spans_ in layers:
            got = _intersect(rest, spans_)
            by_state[state] += _length(got)
            if state == PHASE:
                left = got
                for _, a, b, name in phases:  # shortest first: the innermost takes its time
                    mine = _intersect(left, [(a, b)])
                    if mine:
                        by_phase[name] = by_phase.get(name, 0.0) + _length(mine)
                        left = _subtract(left, [(a, b)])
            rest = _subtract(rest, spans_)
        by_state[NONE] += _length(rest)
    n = len(planes)
    by_state = {k: v / n for k, v in by_state.items()}
    idle_s = idle_total / n
    rounds = sum((min(e[1] + e[2], t1) - max(e[1], t0)) / e[2] for e in whole
                 if min(e[1] + e[2], t1) > max(e[1], t0))
    named = idle_s - by_state[ROUND_ONLY] - by_state[NONE]
    return {
        "window_s": w1 - w0, "span_s": t1 - t0, "idle_s": idle_s, "rounds": rounds, "by_state": by_state,
        "by_phase": {k: v / n for k, v in by_phase.items()},
        "named_share": named / idle_s if idle_s > 0 else None,
    }


def step_by_scope(events: dict, mark: str = STEP_MARK) -> dict | None:
    """Op SELF time (an op's time less the ops nested in it) inside whole
    ``mark`` dispatches of the slice, by program scope. None where the
    slice holds no such dispatch with ops. Keys: ``dispatches``, ``op_s``
    (all op self time in them), ``by_scope`` {scope: s} (only scopes that
    were found, by the op's own ``op_name``), ``waits_by_scope`` {scope: s}
    (async waits without an op_name, ``_is_wait``, given to the scope of the
    next scoped op of the same dispatch: the compiler prefetches a layer's
    weights for the op that follows; the readers count them under that
    scope), ``unscoped_s`` (what is left), ``module_s`` (the dispatches' own
    time) and, to say which time could not be named: ``unscoped_ops``
    {label: s} and ``unscoped_by_next`` {scope of the next scoped op: s}
    (layout copies carry no op_name either; what follows them is most often
    what they were for — a hint for a reader, not a metric)."""
    win = [e for e in events["host"] if e[0] == WINDOW]
    if not win:
        return None
    t0, t1 = win[0][1], win[0][1] + win[0][2]
    dispatches, module_s, op_s = 0, 0.0, 0.0
    by_scope: dict[str, float] = {}
    waits: dict[str, float] = {}
    unscoped_ops: dict[str, float] = {}
    by_next: dict[str, float] = {}
    for plane in sorted(events["devices"]):
        dev = events["devices"][plane]
        mods = sorted((s, s + d) for name, s, d in dev["modules"] if mark in name and t0 <= s and s + d <= t1)
        if not mods:
            continue
        begins = [a for a, _ in mods]
        inside: list[list] = [[] for _ in mods]
        for label, s, d, op_name in dev["ops"]:
            i = bisect.bisect_right(begins, s) - 1
            if i >= 0 and s + d <= mods[i][1] + 1e-9:
                inside[i].append((s, -d, d, op_name, label))
        if not any(inside):
            continue
        dispatches += len(mods)
        module_s += sum(b - a for a, b in mods)
        for ops in inside:
            nxt = NONE
            for _start, own, op_name, label in sorted(_self_times(ops), reverse=True):
                op_s += own
                scope = _scope_of(op_name)
                if scope is not None:
                    by_scope[scope] = by_scope.get(scope, 0.0) + own
                    nxt = scope
                elif nxt != NONE and _is_wait(label):
                    waits[nxt] = waits.get(nxt, 0.0) + own
                else:
                    unscoped_ops[label] = unscoped_ops.get(label, 0.0) + own
                    by_next[nxt] = by_next.get(nxt, 0.0) + own
    if not dispatches:
        return None
    top = dict(sorted(unscoped_ops.items(), key=lambda kv: -kv[1])[:12])
    return {"dispatches": dispatches, "op_s": op_s, "by_scope": by_scope, "waits_by_scope": waits,
            "unscoped_s": sum(unscoped_ops.values()), "module_s": module_s,
            "unscoped_ops": top, "unscoped_by_next": by_next}


def _self_times(ops: list) -> list:
    """[(start, self time, op_name, label)] of ops given as (start, -dur, dur,
    op_name, label): an op's time less that of the ops nested in it (a
    ``while`` or a ``call`` holds its body's ops on the same line)."""
    out, stack = [], []  # stack: [end, start, self time, op_name, label] of the ops still open
    for s, _, d, op_name, label in sorted(ops):  # by start, the longer (the parent) first
        while stack and stack[-1][0] <= s + 1e-12:
            out.append(tuple(stack.pop()[1:]))
        if stack:
            stack[-1][2] -= d
        stack.append([s + d, s, d, op_name, label])
    out += [tuple(x[1:]) for x in stack]
    return out


def reduce_scoped(events: dict) -> dict:
    return {"idle": idle_by_state(events), "step": step_by_scope(events),
            "chunk": step_by_scope(events, CHUNK_MARK), "op_name_stat": events.get("op_name_stat")}


# ----------------------------------------------------- what the readers call


@functools.lru_cache(maxsize=1)
def _of_file(path: str) -> dict:
    return reduce_scoped(read_scoped(path))


def of_run(o: dict) -> dict | None:
    """The scoped reduction of this run's trace, read once for all the
    readers of the run; None on an untraced run."""
    if not o.get("trace"):
        return None
    return _of_file(newest_xplane(TRACE_DIR))


def per_dispatch_ms(r: dict | None) -> dict | None:
    """A ``step_by_scope`` reduction as milliseconds a dispatch, for a run's
    earlier lines (PERF.md section 5 is written from them): each scope with
    its waits, the waits alone, the module's own time and what has no name."""
    if not r:
        return None
    n = r["dispatches"]
    return {
        "dispatches": n, "module_ms": 1e3 * r["module_s"] / n, "op_ms": 1e3 * r["op_s"] / n,
        "by_scope_ms": {k: 1e3 * scoped_s(r, k) / n for k in sorted(set(r["by_scope"]) | set(r["waits_by_scope"]))},
        "waits_ms": {k: 1e3 * v / n for k, v in r["waits_by_scope"].items()},
        "unscoped_ms": 1e3 * r["unscoped_s"] / n,
        "unscoped_ops_ms": {k: 1e3 * v / n for k, v in r["unscoped_ops"].items()},
    }


def idle_ms_per_round(o: dict, *states: str) -> float | None:
    """Idle per round in the given states, ms; None without annotations."""
    r = of_run(o)
    idle = r and r["idle"]
    if not idle or not idle["rounds"]:
        return None
    return 1e3 * sum(idle["by_state"][s] for s in states) / idle["rounds"]


def step_scope_ms(o: dict, *scopes: str) -> float | None:
    """Device time per fused-step dispatch in ops under the given scopes and
    in the compiler's waits for them, ms; None where no op under any of them
    was found."""
    r = of_run(o)
    step = r and r["step"]
    if not step or not any(s in step["by_scope"] for s in scopes):
        return None
    return 1e3 * sum(scoped_s(step, s) for s in scopes) / step["dispatches"]


def scoped_s(step: dict, scope: str | None = None) -> float:
    """Seconds of a ``step_by_scope`` reduction under ``scope`` (under any,
    without one): the ops that carry it and the waits given to it."""
    if scope is None:
        return sum(step["by_scope"].values()) + sum(step["waits_by_scope"].values())
    return step["by_scope"].get(scope, 0.0) + step["waits_by_scope"].get(scope, 0.0)


# -------------------------------------------------- a trace small enough to keep


def trimmed(events: dict, seconds: float) -> dict:
    """``seconds`` of the measured slice, from its first ``decode.round``,
    as ``harness/trace.trimmed`` keeps one, plus each op's op_name and each
    annotation's thread and stats: whole nanoseconds from the piece's
    start, names interned.
    Annotations that begin before the slice keep their true (negative)
    start, so a round or a dispatch cut by the edge still has its length."""
    win = [e for e in events["host"] if e[0] == WINDOW][0]
    starts = [e[1] for e in events["host"] if e[0] == ANN_PREFIX + "round" and e[1] >= win[1]]
    t0 = min(starts, default=win[1])  # from a round's start: a session begins mid-round
    t1 = t0 + seconds
    names: dict[str, int] = {}

    def ns(t: float) -> int:
        return round((t - t0) * 1e9)

    def idx(s: str) -> int:
        return names.setdefault(s, len(names))

    devices = {}
    for plane, dev in events["devices"].items():
        ops = []
        for n, s, d, op_name in dev["ops"]:
            a, b = max(s, t0), min(s + d, t1)  # clipped to the piece, as trace.trimmed clips
            if b > a:
                ops.append([idx(n), ns(a), ns(b) - ns(a), idx(op_name)])
        devices[plane] = {
            "ops": ops,
            "modules": [[idx(n), ns(s), round(d * 1e9)] for n, s, d in dev["modules"] if s < t1 and s + d > t0],
        }
    host = [[idx(n), ns(s), round(d * 1e9), idx(th), st] for n, s, d, th, st in events["host"]
            if n != WINDOW and s < t1 and s + d > t0]
    return {"slice_ns": round(seconds * 1e9), "devices": devices, "host": host, "names": list(names),
            "op_name_stat": events.get("op_name_stat")}


def expanded(kept: dict) -> dict:
    names = kept["names"]
    devices = {
        p: {"ops": [[names[i], s / 1e9, d / 1e9, names[o]] for i, s, d, o in dev["ops"]],
            "modules": [[names[i], s / 1e9, d / 1e9] for i, s, d in dev["modules"]]}
        for p, dev in kept["devices"].items()
    }
    host = [[WINDOW, 0.0, kept["slice_ns"] / 1e9, "", {}]] + [
        [names[i], s / 1e9, d / 1e9, names[t], st] for i, s, d, t, st in kept["host"]]
    return {"devices": devices, "host": host, "op_name_stat": kept.get("op_name_stat")}
