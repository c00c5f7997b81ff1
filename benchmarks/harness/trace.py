"""From a profiler trace to device metrics: the one reduction every PR uses.

Two steps, kept apart so the second can be checked on a small recorded trace
without a chip (``fixtures/trace_small.json``, ``selfcheck/test_trace.py``):

- ``read_xplane`` turns the profiler's ``.xplane.pb`` into plain events:
  per device plane its operations and its modules (one event per executed
  program), and the host annotations the benchmark wrote;
- ``reduce_trace`` turns events into numbers: the union of the intervals in
  which an operation ran (busy), the idle share, device time per program
  family, the operations that took most time, and the longest idle gaps
  named by the host annotation they fell in.

Times are seconds on the trace's own clock. The measured slice is the span
of the ``WINDOW`` annotation that ``run.py`` holds while the profiler runs.
"""

from __future__ import annotations

import glob
import os
import re

WINDOW = "bench_traced_window"
# where run.py lets the profiler write: inside the checkout, git-ignored
TRACE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))), ".bench_trace"
)
HOST_PREFIX = "bench:"  # every annotation the benchmark writes starts so

_OPS_LINES = ("XLA Ops",)
_MODULE_LINES = ("XLA Modules",)


def newest_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def read_xplane(path: str) -> dict:
    """{"devices": {plane: {"ops": [...], "modules": [...]}}, "host": [...],
    "lines": {plane: [line names]}}; an event is [name, start_s, dur_s]."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out: dict = {"devices": {}, "host": [], "lines": {}}
    for plane in data.planes:
        names = []
        is_dev = plane.name.startswith("/device:") and "CPU" not in plane.name
        for line in plane.lines:
            names.append(line.name)
            if is_dev:
                key = (
                    "ops" if line.name in _OPS_LINES
                    else "modules" if line.name in _MODULE_LINES
                    else None
                )
                if key is None:
                    continue
                dev = out["devices"].setdefault(plane.name, {"ops": [], "modules": []})
                dev[key] += [
                    [_short(e.name), e.start_ns / 1e9, e.duration_ns / 1e9] for e in line.events
                ]
            elif plane.name.startswith("/host:"):
                out["host"] += [
                    [e.name, e.start_ns / 1e9, e.duration_ns / 1e9]
                    for e in line.events
                    if e.name.startswith(HOST_PREFIX) or e.name == WINDOW
                ]
        out["lines"][plane.name] = names
    return out


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _clip(events, t0: float, t1: float):
    for name, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            yield name, a, b


_SHAPE = re.compile(r"[a-z]+[0-9]*\[[0-9,]*\]")


def _short(name: str) -> str:
    """A label that merges an operation's instances. The TPU names an
    operation by its whole HLO line (``%copy.369 = f32[1,720,20,16,64]{...}
    copy(...)``) and a module by name and fingerprint
    (``jit__fused_step(1234)``): keep the name without its instance number
    and, for an operation, its first result shape."""
    head, eq, rest = name.partition(" = ")
    head = head.lstrip("%").split("(")[0]
    base, _, tail = head.rpartition(".")
    label = base if base and tail.isdigit() else head
    shape = _SHAPE.search(rest) if eq else None
    return f"{label} {shape.group(0)}" if shape else label


def reduce_trace(events: dict, families: dict[str, str] | None = None, top: int = 10) -> dict:
    """``families`` maps a family's name to the substring that marks its
    modules (``{"step": "fused_step"}``). Returns busy_s / window_s averaged
    over the device planes, idle_share, per-family dispatch counts and mean
    device seconds, the top operations and the longest idle gaps."""
    win = [e for e in events["host"] if e[0] == WINDOW]
    if not win:
        raise ValueError(f"the trace holds no {WINDOW!r} annotation")
    t0, t1 = win[0][1], win[0][1] + win[0][2]
    window_s = t1 - t0
    host = [e for e in events["host"] if e[0] != WINDOW]
    busy_per_dev, op_time, fam, gaps = [], {}, {}, []
    for plane in sorted(events["devices"]):
        dev = events["devices"][plane]
        source = dev["ops"] or dev["modules"]
        spans = _union([(a, b) for _, a, b in _clip(source, t0, t1)])
        busy_per_dev.append(sum(b - a for a, b in spans))
        for name, a, b in _clip(dev["ops"], t0, t1):
            op_time[name] = op_time.get(name, 0.0) + (b - a)
        for name, s, d in dev["modules"]:
            if not (t0 <= s and s + d <= t1):
                continue  # whole dispatches only: a clipped one would read short
            op_time["module:" + name] = op_time.get("module:" + name, 0.0) + d
            for f, mark in (families or {}).items():
                if mark in name:
                    n, tot = fam.get(f, (0, 0.0))
                    fam[f] = (n + 1, tot + d)
        edges = [t0] + [x for ab in spans for x in ab] + [t1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, a, b))
    n_dev = max(len(busy_per_dev), 1)
    busy_s = sum(busy_per_dev) / n_dev
    named_gaps = []
    for d, a, b in sorted(gaps, reverse=True)[:top]:
        mid = (a + b) / 2
        inside = [h for h in host if h[1] <= mid <= h[1] + h[2]]
        # the innermost annotation round the gap's middle names it
        name = min(inside, key=lambda h: h[2])[0][len(HOST_PREFIX):] if inside else "no_annotation"
        named_gaps.append([name, d])
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
        "devices": len(busy_per_dev),
        "families": {f: {"dispatches": n, "mean_s": tot / n} for f, (n, tot) in fam.items()},
        "device_ops": [[k, v] for k, v in sorted(op_time.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": named_gaps,
    }


def trimmed(events: dict, seconds: float) -> dict:
    """The first ``seconds`` of the measured slice, small enough to keep beside
    the reduction: times rebased to the slice's start in whole nanoseconds,
    names interned in a table. ``expanded`` undoes it."""
    win = [e for e in events["host"] if e[0] == WINDOW][0]
    t0, t1 = win[1], win[1] + seconds
    names: dict[str, int] = {}

    def cut(evs):
        return [
            [names.setdefault(n, len(names)), round((a - t0) * 1e9), round((b - a) * 1e9)]
            for n, a, b in _clip(evs, t0, t1)
        ]

    devices = {p: {k: cut(v) for k, v in d.items()} for p, d in events["devices"].items()}
    host = cut([e for e in events["host"] if e[0] != WINDOW])
    return {"slice_ns": round(seconds * 1e9), "devices": devices, "host": host,
            "names": list(names), "lines": events["lines"]}


def expanded(kept: dict) -> dict:
    names = kept["names"]

    def back(evs):
        return [[names[i], s / 1e9, d / 1e9] for i, s, d in evs]

    return {
        "devices": {p: {k: back(v) for k, v in d.items()} for p, d in kept["devices"].items()},
        "host": [[WINDOW, 0.0, kept["slice_ns"] / 1e9]] + back(kept["host"]),
        "lines": kept.get("lines", {}),
    }
