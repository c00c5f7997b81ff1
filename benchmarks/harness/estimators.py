"""Metric arithmetic on client-side event times. Pure Python, no JAX.

Times are seconds on one monotonic clock. ``quantile`` is the nearest-rank
percentile with its sample count and the count beyond it, so a report can
say whether the percentile is a percentile or a maximum.
"""

from __future__ import annotations

import math

# token events closer than this belong to one emission burst (one scheduler
# round delivers its tokens within a millisecond or two; rounds are >= 100 ms)
BURST_GAP_S = 0.02


def quantile(values, q: float) -> dict | None:
    """Nearest-rank q-quantile: value, sample count, samples beyond it."""
    vals = sorted(values)
    if not vals:
        return None
    rank = max(1, math.ceil(q * len(vals)))
    return {"value": vals[rank - 1], "n": len(vals), "beyond": len(vals) - rank}


def bursts(times, gap_s: float = BURST_GAP_S) -> list[tuple[float, int]]:
    """Group sorted event times into emission bursts: (time of the burst's
    first event, events in it)."""
    out: list[list] = []
    last = None
    for t in sorted(times):
        if last is None or t - last > gap_s:
            out.append([t, 0])
        out[-1][1] += 1
        last = t
    return [(t, n) for t, n in out]


def aligned_rate(times, t0: float, t1: float) -> dict | None:
    """Events per second with both edges on emissions.

    Of the events inside [t0, t1), the first emission burst only anchors the
    clock; the events of every later burst, up to and including the last,
    are counted and divided by the time from the first burst to the last.
    No partial round is counted at either edge, so the estimate does not
    depend on where the window's edges fall within a round.
    """
    inside = [t for t in times if t0 <= t < t1]
    bs = bursts(inside)
    if len(bs) < 2:
        return None
    span = bs[-1][0] - bs[0][0]
    counted = sum(n for _, n in bs[1:])
    return {
        "value": counted / span,
        "counted": counted,
        "span_s": span,
        "bursts": len(bs),
        "naive": len(inside) / (t1 - t0),
    }


def gaps_in_window(streams, t0: float, t1: float) -> list[float]:
    """Gaps between consecutive token events of one request, pooled over all
    requests; a gap belongs to the window when its LATER event does. First
    tokens make no gap."""
    out = []
    for ts in streams:
        for a, b in zip(ts, ts[1:]):
            if t0 <= b < t1:
                out.append(b - a)
    return out


def upper_plateau_share(gaps, factor: float = 1.5) -> float | None:
    """Share of gaps longer than ``factor`` x the 5th-percentile gap: with gaps
    on plateaus (a step alone; a step plus a prefill chunk of each bucket) this
    is the share of gaps that contain a chunk, as the client sees it. It
    needs at least a twentieth of the gaps to be steps alone."""
    if not gaps:
        return None
    base = quantile(gaps, 0.05)["value"]
    return sum(1 for g in gaps if g > factor * base) / len(gaps)


def histogram(values, width: float) -> dict:
    """{bin's lower edge in ms: count} over bins of ``width`` seconds, empty
    bins left out: where the gaps' plateaus lie, and how many gaps on each."""
    out: dict[int, int] = {}
    for v in values:
        k = int(v // width)
        out[k] = out.get(k, 0) + 1
    return {str(round(1e3 * k * width)): n for k, n in sorted(out.items())}


def spread(values) -> float:
    """Distance between the first and third quartile as a share of the
    median, by ``statistics.quantiles(values, n=4)`` — the driver's rule."""
    import statistics

    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med
