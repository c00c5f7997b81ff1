"""What one run observed, gathered once for every metric reader.

A reader (``metrics/<name>.py``, ``layer_metrics/<name>.py``) gets this dict
and returns one number, or None where it finds nothing to read. Keys:

- ``t0``, ``t1``, ``seconds``: the measured window on CLOCK_MONOTONIC;
- ``requests``: the load child's records; ``ended``: those that completed
  or failed inside the window; ``measured``: (open loop) those due in it;
- ``token_times``: all token-event times; ``streams``: per request;
  ``gaps``: gaps between a request's consecutive tokens whose later event
  lies in the window;
- ``before``/``after``: the program's counters at the window's edges;
- ``frames``: the flight recorder's rounds inside the window;
- ``trace``: the reduced profiler trace (traced runs), else None;
- ``spans``: (graph tier, traced) puid -> root span duration in ms;
- ``device``, ``setup_s``, ``config``, ``traffic``, ``geometry``, ``tier``.
"""

from __future__ import annotations

from harness import estimators as est


def observations(dep, config, traffic, cell, obs, setup_s, args, device) -> dict:
    t0, t1 = obs["t0"], obs["t1"]
    reqs = obs["report"]["requests"]
    streams = [r["token_times"] for r in reqs if r["token_times"]]
    ended = [r for r in reqs if t0 <= r.get("done", -1.0) < t1 or (r.get("error") and t0 <= r.get("sent", -1.0) < t1)]
    measured = [r for r in reqs if r.get("measured")]
    if traffic["generator"] == "open":
        attempted = len(measured)
        failed = sum(1 for r in measured if r.get("error") or not r["token_times"])
        # (seconds late, second of the window it was due in), the latest first
        o_late = sorted(((r["sent"] - r["due"], r["due"] - t0) for r in measured if "sent" in r), reverse=True)
        late = [d for d, _ in o_late]
    else:
        attempted = len(ended)
        failed = sum(1 for r in ended if r.get("error"))
        late, o_late = [], []
    gaps = est.gaps_in_window(streams, t0, t1)
    o = {
        "cell": cell["name"], "tier": dep.tier, "config": config, "traffic": traffic,
        "t0": t0, "t1": t1, "seconds": t1 - t0, "setup_s": setup_s,
        "requests": reqs, "ended": ended, "measured": measured,
        "streams": streams, "token_times": [t for s in streams for t in s], "gaps": gaps,
        "before": obs["before"], "after": obs["after"], "frames": obs["frames"],
        "attempted": attempted, "failed": failed,
        "geometry": getattr(dep, "geometry", None),
        "trace": None, "spans": None,
    }
    errors = sorted({r["error"] for r in reqs if r.get("error")})[:3]
    o["client_summary"] = {
        "requests_seen": len(reqs), "attempted": attempted, "failed": failed, "errors": errors,
        "generator_late_ms_max": 1e3 * max(late) if late else None,
        "generator_late_ms_mean": 1e3 * sum(late) / len(late) if late else None,
        "generator_late_ms_p95": 1e3 * est.quantile(late, 0.95)["value"] if late else None,
        # the latest few with the second of the window they were due in, beside the moments at
        # which the load child's idle timer woke late: a late send inside one is the process or
        # the machine standing still, not a generator that cannot keep up
        "generator_latest_ms_at_s": [[1e3 * d, at] for d, at in o_late[:4]],
        "generator_late_over_5ms": sum(1 for d in late if d > 0.005),
        "child_stalls_ms_at_s": [[1e3 * d, at - t0] for at, d in obs["report"].get("stalls", []) if t0 <= at < t1][:12],
    }
    if dep.tier == "generative":
        rate = est.aligned_rate(o["token_times"], t0, t1)
        inside = [f for f in obs["frames"] if f.mode == "plain"]
        with_chunk = [f for f in inside if f.busy_ns[0] > 0]
        tok = sum(f.tokens for f in inside)
        o["chunk_gap_share"] = (sum(f.tokens for f in with_chunk) / tok) if tok else None
        bs = est.bursts([t for t in o["token_times"] if t0 <= t < t1])
        p95 = est.quantile(gaps, 0.95)["value"] if gaps else 0.0
        silences = sorted(((b[0] - a[0], a[0] - t0) for a, b in zip(bs, bs[1:])), reverse=True)[:2]
        o["client_summary"].update(
            first_burst_after_t0_s=bs[0][0] - t0 if bs else None,
            last_burst_before_t1_s=t1 - bs[-1][0] if bs else None,
            longest_silences_s_at_s=silences,
            tokens_per_s_aligned=rate and rate["value"], tokens_per_s_naive=rate and rate["naive"],
            aligned_detail=rate,
            gaps=len(gaps),
            gap_hist_5ms=est.histogram(gaps, 0.005),
            # the plateau the 95th percentile sits on, finer: the gaps within a fifth of it
            gap_hist_1ms_near_p95=est.histogram([g for g in gaps if 0.8 * p95 <= g < 1.2 * p95], 0.001),
            chunk_share_of_gaps_by_flight_rounds=o["chunk_gap_share"],
            chunk_share_of_gaps_by_client_plateau=est.upper_plateau_share(gaps),
            rounds_in_window=len(obs["frames"]),
            # the server's own long rounds (a frame's time less the frame before it), on the same clock
            server_rounds_over_100ms_at_s=[
                [(b.t_ns - a.t_ns) / 1e6, b.t_ns / 1e9 - t0]
                for a, b in zip(obs["frames"], obs["frames"][1:]) if b.t_ns - a.t_ns > 100e6][:12],
            admitted_in_window=obs["after"]["admitted"] - obs["before"]["admitted"],
        )
    else:
        o["client_summary"].update(answered=len(ended) - failed)
    device = dict(device)
    if args.trace:
        from harness.trace import TRACE_DIR, newest_xplane, read_xplane, reduce_trace

        events = read_xplane(newest_xplane(TRACE_DIR))
        o["trace"] = reduce_trace(events, dep.families)
        o["trace_lines"], o["trace_events"] = events["lines"], events
        device["busy_s"], device["window_s"] = o["trace"]["busy_s"], o["trace"]["window_s"]
        if dep.tier == "graph":
            from seldon_core_tpu import telemetry

            o["spans"] = {
                rec.puid: rec.duration_ms for rec in telemetry.get_tracer().store.list(n=10**6) if rec.puid
            }
    o["device"] = device
    return o
