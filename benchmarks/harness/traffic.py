"""One general generator: a traffic file's parameters + ``--seed`` -> a plan.

Pure numpy, no JAX: the parent builds the plan, the load child
(``loadgen.py``) executes it and materialises token ids with ``token_ids``.
A plan is JSON: per-client request lists (closed loop) or due times (open
loop). A request names its sizes and the seeds its ids are drawn from.

What ``--seed`` may change is what cannot move the schedule: every token id
(and, in the parent, the weights). Lengths, their order inside a lane, which
client runs which lane and open-loop arrival times come from the traffic
file alone, so every seed offers the same work at the same times (PERF.md
section 6 has the arithmetic: one admission more or less in a window moves
tokens/s by more than the bound). The lanes are not dealt to the clients by
the seed either: the clients connect in their order, so that order decides
which requests share the first prefill rounds and with that the closed
loop's whole course; with it drawn from the seed, one seed's runs repeated
to 0.2% in tokens/s and two seeds differed by up to 3% (PERF.md section 2).
"""

from __future__ import annotations

import numpy as np

_SEED_MASK = (1 << 63) - 1


def _rng(seed: int, *tags: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & _SEED_MASK, *[int(t) for t in tags]])


def token_ids(seed: int, vocab: int, req: dict) -> list[int]:
    """A request's prompt: ``prefix_len`` tokens of shared prefix family
    ``prefix`` (the same for every request of the family), then a tail that
    is unique to the request's ``uid``."""
    total, plen = int(req["prompt_len"]), int(req.get("prefix_len", 0))
    head = (
        _rng(seed, 1, req["prefix"]).integers(0, vocab, plen) if plen else np.zeros(0, np.int64)
    )
    tail = _rng(seed, 2, req["uid"]).integers(0, vocab, total - plen)
    return np.concatenate([head, tail]).astype(np.int64).tolist()


def open_arrivals(rate_rps: float, seconds: float, schedule_seed: int) -> list[float]:
    """N = round(rate x seconds) due times in [0, seconds): sorted uniform
    draws, i.e. a Poisson process conditioned on its count."""
    n = int(round(float(rate_rps) * float(seconds)))
    return np.sort(_rng(schedule_seed, 3).uniform(0.0, float(seconds), n)).tolist()


def _family_cycle(shares, schedule_seed: int) -> list[int]:
    """A fixed order of prefix families honouring ``shares`` over 20 requests."""
    counts = [int(round(float(s) * 20)) for s in shares]
    fams = [k for k, c in enumerate(counts) for _ in range(c)]
    return [int(f) for f in _rng(schedule_seed, 4).permutation(fams)]


def build_plan(traffic: dict, *, seed: int, seconds: float) -> dict:
    """The requests of one run, as offsets from the start of load. The
    measured window is [ramp_s, ramp_s + seconds)."""
    kind = traffic["generator"]
    ramp = float(traffic.get("ramp_s", 0.0))
    plan = {
        "generator": kind,
        "protocol": traffic["protocol"],
        "ramp_s": ramp,
        "seconds": float(seconds),
        "first_token_grace_s": float(traffic.get("first_token_grace_s", 0.0)),
    }
    if traffic["protocol"] == "json":
        plan["clients"] = [
            [{"uid": c, "rows": int(traffic.get("rows", 1))}] for c in range(int(traffic["clients"]))
        ]
        plan["cycle_from"] = 0
        return plan
    base = {
        "prompt_len": int(traffic["prompt_len"]),
        "prefix_len": int(traffic.get("shared_prefix_len", 0)),
        "cache_prefix": int(traffic.get("cache_prefix", 0)),
        "prefix": 0,
    }
    if kind == "closed":
        lanes = traffic["lanes"]
        if len(lanes) != int(traffic["clients"]):
            raise ValueError("one lane per client")
        plan["clients"] = [
            [{**base, "max_new": int(m), "uid": c * 1000 + i} for i, m in enumerate(lane)]
            for c, lane in enumerate(lanes)
        ]
        plan["cycle_from"] = int(traffic.get("cycle_from", 0))
        return plan
    if kind != "open":
        raise ValueError(f"unknown generator {kind!r}")
    table = [int(v) for v in traffic["output_table"]]
    sseed = int(traffic["schedule_seed"])
    deal = [int(i) for i in _rng(sseed, 6).permutation(len(table))]
    fams = _family_cycle(traffic["prefix_shares"], sseed)
    n_pre = int(traffic.get("inflight_at_start", 0))
    arrivals = []
    # requests in flight when the window opens: sent at the start of load,
    # outputs cut short so their ends are spread over one request's length
    for i in range(n_pre):
        full = table[deal[i % len(deal)]]
        arrivals.append(
            {**base, "due": 0.0, "prefix": fams[i % len(fams)], "uid": 500000 + i,
             "max_new": max(4, int(round(full * (i + 0.5) / n_pre))), "measured": False}
        )
    # the same rate through the ramp, then the measured schedule
    n_ramp = int(round(float(traffic["rate_rps"]) * ramp))
    for i in range(n_ramp):
        arrivals.append(
            {**base, "due": ramp * (i + 0.5) / max(n_ramp, 1), "prefix": fams[(n_pre + i) % len(fams)],
             "uid": 600000 + i, "max_new": table[deal[(n_pre + i) % len(deal)]], "measured": False}
        )
    for i, t in enumerate(open_arrivals(traffic["rate_rps"], seconds, sseed)):
        arrivals.append(
            {**base, "due": ramp + t, "prefix": fams[i % len(fams)], "uid": i,
             "max_new": table[deal[i % len(deal)]], "measured": True}
        )
    plan["arrivals"] = arrivals
    return plan


def offered_work(plan: dict) -> list[tuple]:
    """The multiset of sizes a plan offers, for comparing two seeds."""
    if "arrivals" in plan:
        return sorted((round(a["due"], 9), a["max_new"], a["prefix"]) for a in plan["arrivals"])
    return sorted(
        tuple(r.get("max_new", r.get("rows")) for r in lane) for lane in plan["clients"]
    )
