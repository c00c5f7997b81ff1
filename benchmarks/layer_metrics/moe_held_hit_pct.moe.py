"""Share of (expert layer, HELD expert) pairs that a fused step of the
sparse-expert family routed at least one row to: the program's own count
(FlightFrame ``moe_experts_hit``, over the experts this chip holds, real rows
only; the step dispatch's own where the frames give it, ``step_counts``, else
a step-only round's), over the window's steps, over (layers less the leading
dense ones) x the experts held. The masked form reads every held expert's
weights; this is the share of them the step needed. None for a configuration
that holds all its experts."""


from harness.scopes_win import held_share, step_means


def read(o):
    p, m = held_share(o), step_means(o)
    if not p or not m:
        return None
    return 100.0 * m["experts_hit"] / ((p["layers"] - p["dense_layers"]) * p["held"])
