"""Share of (expert layer, HELD expert) pairs that a fused step routed at
least one row to: the program's own count (FlightFrame ``moe_experts_hit``,
over the experts this chip holds, real rows only), over the window's
step-only rounds. The masked form reads every held expert's weights; this is
the share of them the step needed (64 rows x 8 picks over 192: 93% by
expectation)."""


from harness.scopes_mla import published, step_means


def read(o):
    m = step_means(o)
    if not m:
        return None
    p = published(o)
    return 100.0 * m["experts_hit"] / ((p["layers"] - p["dense_layers"]) * p["held"])
