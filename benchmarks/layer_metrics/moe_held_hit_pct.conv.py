"""Share of (expert layer, HELD expert) pairs that a fused step routed at
least one row to, in the short-convolution family's cell: the program's own
count (FlightFrame ``moe_experts_hit``, over the experts this chip holds,
real rows only), over the window's step-only rounds. 64 rows x 4 picks over
64 experts give a held expert 4 rows on average: 98% by expectation.
``moe_held_hit_pct`` selects the latent family's frames and reads its keys."""


from harness.scopes_conv import published, step_means


def read(o):
    m = step_means(o)
    if not m:
        return None
    p = published(o)
    return 100.0 * m["experts_hit"] / ((p["layers"] - p["dense_layers"]) * p["held"])
