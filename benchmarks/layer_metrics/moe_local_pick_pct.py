"""Share of a fused step's routed picks that landed on an expert this chip
holds: the program's own count (FlightFrame ``moe_local_picks`` over
``moe_rows`` x experts per token x expert layers, real rows only), over the
window's step-only rounds. 12 of 192 experts held: 6.25 by expectation; the
rest of a token's gate mass belongs to the absent chips."""


from harness.scopes_mla import published, step_means


def read(o):
    m = step_means(o)
    if not m or not m["rows"]:
        return None
    p = published(o)
    return 100.0 * m["local_picks"] / (m["rows"] * p["per_tok"] * (p["layers"] - p["dense_layers"]))
