"""Share of a fused step's routed picks that landed on an expert this chip
holds, in the short-convolution family's cell: the program's own count
(FlightFrame ``moe_local_picks`` over ``conv_rows`` x experts per token x
expert layers, real rows only), over the window's step-only rounds. 8 of 64
experts held: 12.5 by expectation; the rest of a token's gate mass belongs to
the absent chips."""


from harness.scopes_conv import published, step_means


def read(o):
    m = step_means(o)
    if not m or not m["rows"]:
        return None
    p = published(o)
    return 100.0 * m["local_picks"] / (m["rows"] * p["per_tok"] * (p["layers"] - p["dense_layers"]))
