"""Share of (expert layer, HELD expert) pairs that a fused step routed at
least one row to, in the third shape's cell: the program's own count (the
step dispatch's ``moe_experts_hit`` in a frame's ``step_counts``, over the
experts this chip holds, real rows only). 64 rows x 10 picks over 512 experts
give a held expert 1.25 rows on average: 71% by expectation (1 - e^-1.25),
the lowest of any cell. The masked form reads every held expert's weights;
``step_roofline.gdn_moe`` counts the ones hit."""


from harness.scopes_gdn import published, step_means


def read(o):
    p, m = published(o), step_means(o)
    if not p or not m:
        return None
    return 100.0 * m["experts_hit"] / (p["expert_layers"] * p["held"])
