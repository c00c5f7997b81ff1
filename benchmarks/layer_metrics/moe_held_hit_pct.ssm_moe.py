"""Share of (expert layer, HELD expert) pairs that a fused step routed at
least one row to, in the hybrid family's cell: the program's own count (the
step dispatch's ``moe_experts_hit`` in a frame's ``step_counts``, over the
experts this chip holds, real rows only). 64 rows x 6 picks over 128 experts
give a held expert 3 rows on average: 95% by expectation. The masked form
reads every held expert's weights; ``step_roofline.ssm_moe`` counts the ones
hit."""


from harness.scopes_ssm_moe import published, step_means


def read(o):
    p, m = published(o), step_means(o)
    if not p or not m:
        return None
    return 100.0 * m["experts_hit"] / (p["expert_layers"] * p["held"])
