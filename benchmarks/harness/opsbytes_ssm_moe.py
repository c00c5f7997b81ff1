"""Operations and bytes of one fused step of the hybrid decoder whose every
layer is ONE sublayer: Mamba-2 with B/C groups, grouped-query attention, or
an expert layer that holds a share of squared-ReLU experts. Companion of
``opsbytes_ssm.py`` (granite's mixer-plus-MLP pairs, one group) for the
``nemotron-3-nano-30b-a3b`` configuration (``models/hybrid_decoder.py``).

Each returns ``(flops, bytes)`` for ONE fused decode step. Matmul FLOPs are
2*MACs. Bytes are the least the mathematics needs, at the PUBLISHED widths
whatever the program stores (an expert is 2 x hidden x 1856 numbers: up and
down, no gate projection): every weight the step touches once (a held expert
no row was routed to is NOT read), the float32 state and conv cache of the
rows that generate read once and written once, the K/V rows the attention
layers have to read, the new rows written. Counted that low, a share of the
roofline cannot pass 100%.
"""

from harness.opsbytes_moe import least_seconds  # noqa: F401  (the roofline's least time: one definition)


def ssm_scan(*, rows, ssm_layers, ssm_heads, ssm_head_dim, ssm_state, ssm_conv, ssm_groups, state_bytes=4):
    """The recurrence of one step under the ``ssm_scan`` scope: per row and
    Mamba layer the state [heads, head_dim, state] read and written, the
    conv cache (``ssm_conv - 1`` inputs of ``d_inner + 2 x groups x state``)
    written (its read is the ``ssm_conv`` scope's); a state element costs 5
    operations (decay, the outer product's term, their sum, the product with
    C and its sum)."""
    state = ssm_heads * ssm_head_dim * ssm_state
    conv = (ssm_conv - 1) * (ssm_heads * ssm_head_dim + 2 * ssm_groups * ssm_state)
    return float(5.0 * state * rows * ssm_layers), float(rows * ssm_layers * (2 * state + conv) * state_bytes)


def ssm_moe_step(*, hidden, vocab, ssm_layers, attn_layers, expert_layers, heads, kv_heads, head_dim, ssm_heads,
                 ssm_head_dim, ssm_state, ssm_conv, ssm_groups, ffn, shared_ffn, experts, experts_hit, local_picks,
                 rows, ctx_tokens, param_bytes=2, kv_bytes=2):
    """The whole fused step: ``rows`` slots generate one token each over
    ``ctx_tokens`` cached positions summed over them (the attention layers
    alone attend over them); ``experts_hit`` (layer, held expert) pairs had a
    row and ``local_picks`` picks landed on a held expert, as the program's
    frames count them; ``experts`` is the router's width."""
    d_inner, q_w, kv_w = ssm_heads * ssm_head_dim, heads * head_dim, kv_heads * head_dim
    conv_w = d_inner + 2 * ssm_groups * ssm_state
    mamba_w = hidden * (d_inner + conv_w + ssm_heads) + d_inner * hidden + conv_w * (ssm_conv + 1) + d_inner \
        + 3 * ssm_heads + hidden
    attn_w = hidden * (q_w + 2 * kv_w) + q_w * hidden + hidden
    one_expert = 2 * hidden * ffn  # up and down: no gate projection
    every_token = hidden * experts + 2 * hidden * shared_ffn + hidden  # router, the shared expert, the norm
    dense = ssm_layers * mamba_w + attn_layers * attn_w + expert_layers * every_token + vocab * hidden + hidden
    s_flops, s_bytes = ssm_scan(rows=rows, ssm_layers=ssm_layers, ssm_heads=ssm_heads, ssm_head_dim=ssm_head_dim,
                                ssm_state=ssm_state, ssm_conv=ssm_conv, ssm_groups=ssm_groups)
    conv_read = rows * ssm_layers * (ssm_conv - 1) * conv_w * 4
    keys_read = attn_layers * ctx_tokens  # K rows (and as many V rows)
    flops = 2.0 * rows * dense + 2.0 * one_expert * local_picks + 4.0 * q_w * keys_read + s_flops
    kv = 2 * kv_w * (keys_read + attn_layers * rows)  # read, and the new rows written
    weights = dense + experts_hit * one_expert + rows * hidden  # + the embedding rows read
    return float(flops), float(weights * param_bytes + s_bytes + conv_read + kv * kv_bytes)
