"""Operations and bytes of the hybrid decoder's step, from shapes and from
what the program counted. Companion of ``opsbytes.py`` for the
``granite-4.0-h-micro`` configuration (``models/hybrid_decoder.py``).

Each returns ``(flops, bytes)`` for ONE fused decode step. Matmul FLOPs are
2*MACs. Bytes are the least the mathematics needs: every weight once, the
float32 state of the rows that generate read once and written once (a slot
that does not generate is not touched), the K/V rows the four attention
layers have to read, the new rows written. Counted that low, a share of the
roofline cannot pass 100%.
"""

from harness.opsbytes_moe import least_seconds  # noqa: F401  (the roofline's least time: one definition)


def ssm_scan(*, rows, ssm_layers, ssm_heads, ssm_head_dim, ssm_state, ssm_conv, state_bytes=4):
    """The recurrence of one step under the ``ssm_scan`` scope: per row and
    Mamba layer the state [heads, head_dim, state] read and written, the
    conv cache written (its read is the ``ssm_conv`` scope's); a state
    element costs 5 operations (decay, the outer product's term, their sum,
    the product with C and its sum)."""
    state = ssm_heads * ssm_head_dim * ssm_state
    conv = (ssm_conv - 1) * (ssm_heads * ssm_head_dim + 2 * ssm_state)
    flops = 5.0 * state * rows * ssm_layers
    return float(flops), float(rows * ssm_layers * (2 * state + conv) * state_bytes)


def hybrid_decoder_step(*, hidden, layers, attn_layers, ffn, vocab, heads, kv_heads, head_dim, ssm_heads,
                        ssm_head_dim, ssm_state, ssm_conv, rows, ctx_tokens, param_bytes=2, kv_bytes=2):
    """The whole fused step: ``rows`` slots generate one token each over
    ``ctx_tokens`` cached positions summed over them (the attention layers
    alone attend over them)."""
    ssm_layers = layers - attn_layers
    d_inner, q_w, kv_w = ssm_heads * ssm_head_dim, heads * head_dim, kv_heads * head_dim
    conv_w = d_inner + 2 * ssm_state
    mamba_w = hidden * (2 * d_inner + 2 * ssm_state + ssm_heads) + d_inner * hidden + conv_w * (ssm_conv + 1) \
        + d_inner + 3 * ssm_heads
    attn_w = hidden * (q_w + 2 * kv_w) + q_w * hidden
    mlp_w = 3 * hidden * ffn
    weights = ssm_layers * mamba_w + attn_layers * attn_w + layers * mlp_w + vocab * hidden  # the tied head
    s_flops, s_bytes = ssm_scan(rows=rows, ssm_layers=ssm_layers, ssm_heads=ssm_heads, ssm_head_dim=ssm_head_dim,
                                ssm_state=ssm_state, ssm_conv=ssm_conv)
    conv_read = rows * ssm_layers * (ssm_conv - 1) * conv_w * 4
    keys_read = attn_layers * ctx_tokens  # K rows (and as many V rows)
    flops = 2.0 * rows * weights + 4.0 * q_w * keys_read + s_flops
    kv = 2 * kv_w * (keys_read + attn_layers * rows)  # read, and the new rows written
    nbytes = (weights + rows * hidden) * param_bytes + s_bytes + conv_read + kv * kv_bytes
    return float(flops), float(nbytes)
