"""Operations and bytes of the short-convolution decoder's step, from shapes
and from what the program counted. Companion of ``opsbytes.py`` for the
``lfm2-24b-a2b`` configuration (``models/conv_decoder.py``).

Each returns ``(flops, bytes)`` for ONE fused decode step. Matmul FLOPs are
2*MACs. Bytes are the least the mathematics needs: a weight the step touches
once (a held expert no row was routed to is NOT read, an absent expert is not
there), the K/V rows a generating slot attends over read once in each
ATTENTION layer (the conv layers hold none), the conv state of the rows that
advanced read and written once (float32), the new K/V rows written once.
Counted that low, a share of the roofline cannot pass 100%.
"""

from harness.opsbytes_moe import least_seconds  # noqa: F401  (the roofline's least time: one definition)


def conv_mix(*, hidden, conv_layers, taps, rows, param_bytes=2, state_bytes=4):
    """The gated short convolution of one step under ``qkv/conv_in``,
    ``attn/conv_mix`` and ``attn_out/conv_out``: a layer's ``in_proj``
    (hidden x 3 hidden) and ``out_proj`` (hidden x hidden) read once, its
    taps once, and the ``taps - 1`` cached inputs of each of the ``rows``
    that advance read and written; a row costs the two projections'
    multiply-adds, two gates and ``taps`` multiply-adds a channel."""
    weights = 4 * hidden * hidden + taps * hidden
    macs = rows * (4 * hidden * hidden + (taps + 2) * hidden)
    state = 2 * rows * (taps - 1) * hidden  # read, and written back
    return float(2.0 * macs * conv_layers), float((weights * param_bytes + state * state_bytes) * conv_layers)


def conv_decoder_step(*, hidden, layers, ffn, vocab, attn_layers, heads, kv_heads, head_dim, taps, dense_layers,
                      dense_ffn, experts, held, per_tok, rows, ctx_tokens, experts_hit, local_picks,
                      param_bytes=2, kv_bytes=2, state_bytes=4):
    """The whole fused step: ``rows`` slots generate one token each over
    ``ctx_tokens`` cached positions summed over them (read in the
    ``attn_layers`` attention layers only). ``experts_hit`` is the (layer,
    held expert) pairs with a row, ``local_picks`` the picks that landed on a
    held expert, both summed over the expert layers, as the program's frames
    count them. ``held`` and ``per_tok`` are not needed: an expert that is
    held and not hit is not read, an absent pick costs nothing."""
    del held, per_tok
    conv_layers, expert_layers = layers - attn_layers, layers - dense_layers
    q_w, kv_w = heads * head_dim, kv_heads * head_dim
    attn_w = hidden * (q_w + 2 * kv_w) + q_w * hidden
    one_expert = 3 * hidden * ffn
    c_flops, c_bytes = conv_mix(hidden=hidden, conv_layers=conv_layers, taps=taps, rows=rows,
                                param_bytes=param_bytes, state_bytes=state_bytes)
    # per generated token outside the conv operators: attention's projections, the dense MLPs, the routers, the tied head
    per_token = attn_layers * attn_w + dense_layers * 3 * hidden * dense_ffn + expert_layers * hidden * experts \
        + hidden * vocab
    flops = c_flops + 2.0 * rows * per_token + 2.0 * one_expert * local_picks
    flops += 4.0 * q_w * attn_layers * ctx_tokens  # scores + context, every query head over its keys
    weights = per_token + experts_hit * one_expert + rows * hidden  # + the embedding rows read
    kv = 2 * kv_w * attn_layers * (ctx_tokens + rows)  # K and V read, and the new rows written
    return float(flops), float(c_bytes + weights * param_bytes + kv * kv_bytes)
