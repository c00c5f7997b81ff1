"""Operations and bytes of one fused step of the sparse-expert decoder as
PR 47 left it: two head counts by layer kind, a per-head output gate, a
leading dense layer, a shared expert, a SHARE of the routed experts, and a
cache of two page kinds. Companion of ``opsbytes_moe.py`` (which counts the
block without them) for the ``laguna-s-2.1`` configuration.

``(flops, bytes)`` for ONE step. Matmul FLOPs are 2*MACs. Bytes are the least
the mathematics needs: every weight the step touches once (a held expert no
row was routed to is NOT read), the K/V rows a layer has to read once (a
sliding layer: the window, not the context), the new rows written once.
Counted that low, a share of the roofline cannot pass 100%.
"""

from harness.opsbytes_moe import least_seconds  # noqa: F401  (the readers' one import)


def moe_held_step(*, hidden, layers, ffn, vocab, heads_by_layer, full_by_layer, kv_heads, head_dim, dense_layers,
                  dense_ffn, experts, held, per_tok, window, gated, experts_hit, local_picks, rows, ctx_tokens,
                  param_bytes=2, kv_bytes=2):
    """``rows`` slots generate one token each over ``ctx_tokens`` cached
    positions summed over them; ``experts_hit`` (layer, held expert) pairs
    had a row and ``local_picks`` picks landed on a held expert, as the
    program's frames count them."""
    del held  # what is read of the share is what was hit
    kv_w = kv_heads * head_dim
    one_expert = 3 * hidden * ffn
    expert_layers = layers - dense_layers
    weights = hidden * vocab + rows * hidden  # the head's slice + the embedding rows read
    flops = 2.0 * rows * hidden * vocab
    keys_read = 0.0
    for h, full in zip(heads_by_layer, full_by_layer):
        q_w = h * head_dim
        attn_w = hidden * (q_w + 2 * kv_w) + q_w * hidden + (hidden * h if gated else 0)
        weights += attn_w
        flops += 2.0 * rows * attn_w
        keys = ctx_tokens if full else min(ctx_tokens, rows * window)
        keys_read += keys
        flops += 4.0 * q_w * keys  # scores + context, every query head over its keys
    weights += dense_layers * 3 * hidden * dense_ffn + expert_layers * (hidden * experts + one_expert)  # + router, shared
    flops += 2.0 * rows * (dense_layers * 3 * hidden * dense_ffn + expert_layers * (hidden * experts + one_expert))
    flops += 2.0 * one_expert * local_picks
    kv = 2 * kv_w * (keys_read + layers * rows)  # K and V read, and the new rows written
    return float(flops), float((weights + experts_hit * one_expert) * param_bytes + kv * kv_bytes)
