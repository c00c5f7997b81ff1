"""Operations and bytes of the latent-attention decoder's step, from shapes
and from what the program counted. Companion of ``opsbytes.py`` for the
``a.x-k1`` configuration (``models/mla_decoder.py``).

Each returns ``(flops, bytes)`` for ONE fused decode step. Matmul FLOPs are
2*MACs. Bytes are the least the mathematics needs: a weight the step touches
once (a held expert no row was routed to is NOT read, an absent expert is not
there), each latent row a generating slot attends over read ONCE a layer at
its ``rank + rope`` numbers (576: not the 640 lanes it is stored in, not a
second time for the context) and shared by all heads, the new rows written
once. Counted that low, a share of the roofline cannot pass 100%.
"""

from harness.opsbytes_moe import least_seconds  # noqa: F401  (the roofline's least time: one definition)


def mla_decode(*, ctx_rows, rows, layers, heads, kv_rank, rope, nope, v_dim, cache_bytes=2, param_bytes=2):
    """The absorbed attention of one step under ``attn/mla_core`` and
    ``attn/mla_absorb``: ``ctx_rows`` latent rows (summed over the ``rows``
    generating slots; one layer's count) read once a layer, ``kv_b`` once a
    layer; a row and head cost ``(rank + rope) + rank`` multiply-adds (score,
    context), a slot and head ``nope * rank + rank * v`` (Wuk, Wuv)."""
    macs = heads * (ctx_rows * (2 * kv_rank + rope) + rows * kv_rank * (nope + v_dim))
    nbytes = ctx_rows * (kv_rank + rope) * cache_bytes + kv_rank * heads * (nope + v_dim) * param_bytes
    return float(2.0 * macs * layers), float(nbytes * layers)


def mla_decoder_step(*, hidden, layers, ffn, vocab, heads, q_rank, kv_rank, nope, rope, v_dim, dense_layers,
                     dense_ffn, experts, held, per_tok, rows, ctx_rows, experts_hit, local_picks,
                     param_bytes=2, cache_bytes=2):
    """The whole fused step: ``rows`` slots generate one token each over
    ``ctx_rows`` latent rows summed over them. ``experts_hit`` is the (layer,
    held expert) pairs with a row, ``local_picks`` the picks that landed on
    a held expert, both summed over the expert layers, as the program's
    frames count them. ``held`` is not needed: an expert that is held and
    not hit is not read."""
    del held
    expert_layers = layers - dense_layers
    attn_w = hidden * q_rank + q_rank * heads * (nope + rope) + hidden * (kv_rank + rope) \
        + kv_rank * heads * (nope + v_dim) + heads * v_dim * hidden
    one_expert = 3 * hidden * ffn
    per_token = layers * (attn_w - kv_rank * heads * (nope + v_dim)) + dense_layers * 3 * hidden * dense_ffn \
        + expert_layers * (hidden * experts + one_expert) + hidden * vocab  # router + the shared expert; the head's slice
    a_flops, a_bytes = mla_decode(ctx_rows=ctx_rows, rows=rows, layers=layers, heads=heads, kv_rank=kv_rank, rope=rope,
                                  nope=nope, v_dim=v_dim, cache_bytes=cache_bytes, param_bytes=param_bytes)
    flops = 2.0 * rows * per_token + 2.0 * one_expert * local_picks + a_flops
    weights = per_token + experts_hit * one_expert + rows * hidden  # + the embedding rows read
    written = layers * rows * (kv_rank + rope)
    return float(flops), float(weights * param_bytes + a_bytes + written * cache_bytes)
