"""Operations and bytes of the hybrid decoder's third shape: a gated
delta-rule or gated attention mixer and an expert layer (a share of
gated-SiLU experts, a sigmoid-gated shared one) in every layer. Companion of
``opsbytes_ssm_moe.py`` for the ``qwen3-next-80b-a3b`` configuration
(``models/hybrid_decoder.py``, ``ops/gated_delta.py``).

Each returns ``(flops, bytes)``. Matmul FLOPs are 2*MACs. Bytes are the
least the MATHEMATICS needs, whatever the program does (its step reads a
row's state twice: counted once): every weight the dispatch touches once (a
held expert no row was routed to is NOT read), the float32 matrix state and
conv cache of the rows that advance read once and written once, q, k and v
as the recurrence takes them, the K/V rows the attention layers have to
read, the new rows written. Operations are those of the recurrence itself,
not of the blocked form that computes it (whose extra products depend on the
block length). Counted that low, a share of the roofline cannot pass 100%.
"""

from harness.opsbytes_moe import least_seconds  # noqa: F401  (the roofline's least time: one definition)


def _state(value_heads, key_dim, value_dim):
    return value_heads * key_dim * value_dim


def _conv_width(key_heads, value_heads, key_dim, value_dim):
    return 2 * key_heads * key_dim + value_heads * value_dim  # q | k | v


def gdn_scan(*, rows, gdn_layers, key_heads, value_heads, key_dim, value_dim, conv, state_bytes=4):
    """The delta rule of one STEP under the ``gdn_scan`` scope: per row and
    layer the state [value heads, d_k, d_v] read once and written once, the
    conv cache (``conv - 1`` inputs of q | k | v) written (its read is the
    ``gdn_conv`` scope's), q, k and v read in float32; a state element costs
    7 operations (the decay, S^T k's product and sum, the outer product's
    term and its sum, S^T q's product and sum)."""
    state = _state(value_heads, key_dim, value_dim)
    width = _conv_width(key_heads, value_heads, key_dim, value_dim)
    return float(7.0 * state * rows * gdn_layers), float(rows * gdn_layers * (2 * state + conv * width) * state_bytes)


def gdn_chunk(*, rows, tokens, gdn_layers, key_heads, value_heads, key_dim, value_dim, conv, state_bytes=4):
    """The delta rule of one CHUNK dispatch under the ``gdn_scan`` scope:
    ``rows`` live rows' states read once and written once (the snapshot's
    copy is not counted), their conv caches written, ``tokens`` real tokens'
    q, k and v read and outputs written in float32; a token costs a value
    head the recurrence's three [d_k, d_v] products (6 operations a state
    element) and the decay (1)."""
    state = _state(value_heads, key_dim, value_dim)
    width = _conv_width(key_heads, value_heads, key_dim, value_dim)
    per_token = (width + value_heads * value_dim) * state_bytes
    nbytes = gdn_layers * (rows * (2 * state + (conv - 1) * width) * state_bytes + tokens * per_token)
    return float(7.0 * state * tokens * gdn_layers), float(nbytes)


def gdn_moe_step(*, hidden, vocab, gdn_layers, attn_layers, expert_layers, heads, kv_heads, head_dim, key_heads,
                 value_heads, key_dim, value_dim, conv, ffn, shared_ffn, experts, experts_hit, local_picks, rows,
                 ctx_tokens, param_bytes=2, kv_bytes=2):
    """The whole fused step: ``rows`` slots generate one token each over
    ``ctx_tokens`` cached positions summed over them (the attention layers
    alone attend over them); ``experts_hit`` (layer, held expert) pairs had a
    row and ``local_picks`` picks landed on a held expert, as the program's
    frames count them; ``experts`` is the router's width."""
    q_w, kv_w = heads * head_dim, kv_heads * head_dim
    width, value_w = _conv_width(key_heads, value_heads, key_dim, value_dim), value_heads * value_dim
    gdn_w = hidden * (width + value_w + 2 * value_heads) + value_w * hidden + conv * width + 2 * value_heads \
        + value_dim + hidden
    attn_w = hidden * (2 * q_w + 2 * kv_w) + q_w * hidden + 2 * head_dim + hidden  # a gate beside each head's query
    one_expert = 3 * hidden * ffn  # gate, up and down
    every_token = hidden * experts + 3 * hidden * shared_ffn + 2 * hidden  # router, the shared expert, its gate, the norm
    dense = gdn_layers * gdn_w + attn_layers * attn_w + expert_layers * every_token + vocab * hidden + hidden
    s_flops, s_bytes = gdn_scan(rows=rows, gdn_layers=gdn_layers, key_heads=key_heads, value_heads=value_heads,
                                key_dim=key_dim, value_dim=value_dim, conv=conv)
    conv_read = rows * gdn_layers * (conv - 1) * width * 4
    keys_read = attn_layers * ctx_tokens  # K rows (and as many V rows)
    flops = 2.0 * rows * dense + 2.0 * one_expert * local_picks + 4.0 * q_w * keys_read + s_flops
    kv = 2 * kv_w * (keys_read + attn_layers * rows)  # read, and the new rows written
    weights = dense + experts_hit * one_expert + rows * hidden  # + the embedding rows read
    return float(flops), float(weights * param_bytes + s_bytes + conv_read + kv * kv_bytes)
