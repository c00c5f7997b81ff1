"""Operations and bytes the algorithm needs per dispatch, from shapes alone.

One function per program family; each returns ``(flops, bytes)`` for ONE
dispatch. Matmul FLOPs are 2*MACs. Bytes are the least a dispatch must move:
every weight once, the context's K/V once, the new K/V written once.
"""


def decoder_step(*, hidden, layers, ffn, vocab, slots, ctx_tokens, param_bytes=4, kv_bytes=4):
    """One fused decode step: one token for each of ``slots`` slots, over
    ``ctx_tokens`` cached positions summed over the slots that generate."""
    per_token_layer = 2 * (3 * hidden * hidden + hidden * hidden + 2 * hidden * ffn)
    flops = slots * (layers * per_token_layer + 2 * hidden * vocab)
    flops += 4 * ctx_tokens * hidden * layers  # scores + context
    weights = layers * (4 * hidden * hidden + 2 * hidden * ffn) + vocab * hidden
    kv = 2 * layers * hidden * (ctx_tokens + slots)
    return float(flops), float(weights * param_bytes + kv * kv_bytes)


def bert_forward(*, hidden, layers, ffn, seq, rows, param_bytes=2):
    """One BERT forward of ``rows`` sequences of ``seq`` tokens (copied from
    bench.py ``bert_base_flops_per_pred``: 8h^2 + 4*h*ffn MAC-FLOPs per token
    per layer plus 4*s*h for scores and context; embeddings and head are
    negligible)."""
    per_token_layer = 8 * hidden * hidden + 4 * hidden * ffn + 4 * seq * hidden
    flops = per_token_layer * layers * seq * rows
    weights = layers * (4 * hidden * hidden + 2 * hidden * ffn)
    return float(flops), float(weights * param_bytes)
