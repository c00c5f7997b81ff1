"""Device time of one prefill chunk round in latent attention: op time under
the program's ``attn/mla_*`` scopes per jit__fused_chunk dispatch of the
traced slice (absorbed for a chunk of up to 170 queries, kv_b over each block
of cached rows under ``mla_expand`` for a longer one)."""


from harness.scopes_mla import ATTN, nested_ms


def read(o):
    return nested_ms(o, "chunk", *ATTN)
