"""Device time of one prefill chunk dispatch in the FULL-attention layers'
page gather and scores (``full/kv_gather`` + ``full/attn``), per
jit__fused_chunk dispatch of the traced slice: a chunk's queries against the
whole context on those layers, against a window and a chunk on the others.
The mean over whatever ``chunk_buckets`` entries the slice held (the entry is
on the run's ``{"phase": "dispatches"}`` line). None for a program without
the scopes."""


from harness.scopes_moe import nested_ms


def read(o):
    return nested_ms(o, "chunk", "full/kv_gather", "full/attn")
