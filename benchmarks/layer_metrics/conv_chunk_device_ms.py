"""Device time of one prefill chunk round in the gated short convolution: op
time under the program's three ``conv_*`` scopes per jit__fused_chunk
dispatch of the traced slice (every entry of the chunk ladder that ran,
averaged; the state rows are gathered by ``state_rows`` and written twice
where a snapshot is taken)."""


from harness.scopes_conv import CONV, nested_ms


def read(o):
    return nested_ms(o, "chunk", *CONV)
