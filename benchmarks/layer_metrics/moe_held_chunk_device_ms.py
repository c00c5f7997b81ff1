"""Device time of one prefill chunk round in the routed part of an expert
layer that holds a share of its experts (router over all 64, dispatch, the
held experts' products, combine): op time under the program's ``mlp/moe_*``
scopes per jit__fused_chunk dispatch of the traced slice, read with the
short-convolution family's names. The chunk twin of ``moe_held_device_ms``:
this cell's chunks bring the layer 256 real rows a prefilling slot (the
masked form to 256 rows, the grouped form above)."""


from harness.scopes_conv import MOE, nested_ms


def read(o):
    return nested_ms(o, "chunk", *MOE)
