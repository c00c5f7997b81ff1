"""Device time of one prefill chunk round in the routed part of the hybrid
family's expert layers (router over all 128, dispatch, the held experts'
products, combine): op time under the program's ``mlp/moe_*`` scopes per
jit__fused_chunk dispatch of the traced slice. The chunk twin of
``moe_held_device_ms.ssm_moe``: this cell's chunks bring a layer 256 real
rows a prefilling slot (the masked form to 256 rows, the compact grouped
form above); ``moe_held_chunk_device_ms`` reads the same scopes with the
short-convolution family's names beside them."""


from harness.scopes_ssm_moe import moe_ms


def read(o):
    return moe_ms(o, "chunk")
