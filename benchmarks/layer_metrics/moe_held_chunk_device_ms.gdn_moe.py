"""Device time of one prefill chunk round in the routed part of the third
shape's expert layers (router over all 512, dispatch, the held experts'
products, combine): op time under the program's ``mlp/moe_*`` scopes per
jit__fused_chunk dispatch of the traced slice. The chunk twin of
``moe_held_device_ms.gdn_moe``: this cell's chunks bring a layer 256 real
rows a prefilling slot (the masked form to 256 rows, the compact grouped
form above)."""


from harness.scopes_gdn import moe_ms


def read(o):
    return moe_ms(o, "chunk")
