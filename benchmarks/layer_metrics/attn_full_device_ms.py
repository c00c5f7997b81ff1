"""Device time of one fused decode step in the FULL-attention layers' page
gather and attention: op time under the program's ``full/*`` scopes
(``full/kv_gather`` + ``full/attn``) per jit__fused_step dispatch of the
traced slice: the layers whose rows grow with the context. None for a program
without the scopes."""


from harness.scopes_moe import nested_ms


def read(o):
    return nested_ms(o, "step", "full/kv_gather", "full/attn")
