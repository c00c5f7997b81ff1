"""Device time of one fused decode step in latent attention: op time under
the program's ``attn/mla_absorb`` (the Wuk and Wuv products), ``attn/mla_core``
(the walk over the latent rows, page gathers included) and ``attn/mla_expand``
scopes per jit__fused_step dispatch of the traced slice. None for a program
without those scopes."""


from harness.scopes_mla import ATTN, nested_ms


def read(o):
    return nested_ms(o, "step", *ATTN)
