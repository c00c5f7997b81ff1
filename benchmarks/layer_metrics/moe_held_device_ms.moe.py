"""Device time of one fused decode step in the routed part of an expert layer
that holds a SHARE of its experts, in the sparse-expert family (router over
all of them, dispatch, the held experts' products, combine): op time under
the program's ``mlp/moe_*`` scopes per jit__fused_step dispatch of the traced
slice. ``moe_held_device_ms`` is the same reading by the latent-attention
family's names (its reader wants ``mla_*`` scopes beside them). The shared
expert and the dense layer are not in it. None for a configuration that holds
all its experts."""


from harness.scopes_moe import MOE, nested_ms
from harness.scopes_win import held_share


def read(o):
    return nested_ms(o, "step", *MOE) if held_share(o) else None
