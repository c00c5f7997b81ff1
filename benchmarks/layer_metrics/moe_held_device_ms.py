"""Device time of one fused decode step in the routed part of an expert layer
that holds a share of its experts (router over all 192, dispatch, the held
experts' products, combine): op time under the program's ``mlp/moe_*`` scopes
per jit__fused_step dispatch of the traced slice, read with the
latent-attention family's names (``moe_device_ms`` is the sparse-expert
family's, and its list is held to that family's cell). The shared expert is
``shared_expert_device_ms``."""


from harness.scopes_mla import nested_ms


def read(o):
    return nested_ms(o, "step", "moe_router", "moe_dispatch", "moe_experts", "moe_combine")
