"""Device time of one fused decode step in the routed part of the hybrid
family's expert layers, which hold a SHARE of their experts (router over all
128, dispatch, the held squared-ReLU experts' products, combine): op time
under the program's ``mlp/moe_*`` scopes per jit__fused_step dispatch of the
traced slice. ``moe_held_device_ms`` and ``.moe`` are the same reading for
families whose readers want their own scopes or key names beside these. The
shared expert is ``shared_expert_device_ms.ssm_moe``."""


from harness.scopes_ssm_moe import moe_ms


def read(o):
    return moe_ms(o, "step")
