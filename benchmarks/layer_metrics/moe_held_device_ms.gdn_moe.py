"""Device time of one fused decode step in the routed part of the third
shape's expert layers, which hold a SHARE of their experts (softmax router
over all 512, dispatch, the held gated-SiLU experts' products, combine): op
time under the program's ``mlp/moe_*`` scopes per jit__fused_step dispatch of
the traced slice. ``moe_held_device_ms.ssm_moe`` is the same reading behind
Nemotron-H's key names. The shared expert is
``shared_expert_device_ms.gdn_moe``."""


from harness.scopes_gdn import moe_ms


def read(o):
    return moe_ms(o, "step")
