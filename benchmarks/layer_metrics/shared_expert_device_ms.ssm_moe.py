"""Device time of one fused decode step in the hybrid family's shared expert
(the 3712-wide squared-ReLU MLP every token takes beside its routed
experts): op time under the program's ``mlp/shared_expert`` scope per
jit__fused_step dispatch of the traced slice. ``shared_expert_device_ms``
reads the same scope where the latent family's ``mla_*`` scopes lie beside
it."""


from harness.scopes_ssm_moe import shared_ms


def read(o):
    return shared_ms(o, "step")
