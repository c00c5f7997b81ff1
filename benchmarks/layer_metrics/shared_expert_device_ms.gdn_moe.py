"""Device time of one fused decode step in the third shape's shared expert
(the 512-wide gated-SiLU MLP every token takes beside its routed experts,
times its one-scalar sigmoid gate): op time under the program's
``mlp/shared_expert`` scope per jit__fused_step dispatch of the traced
slice. ``shared_expert_device_ms.ssm_moe`` reads the same scope behind
Nemotron-H's key names."""


from harness.scopes_gdn import shared_ms


def read(o):
    return shared_ms(o, "step")
