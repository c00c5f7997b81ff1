"""Device time of one fused decode step in the shared expert (the gated MLP
every token takes beside its routed experts): op time under the program's
``mlp/shared_expert`` scope per jit__fused_step dispatch of the traced slice."""


from harness.scopes_mla import nested_ms


def read(o):
    return nested_ms(o, "step", "shared_expert")
