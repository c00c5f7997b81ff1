"""Device time of one fused decode step in attention over the gathered
cache (scores, mask, softmax, context): op time under the program's ``attn``
scope per jit__fused_step dispatch of the traced slice."""


from harness.scopes import step_scope_ms


def read(o):
    return step_scope_ms(o, "attn")
