"""Device time of one fused decode step spent gathering the slots' pages
into the virtual contiguous cache: op time under the program's ``kv_gather``
scope per jit__fused_step dispatch of the traced slice. ROADMAP S2's number."""


from harness.scopes import step_scope_ms


def read(o):
    return step_scope_ms(o, "kv_gather")
