"""Device time of one fused decode step in the weight matmuls: op time under
the program's ``qkv``, ``attn_out``, ``mlp`` and ``lm_head`` scopes per
jit__fused_step dispatch of the traced slice — the part step_roofline's
least time (the weights read once) is about."""


from harness.scopes import step_scope_ms


def read(o):
    return step_scope_ms(o, "qkv", "attn_out", "mlp", "lm_head")
