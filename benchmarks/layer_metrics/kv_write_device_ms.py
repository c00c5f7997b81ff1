"""Device time of one fused decode step spent writing the KV pool: op time
under the program's ``kv_write`` (the scatter through the block tables) and
``pool_restack`` (the per-layer pool slice and the closing stack) scopes,
per jit__fused_step dispatch of the traced slice. ROADMAP S1's number."""


from harness.scopes import step_scope_ms


def read(o):
    return step_scope_ms(o, "kv_write", "pool_restack")
