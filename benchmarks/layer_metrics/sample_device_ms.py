"""Device time of one fused decode step in the sampler: op time under the
program's ``sample`` scope (the argmax and, where a row of the dispatch asks
for them, the draw over the vocabulary and the top_k cutoff) per
jit__fused_step dispatch of the traced slice."""


from harness.scopes import step_scope_ms


def read(o):
    return step_scope_ms(o, "sample")
