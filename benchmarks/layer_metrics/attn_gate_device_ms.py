"""Device time of one fused decode step in the per-head output gate (the
gate's product from the normed input, the sigmoid, the head-wise multiply):
op time under the program's ``attn_out/gate`` scope per jit__fused_step
dispatch of the traced slice. None for a program without the gate."""


from harness.scopes_win import nested_ms


def read(o):
    return nested_ms(o, "step", "gate")
