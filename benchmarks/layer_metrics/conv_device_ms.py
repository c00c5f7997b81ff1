"""Device time of one fused decode step in the gated short convolution: op
time under the program's ``qkv/conv_in``, ``attn/conv_mix`` (the gates, the
taps, the state rows' read and write) and ``attn_out/conv_out`` scopes per
jit__fused_step dispatch of the traced slice. None for a program without
those scopes."""


from harness.scopes_conv import CONV, nested_ms


def read(o):
    return nested_ms(o, "step", *CONV)
