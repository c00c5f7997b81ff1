"""Device time of one fused decode step in the Mamba-2 mixers' convolution and
recurrence: op time under the program's ``attn/ssm_conv`` and ``attn/ssm_scan``
scopes (the state rows' read and write among it) per jit__fused_step dispatch
of the traced slice. None for a program without those scopes."""


from harness.scopes_ssm import nested_ms


def read(o):
    return nested_ms(o, "step", "ssm_conv", "ssm_scan")
