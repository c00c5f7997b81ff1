"""Device time of one fused decode step in the gated delta-rule mixers'
convolution and state update: op time under the program's ``attn/gdn_conv``
and ``attn/gdn_scan`` scopes (the state rows' read and write among it) per
jit__fused_step dispatch of the traced slice. ``ssm_device_ms`` is the same
reading of the Mamba-2 mixers' scopes. None for a program without these."""


from harness.scopes_gdn import nested_ms


def read(o):
    return nested_ms(o, "step", "gdn_conv", "gdn_scan")
