"""Device time of one prefill chunk dispatch in the Mamba-2 mixers' convolution
and chunked scan: op time under ``attn/ssm_conv`` and ``attn/ssm_scan`` (the
state rows' gather and scatter, the snapshot's write among it) per
jit__fused_chunk dispatch of the traced slice. None for a program without
those scopes."""


from harness.scopes_ssm import nested_ms


def read(o):
    return nested_ms(o, "chunk", "ssm_conv", "ssm_scan")
