"""Device time of one prefill chunk dispatch in the gated delta-rule mixers'
convolution and blocked form: op time under ``attn/gdn_conv`` and
``attn/gdn_scan`` (the state rows' gather and scatter, the snapshot's write
and the triangular solve among it) per jit__fused_chunk dispatch of the
traced slice. None for a program without those scopes."""


from harness.scopes_gdn import nested_ms


def read(o):
    return nested_ms(o, "chunk", "gdn_conv", "gdn_scan")
