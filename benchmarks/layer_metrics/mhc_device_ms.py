"""Device time of one fused decode step in the multi-stream residual path: op
time under the program's ``mhc_map`` (sum of squares, the three products,
sigmoid, exp, the Sinkhorn loop), ``mhc_pre`` and ``mhc_post`` scopes, nested
under ``qkv``, ``attn_out`` and ``mlp``, per jit__fused_step dispatch of the
traced slice (harness/scopes_mhc.py). ``dense_device_ms`` holds this time too:
the weights' time is the difference. None for a program without those scopes."""


from harness.scopes_mhc import nested_ms


def read(o):
    return nested_ms(o, "step")
