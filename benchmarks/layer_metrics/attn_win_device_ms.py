"""Device time of one fused decode step in the SLIDING-WINDOW layers' page
gather and attention: op time under the program's ``win/*`` scopes
(``win/kv_gather`` + ``win/attn``) per jit__fused_step dispatch of the traced
slice. With ``attn_full_device_ms`` it splits what ``kv_gather_device_ms`` and
``attn_device_ms`` read together. None for a program without the scopes."""


from harness.scopes_moe import nested_ms


def read(o):
    return nested_ms(o, "step", "win/kv_gather", "win/attn")
