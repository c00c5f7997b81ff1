"""Device time of one prefill chunk dispatch in the multi-stream residual
path: op time under the program's ``mhc_*`` scopes per jit__fused_chunk
dispatch of the slice's most frequent ``(rows, c)`` entry
(harness/scopes_mhc.py; the entry is the one ``chunk_entry_device_ms`` reads)."""


from harness.scopes_mhc import nested_ms


def read(o):
    return nested_ms(o, "chunk")
