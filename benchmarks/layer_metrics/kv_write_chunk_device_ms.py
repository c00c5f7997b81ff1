"""Device time of one prefill chunk dispatch spent writing the KV pool: op
time under the program's ``kv_write`` scope (the new rows laid out by page,
the read of the pages they land in, the merge, the scatter through the block
tables; one scatter index a row on a program from before PR 40) and the
compiler's waits for them, per jit__fused_chunk dispatch of the traced slice:
a mean over whatever ``chunk_buckets`` entries the slice's dispatches took.
``kv_write_device_ms`` is the same scope in the fused step. None where the
slice holds no chunk dispatch or no op of one carries the scope."""


from harness.scopes import of_run, scoped_s


def read(o):
    r = of_run(o)
    chunk = r and r["chunk"]
    if not chunk or "kv_write" not in chunk["by_scope"]:
        return None
    return 1e3 * scoped_s(chunk, "kv_write") / chunk["dispatches"]
