"""Share of a fused decode step's op time that lies under any program scope
(models/decoder.py PAGED_SCOPES), the compiler's async waits counted under
the scope of the op they are for: the instrument's own check. None where no
op of the step carries a scope (a program without them, or executables a
compile cache handed back without metadata)."""


from harness.scopes import of_run, scoped_s


def read(o):
    step = (of_run(o) or {}).get("step")
    if not step or not step["by_scope"] or not step["op_s"]:
        return None
    return 100.0 * scoped_s(step) / step["op_s"]
