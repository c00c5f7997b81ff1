"""Share of a fused decode step's op time that lies under any program scope
(models/decoder.py PAGED_SCOPES): the instrument's own check. None where no
op of the step carries a scope (a program without them, or executables a
compile cache handed back without metadata)."""


from harness.scopes import of_run


def read(o):
    step = (of_run(o) or {}).get("step")
    if not step or not step["by_scope"] or not step["op_s"]:
        return None
    return 100.0 * sum(step["by_scope"].values()) / step["op_s"]
