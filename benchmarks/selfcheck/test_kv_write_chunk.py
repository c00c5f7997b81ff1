"""``kv_write_chunk_device_ms`` (PR 40) reads the ``kv_write`` scope of the
slice's jit__fused_chunk dispatches, the compiler's waits for it among them,
and not the step's: from events built as ``test_scopes.py`` builds them."""

import pytest
from conftest import ROOT

from harness import cells
from harness import scopes as sc


def _reader(name):
    """The metric's reader as run.py loads it."""
    return cells.load_module(ROOT, cells.load_bench(ROOT), "layer_metrics", name).read


def _events(chunk_write: bool = True):
    """One step dispatch [0.10, 0.30) and two chunk dispatches [0.40, 0.60),
    [0.70, 0.90); the chunks write their pages in a gather, a merge and a
    scatter, the second after a wait without a name."""
    step, chunk = "jit(_fused_step)/jit(main)/", "jit(_fused_chunk)/jit(main)/"
    ops = [("fusion", 0.10, 0.05, step + "qkv/dot_general:"),
           ("fusion f32[36,720,16,1280]", 0.15, 0.07, step + "kv_write/scatter:"),
           ("fusion", 0.22, 0.08, step + "attn/custom_call:")]
    for t0, wait in ((0.40, 0.0), (0.70, 0.01)):
        ops += [("fusion", t0, 0.04, chunk + "qkv/dot_general:")]
        if chunk_write:
            ops += [("fusion f32[34,16,1280]", t0 + 0.04, 0.010, chunk + "kv_write/gather:"),
                    ("copy-done f32[2,288,1280]", t0 + 0.05, wait, ""),
                    ("fusion f32[34,16,1280]", t0 + 0.06, 0.005, chunk + "kv_write/select_n:"),
                    ("fusion f32[36,720,16,1280]", t0 + 0.065, 0.015, chunk + "kv_write/scatter:")]
        ops += [("fusion", t0 + 0.09, 0.10, chunk + "mlp/dot_general:")]
    modules = [("jit__fused_step", 0.10, 0.20), ("jit__fused_chunk", 0.40, 0.20), ("jit__fused_chunk", 0.70, 0.20)]
    return {
        "devices": {"/device:TPU:0": {"ops": [list(o) for o in ops if o[2] > 0], "modules": [list(m) for m in modules]}},
        "host": [[sc.WINDOW, 0.0, 1.0, "", {}]],
        "op_name_stat": "tf_op",
    }


@pytest.fixture
def served():
    """``of_run`` reading the events a test hands it instead of a file."""
    real = sc.newest_xplane, sc.read_scoped
    box = {}
    sc.newest_xplane, sc.read_scoped = (lambda d: "x"), (lambda p: box["events"])

    def serve(events):
        sc._of_file.cache_clear()
        box["events"] = events
        return {"trace": {"families": {}}}

    yield serve
    sc.newest_xplane, sc.read_scoped = real
    sc._of_file.cache_clear()


def test_it_reads_the_chunks_kv_write_scope_and_not_the_steps(served):
    o = served(_events())
    # two dispatches: (0.010 + 0.005 + 0.015) each, and the second's 0.01 wait for the merge
    assert _reader("kv_write_chunk_device_ms")(o) == pytest.approx(1e3 * (2 * 0.030 + 0.01) / 2)
    assert _reader("kv_write_device_ms")(o) == pytest.approx(1e3 * 0.07)  # the step's own, untouched by the chunks'


def test_none_without_the_scope_a_chunk_or_a_trace(served):
    read = _reader("kv_write_chunk_device_ms")
    assert read(served(_events(chunk_write=False))) is None  # chunk dispatches, none of their ops under the scope
    only_step = _events()
    only_step["devices"]["/device:TPU:0"]["modules"] = only_step["devices"]["/device:TPU:0"]["modules"][:1]
    assert read(served(only_step)) is None  # no chunk dispatch in the slice
    bare = _events()
    for op in bare["devices"]["/device:TPU:0"]["ops"]:
        op[3] = ""  # a program (or a cache) without op names
    assert read(served(bare)) is None
    assert read({"trace": None}) is None  # an untraced run
