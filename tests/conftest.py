"""Test harness: force an 8-device virtual CPU mesh BEFORE jax initialises.

SURVEY §4 implication: the reference has no simulated-cluster test mode; we
add one — every test runs against 8 virtual devices so sharding/collective
code paths are exercised without TPU hardware."""

import os
import sys

# both are read when the backend initialises, which no import has done yet
os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import asyncio  # noqa: E402
import inspect  # noqa: E402
import socket  # noqa: E402

import pytest  # noqa: E402


def _memory_maps() -> int:
    try:
        with open("/proc/self/maps") as f:
            return sum(1 for _ in f)
    except OSError:  # not Linux: no such limit to watch
        return 0


@pytest.fixture(autouse=True, scope="module")
def _release_compiled_programs():
    """An XLA:CPU executable holds several memory mappings, and one process
    running the whole suite climbs past vm.max_map_count (65530) near its
    end — the next compile then segfaults. At the end of a test module that
    leaves the process past 40,000, drop every compiled program: once in a
    whole run, ~10 s (dropping them after EVERY module cost the suite over
    a minute of recompiles it cannot spare)."""
    yield
    if _memory_maps() > 40_000:
        import gc

        sys.modules["jax"].clear_caches()
        gc.collect()


def free_port() -> int:
    """Ephemeral localhost port for test servers (shared test utility)."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.hookimpl(tryfirst=True)
def pytest_pyfunc_call(pyfuncitem):
    """Minimal async test support (no pytest-asyncio dependency)."""
    fn = pyfuncitem.function
    if inspect.iscoroutinefunction(fn):
        kwargs = {
            name: pyfuncitem.funcargs[name]
            for name in pyfuncitem._fixtureinfo.argnames
        }
        asyncio.run(fn(**kwargs))
        return True
    return None


@pytest.fixture()
def small_chunk_kernel_blocks(monkeypatch):
    """ops/gqa_decode.py's kernels at the tests' sizes: runs of 2 table
    entries, key blocks of 4, query blocks of 16 score rows a lane tile (4
    queries at the tiny families' 4 heads in one 32-lane tile), so that a
    chunk of 8 or 16 is several work items a row over several key blocks."""
    from seldon_core_tpu.ops import gqa_decode, mla

    monkeypatch.setattr(mla, "RUN_PAGES", 2)
    monkeypatch.setattr(mla, "BLOCK_PAGES", 4)
    monkeypatch.setattr(mla, "CHUNK_Q_ROWS", 16)
    monkeypatch.setattr(gqa_decode, "CHUNK_BLOCK_PAGES", 4)
