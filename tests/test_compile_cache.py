"""The compile-cache rule (utils/compile_cache.py): where
JAX_COMPILATION_CACHE_DIR is set the program sets no directory in code;
unset, every process of a checkout resolves the same fixed in-checkout path."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import jax

from seldon_core_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _recorded_updates(monkeypatch) -> dict:
    """enable_compile_cache() with jax.config.update stubbed out: the test
    process's own config stays untouched."""
    seen: dict = {}
    monkeypatch.setattr(jax.config, "update", lambda k, v: seen.__setitem__(k, v))
    seen["returned"] = compile_cache.enable_compile_cache()
    return seen


def test_env_var_set_means_no_directory_set_in_code(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.CACHE_DIR_ENV, str(tmp_path))
    seen = _recorded_updates(monkeypatch)
    assert "jax_compilation_cache_dir" not in seen
    assert seen["returned"] == str(tmp_path)
    # the decode tier's sub-second programs are admitted either way
    assert seen["jax_persistent_cache_min_compile_time_secs"] == 0.0
    # the key covers op metadata (a cache warmed by a build without the
    # named scopes must not hand its executables to one with them), with
    # source paths relative to the checkout so another checkout still hits
    assert seen["jax_compilation_cache_include_metadata_in_key"] is True
    import re

    assert re.sub(seen["jax_hlo_source_file_canonicalization_regex"], "",
                  os.path.join(REPO, "seldon_core_tpu", "models", "decoder.py")) == (
        os.path.join("seldon_core_tpu", "models", "decoder.py"))
    assert jax.config.jax_persistent_cache_min_entry_size_bytes <= 0


def test_cpu_pinned_process_gets_no_default_directory(monkeypatch):
    monkeypatch.delenv(compile_cache.CACHE_DIR_ENV, raising=False)
    assert jax.config.jax_platforms == "cpu"  # tests/conftest.py pins it
    seen = _recorded_updates(monkeypatch)
    assert seen["returned"] is None and "jax_compilation_cache_dir" not in seen


def test_unset_resolves_to_the_fixed_checkout_path_in_every_process(tmp_path):
    env = {
        k: v
        for k, v in os.environ.items()
        if k not in (compile_cache.CACHE_DIR_ENV, "JAX_PLATFORMS")
    }
    env["PYTHONPATH"] = REPO
    code = (
        "import json, jax\n"
        "from seldon_core_tpu.utils.compile_cache import enable_compile_cache\n"
        "print(json.dumps([enable_compile_cache(), jax.config.jax_compilation_cache_dir]))"
    )
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", code], env=env, cwd=cwd,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )
        for cwd in (REPO, str(tmp_path))  # the path does not follow the cwd
    ]
    seen = []
    for proc in procs:
        out, err = proc.communicate(timeout=120)
        assert proc.returncode == 0, err[-1000:]
        seen.append(json.loads(out.splitlines()[-1]))
    want = os.path.join(REPO, ".jax_cache")
    assert seen == [[want, want], [want, want]]
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_every_process_main_places_the_cache():
    for rel in (
        "seldon_core_tpu/serving/server.py",
        "seldon_core_tpu/platform.py",
        "seldon_core_tpu/serving/microservice.py",
        "seldon_core_tpu/tools/soak.py",
        "benchmarks/run.py",
        "chip_smoke.py",
    ):
        with open(os.path.join(REPO, rel)) as f:
            assert "enable_compile_cache()" in f.read(), rel
