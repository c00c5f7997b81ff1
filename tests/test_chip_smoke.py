"""chip_smoke.py's contract off the chip: no accelerator -> non-zero exit
and no result; alone in a directory -> the same; --rehearse walks every
one-chip phase on the CPU backend and still cannot print the passing line
(that one boots four servers — half a minute the tier-1 budget does not
have, so it carries the ``slow`` marker: run it, or `python chip_smoke.py
--rehearse` itself, before spending chip time)."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, cwd=REPO, script=SMOKE, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # one CPU device, as in a sandbox without a chip
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, script, *args], env=env, cwd=cwd,
        capture_output=True, text=True, timeout=timeout,
    )


def test_no_accelerator_fails_and_prints_no_result():
    out = _run([])
    assert out.returncode == 2
    assert out.stdout == ""
    assert "no accelerator" in out.stderr


def test_alone_in_a_directory_fails_and_prints_no_result(tmp_path):
    alone = shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    out = _run([], cwd=str(tmp_path), script=str(alone))
    assert out.returncode not in (0, 2)  # not even the device check: no program
    assert out.stdout == ""
    assert "seldon_core_tpu" in out.stderr


@pytest.mark.slow
def test_rehearsal_walks_every_phase_and_cannot_pass():
    out = _run(["--rehearse"])
    lines = [json.loads(line) for line in out.stdout.splitlines()]
    assert out.returncode == 3, out.stderr[-2000:]
    assert lines[-1]["ok"] is False and lines[-1]["rehearsal"] is True
    phases = {line.get("phase") for line in lines}
    assert {"classic", "generative:f32-pool", "generative:int8-pool", "done"} <= phases
    for line in lines:
        assert line.get("recompiles_after_warmup", 0) == 0
