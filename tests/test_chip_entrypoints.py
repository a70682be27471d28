"""The two commands that need the chip refuse to run without it.

``bench.py`` and ``chip_smoke.py`` report device numbers and device
checks; on the CPU they have nothing to report, and a fallback that ran
them there anyway is how a CPU timing once ended up under a device
metric's name. These run each command as a user would, pinned to the CPU
backend, and hold it to "non-zero, and no result".
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

from tests.conftest import REPO_ROOT


def _run(script: str, *args: str, cwd: str = REPO_ROOT):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, script, *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300,
    )


def test_bench_fails_without_a_tpu():
    proc = _run(os.path.join(REPO_ROOT, "bench.py"), "--quick")
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert proc.stdout.strip() == ""  # no JSON line for the driver to read


def test_chip_smoke_fails_without_a_tpu():
    proc = _run(os.path.join(REPO_ROOT, "chip_smoke.py"))
    assert proc.returncode != 0
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["ok"] is False and "device" not in last
    assert last["failed_phase"] == "solver"  # the first phase; none ran on


def test_chip_smoke_alone_prints_no_result(tmp_path):
    shutil.copy(os.path.join(REPO_ROOT, "chip_smoke.py"), tmp_path)
    proc = _run(str(tmp_path / "chip_smoke.py"), cwd=str(tmp_path))
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
