"""True multi-process distributed test: two OS processes, one jax
process group, one global mesh, one sharded solve.

This is the integration the single-process tests cannot give: separate
XLA clients coordinating through jax.distributed (the DCN topology's
shape, minus the second physical host). Workers are pinned to the CPU
backend, one device each.
"""

from __future__ import annotations

import os
import re
import socket
import subprocess
import sys

import pytest

WORKER = os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    "testdata", "distributed_worker.py",
)


@pytest.mark.slow
def test_two_process_group_runs_sharded_solve():
    from tests.conftest import subprocess_pythonpath

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)  # 1 device per process; mesh spans processes
    env["PYTHONPATH"] = subprocess_pythonpath()

    procs = [
        subprocess.Popen(
            [sys.executable, WORKER, str(rank), "2", str(port)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True,
        )
        for rank in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=240)
            outs.append(out)
    finally:
        # a wedged worker (e.g. lost coordinator port) must not orphan
        # the pair holding the port past the test
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait(timeout=10)
    placed = set()
    for rank, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {rank} failed:\n{out}"
        m = re.search(rf"rank {rank}: placed (\d+)", out)
        assert m, f"rank {rank} output unparseable:\n{out}"
        placed.add(int(m.group(1)))
    # SPMD: both processes computed the same global result
    assert len(placed) == 1, outs
