"""Chain-differenced mega-kernel timing on the live TPU (dev harness).

Usage (from the repo root, on a machine with the chip):
    python scripts/mega_timing.py
"""

from __future__ import annotations

import functools
import statistics
import time
from dataclasses import replace

import numpy as np


def _chain(fn, p, k, reps=9):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def run(problem):
        def body(carry, _):
            nodes = replace(
                problem.nodes, gpu_free=problem.nodes.gpu_free + carry
            )
            out = fn(replace(problem, nodes=nodes))
            return out.placed.astype(jnp.float32) * 1e-9, ()

        final, _ = jax.lax.scan(body, jnp.float32(0.0), None, length=k)
        return final

    np.asarray(run(p))  # lint: allow[host-sync] warm-up sync: forces the compile before timing
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        np.asarray(run(p))  # lint: allow[host-sync] the timed readback IS the measurement
        ts.append(time.perf_counter() - t0)
    return statistics.median(ts)


def per_solve_ms(fn, p, k_long=80, k_short=8):
    return (_chain(fn, p, k_long) - _chain(fn, p, k_short)) / (
        k_long - k_short
    ) * 1e3


def main() -> None:
    import jax

    from bench import build_request
    from kubeinfer_tpu.solver.core import solve_greedy
    from kubeinfer_tpu.solver.problem import encode_problem_arrays

    print(f"# backend: {jax.devices()[0]}")

    def enc(req):
        perm = np.argsort(-req.job_priority, kind="stable")
        return encode_problem_arrays(
            job_gpu=req.job_gpu[perm],
            job_mem_gib=req.job_mem_gib[perm],
            job_priority=req.job_priority[perm],
            job_gang=req.job_gang[perm] if req.job_gang is not None else None,
            job_model=req.job_model[perm],
            node_gpu_free=req.node_gpu_free,
            node_mem_free_gib=req.node_mem_free_gib,
            node_cached=req.node_cached,
            node_topology=req.node_topology,
        )

    req = build_request(10_000, 1_000, gang_fraction=0.2)
    p = enc(req)

    for accel in ("mega", "pallas"):
        fn = functools.partial(solve_greedy, accel=accel)
        out = jax.jit(fn)(p)
        rounds, placed = int(out.rounds), int(out.placed)
        t = per_solve_ms(fn, p)
        print(f"{accel:8s}: {t:7.3f}ms  rounds={rounds} placed={placed}")


if __name__ == "__main__":
    main()
