"""Solver roofline readings on the live backend (dev harness).

Every number is chain-differenced — (long-chain − short-chain)/Δk over
single-dispatch solve chains — so the dispatch and readback around a
millisecond-scale solve cancel instead of being estimated. Uses only
public solver entry points (no duplicated core internals).

Usage (from the repo root, on a machine with the chip):
    python scripts/profile_roofline.py
"""

from __future__ import annotations

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
            # data dependency between iterations so XLA cannot collapse
            # the chain; 1e-9 chips is semantically invisible
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

    def enc(req, sort=True):
        if sort and req.job_priority is not None:
            perm = np.argsort(-req.job_priority, kind="stable")
        else:
            perm = np.arange(req.job_gpu.shape[0])
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

    # Headline shape: 10k x 1k, 20% gang, 8 priority levels.
    req = build_request(10_000, 1_000, gang_fraction=0.2)
    p = enc(req)
    out = jax.jit(solve_greedy)(p)
    rounds = int(out.rounds)
    t_full = per_solve_ms(solve_greedy, p)
    print(f"headline solve      : {t_full:7.3f}ms  rounds={rounds} "
          f"placed={int(out.placed)}")

    # Unsorted twin: quantifies what the backend's priority sort (and the
    # per-J-tile early-out it enables) is worth.
    p_uns = enc(req, sort=False)
    print(f"  unsorted twin     : {per_solve_ms(solve_greedy, p_uns):7.3f}ms"
          "  (no tile skipping possible)")

    # Fixed cost: a problem where nothing is placeable solves in ~1 empty
    # round — S build + rank + keys + loop entry, no repair/fill (cond).
    p_fixed = encode_problem_arrays(
        job_gpu=np.full(10_000, 1e6, np.float32),
        job_mem_gib=np.full(10_000, 1e6, np.float32),
        job_priority=np.zeros(10_000, np.float32),
        node_gpu_free=np.full(1_000, 64.0, np.float32),
        node_mem_free_gib=np.full(1_000, 512.0, np.float32),
    )
    t_fixed = per_solve_ms(solve_greedy, p_fixed)
    print(f"fixed (setup) cost  : {t_fixed:7.3f}ms")
    print(f"per-round (derived) : {(t_full - t_fixed) / rounds * 1e3:7.0f}us"
          f"  x {rounds} rounds")

    # Single-class variant: fence pipeline depth -> round count.
    req1 = build_request(10_000, 1_000, gang_fraction=0.0)
    req1.job_priority = np.zeros_like(req1.job_priority)
    p1 = enc(req1)
    o1 = jax.jit(solve_greedy)(p1)
    print(f"single-class solve  : {per_solve_ms(solve_greedy, p1):7.3f}ms"
          f"  rounds={int(o1.rounds)} (fence pipeline collapsed)")


if __name__ == "__main__":
    main()
