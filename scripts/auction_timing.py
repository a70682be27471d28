"""On-chip auction check: fused-kernel parity vs the jnp twin + chain-
differenced device timing (dispatch cancels; see bench.device_solve_ms).

Usage (from the repo root, on a machine with the chip):
    python scripts/auction_timing.py
"""
import functools
import os
import sys
import time

import numpy as np

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

from kubeinfer_tpu.scheduler import SolveRequest
from kubeinfer_tpu.solver.core import solve_auction
import bench


def main():
    import jax
    print("backend:", jax.default_backend())
    rng = np.random.default_rng(3)
    areq = SolveRequest(
        job_gpu=np.full(1_000, 64.0, np.float32),
        job_mem_gib=rng.integers(64, 512, 1_000).astype(np.float32),
        job_priority=rng.integers(0, 8, 1_000).astype(np.float32),
        job_model=rng.integers(0, 256, 1_000).astype(np.int32),
        node_gpu_free=np.full(1_000, 64.0, np.float32),
        node_mem_free_gib=np.full(1_000, 512.0, np.float32),
        node_cached=(rng.random((1_000, 256)) < 0.02).astype(np.uint8),
    )
    # parity on the real chip: fused (auto->pallas on tpu) vs jnp twin
    from kubeinfer_tpu.solver.problem import encode_problem_arrays
    p = encode_problem_arrays(
        job_gpu=areq.job_gpu, job_mem_gib=areq.job_mem_gib,
        job_priority=areq.job_priority, job_model=areq.job_model,
        node_gpu_free=areq.node_gpu_free,
        node_mem_free_gib=areq.node_mem_free_gib,
        node_cached=areq.node_cached.astype(bool),
    )
    t0 = time.time()
    a_pallas = solve_auction(p, accel="pallas")
    asg_p = np.asarray(a_pallas.node)  # lint: allow[host-sync] timing-harness readback
    # lint: allow[host-sync] timing-harness readback
    print(f"pallas compile+run {time.time()-t0:.1f}s; placed={int(a_pallas.placed)} iters={int(a_pallas.rounds)}")
    t0 = time.time()
    a_jnp = solve_auction(p, accel="jnp")
    asg_j = np.asarray(a_jnp.node)  # lint: allow[host-sync] timing-harness readback
    # lint: allow[host-sync] timing-harness readback
    print(f"jnp    compile+run {time.time()-t0:.1f}s; placed={int(a_jnp.placed)} iters={int(a_jnp.rounds)}")
    same = np.array_equal(asg_p, asg_j)
    print("bitwise assigned parity:", same)
    if not same:
        d = np.nonzero(asg_p != asg_j)[0]
        print("  mismatches:", len(d), "first:", d[:10],
              asg_p[d[:10]], asg_j[d[:10]])

    for label, fn in (
        ("fused", functools.partial(solve_auction, accel="pallas")),
        ("jnp-loop", functools.partial(solve_auction, accel="jnp")),
    ):
        adev, floor, jitter = bench.device_solve_ms(
            areq, k_short=4, k_long=24, reps=5, solve_fn=fn
        )
        print(f"{label}: device {adev:.3f} ms  floor {floor:.1f}  jitter {jitter:.1f}")


if __name__ == "__main__":
    main()
