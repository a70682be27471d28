#!/usr/bin/env python3
"""chip_smoke.py — does the system still start, and answer right, on the chip?

Drives the solver and the serving path once through the entry points a
user calls, on one TPU v5e, and checks what comes out against the repo's
own references. It is a proof of life, not a benchmark: every time it
prints is a *smoke reading* from one cold pass and is no metric.

    python chip_smoke.py              # one chip: solver, kernels, server
    python chip_smoke.py --chips 4    # four chips: tensor parallel only

One process per chip. This parent never imports jax. Each phase is a
child that owns the chip and has exited before the next one starts:

- solver:  ``get_backend(policy).solve`` at the cluster sizes BASELINE
  uses; the kernel path against its jnp twin, bit for bit; capacity and
  gang constraints; the route solve through ``route_pick_pallas``.
- kernels: every serving kernel at qwen2-7b widths against its jnp twin
  and against dense float32 attention / matmul; then the step programs
  ``ContinuousEngine`` jits are lowered for the chip and must contain
  their Pallas calls (the ``*_auto`` routers fall to dense in silence).
- server:  ``python -m kubeinfer_tpu.inference.server`` with qwen2-7b at
  published widths and full depth (random weights from a fixed seed,
  int8 projections), answering token-id prompts over HTTP.

``--chips 4`` runs only the same server at ``--tensor-parallel-size 4``
and at 1 on the same prompts, and compares the two.

Every phase prints one JSON line. The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``
with the device as a child read it off ``jax.devices()``. Anything that
fails — no TPU, a phase, a check — makes that line ``"ok": false`` and
the exit code non-zero; nothing is caught and carried past.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import signal
import socket
import subprocess
import sys
import threading
import time
import traceback
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))

# The driver allows 1200 s, compilation included; stop short of it.
BUDGET_S = 1150.0

# --- the model the server phase serves --------------------------------------
# qwen2-7b at its published widths (inference/config.py PRESETS, from the
# model's config.json): nothing is cut, depth included. bf16 weights are
# 15.2 GB and leave no room for a cache on a 16 GB chip, int8 projections
# with bf16 embedding and head are 8.7 GB (ROADMAP R1).
MODEL = "qwen2-7b"
WIDTHS = dict(L=28, H=3584, F=18944, V=152064, n_q=28, n_kv=4, D=128)
N_SLOTS, MAX_LEN, BLOCK = 8, 4096, 128
NEW_TOKENS = 17  # 1 at admit + 16: two whole K=8 windows when alone
SHORT_LEN, LONG_LEN = 70, 1100  # both end in a 128-token admit bucket


def expected_param_bytes() -> int:
    """kubeinfer_model_param_bytes for MODEL under --weight-dtype int8:
    int8 codes + one f32 scale per out column for the seven projections,
    bf16 for norms, qkv biases, embedding and head. What keeps a server
    that quietly fell back to the ``tiny`` preset from passing."""
    w = WIDTHS
    q_dim, kv_dim = w["n_q"] * w["D"], w["n_kv"] * w["D"]
    H, F, V = w["H"], w["F"], w["V"]
    proj = [(H, q_dim), (H, kv_dim), (H, kv_dim), (q_dim, H),
            (H, F), (H, F), (F, H)]
    layer = sum(i * o + 4 * o for i, o in proj)
    layer += 2 * (2 * H) + 2 * (q_dim + 2 * kv_dim)
    return w["L"] * layer + 2 * (V * H) * 2 + 2 * H


class SmokeFailure(Exception):
    """A check that did not hold."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def emit(doc: dict) -> None:
    print(json.dumps(doc), flush=True)


def failure(e: Exception) -> str:
    """What went wrong, for the JSON line; anything that is not one of
    the smoke's own checks also leaves its traceback on stderr."""
    if isinstance(e, SmokeFailure):
        return str(e)
    traceback.print_exc()
    return f"{type(e).__name__}: {e}"


# =============================================================================
# children: each owns the chip for its lifetime
# =============================================================================


def _start_jax():
    """Compile cache on, then the device — or fail: the phases are
    written for the chip and are not run on anything else."""
    from kubeinfer_tpu.utils.compile_cache import (
        enable_compile_cache, watch_compiles,
    )

    cache_dir = enable_compile_cache()
    watch_compiles()
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    check(device["platform"] == "tpu",
          f"JAX found no TPU (devices: {device}); the smoke runs on the "
          "chip or not at all")
    return jax, device, cache_dir


def _compile_readings() -> dict:
    """Seconds this child spent in JAX's backend compiles, and how many
    programs came out of the persistent cache instead."""
    from kubeinfer_tpu.utils.compile_cache import compile_watch

    totals = compile_watch().totals()
    return {"compile_s": round(totals["backend_s"], 1),
            "compile_cache_hits": totals["fetched"]}


def _peak_bytes(jax) -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


# --- solver ------------------------------------------------------------------

# (policy, jobs, nodes, gang fraction, request seed): the 10k x 1k gang
# cluster BASELINE and ROADMAP S1 use, bench.py's 50k soak, and the
# whole-node 1k x 1k instance the auction is scoped to.
SOLVER_CASES = (
    ("jax-greedy", 10_000, 1_000, 0.2, 0),
    ("jax-greedy", 50_000, 1_000, 0.1, 1),
    ("jax-auction", 1_000, 1_000, 0.0, 3),
)
ROUTE_BUCKET = (256, 128)  # requests x replicas, both kernel-aligned


def _accel_pair(J: int, N: int) -> tuple[str, str]:
    """What ``accel="auto"`` resolves to at this bucket, and its jnp
    twin. The resolution must be a kernel: ``jnp`` here means the
    backend was not seen as a TPU and the solver fell back in silence."""
    from kubeinfer_tpu.solver.core import _resolve_accel

    accel = _resolve_accel("auto", J, N)
    check(accel in ("mega", "pallas"),
          f"_resolve_accel chose {accel!r} at {J}x{N}, not a kernel")
    return accel, {"mega": "mega-jnp", "pallas": "jnp"}[accel]


def _solver_request(policy, J, N, gang, seed):
    import numpy as np

    import bench
    from kubeinfer_tpu.scheduler import SolveRequest

    if policy != "jax-auction":
        return bench.build_request(J, N, seed=seed, gang_fraction=gang)
    # whole-node requests, one job per node: the shape solve_auction is
    # scoped to (auction_suitable would hand anything else to greedy)
    rng = np.random.default_rng(seed)
    return SolveRequest(
        job_gpu=np.full(J, 64.0, np.float32),
        job_mem_gib=rng.integers(64, 512, J).astype(np.float32),
        job_priority=rng.integers(0, 8, J).astype(np.float32),
        job_model=rng.integers(0, 256, J).astype(np.int32),
        node_gpu_free=np.full(N, 64.0, np.float32),
        node_mem_free_gib=np.full(N, 512.0, np.float32),
        node_cached=(rng.random((N, 256)) < 0.02).astype(np.uint8),
    )


def _check_constraints(req, assignment) -> None:
    """Capacity on both resources and all-or-nothing gangs, in numpy,
    independent of the solver."""
    import numpy as np

    a = np.asarray(assignment)
    check(a.shape == (req.num_jobs,), f"assignment shape {a.shape}")
    check(bool(((a >= -1) & (a < req.num_nodes)).all()),
          "assignment names a node that does not exist")
    placed = a >= 0
    for demand, free, what in (
        (req.job_gpu, req.node_gpu_free, "gpu"),
        (req.job_mem_gib, req.node_mem_free_gib, "memory"),
    ):
        used = np.bincount(a[placed], weights=demand[placed],
                           minlength=req.num_nodes)
        check(bool((used <= free + 1e-3).all()),
              f"{what} capacity exceeded on {int((used > free + 1e-3).sum())}"
              " nodes")
    if req.job_gang is not None:
        gang = np.asarray(req.job_gang)
        ganged = gang >= 0
        size = np.bincount(gang[ganged])
        got = np.bincount(gang[ganged & placed], minlength=size.shape[0])
        check(bool(((got == 0) | (got == size)).all()),
              f"{int(((got != 0) & (got != size)).sum())} gangs placed in "
              "part")


def phase_solver(args) -> dict:
    jax, device, cache_dir = _start_jax()
    import numpy as np

    from kubeinfer_tpu.api.types import SchedulerPolicy
    from kubeinfer_tpu.scheduler import JaxBackend, get_backend
    from kubeinfer_tpu.solver.problem import bucket_size

    cases = []
    for policy, J, N, gang, seed in SOLVER_CASES:
        req = _solver_request(policy, J, N, gang, seed + args.seed)
        Jp, Np = bucket_size(J), bucket_size(N)
        accel, twin = _accel_pair(Jp, Np)
        backend = get_backend(policy)  # accel="auto": what users run
        t0 = time.perf_counter()
        res = backend.solve(req)
        cold_s = time.perf_counter() - t0
        warm = backend.solve(req)
        check(res.policy == policy,
              f"{policy} fell back to {res.policy}")
        check(np.array_equal(res.assignment, warm.assignment),
              f"{policy} {J}x{N}: two solves of one request differ")
        ref = JaxBackend(SchedulerPolicy(policy), accel=twin).solve(req)
        diff = int((res.assignment != ref.assignment).sum())
        check(diff == 0 and res.rounds == ref.rounds,
              f"{policy} {J}x{N}: {accel} differs from its twin {twin} on "
              f"the same device in {diff} of {J} assignments (rounds "
              f"{res.rounds} vs {ref.rounds})")
        _check_constraints(req, res.assignment)
        native = get_backend("native-greedy").solve(req)
        _check_constraints(req, native.assignment)
        # the serial C++ scorer is another algorithm, so counts may
        # differ a little; a kernel that places far fewer is broken
        check(res.placed >= 0.9 * native.placed,
              f"{policy} {J}x{N}: placed {res.placed}, serial scorer "
              f"placed {native.placed}")
        cases.append({
            "policy": policy, "jobs": J, "nodes": N,
            "bucket": [Jp, Np], "accel": accel, "twin": twin,
            "twin_bit_identical": True, "placed": int(res.placed),
            "native_placed": int(native.placed), "rounds": int(res.rounds),
            "smoke_first_solve_s": round(cold_s, 2),
            "smoke_solve_ms": round(warm.solve_ms, 3),
        })

    routes = _check_routes(np, args.seed)
    return {
        "device": device, "compile_cache_dir": cache_dir, "cases": cases,
        "routes": routes, "smoke_peak_bytes_in_use": _peak_bytes(jax),
        **_compile_readings(),
    }


def _check_routes(np, seed: int) -> dict:
    from kubeinfer_tpu.solver.routing import (
        _route_accel, pack_route_arrays, solve_routes,
    )

    B, R = ROUTE_BUCKET
    rng = np.random.default_rng(seed + 11)
    rp, Bp, Rp = pack_route_arrays(
        rng.integers(-1, 12, (B, R)).astype(np.int32),
        (rng.random(R) * 2).astype(np.float32),
        rng.random(R) < 0.1,
        rng.integers(1, 9, R).astype(np.float32),
        rng.random(R).astype(np.float32),
    )
    accel = _route_accel("auto", Bp, Rp)
    check(accel == "pallas",
          f"_route_accel chose {accel!r} at {Bp}x{Rp}, not the kernel")
    for mode in ("parity", "greedy", "auction"):
        got = solve_routes(rp, mode=mode, accel="auto")
        ref = solve_routes(rp, mode=mode, accel="jnp")
        check(_bits_differ(np, got.replica, ref.replica) == 0
              and _bits_differ(np, got.score, ref.score) == 0,
              f"solve_routes({mode}): route_pick_pallas differs from "
              "route_pick_jnp")
    return {"bucket": [Bp, Rp], "accel": accel,
            "modes_bit_identical": ["parity", "greedy", "auction"]}


# --- kernels -----------------------------------------------------------------

# Errors are |got - ref| / (|ref| + rms(ref)), the worst element.
#
# Attention, kernel and twin against dense float32: the operands are the
# same bf16 values on both sides and scores accumulate in f32
# everywhere, so what differs is two bf16 roundings the kernels make and
# the reference does not (probabilities before the PV matmul, the
# output), at most 2^-8 relative each, plus an ulp or two of exp.
# Interpret mode on the CPU reads 0.004-0.007; 2^-5 leaves 4x and is
# still far below what a mask off by one position costs (order 0.1).
ATTN_TOL = 2.0 ** -5
# int8 matmul: codes times bf16 activations are exact in f32 and the
# accumulation is f32 on both sides, so only the output's one bf16
# rounding differs: at most 2^-8 relative; 2^-7 leaves 2x on a hard
# bound. Against its jnp twin the kernel must agree bit for bit
# (weight_quant.py's contract).
MATMUL_TOL = 2.0 ** -7

KERNEL_T = 512  # prefill chunk: Engine's PREFILL_CHUNK, the server's 4 blocks
KERNEL_POOL_BLOCKS = 1 + 2 * N_SLOTS * (MAX_LEN // BLOCK)  # the server's pool
VERIFY_T = 5  # speculative verify window: --speculation-depth 4, plus one
# every quant_matmul call the int8 step programs issue: decode's 8 live
# rows, the 64/128/256/512-row admit buckets (64: a question behind a
# cached document) and the 512-row prefill chunk, each times gate/up,
# down, q/o and k/v at MODEL's extents
MATMUL_ROWS = (8, 64, 128, 256, 512)


def matmul_projections() -> dict[str, tuple[int, int]]:
    w = WIDTHS
    q_dim, kv_dim = w["n_q"] * w["D"], w["n_kv"] * w["D"]
    return {"gate_up": (w["H"], w["F"]), "down": (w["F"], w["H"]),
            "q_o": (w["H"], q_dim), "k_v": (w["H"], kv_dim)}


def _bits_differ(np, a, b) -> int:
    """Elements of two device results that are not the same bits (NaN
    payloads and signed zeros included): 0 means bit-identical."""
    a, b = np.asarray(a), np.asarray(b)
    if a.shape != b.shape or a.dtype != b.dtype:
        return max(a.size, b.size, 1)
    return int((a.view(np.uint8) != b.view(np.uint8)).reshape(
        a.size, -1).any(axis=1).sum()) if a.size else 0


def _close(np, got, ref, tol: float, what: str) -> float:
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    check(got.shape == ref.shape, f"{what}: shape {got.shape} vs {ref.shape}")
    check(bool(np.isfinite(got).all()), f"{what}: not finite")
    scale = max(float(np.sqrt(np.mean(ref * ref))), 1e-6)
    err = float(np.max(np.abs(got - ref) / (np.abs(ref) + scale)))
    check(err <= tol, f"{what}: error {err:.3g} above {tol:.3g}")
    return err


def phase_kernels(args) -> dict:
    jax, device, cache_dir = _start_jax()
    import jax.numpy as jnp
    import numpy as np

    from kubeinfer_tpu.inference import flash_attention as fa
    from kubeinfer_tpu.inference import weight_quant as wq
    from kubeinfer_tpu.inference.kv_blocks import pool_shape
    from kubeinfer_tpu.inference.model import attention as dense_attention

    w = WIDTHS
    nq, nkv, D = w["n_q"], w["n_kv"], w["D"]
    keys = iter(jax.random.split(jax.random.PRNGKey(args.seed), 64))
    results = {}

    def normal(shape, dtype=jnp.bfloat16):
        return jax.random.normal(next(keys), shape, jnp.float32).astype(dtype)

    def dense32(q, k, v, mask):
        with jax.default_matmul_precision("highest"):
            return jax.jit(dense_attention)(
                q.astype(jnp.float32), k.astype(jnp.float32),
                v.astype(jnp.float32), mask,
            )

    # chunked-prefill attention: one chunk in the middle of a longer row
    T, S = KERNEL_T, MAX_LEN
    q, k, v = (normal((1, T, nq, D)), normal((1, S, nkv, D)),
               normal((1, S, nkv, D)))
    q_off, row_len = 2 * T, 3 * T
    got = jax.jit(fa.flash_attention_ragged)(
        q, k, v, jnp.int32(q_off), jnp.asarray([row_len], jnp.int32))
    s_pos, t_pos = jnp.arange(S)[None, None, :], jnp.arange(T)[None, :, None]
    mask = (s_pos <= q_off + t_pos) & (s_pos < row_len)
    results["flash_attention_ragged"] = {
        "T": T, "S": S, "vs_dense_f32": _close(
            np, got, dense32(q, k, v, mask), ATTN_TOL,
            "flash_attention_ragged vs dense f32"),
    }

    # paged decode attention over the server's pool, ragged live lengths
    Bq, M, nb = N_SLOTS, MAX_LEN // BLOCK, KERNEL_POOL_BLOCKS
    rng = np.random.default_rng(args.seed + 5)
    tables = jnp.asarray(np.stack([
        rng.permutation(np.arange(1, nb))[:M] for _ in range(Bq)
    ]).astype(np.int32))
    live = [VERIFY_T, BLOCK - 1, BLOCK, BLOCK + 1, 7 * BLOCK + 3,
            MAX_LEN // 2, MAX_LEN - 1, MAX_LEN][:Bq]
    lengths = jnp.asarray(live, jnp.int32)
    # pools and tails in the stored (head-major) page layout
    pool, tail = (pool_shape(nb, BLOCK, nkv, D),
                  (Bq, *pool_shape(2, BLOCK, nkv, D)))
    k_pool, v_pool = normal(pool), normal(pool)
    kq = jax.random.randint(next(keys), pool, -127, 128, jnp.int8)
    vq = jax.random.randint(next(keys), pool, -127, 128, jnp.int8)
    ks = jax.random.uniform(next(keys), (nb, nkv), jnp.float32, 0.005, 0.03)
    vs = jax.random.uniform(next(keys), (nb, nkv), jnp.float32, 0.005, 0.03)
    k_tail, v_tail = normal(tail), normal(tail)
    for T in (1, VERIFY_T):
        q = normal((Bq, T, nq, D))
        # the routers' mask contract: query t sits at lengths - T + t
        mask = (jnp.arange(MAX_LEN)[None, None, :]
                <= (lengths[:, None, None] - T
                    + jnp.arange(T)[None, :, None]))
        got = jax.jit(fa.decode_attention_blocks)(
            q, k_pool, v_pool, tables, lengths)
        twin = jax.jit(fa.decode_attention_blocks_jnp)(
            q, k_pool, v_pool, tables, lengths)
        ref = dense32(q, fa.gather_block_kv(k_pool, tables),
                      fa.gather_block_kv(v_pool, tables), mask)
        name = f"decode_attention_blocks T={T}"
        results[name] = {
            "vs_twin": _close(np, got, twin, ATTN_TOL, f"{name} vs twin"),
            "twin_bit_identical": _bits_differ(np, got, twin) == 0,
            "vs_dense_f32": _close(np, got, ref, ATTN_TOL,
                                   f"{name} vs dense f32"),
        }
        got = jax.jit(fa.decode_attention_blocks_q8)(
            q, kq, vq, ks, vs, k_tail, v_tail, tables, lengths)
        twin = jax.jit(fa.decode_attention_blocks_q8_jnp)(
            q, kq, vq, ks, vs, k_tail, v_tail, tables, lengths)
        tb = jnp.maximum(lengths - T, 0) // BLOCK
        ref = dense32(
            q, fa.dequant_gather_block_kv(kq, ks, k_tail, tables, tb),
            fa.dequant_gather_block_kv(vq, vs, v_tail, tables, tb), mask)
        name = f"decode_attention_blocks_q8 T={T}"
        results[name] = {
            "vs_twin": _close(np, got, twin, ATTN_TOL, f"{name} vs twin"),
            "twin_bit_identical": _bits_differ(np, got, twin) == 0,
            "vs_dense_f32": _close(np, got, ref, ATTN_TOL,
                                   f"{name} vs dense f32"),
        }

    # fused dequant-matmul at every shape the step programs issue, each at
    # the tiles quant_matmul_tiles picks for it. The rows are the leading
    # rows of one draw, so a row's bits can be held equal across row
    # counts: the same prompt rides in an admit bucket, a chunk, a window
    for proj, (K, N) in matmul_projections().items():
        x_all = normal((max(MATMUL_ROWS), K))
        qw = jax.random.randint(next(keys), (K, N), -127, 128, jnp.int8)
        scale = jax.random.uniform(next(keys), (N,), jnp.float32,
                                   1e-3, 3e-3)
        first = None
        for rows in MATMUL_ROWS:
            x = x_all[:rows]
            got = wq.quant_matmul(x, qw, scale)
            twin = jax.jit(wq.quant_matmul_jnp)(x, qw, scale)
            name = f"quant_matmul {proj} {rows}x{K}x{N}"
            differ = _bits_differ(np, got, twin)
            check(differ == 0,
                  f"{name}: kernel is not bit-identical to quant_matmul_jnp "
                  f"({differ} of {rows * N} elements differ)")
            first = got if first is None else first
            moved = _bits_differ(np, got[:MATMUL_ROWS[0]], first)
            check(moved == 0,
                  f"{name}: {moved} elements of the first {MATMUL_ROWS[0]} "
                  f"rows differ from the {MATMUL_ROWS[0]}-row call's: a "
                  "row's sum depends on the rows beside it")
            with jax.default_matmul_precision("highest"):
                ref = (x.astype(jnp.float32) @ qw.astype(jnp.float32)) * scale
            results[name] = {
                "tiles": list(wq.quant_matmul_tiles(rows, K, N, 2)[:3]),
                "twin_bit_identical": True, "rows_bit_identical": True,
                "vs_dense_f32": _close(np, got, ref, MATMUL_TOL,
                                       f"{name} vs dense f32"),
            }

    lowered = _lower_engine_steps(jax, jnp)
    return {
        "device": device, "compile_cache_dir": cache_dir,
        "tolerance": {"attention": ATTN_TOL, "matmul": MATMUL_TOL},
        "kernels": results, "lowered_steps": lowered,
        "smoke_peak_bytes_in_use": _peak_bytes(jax), **_compile_readings(),
    }


def _lower_engine_steps(jax, jnp) -> dict:
    """Lower — for this device, from shapes alone — the three programs
    ContinuousEngine dispatches for MODEL with int8 weights, and name
    the Pallas kernels inside each. The ``*_auto`` routers pick their
    branch while tracing and fall to dense without a word, so a step
    that lost its kernel would still serve, only slower."""
    import functools
    import re

    from jax.sharding import SingleDeviceSharding

    from kubeinfer_tpu.inference import batching
    from kubeinfer_tpu.inference.config import PRESETS
    from kubeinfer_tpu.inference.model import init_params
    from kubeinfer_tpu.inference.stepper import decode_window, init_slot_state

    cfg = PRESETS[MODEL]
    here = SingleDeviceSharding(jax.devices()[0])

    def placed(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=here),
            tree,
        )

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=here)

    params = placed(jax.eval_shape(functools.partial(
        init_params, cfg, dtype=jnp.bfloat16, weight_dtype="int8",
    ), jax.random.PRNGKey(0)))
    param_bytes = sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    check(param_bytes == expected_param_bytes(),
          f"{MODEL} int8 is {param_bytes} bytes by eval_shape, the smoke "
          f"expects {expected_param_bytes()}: WIDTHS drifted from PRESETS")
    state = placed(jax.eval_shape(functools.partial(
        init_slot_state, cfg, N_SLOTS, MAX_LEN, jnp.bfloat16,
        KERNEL_POOL_BLOCKS, BLOCK,
    )))
    M = MAX_LEN // BLOCK
    i32, f32 = jnp.int32, jnp.float32
    row = (arg((M,), i32), arg((M,), jnp.bool_))
    programs = {
        "decode_window": (
            decode_window.lower(params, state, cfg, 1),
            {"decode_attention_blocks", "quant_matmul"}),
        "_prefill_chunk": (
            batching._prefill_chunk.lower(
                params, state, arg((1, 4 * BLOCK), i32), arg((), i32),
                cfg, *row),
            {"quant_matmul"}),
        "_admit_slot": (
            batching._admit_slot.lower(
                params, state, arg((1, BLOCK), i32), arg((), i32),
                arg((), i32), arg((), i32), cfg, arg((), i32), *row,
                arg((), f32), arg((), i32), arg((), f32), arg((), f32),
                arg((2,), jnp.uint32),
                arg((1, cfg.vocab_size), jnp.bool_)),
            {"quant_matmul"}),
    }
    out = {"param_bytes": int(param_bytes)}
    for name, (low, want) in programs.items():
        text = low.as_text()
        kernels = re.findall(r'kernel_name = "(\w+)"', text)
        check("tpu_custom_call" in text and want <= set(kernels),
              f"{name} lowered without {sorted(want - set(kernels))}: a "
              "router fell to its dense branch")
        out[name] = {k: kernels.count(k) for k in sorted(set(kernels))}
    return out


def phase_device(args) -> dict:
    _jax, device, _cache = _start_jax()
    return {"device": device}


PHASES = {"solver": phase_solver, "kernels": phase_kernels,
          "device": phase_device}


def child_main(args) -> int:
    try:
        doc = PHASES[args.phase](args)
    except Exception as e:  # the phase boundary: report, then fail
        emit({"phase": args.phase, "ok": False, "error": failure(e)})
        return 1
    emit({"phase": args.phase, "ok": True, **doc})
    return 0


# =============================================================================
# parent: starts children, talks HTTP, never imports jax
# =============================================================================


class Parent:
    def __init__(self, args) -> None:
        self.args = args
        self.t0 = time.monotonic()
        self.children: list[subprocess.Popen] = []
        env = dict(os.environ)
        env["PYTHONPATH"] = HERE + (
            os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
        self.env = env

    def left(self) -> float:
        left = BUDGET_S - (time.monotonic() - self.t0)
        check(left > 0, f"out of time: {BUDGET_S:.0f} s spent")
        return left

    def spawn(self, cmd: list[str], **kw) -> subprocess.Popen:
        # own session: stop_all can end the whole group, and a ctrl-c at
        # the parent's terminal is the parent's to pass on
        proc = subprocess.Popen(cmd, cwd=HERE, env=self.env,
                                start_new_session=True, **kw)
        self.children.append(proc)
        return proc

    def stop_all(self) -> None:
        for proc in self.children:
            if proc.poll() is None:
                try:
                    os.killpg(proc.pid, signal.SIGKILL)
                except ProcessLookupError:
                    pass
                proc.wait()

    def run_phase(self, name: str) -> dict:
        """One child, one chip, one JSON line; the child has exited by
        the time this returns."""
        proc = self.spawn(
            [sys.executable, os.path.abspath(__file__), "--phase", name,
             "--seed", str(self.args.seed)],
            stdout=subprocess.PIPE, text=True,
        )
        try:
            out, _ = proc.communicate(timeout=self.left())
        except subprocess.TimeoutExpired:
            raise SmokeFailure(f"phase {name} ran out of time") from None
        lines = [ln for ln in out.splitlines() if ln.strip()]
        for ln in lines:
            print(ln, flush=True)
        try:
            doc = json.loads(lines[-1])
        except (IndexError, ValueError):
            doc = {}
        check(proc.returncode == 0 and doc.get("ok") is True
              and doc.get("phase") == name,
              doc.get("error") or f"phase {name} exited "
              f"{proc.returncode} without a result")
        return doc


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _http(url: str, body: dict | None = None, timeout: float = 60.0):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, resp.read().decode()


def _samples(text: str, name: str):
    """(label text, value) of every sample of one series on a
    Prometheus text page."""
    for line in text.splitlines():
        head, _, value = line.rpartition(" ")
        series, _, labels = head.partition("{")
        if series == name:
            yield labels, float(value)


def _metric(text: str, name: str, **labels) -> float | None:
    """The first sample whose labels include ``labels``; None when the
    series is absent."""
    for have, value in _samples(text, name):
        if all(f'{k}="{v}"' in have for k, v in labels.items()):
            return value
    return None


def _metric_by_label(text: str, name: str, label: str) -> dict[str, float]:
    return {have.partition(f'{label}="')[2].partition('"')[0]: value
            for have, value in _samples(text, name)}


class Server:
    """``python -m kubeinfer_tpu.inference.server`` as a child, driven
    over HTTP exactly as a client would."""

    def __init__(self, parent: Parent, tp: int) -> None:
        self.parent, self.tp = parent, tp
        self.port = _free_port()
        self.url = f"http://127.0.0.1:{self.port}"
        t0 = time.monotonic()
        self.proc = parent.spawn([
            sys.executable, "-m", "kubeinfer_tpu.inference.server",
            "--model", MODEL, "--random-init", "--weight-dtype", "int8",
            "--batch-slots", str(N_SLOTS), "--max-model-len", str(MAX_LEN),
            "--tensor-parallel-size", str(tp),
            "--host", "127.0.0.1", "--port", str(self.port),
        ])
        while True:
            check(self.proc.poll() is None,
                  f"server (tp={tp}) exited {self.proc.returncode} "
                  "before it was ready")
            parent.left()
            try:
                if _http(self.url + "/health", timeout=5)[0] == 200:
                    break
            except (urllib.error.URLError, OSError):
                pass  # not listening yet: the weights are still being made
            time.sleep(1.0)
        self.setup_s = time.monotonic() - t0

    def complete(self, prompt: list[int], max_tokens: int) -> dict:
        t0 = time.monotonic()
        try:
            status, text = _http(
                self.url + "/v1/completions",
                {"prompt": prompt, "max_tokens": max_tokens},
                timeout=self.parent.left())
        except urllib.error.HTTPError as e:
            raise SmokeFailure(
                f"HTTP {e.code}: {e.read().decode()[:300]}") from None
        wall_s = time.monotonic() - t0
        check(status == 200, f"HTTP {status}")
        doc = json.loads(text)
        tokens = doc["choices"][0]["tokens"]
        check(len(tokens) == max_tokens
              and doc["usage"]["completion_tokens"] == max_tokens,
              f"asked for {max_tokens} tokens, got {len(tokens)}")
        check(all(isinstance(t, int) and 0 <= t < WIDTHS["V"]
                  for t in tokens), "a token id outside the vocabulary")
        check(doc["kubeinfer"]["route"] == "continuous",
              f"served by route {doc['kubeinfer']['route']!r}, not the "
              "continuous batcher")
        return {"tokens": tokens, "wall_s": wall_s,
                "ttft_ms": doc["kubeinfer"]["ttft_ms"],
                "tpot_ms": doc["kubeinfer"]["tpot_ms"]}

    def metrics(self) -> str:
        return _http(self.url + "/metrics", timeout=60)[1]

    def check_identity(self, text: str, n_requests: int) -> None:
        got = _metric(text, "kubeinfer_inference_requests_total",
                      route="continuous", outcome="ok")
        check(got == n_requests,
              f"/metrics counts {got} ok requests on the continuous "
              f"route, sent {n_requests}")
        pb = _metric(text, "kubeinfer_model_param_bytes")
        check(pb == expected_param_bytes(),
              f"kubeinfer_model_param_bytes is {pb}, {MODEL} at int8 is "
              f"{expected_param_bytes()}: another model is being served")
        check(_metric(text, "kubeinfer_engine_tp_degree") == self.tp,
              "kubeinfer_engine_tp_degree is not what was asked for")

    def stop(self) -> None:
        self.proc.send_signal(signal.SIGTERM)
        try:
            rc = self.proc.wait(timeout=min(60.0, self.parent.left()))
        except subprocess.TimeoutExpired:
            raise SmokeFailure("server ignored SIGTERM for 60 s") from None
        check(rc == 0, f"server exited {rc} on SIGTERM")


def _prompts(seed: int, n_short: int, n_long: int):
    rnd = random.Random(seed)

    def ids(n):
        return [rnd.randrange(WIDTHS["V"]) for _ in range(n)]

    return ([ids(SHORT_LEN) for _ in range(n_short)],
            [ids(LONG_LEN) for _ in range(n_long)])


def phase_server(parent: Parent) -> dict:
    (short_a, short_b), (long_a, long_b) = _prompts(parent.args.seed, 2, 2)
    srv = Server(parent, tp=1)
    # cold then warm, same prompt: the difference is what compiling the
    # admit and decode programs cost; greedy twice must agree
    cold = srv.complete(short_a, NEW_TOKENS)
    warm = srv.complete(short_a, NEW_TOKENS)
    check(cold["tokens"] == warm["tokens"],
          "the same greedy prompt gave different tokens the second time")
    # >= 1024 tokens: chunked prefill, then again from the radix cache
    long_cold = srv.complete(long_a, NEW_TOKENS)
    hits0 = _metric(srv.metrics(), "kubeinfer_prefix_cache_hits_total") or 0
    long_warm = srv.complete(long_a, NEW_TOKENS)
    check(long_cold["tokens"] == long_warm["tokens"],
          "the repeated long prompt gave different tokens from the cache")
    # two at once, new prompts, unequal lengths: a ragged decode batch
    pair: list = [None, None]

    def post(i, prompt):
        try:
            pair[i] = srv.complete(prompt, NEW_TOKENS)
        except Exception as e:  # handed to the main thread just below
            pair[i] = e

    threads = [threading.Thread(target=post, args=(i, p))
               for i, p in enumerate((short_b, long_b))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for r in pair:
        if isinstance(r, Exception):
            raise r
    text = srv.metrics()
    srv.check_identity(text, n_requests=6)
    hits = _metric(text, "kubeinfer_prefix_cache_hits_total") or 0
    check(hits > hits0, "the repeated long prompt did not hit the prefix "
          "cache")
    chunks = _metric(text, "kubeinfer_prefill_chunks_total") or 0
    check(chunks >= 2 * (LONG_LEN // (4 * BLOCK)),
          f"{chunks} prefill chunks: the long prompts were not chunked")
    peak = _metric_by_label(
        text, "kubeinfer_device_peak_bytes_in_use", "device")
    check(bool(peak), "/metrics carries no device memory")
    srv.stop()
    return {
        "model": MODEL, "widths": WIDTHS, "weight_dtype": "int8",
        "depth_cut": None, "requests": 6, "new_tokens_each": NEW_TOKENS,
        "route": "continuous", "greedy_repeat_identical": True,
        "prefix_cache_hits": hits - hits0,
        "prefill_chunks": chunks,
        "model_param_bytes": expected_param_bytes(),
        "kv_pool_bytes": _metric(text, "kubeinfer_kv_pool_bytes"),
        "engine_compiles": _metric(text, "kubeinfer_engine_compiles_total"),
        "smoke_setup_s": round(srv.setup_s, 1),
        "smoke_compile_s_short": round(cold["wall_s"] - warm["wall_s"], 1),
        "smoke_compile_s_long": round(
            long_cold["wall_s"] - long_warm["wall_s"], 1),
        "smoke_first_token_ms": {"short_warm": warm["ttft_ms"],
                                 "long_cached": long_warm["ttft_ms"]},
        "smoke_per_token_ms": {"short_warm": warm["tpot_ms"],
                               "long_cached": long_warm["tpot_ms"]},
        "smoke_request_wall_s": {
            "short_cold": round(cold["wall_s"], 2),
            "short_warm": round(warm["wall_s"], 2),
            "long_cold": round(long_cold["wall_s"], 2),
            "long_cached": round(long_warm["wall_s"], 2),
            "pair": [round(r["wall_s"], 2) for r in pair],
        },
        "smoke_peak_bytes_in_use": peak,
    }


# tp=4 against tp=1. The weights are random, so the logits are nearly
# flat: the top two of 152k candidates sit about a quarter of a logit
# apart, and tp=1 runs the Pallas kernels where tp=4 runs the dense
# branches, with other rounding. An argmax therefore flips now and then,
# and a stream that has flipped once shares nothing afterwards. What is
# robust is agreement at the FIRST step of each prompt and the share of
# tokens before the first flip. A tensor-parallel path that is wrong
# (bad sharding, bad collective) agrees on one token in 152k, so both
# bars sit far below what a right one reaches and far above zero. The
# first chip run (PR 22, 9 prompts) read 0.67 and 0.43: one first step
# in three flipped. At that rate 32 prompts fall under 0.5 less than
# once in a hundred runs, where 9 prompts did so one time in seven.
TP_PROMPTS = 31  # short ones, plus one long: each costs about a second
TP_NEW_TOKENS = 9  # 1 at admit + one K=8 window
TP_FIRST_STEP_SHARE = 0.5
TP_PREFIX_SHARE = 0.1


def phase_tp(parent: Parent, device: dict) -> dict:
    check(device["count"] >= 4,
          f"--chips 4 on a machine with {device['count']} device(s)")
    shorts, longs = _prompts(parent.args.seed, TP_PROMPTS, 1)
    prompts = shorts + longs
    runs = {}
    for tp in (1, 4):
        srv = Server(parent, tp=tp)
        first = srv.complete(prompts[0], TP_NEW_TOKENS)
        outs = [first] + [srv.complete(p, TP_NEW_TOKENS)
                          for p in prompts[1:]]
        again = srv.complete(prompts[0], TP_NEW_TOKENS)
        check(again["tokens"] == first["tokens"],
              f"tp={tp}: the same greedy prompt gave different tokens")
        text = srv.metrics()
        srv.check_identity(text, n_requests=len(prompts) + 1)
        check(_metric(text, "kubeinfer_mesh_devices") == tp,
              f"tp={tp}: kubeinfer_mesh_devices disagrees")
        in_use = _metric_by_label(
            text, "kubeinfer_device_bytes_in_use", "device")
        srv.stop()
        runs[tp] = {
            "tokens": [o["tokens"] for o in outs],
            "device_bytes_in_use": in_use,
            "smoke_setup_s": round(srv.setup_s, 1),
            "smoke_first_request_s": round(first["wall_s"], 1),
            "smoke_per_token_ms": again["tpot_ms"],
        }
    # the weights are spread: four devices hold memory, none of them the
    # whole model, and no device holds much more than another
    held = sorted(runs[4]["device_bytes_in_use"].values())
    check(len(held) == 4 and held[0] > 0,
          f"tp=4: device memory {runs[4]['device_bytes_in_use']}")
    check(held[-1] < 0.6 * expected_param_bytes()
          and held[-1] < 1.5 * held[0],
          f"tp=4: weights not spread evenly: {held}")
    first_step = prefix = total = 0
    for a, b in zip(runs[1]["tokens"], runs[4]["tokens"]):
        same = 0
        while same < len(a) and a[same] == b[same]:
            same += 1
        first_step += same > 0
        prefix += same
        total += len(a)
    first_share, prefix_share = first_step / len(prompts), prefix / total
    check(first_share >= TP_FIRST_STEP_SHARE
          and prefix_share >= TP_PREFIX_SHARE,
          f"tp=4 against tp=1: first token agrees on {first_share:.2f} of "
          f"prompts (need {TP_FIRST_STEP_SHARE}), {prefix_share:.2f} of "
          f"tokens precede the first flip (need {TP_PREFIX_SHARE})")
    for r in runs.values():
        del r["tokens"]
    return {
        "model": MODEL, "widths": WIDTHS, "weight_dtype": "int8",
        "prompts": len(prompts), "new_tokens_each": TP_NEW_TOKENS,
        "criterion": {"first_step_share_min": TP_FIRST_STEP_SHARE,
                      "matching_prefix_share_min": TP_PREFIX_SHARE},
        "first_step_share": round(first_share, 3),
        "matching_prefix_share": round(prefix_share, 3),
        "note": "under tp every attention and matmul kernel is pinned to "
                "its dense branch (gspmd=True); tp=1 runs the Pallas "
                "kernels",
        "tp1": runs[1], "tp4": runs[4],
    }


def parent_main(args) -> int:
    if not os.path.isdir(os.path.join(HERE, "kubeinfer_tpu")):
        print("chip_smoke.py: the repository is not next to this script",
              file=sys.stderr)
        return 2
    parent = Parent(args)
    phase = "start"
    try:
        if args.chips == 4:
            phase = "device"
            device = parent.run_phase("device")["device"]
            phase = "tp"
            emit({"phase": phase, "ok": True, **phase_tp(parent, device)})
        else:
            phase = "solver"
            device = parent.run_phase("solver")["device"]
            phase = "kernels"
            parent.run_phase("kernels")
            phase = "server"
            emit({"phase": phase, "ok": True, **phase_server(parent)})
    except Exception as e:  # the run's boundary: report, then fail
        emit({"ok": False, "failed_phase": phase, "error": failure(e)})
        return 1
    finally:
        parent.stop_all()
    emit({"ok": True, "device": device})
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the tensor-parallel comparison, nothing else")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed for every random input and prompt")
    ap.add_argument("--phase", choices=sorted(PHASES),
                    help=argparse.SUPPRESS)  # how the parent starts a child
    args = ap.parse_args(argv)
    if args.phase:
        return child_main(args)
    return parent_main(args)


if __name__ == "__main__":
    sys.exit(main())
