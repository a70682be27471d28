"""SchedulerBackend interface + implementations.

``SolveRequest`` is the host-side problem description (numpy SoA, unpadded):
the same shape the controller builds per tick, the sidecar service ships
over its wire protocol, and both solver tiers consume. ``SolveResult``
carries the assignment plus timing diagnostics the metrics layer exports
(per-solve latency is a first-class product requirement — BASELINE.json's
driver metric is p50 assign latency).

Backend selection: ``get_backend(policy)`` maps the ``schedulerPolicy`` spec
field to an implementation (SURVEY.md §7: "pluggable SchedulerBackend
selected by a new schedulerPolicy spec field").
"""

from __future__ import annotations

import contextlib
import functools
import logging
import os
import time
from dataclasses import dataclass, field

import numpy as np

from kubeinfer_tpu.api.types import SchedulerPolicy

log = logging.getLogger(__name__)


def _profile_ctx():
    """Per-solve jax.profiler capture, enabled by KUBEINFER_PROFILE_DIR
    (SURVEY.md §5: "add jax.profiler traces from day one"). Each solve
    writes a TensorBoard-loadable trace under <dir>/plugins/profile/...;
    off (the default) costs nothing.
    """
    profile_dir = os.environ.get("KUBEINFER_PROFILE_DIR", "")
    if not profile_dir:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.trace(profile_dir)


@functools.cache
def _packed_solver():
    """Jitted unpack+solve over the single packed buffer (one compile per
    (padded bucket pair, policy); cached like any jit)."""
    import jax

    from kubeinfer_tpu.solver import solve as jax_solve
    from kubeinfer_tpu.solver.problem import unpack_problem

    @functools.partial(
        jax.jit, static_argnames=("J", "N", "policy", "accel", "seeded")
    )
    def solve_packed(
        buf, J: int, N: int, policy: str, accel: str, seeded: bool
    ):
        return jax_solve(
            unpack_problem(buf, J, N), policy=policy, accel=accel,
            seeded=seeded,
        )

    return solve_packed


def request_has_incumbents(
    job_current_node: "np.ndarray | None",
) -> bool:
    """Whether a request carries incumbent placements — the single
    definition both the production backend and bench.py use to decide
    the solver's static ``seeded`` flag (core.solve_greedy), so the
    benchmark always measures the same compiled graph production runs.
    """
    return job_current_node is not None and bool(
        np.any(np.asarray(job_current_node) >= 0)
    )


@dataclass
class SolveRequest:
    """One tick's batched placement problem (host-side, unpadded).

    Conventions match solver.problem.encode_problem_arrays: one job row per
    replica; gang ids couple rows all-or-nothing; current_node (-1 = none)
    feeds move hysteresis; node_cached is a [N, M] model-slot bitmap.
    """

    job_gpu: np.ndarray
    job_mem_gib: np.ndarray
    node_gpu_free: np.ndarray
    node_mem_free_gib: np.ndarray
    job_priority: np.ndarray | None = None
    job_gang: np.ndarray | None = None
    job_model: np.ndarray | None = None
    job_current_node: np.ndarray | None = None
    node_gpu_capacity: np.ndarray | None = None
    node_mem_capacity_gib: np.ndarray | None = None
    node_topology: np.ndarray | None = None
    node_cached: np.ndarray | None = None

    @property
    def num_jobs(self) -> int:
        return int(self.job_gpu.shape[0])

    @property
    def num_nodes(self) -> int:
        return int(self.node_gpu_free.shape[0])


@dataclass
class SolveResult:
    """Assignment (node index per job, -1 unplaced) + diagnostics."""

    assignment: np.ndarray  # i32[J]
    placed: int
    solve_ms: float
    policy: str
    rounds: int = 0
    extras: dict[str, float] = field(default_factory=dict)


def _descending_stable_perm(pr: np.ndarray) -> np.ndarray:
    """Stable descending-priority permutation.

    Priorities are almost always a handful of small integer levels;
    mapping them to uint8 keys lets numpy's stable integer argsort take
    its radix path (~15x cheaper than the f32 mergesort at the 10k-job
    scale, and this sort sits inside the headline pack+solve latency).
    Arbitrary floats (or a >256-level integer range) fall back to the
    f32 argsort. Output is identical to ``np.argsort(-pr,
    kind="stable")`` in all cases.
    """
    if not np.isfinite(pr).all():
        # NaN/inf priorities: the int cast below would emit a numpy
        # RuntimeWarning per solve; mergesort handles them directly
        return np.argsort(-pr, kind="stable")
    pi = pr.astype(np.int64)
    if (pi == pr).all():
        lo, hi = int(pi.min()), int(pi.max())
        if 1 < hi - lo + 1 <= 256:
            # numpy's stable argsort on uint8 keys is a radix sort
            # (~0.02ms at 10k vs ~0.35ms for f32 mergesort)
            return np.argsort((hi - pi).astype(np.uint8), kind="stable")
    return np.argsort(-pr, kind="stable")


class SchedulerBackend:
    """Places a batch of replicas onto nodes."""

    name = "abstract"

    def solve(self, req: SolveRequest) -> SolveResult:
        raise NotImplementedError

    def warmup(self) -> None:
        """Pre-pay one-time costs (jit compiles, library builds) so the
        first production tick stays inside the latency budget."""


class NativeGreedyBackend(SchedulerBackend):
    """Serial first-fit-decreasing via the C++ native tier.

    The comparison baseline for the >=100x claim and the no-accelerator
    fallback. Import is deferred so environments without a compiler can
    still use the JAX backends.
    """

    name = SchedulerPolicy.NATIVE_GREEDY.value

    def warmup(self) -> None:
        from kubeinfer_tpu.native import load_native

        load_native()

    def solve(self, req: SolveRequest) -> SolveResult:
        from kubeinfer_tpu.native import solve_greedy_native

        t0 = time.perf_counter()
        assignment, placed = solve_greedy_native(
            job_gpu=req.job_gpu,
            job_mem_gib=req.job_mem_gib,
            job_priority=req.job_priority,
            job_gang=req.job_gang,
            job_model=req.job_model,
            job_current_node=req.job_current_node,
            node_gpu_free=req.node_gpu_free,
            node_mem_free_gib=req.node_mem_free_gib,
            node_gpu_capacity=req.node_gpu_capacity,
            node_mem_capacity_gib=req.node_mem_capacity_gib,
            node_topology=req.node_topology,
            node_cached=req.node_cached,
        )
        ms = (time.perf_counter() - t0) * 1e3
        # encode_ms is 0 by construction, not by omission: the serial
        # tier has no device, so problem packing is inside solve_ms and
        # there is no separate host->device encode step to report.
        return SolveResult(
            assignment, placed, ms, self.name, extras={"encode_ms": 0.0}
        )


def auction_suitable(req: SolveRequest) -> bool:
    """Is this a one-replica-per-node instance the auction solver is
    built for (core.solve_auction's documented scope)?

    Two disqualifiers, each of which silently under-places under auction:
    - more jobs than nodes: auction places at most one job per node;
    - node-sharing demands: a job asking for at most half a node's chips
      could legally share the node — auction would still dedicate the
      whole node to it.
    """
    if req.num_jobs > req.num_nodes:
        return False
    caps = (
        req.node_gpu_capacity
        if req.node_gpu_capacity is not None
        else req.node_gpu_free
    )
    max_cap = float(np.max(caps)) if caps.size else 0.0
    min_demand = float(np.min(req.job_gpu)) if req.job_gpu.size else 0.0
    return min_demand * 2.0 > max_cap


class JaxBackend(SchedulerBackend):
    """Batched solve on the live JAX backend (TPU when present).

    One instance per policy (greedy/auction). Encoding pads both axes to
    buckets so the jit cache stays small; ``warmup`` pre-compiles the
    bucket a deployment expects to hit.

    ``jax-auction`` is guarded: the auction algorithm only handles
    one-replica-per-node (whole-node-request) instances and ignores
    priority (core.solve_auction docstring). A user-selected auction
    policy on an unsuitable problem auto-falls back to ``jax-greedy``
    with a warning and a metric rather than silently under-placing.
    """

    def __init__(self, policy: SchedulerPolicy, accel: str = "auto"):
        if policy not in (SchedulerPolicy.JAX_GREEDY, SchedulerPolicy.JAX_AUCTION):
            raise ValueError(f"not a JAX policy: {policy}")
        self._policy = policy
        self.name = policy.value
        # round-op implementation (core._resolve_accel's vocabulary).
        # Production takes "auto"; parity checks build a second backend
        # pinned to a kernel's jnp twin and compare whole assignments.
        self._accel = accel

    def warmup(
        self, num_jobs: int = 1024, num_nodes: int = 128
    ) -> None:
        if self._policy is SchedulerPolicy.JAX_AUCTION:
            # The warmup problem must be one auction actually accepts
            # (whole-node requests, jobs <= nodes), or the fallback guard
            # fires, the GREEDY kernel compiles instead, and the first
            # production auction solve pays the jit compile in-tick.
            num_jobs = min(num_jobs, num_nodes)
            req = SolveRequest(
                job_gpu=np.full(num_jobs, 8.0, np.float32),
                job_mem_gib=np.full(num_jobs, 64.0, np.float32),
                node_gpu_free=np.full(num_nodes, 8.0, np.float32),
                node_mem_free_gib=np.full(num_nodes, 64.0, np.float32),
            )
        else:
            req = SolveRequest(
                job_gpu=np.ones(num_jobs, np.float32),
                job_mem_gib=np.ones(num_jobs, np.float32),
                node_gpu_free=np.full(num_nodes, 8.0, np.float32),
                node_mem_free_gib=np.full(num_nodes, 64.0, np.float32),
            )
        self.solve(req)

    def solve(self, req: SolveRequest) -> SolveResult:
        import jax

        from kubeinfer_tpu.solver.problem import pack_problem_arrays

        policy = self._policy.value
        fellback = False
        if (
            self._policy is SchedulerPolicy.JAX_AUCTION
            and not auction_suitable(req)
        ):
            from kubeinfer_tpu import metrics

            metrics.auction_fallback_total.inc()
            log.warning(
                "jax-auction requested for a non-whole-node problem "
                "(%d jobs, %d nodes): falling back to jax-greedy to avoid "
                "under-placement",
                req.num_jobs, req.num_nodes,
            )
            policy = SchedulerPolicy.JAX_GREEDY.value
            fellback = True

        t0 = time.perf_counter()
        # Priority-sort the job axis (stable, descending) before packing.
        # The solver's per-node fence means only one fence class can bid
        # in any round; with classes contiguous along J, the Pallas round
        # kernels' per-J-tile early-out skips the inactive ~3/4 of every
        # round's compute and S-field HBM traffic (pallas_kernels.py
        # module docstring). Pure host-side reordering — the solve itself
        # is order-independent up to tie-breaks — undone on the way out.
        perm = None
        if req.job_priority is not None and req.num_jobs > 1:
            pr = np.asarray(req.job_priority)
            if np.any(pr[1:] > pr[:-1]):  # not already descending
                perm = _descending_stable_perm(pr)

        # Single-buffer packing: the whole problem ships in ONE transfer
        # and unpacks with free slices/bitcasts inside the jitted solve —
        # one host-to-device copy instead of fourteen, each of which is
        # a dispatch of its own (see problem.py packing layout). The
        # priority permutation is applied inside the padding copies
        # (job_perm) rather than as a separate pass per field.
        buf, _, _, J, N = pack_problem_arrays(
            job_gpu=req.job_gpu,
            job_mem_gib=req.job_mem_gib,
            job_priority=req.job_priority,
            job_gang=req.job_gang,
            job_model=req.job_model,
            job_current_node=req.job_current_node,
            node_gpu_free=req.node_gpu_free,
            node_mem_free_gib=req.node_mem_free_gib,
            node_gpu_capacity=req.node_gpu_capacity,
            node_mem_capacity_gib=req.node_mem_capacity_gib,
            node_topology=req.node_topology,
            node_cached=req.node_cached,
            job_perm=perm,
        )
        t_encode = time.perf_counter()
        # Incumbent seeding/preemption-repair machinery is compiled in
        # only when the request actually carries placements — fresh
        # solves skip ~0.2ms of inert control flow (core.solve_greedy's
        # `seeded` note).
        seeded = request_has_incumbents(req.job_current_node)
        with _profile_ctx():
            out = _packed_solver()(
                buf, J=J, N=N, policy=policy, accel=self._accel,
                seeded=seeded,
            )
            # ONE host readback for everything the caller needs: each extra
            # sync (a separate np.asarray/int() call) is a full host<->device
            # round trip and stalls the dispatch pipeline.
            # Inside the profile context: dispatch is async, so the trace
            # must stay open until this sync or device activity is lost.
            # lint: allow[host-sync] the ONE deliberate readback described above
            node_host, rounds_host = jax.device_get((out.node, out.rounds))
        if perm is None:
            assignment = np.asarray(node_host[: req.num_jobs], np.int32)
        else:
            assignment = np.empty(req.num_jobs, np.int32)
            assignment[perm] = np.asarray(
                node_host[: req.num_jobs], np.int32
            )
        # Padded job rows can't place (valid=False) and padded node columns
        # can't be chosen (valid=False), so clipping to the true axes is
        # lossless; count placed on the clipped view.
        placed = int((assignment >= 0).sum())
        t1 = time.perf_counter()
        extras = {"encode_ms": (t_encode - t0) * 1e3}
        if fellback:
            extras["auction_fallback"] = 1.0
        return SolveResult(
            assignment,
            placed,
            (t1 - t0) * 1e3,
            policy,  # the policy that actually solved (fallback-aware)
            rounds=int(rounds_host),
            extras=extras,
        )


def solve_service_handler(body: dict) -> dict:
    """JSON solve RPC (the /solve endpoint's business logic).

    Request: ``{"policy": "...", "jobs": {gpu, memGib, priority?, gang?,
    model?, currentNode?}, "nodes": {gpuFree, memFreeGib, gpuCapacity?,
    memCapacityGib?, topology?}}`` — arrays as JSON lists, one entry per
    replica/node. Response: assignment + diagnostics. External
    controllers get placements without embedding JAX; the manager's own
    reconciler keeps the in-process fast path.
    """
    if not isinstance(body, dict):
        raise ValueError("body must be a JSON object")
    jobs = body.get("jobs") or {}
    nodes = body.get("nodes") or {}
    if not isinstance(jobs, dict) or not isinstance(nodes, dict):
        raise ValueError("jobs and nodes must be JSON objects")
    if "gpu" not in jobs or "gpuFree" not in nodes:
        raise ValueError("body needs jobs.gpu and nodes.gpuFree arrays")

    def arr(v, dtype, default=None):
        if v is None:
            return default
        return np.asarray(v, dtype)

    J, N = len(jobs["gpu"]), len(nodes["gpuFree"])
    req = SolveRequest(
        job_gpu=np.asarray(jobs["gpu"], np.float32),
        job_mem_gib=arr(
            jobs.get("memGib"), np.float32, np.zeros(J, np.float32)
        ),
        job_priority=arr(jobs.get("priority"), np.float32),
        job_gang=arr(jobs.get("gang"), np.int32),
        job_model=arr(jobs.get("model"), np.int32),
        job_current_node=arr(jobs.get("currentNode"), np.int32),
        node_gpu_free=np.asarray(nodes["gpuFree"], np.float32),
        node_mem_free_gib=arr(
            nodes.get("memFreeGib"), np.float32, np.zeros(N, np.float32)
        ),
        node_gpu_capacity=arr(nodes.get("gpuCapacity"), np.float32),
        node_mem_capacity_gib=arr(nodes.get("memCapacityGib"), np.float32),
        node_topology=arr(nodes.get("topology"), np.int32),
    )
    res = get_backend(body.get("policy", "jax-greedy")).solve(req)
    return {
        "assignment": res.assignment.tolist(),
        "placed": int(res.placed),
        "solveMs": round(res.solve_ms, 3),
        "policy": res.policy,
        "rounds": res.rounds,
    }


_BACKENDS: dict[str, SchedulerBackend] = {}


def get_backend(policy: str | SchedulerPolicy) -> SchedulerBackend:
    """Backend for a schedulerPolicy value; instances are cached (jit
    caches and native lib handles live on them)."""
    policy = SchedulerPolicy(policy)
    backend = _BACKENDS.get(policy.value)
    if backend is None:
        if policy is SchedulerPolicy.NATIVE_GREEDY:
            backend = NativeGreedyBackend()
        else:
            backend = JaxBackend(policy)
        _BACKENDS[policy.value] = backend
    return backend
