"""Batched assignment solvers under ``jax.jit``.

Two device-side algorithms, selected per job via ``schedulerPolicy``:

``solve_greedy`` — parallel greedy with per-node conflict resolution.
  Each round, every unplaced replica bids on its min-cost feasible node via
  a single masked min-reduce over a resident node-major [N, J] cost field
  (bids are packed (cost | node) i32s, so the reduce yields node and cost
  together); nodes accept all bidders when they jointly fit, else their
  single best bidder by a fused (priority, demand, job) key — sort-free
  and scatter-free (see ``_dense_accept``); conflict losers retry an
  alternate node in a same-round second-chance pass; capacities update and
  the loop repeats under ``lax.while_loop`` until a fixpoint or round
  budget. At a fixpoint every still-unplaced job provably had no feasible
  node left. On TPU the round ops run as Pallas kernels (pallas_kernels.py)
  that stream S through VMEM once per round; the jnp twins in this module
  are the CPU/sharded path and the parity reference.
  Priority inversion is prevented by a pipelined per-node fence: job j may
  bid node n only if no unplaced higher-priority job currently finds n
  feasible (see the ``minrank`` reduction). Per-node accept order alone
  can't stop a low-priority job from committing capacity on a node the
  high-priority class only discovers a round later; the fence closes that
  without serializing priority classes into gated phases (all levels make
  progress in the same round on disjoint nodes).
  Round count at the 10k x 1k benchmark shape is ~12 and is set by this
  fence pipeline (~3 settlement rounds per fence class), NOT by per-node
  conflict churn: the joint-fit accept already admits all bidders on
  typically contended nodes, so richer conflict resolution (measured:
  winner-first fair-share multi-accept) does not reduce rounds. Shaving
  rounds further means relaxing fence granularity, a correctness trade.

``solve_auction`` — Bertsekas-style auction for one-replica-per-node
  instances (whole-node requests), giving Hungarian-quality assignments
  with bounded suboptimality J*eps. Dense bid matrix per iteration; pick it
  when quality beats cost (BASELINE.json config 3's "Hungarian" tier).

Design notes (SURVEY.md §7 hard parts 1-4):
- Everything is static-shape; no data-dependent Python control flow.
- Priority + preemption fall out of full re-solves: incumbents re-bid with a
  hysteresis (move-penalty) cost term, so placements are stable unless a
  higher-priority bidder genuinely needs the capacity.
- Gang all-or-nothing is a post-solve repair: incompletely-placed gangs are
  unwound and their capacity returned (broadcast-compare reductions — see
  ``_gang_repair``), then a fenced fill pass re-offers the freed capacity.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax

from kubeinfer_tpu.solver.problem import Problem

INFEASIBLE = jnp.float32(1e9)
_EPS = 1e-4  # capacity comparison slack for f32 fractional demands
# Floor on the tie-spreading scale. Even at weights.noise=0, perfectly tied
# jobs must not all bid one node per round (that caps placement at
# max_rounds nodes and silently under-schedules); a 1e-3 perturbation is far
# below any meaningful cost gap but keeps bids spread.
_MIN_TIE_NOISE = 1e-3
# Finite "may not bid" sentinel for fence ranks (placed/invalid jobs);
# finite so rank comparisons stay well-defined in i32/f32 arithmetic.
# Mirrored in pallas_kernels.RANK_INF.
RANK_INF = jnp.float32(1e9)

# Auction tie/war handling (see the commentary in solve_auction): values
# within _TIE_TOL of a job's best count as tied for hash tie-breaking;
# _STALE_ITERS bounds how long the loop may run without placing a new
# job before delegating the stragglers to the completeness fill. 16 is a
# measured choice (r5, v5e, bench 1kx1k: 64 -> 131 iterations / 16 -> 37,
# ~23.7us each in the fused kernel, auction-placed 995 -> 991 with the
# fill completing to 1000 either way): iterations past a 16-stale window
# are price-war plateau involving <1% of jobs, and the war's end state is
# the fill's output by construction (see the stagnation-exit notes in
# solve_auction), so the extra patience bought ~2.2ms of device time and
# 4 placements whose J*eps bound the fill forfeits anyway.
_TIE_TOL = 1e-5
_STALE_ITERS = 16


@dataclass(frozen=True)
class ScoreWeights:
    """Cost-matrix weights. Lower cost = better placement.

    ``fit_gpu``/``fit_mem`` implement best-fit pressure: leftover capacity
    (normalized by node capacity, so each term is bounded in [0, 1]) is
    cost — tight fits win and fragmentation stays low, but no node is ever
    more than ~1.5 cost away from another on fit alone, which keeps the
    tie-spreading noise effective (see ``noise``).
    ``cache`` discounts nodes that already hold the replica's model (the
    whole point of the reference's shared-cache plane). ``move`` is the
    hysteresis penalty keeping re-solves from thrashing incumbents.
    ``topology`` penalizes leaving the replica's preferred topology group.
    """

    fit_gpu: float = 1.0
    fit_mem: float = 0.5
    cache: float = 5.0
    move: float = 8.0
    topology: float = 2.0
    # Tie-spreading temperature: deterministic Gumbel perturbation added to
    # the greedy cost matrix. Identical jobs see identical costs, so without
    # it the whole fleet bids the same argmin node every round and per-round
    # acceptance collapses to one node's capacity. Noise ~0.3 spreads bids
    # across near-tied nodes while leaving real cost gaps (cache hit = 5.0,
    # move = 8.0) intact: P(flip) < 1e-7. Floored at _MIN_TIE_NOISE (1e-3)
    # even when set to 0: fully deterministic cost-exact argmin is not
    # offered, because it caps placement at max_rounds nodes for tied
    # fleets; fit gaps below ~2e-2 may resolve either way under the floor.
    noise: float = 0.3


jax.tree_util.register_dataclass(
    ScoreWeights,
    data_fields=[],
    meta_fields=["fit_gpu", "fit_mem", "cache", "move", "topology", "noise"],
)


@dataclass
class Assignment:
    """Solver output: per-job node index (-1 = unplaced) + diagnostics."""

    node: jax.Array  # i32[J]
    gpu_free: jax.Array  # f32[N] capacity remaining after placement
    mem_free: jax.Array  # f32[N]
    rounds: jax.Array  # i32 rounds/iterations used
    placed: jax.Array  # i32 number of placed (valid) jobs


jax.tree_util.register_dataclass(
    Assignment,
    data_fields=["node", "gpu_free", "mem_free", "rounds", "placed"],
    meta_fields=[],
)


def _static_cost_t(p: Problem, w: ScoreWeights) -> jax.Array:
    """[N, J] cost terms that don't depend on remaining capacity.

    Node-major: nodes on the sublane axis, jobs on the lane axis — the
    orientation the round loop (and its Pallas tiles) consumes.
    """
    jobs, nodes = p.jobs, p.nodes
    # cache affinity: cached[n, model_id[j]] -> [N, J]. Expressed as a
    # one-hot matmul on the MXU rather than jnp.take — a [N, J] gather
    # from the bitmap costs ~0.15ms at 1024x12288 (TPU gathers
    # serialize) vs ~0.06ms for the [N, M] x [M, J] contraction. Exact:
    # model_id selects one slot, so each product-sum is 0 or 1 in bf16.
    n_models = nodes.cached.shape[1]
    onehot = (
        jobs.model_id[:, None]
        == jnp.arange(n_models, dtype=jnp.int32)[None, :]
    )
    hit = (
        jax.lax.dot_general(
            nodes.cached.astype(jnp.bfloat16),
            onehot.astype(jnp.bfloat16),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        > 0.5
    )  # [N, J] bool
    cost = w.cache * (1.0 - hit.astype(jnp.float32))

    n_idx = jnp.arange(nodes.valid.shape[0], dtype=jnp.int32)
    has_home = jobs.current_node >= 0
    moved = has_home[None, :] & (jobs.current_node[None, :] != n_idx[:, None])
    cost = cost + w.move * moved.astype(jnp.float32)

    # preferred topology group = incumbent node's group (when placed)
    home = jnp.clip(jobs.current_node, 0, nodes.valid.shape[0] - 1)
    pref = jnp.where(has_home, nodes.topology[home], -1)
    topo_miss = (pref[None, :] >= 0) & (pref[None, :] != nodes.topology[:, None])
    cost = cost + w.topology * topo_miss.astype(jnp.float32)
    return cost


def _fit_cost(
    gpu_free: jax.Array,  # f32[N] free capacity the fit is scored against
    mem_free: jax.Array,
    p: Problem,
    w: ScoreWeights,
    inv_gpu_cap: jax.Array,  # f32[N] 1/capacity normalizers
    inv_mem_cap: jax.Array,
) -> jax.Array:
    """[J, N] best-fit pressure: normalized leftover capacity as cost."""
    jobs = p.jobs
    cost = w.fit_gpu * (
        (gpu_free[None, :] - jobs.gpu_demand[:, None]) * inv_gpu_cap[None, :]
    )
    return cost + w.fit_mem * (
        (mem_free[None, :] - jobs.mem_demand[:, None]) * inv_mem_cap[None, :]
    )


def _fence_minrank(
    gpu_free: jax.Array,  # [N]
    mem_free: jax.Array,  # [N]
    gpu_demand: jax.Array,  # [J]
    mem_demand: jax.Array,  # [J]
    rankf_eff: jax.Array,  # [J]
) -> jax.Array:
    """[N] per-node fence minimum: the best (lowest) priority rank among
    unplaced jobs that find the node feasible. Vector inputs only, so XLA
    fuses the [N, J] broadcast into the reduction without materializing
    it; shared by the jnp and Pallas bid paths (the Pallas kernel tiles J
    and so cannot compute a full-J reduction per node itself)."""
    feas = (gpu_demand[None, :] <= gpu_free[:, None] + _EPS) & (
        mem_demand[None, :] <= mem_free[:, None] + _EPS
    )
    return jnp.min(jnp.where(feas, rankf_eff[None, :], RANK_INF), axis=1)


def _round_bids_jnp(
    S: jax.Array,  # [N, J] resident cost field
    u: jax.Array,  # [N] live best-fit pressure
    gpu_free: jax.Array,  # [N] (invalid nodes pre-folded to -1)
    mem_free: jax.Array,  # [N]
    gpu_demand: jax.Array,  # [J]
    mem_demand: jax.Array,  # [J]
    rankf_eff: jax.Array,  # [J] fence rank; RANK_INF = may not bid
    minrank: jax.Array,  # [N] fence minimum (see _fence_minrank)
    current_node: jax.Array,  # i32[J] incumbent node, -1 = none
    num_nodes: int,
    q_lo: float,
    q_scale: float,
    q_max: float,
    node_idx_bits: int,
) -> tuple[jax.Array, jax.Array]:
    """One pass over S -> (primary, alternate) packed i32 bids per job.

    Bids are packed non-negative i32s — (cost << node_idx_bits) | node
    — so ONE masked min-reduce yields both the argmin node and its cost:
    no argmin/min dual pass, no take_along_axis re-gather. Quantization
    bounds are STATIC (derived from the weights, with the gumbel noise
    clipped at generation): granularity at N=1024 is (hi-lo)/2^21 ~ 1e-5
    (cost_bits = 31 - node_idx_bits), far below the 1e-3 noise floor, so
    quantization never flips a meaningful comparison. The alternate bid is the best node in the other
    half of the node axis — a decent second choice for the second-chance
    pass without a second S read or a top-2 sort. The per-node priority
    fence (see solve_greedy) is fused into the same pass. The Pallas twin
    is ``pallas_kernels.bid_reduce_pallas``.
    """
    big = jnp.int32(0x7FFFFFFF)
    feas = (gpu_demand[None, :] <= gpu_free[:, None] + _EPS) & (
        mem_demand[None, :] <= mem_free[:, None] + _EPS
    )
    n_iota_col = jnp.arange(num_nodes, dtype=jnp.int32)[:, None]
    # Home-bid fence exemption: an incumbent may always bid its OWN node
    # (placement stability under churn); priority protection there comes
    # from rank-ordered acceptance on the contested node itself, which a
    # same-node higher-priority bidder still wins.
    is_home = current_node[None, :] == n_iota_col
    allowed = (
        feas
        & (
            (rankf_eff[None, :] <= minrank[:, None]) | is_home
        )
        & (rankf_eff[None, :] < RANK_INF * 0.5)
    )
    q = jnp.clip((S + u[:, None] - q_lo) * q_scale, 0.0, q_max)
    n_iota = jnp.arange(num_nodes, dtype=jnp.int32)
    packed = jnp.where(
        allowed,
        (q.astype(jnp.int32) << node_idx_bits) | n_iota[:, None],
        big,
    )
    # Group mins: 16-node groups when 128-aligned (bit-identical to the
    # Pallas kernel's per-16-node-group output, so accel paths are
    # parity-testable), else halves, else an exact masked second pass.
    if num_nodes % 128 == 0:
        groups = num_nodes // 16
    elif num_nodes % 2 == 0:
        groups = 2
    else:
        groups = 1
    if groups > 1:
        per_group = jnp.min(
            packed.reshape(groups, num_nodes // groups, -1), axis=1
        )  # [groups, J]
        prim = jnp.min(per_group, axis=0)
        prim_group = jnp.argmin(per_group, axis=0)
        g_iota = jnp.arange(groups, dtype=jnp.int32)
        alt = jnp.min(
            jnp.where(
                g_iota[:, None] == prim_group[None, :], big, per_group
            ),
            axis=0,
        )
    else:  # odd N only via exotic node_multiple paddings
        prim = jnp.min(packed, axis=0)
        alt = jnp.min(
            jnp.where(packed == prim[None, :], big, packed), axis=0
        )
    return prim, alt


def _accept_reduce_jnp(
    choice: jax.Array,  # i32[J], node index or N (= no bid sentinel)
    accept_key: jax.Array,  # i32[J]
    gpu_demand: jax.Array,
    mem_demand: jax.Array,
    num_nodes: int,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Per-node (gpu total, mem total, winner key, winner gpu, winner mem)
    over bidders.

    Column reductions over an on-the-fly ``choice[j] == n`` broadcast whose
    inputs are [J]/[N] VECTORS. This is deliberately NOT jax.ops.segment_*
    (XLA lowers those to scatters, which TPUs serialize — measured
    ~2.1ms/round at 12288x1024, the whole budget) and NOT a sort
    (log^2-depth bitonic stages, ~0.8ms/round). Winner demands come from
    unpacking the job index embedded in the reduced key — one [N]-from-[J]
    gather, acceptable on the CPU/sharded paths this serves; the Pallas
    twin (``pallas_kernels.accept_phase_pallas``'s verdict kernel) tracks
    them inside the reduction instead (the gather cost ~15us/accept on
    TPU).
    """
    J = choice.shape[0]
    idx_bits = max((J - 1).bit_length(), 1)
    idx_mask = jnp.int32((1 << idx_bits) - 1)
    n_iota = jnp.arange(num_nodes, dtype=jnp.int32)
    mine = choice[None, :] == n_iota[:, None]  # [N, J]; sentinel matches none
    tot_gpu = jnp.sum(jnp.where(mine, gpu_demand[None, :], 0.0), axis=1)
    tot_mem = jnp.sum(jnp.where(mine, mem_demand[None, :], 0.0), axis=1)
    big = jnp.int32(0x7FFFFFFF)
    win_key = jnp.min(jnp.where(mine, accept_key[None, :], big), axis=1)
    has_win = win_key != big
    win_j = jnp.where(has_win, win_key & idx_mask, J - 1)
    win_gpu = jnp.where(has_win, gpu_demand[win_j], 0.0)
    win_mem = jnp.where(has_win, mem_demand[win_j], 0.0)
    return tot_gpu, tot_mem, win_key, win_gpu, win_mem


def _dense_accept(
    choice: jax.Array,  # i32[J], node index or N (= no bid sentinel)
    accept_key: jax.Array,  # i32[J] fused (rank | demand | job index) key
    gpu_demand: jax.Array,
    mem_demand: jax.Array,
    gpu_free: jax.Array,  # f32[N]
    mem_free: jax.Array,
    num_nodes: int,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Scatter- and sort-free per-node conflict resolution.

    Returns ``(accept bool[J], used_gpu f32[N], used_mem f32[N])``.

    A node whose bidders' total demand fits its remaining capacity accepts
    ALL of them — the common case once tie-noise has spread bids. A
    contested node accepts only its single best bidder this pass (lowest
    ``accept_key``: priority rank, then demand DESCENDING — the
    first-fit-decreasing rule; see the key construction in solve_greedy —
    then job index for single-valuedness);
    losers immediately retry their alternate node in the caller's
    second-chance pass and re-bid next round after that. The winner's
    demand comes out of ``accept_reduce`` alongside the key — no gather
    chain back through [J] on the accelerated path.

    The winner must still fit the CURRENT free capacity (``fits_win``):
    bids are made against round-start capacities, but the second-chance
    pass calls this with post-first-pass capacities, where a round-start-
    feasible bid can exceed what's left.
    """
    tot_gpu, tot_mem, win_key, win_gpu, win_mem = _accept_reduce_jnp(
        choice, accept_key, gpu_demand, mem_demand, num_nodes
    )
    fits_all = (tot_gpu <= gpu_free + _EPS) & (tot_mem <= mem_free + _EPS)

    has_win = win_key != jnp.int32(0x7FFFFFFF)
    fits_win = (
        has_win
        & (win_gpu <= gpu_free + _EPS)
        & (win_mem <= mem_free + _EPS)
    )

    used_gpu = jnp.where(fits_all, tot_gpu, jnp.where(fits_win, win_gpu, 0.0))
    used_mem = jnp.where(fits_all, tot_mem, jnp.where(fits_win, win_mem, 0.0))

    # Gather-free accept flags. The direct form — fits_all[node_of] etc. —
    # is three [J]-from-[N] gathers per accept pass; TPU lowers those to
    # serialized dynamic-slice loops (measured ~0.53ms/round at 12288x1024,
    # 70% of the whole round). One fused [N, J] broadcast-compare + any()
    # on the VPU instead (the Pallas twin, accept_phase_pallas, skips
    # bidder-free J tiles too). Winner identity rides the reduced key
    # itself: win_key[n] == accept_key[j] iff j won node n (the key
    # embeds the job index, so it is single-valued per job).
    n_iota = jnp.arange(num_nodes, dtype=jnp.int32)
    mine = choice[None, :] == n_iota[:, None]  # [N, J]; sentinel: none
    accept = jnp.any(
        mine
        & (
            fits_all[:, None]
            | (
                fits_win[:, None]
                & (win_key[:, None] == accept_key[None, :])
            )
        ),
        axis=0,
    )
    return accept, used_gpu, used_mem


def _prank_sorted(neg_p: jax.Array) -> jax.Array:
    """Dense rank of a NON-DECREASING key vector: cumsum over new-distinct
    markers. Only valid under the sortedness predicate checked by
    solve_greedy's lax.cond; must agree with ``_prank_dense`` on every
    sorted input (parity-tested)."""
    first = jnp.concatenate([jnp.ones((1,), bool), neg_p[1:] != neg_p[:-1]])
    return jnp.cumsum(first.astype(jnp.int32)) - 1


def _prank_dense(neg_p: jax.Array) -> jax.Array:
    """Dense rank for arbitrary order by comparison counting (see the
    rank commentary in solve_greedy): first_occ marks one representative
    per distinct value, so counting smaller representatives yields the
    number of DISTINCT smaller values — the sort+cumsum dense rank."""
    J = neg_p.shape[0]
    j_iota = jnp.arange(J, dtype=jnp.int32)
    first_occ = ~jnp.any(
        (neg_p[None, :] == neg_p[:, None])
        & (j_iota[None, :] < j_iota[:, None]),
        axis=1,
    )
    return jnp.sum(
        ((neg_p[None, :] < neg_p[:, None]) & first_occ[None, :]).astype(
            jnp.int32
        ),
        axis=1,
    )


def _resolve_accel(accel: str, J: int, N: int) -> str:
    """Pick the round-op implementation for a (statically shaped) solve.

    ``pallas``/``mega`` need both axes divisible by the 128-lane/TILE_N
    layout and a real TPU backend; GSPMD-sharded solves must pass
    ``accel='jnp'`` explicitly (pallas_call does not auto-partition).
    ``interpret``/``mega-interpret`` run the Pallas kernels through the
    interpreter on any backend — parity tests use them. ``mega`` (the TPU
    default) is the class-serialized round-fusion path; it assumes the
    job axis is priority-sorted (backends.py guarantees this) — on
    unsorted input its safety invariants still hold but priority may be
    inverted across class windows. ``mega-jnp`` is its pure-jnp twin.
    """
    if accel != "auto":
        if accel not in (
            "jnp", "pallas", "interpret", "mega", "mega-interpret",
            "mega-jnp",
        ):
            raise ValueError(f"unknown accel {accel!r}")
        return accel
    if J % 128 == 0 and N % 128 == 0 and jax.default_backend() == "tpu":
        from kubeinfer_tpu.solver import pallas_kernels as pk

        return "mega" if pk.mega_window(N, J) is not None else "pallas"
    return "jnp"


@functools.partial(
    jax.jit, static_argnames=("max_rounds", "accel", "seeded")
)
def solve_greedy(
    p: Problem,
    weights: ScoreWeights = ScoreWeights(),
    max_rounds: int = 64,
    accel: str = "auto",
    seeded: bool = True,
) -> Assignment:
    """Parallel greedy with conflict resolution (policy ``jax-greedy``).

    ``max_rounds`` bounds one pipelined main/repair loop invocation; on
    the mega path it is a PER-WINDOW budget (windows exit at their
    fixpoint far earlier). ``Assignment.rounds`` is the summed
    diagnostic across invocations/windows, and budget exhaustion is
    signalled out-of-band so the repair/fill safety net still fires
    exactly when progress was possible.

    ``seeded`` (STATIC; every accel flavor) compiles the incumbent-
    seeding + preemption-repair machinery into the solve: joint-fitting
    incumbents hold their seats up front, and a repair loop unseats
    lower-priority seats when they strand a higher-priority job. It is
    semantically inert on problems with no incumbents but costs ~0.2ms
    of skipped-branch control flow at the headline shape, so backends
    pass ``seeded=False`` when the request carries no ``current_node``
    — fresh solves trace none of it. Default True: the raw API stays
    stability-correct for incumbent problems without callers having to
    know the flag.
    """
    jobs, nodes = p.jobs, p.nodes
    J = jobs.valid.shape[0]
    N = nodes.valid.shape[0]
    accel = _resolve_accel(accel, J, N)
    static_cost = _static_cost_t(p, weights)
    inv_gpu_cap = 1.0 / jnp.maximum(nodes.gpu_capacity, 1.0)
    inv_mem_cap = 1.0 / jnp.maximum(nodes.mem_capacity, 1.0)

    # Dense priority rank (0 = highest priority), full resolution: drives
    # both the accept sort key (exact priority order within a node) and the
    # per-node priority fence below. Padded rows sort last (neg_p=+inf) and
    # get the highest ranks, but invalid jobs never bid, so they cannot
    # influence the fence.
    # Two algorithms, picked at runtime by lax.cond (both produce the
    # identical dense rank, so the choice is invisible downstream):
    # - Sorted fast path: the backend priority-sorts the job axis before
    #   packing (backends.py, for the per-J-tile early-out), making neg_p
    #   non-decreasing — dense rank is then a cumsum over new-distinct
    #   markers, pure [J] vector work.
    # - Dense fallback (arbitrary order): comparison counting, not
    #   argsort — a [J] f32 sort costs ~0.56ms at J=12288 on TPU
    #   (log^2-depth bitonic stages) plus a scatter to undo the
    #   permutation; two fused [J, J] broadcast-compare reductions cost
    #   ~0.15ms on the VPU and XLA never materializes the square.
    #   first_occ marks one representative per distinct value (the lowest
    #   index), so counting smaller representatives yields the number of
    #   DISTINCT smaller values — exactly the sort+cumsum dense rank.
    #   CPU caveat (advisor r2): if XLA's CPU backend fails to fuse the
    #   [J, J] square it materializes ~1.2GB bool at 12k jobs — but the
    #   dense branch only executes for UNSORTED inputs, and every
    #   production path (JaxBackend) sorts; large-J CPU solves through
    #   the raw solver API should pre-sort by priority. (Gang repair's
    #   former [J, J] squares are gone — see _gang_repair.)
    neg_p = jnp.where(jobs.valid, -jobs.priority, jnp.inf)
    prank = lax.cond(
        jnp.all(neg_p[1:] >= neg_p[:-1]), _prank_sorted, _prank_dense, neg_p
    )
    # The fence uses a class-compressed rank: at full resolution a node is
    # biddable only by its single highest interested priority level, and
    # nodes idle whenever that level's jobs bid elsewhere (measured: 30
    # rounds vs 20 on the 10k x 1k shape). Four classes keep inversion
    # protection at class granularity while letting near-priority jobs
    # contend in the same round; exact order within a node still comes from
    # full-resolution prank in the accept key. Padded rows are excluded
    # from the class count (phantom-class regression, advisor r1).
    n_classes = jnp.max(jnp.where(jobs.valid, prank, -1)) + 1
    fence_classes = 4
    crank = (prank * fence_classes) // jnp.maximum(n_classes, 1)
    crank = jnp.minimum(crank, fence_classes - 1)
    rankf = jnp.where(jobs.valid, crank.astype(jnp.float32), RANK_INF)

    # Tie-spreading field, sampled ONCE per solve: per-round noise over
    # [N, J] would dominate the round cost on TPU. No per-round rotation
    # either: the field already differs per (job, node), so conflict losers
    # diverge to different second choices without it — and a [N, J] roll is
    # a full HBM gather pass per round.
    # The generator is a 2-mix integer hash (fmix-style), not threefry:
    # tie-spreading needs decorrelation across (node, job), not
    # cryptographic quality, and the hash is ~6 VPU ops/element vs
    # threefry's ~100. Output is uniform in [0, 1): bounded by
    # construction, so (unlike a gumbel) it cannot escape the static
    # quantization bounds below.
    _n = lax.broadcasted_iota(jnp.int32, (N, J), 0)
    _j = lax.broadcasted_iota(jnp.int32, (N, J), 1)
    _h = _n * jnp.int32(-1640531527) + _j * jnp.int32(40503)
    _h = _h ^ (_h >> 13)
    _h = _h * jnp.int32(-1274126529)
    _h = _h ^ (_h >> 16)
    # Spread over [-2, 6) — the clipped-gumbel support the weights/round
    # count were tuned against (narrower spread measurably raises the
    # round count: collisions among near-ties settle one per round).
    base_noise = max(weights.noise, _MIN_TIE_NOISE) * (
        (_h & jnp.int32(0x7FFFFF)).astype(jnp.float32) * (8.0 / float(1 << 23))
        - 2.0
    )

    # Everything round-invariant folds into ONE resident node-major [N, J]
    # field, so a round reads S exactly once and the rest is fused
    # broadcasts/reductions: the best-fit term w*(free[n]-d[j])/cap[n]
    # splits into a per-round [N] vector (w*free[n]/cap[n], recomputed from
    # live capacity below) plus a round-invariant rank-1 outer product
    # (-d[j]*w/cap[n]) folded here.
    v_g = weights.fit_gpu * inv_gpu_cap  # [N]
    v_m = weights.fit_mem * inv_mem_cap
    S = (
        static_cost
        + base_noise
        - v_g[:, None] * jobs.gpu_demand[None, :]
        - v_m[:, None] * jobs.mem_demand[None, :]
    )
    # Invalid nodes fold into the capacity vector (never feasible) so the
    # round ops need no separate validity input.
    gf_valid = jnp.where(nodes.valid, nodes.gpu_free, -1.0)

    # Bids are packed non-negative i32s — (quantized cost << node_idx_bits) | node index
    # — so ONE masked min-reduce per half yields both the argmin node and
    # its cost, with no argmin/min dual pass, no take_along_axis re-gather.
    # Quantization bounds are STATIC (derived from the weights, with the
    # gumbel noise clipped to [-2, 6] sigma at generation): granularity at
    # N=1024 is (hi-lo)/2^21 ~ 1e-5, far below the 1e-3 noise floor, so
    # quantization never flips a meaningful comparison.
    # i31 packing: Mosaic (Pallas TPU) has no unsigned reductions and no
    # f32->u32 casts, so packed bids live in non-negative int32.
    node_idx_bits = max((N - 1).bit_length(), 1)
    cost_bits = 31 - node_idx_bits
    fit_sum = weights.fit_gpu + weights.fit_mem
    noise_scale = max(weights.noise, _MIN_TIE_NOISE)
    # noise is uniform in [-2, 6) * scale: bounds are exact, not tail
    # estimates
    q_lo = -fit_sum - 2.0 * noise_scale
    q_hi = (
        weights.cache + weights.move + weights.topology
        + fit_sum + 6.0 * noise_scale
    )
    q_max = float((1 << cost_bits) - 2)
    q_scale = q_max / (q_hi - q_lo)
    node_mask = jnp.int32((1 << node_idx_bits) - 1)
    BIG = jnp.int32(0x7FFFFFFF)

    # Per-job accept key (round-invariant): priority rank, then demand
    # DESCENDING, then job index — see _dense_accept. Descending is the
    # first-fit-decreasing rule: a contested node goes to its largest
    # bidder, because small losers nearly always fit somewhere else while
    # a stranded large job often fits nowhere (an 8-chip job losing its
    # only whole-idle node to a 1-chip job is unrecoverable; the reverse
    # is a shrug).
    j_idx_bits = max((J - 1).bit_length(), 1)
    rank_bits = 31 - j_idx_bits - 4
    rank_c = jnp.clip(prank, 0, (1 << rank_bits) - 1)
    dmax = jnp.maximum(jnp.max(jobs.gpu_demand), 1.0)
    demand_q = jnp.clip(jobs.gpu_demand * (15.0 / dmax), 0, 15).astype(jnp.int32)
    accept_key = (
        (rank_c << (4 + j_idx_bits))
        | ((15 - demand_q) << j_idx_bits)
        | jnp.arange(J, dtype=jnp.int32)
    )

    # The mega (class-serialized) path replaces the main round loop only;
    # the gang-repair fill pass still runs the pipelined round machinery,
    # so its closures are set up for every accel flavor: pipelined kernels
    # for the TPU flavors when the axes meet their 128-alignment contract,
    # jnp otherwise (bit-identical by the parity invariant, so the swap is
    # invisible — mega itself only needs N % 8, e.g. the J=N=64 bucket).
    pallas_fill_ok = J % 128 == 0 and N % 128 == 0
    if accel in ("pallas", "interpret") or (
        accel in ("mega", "mega-interpret") and pallas_fill_ok
    ):
        from kubeinfer_tpu.solver import pallas_kernels as pk

        interp = accel in ("interpret", "mega-interpret")

        def tile_activity(active_j):
            return pk.tile_activity(active_j, J)

        def round_bids(u, gf, mf, rankf_eff, minrank, alias, act):
            return pk.bid_reduce_pallas(
                S, u, gf, mf, jobs.gpu_demand, jobs.mem_demand, rankf_eff,
                minrank, jobs.current_node, alias, act,
                q_lo=q_lo, q_scale=q_scale, q_max=q_max,
                node_idx_bits=node_idx_bits, interpret=interp,
            )

        # The accepts reuse the round's bid-activity tiles: bidders are
        # a subset of bid-active jobs, and a superset activity only
        # costs skipped-tile compute, never correctness. The verdict
        # kernel folds totals + fit checks + consumed capacity into one
        # sweep, feeding the flags kernel directly.
        def accept_pass(choice, gpu_free, mem_free, act):
            return pk.accept_phase_pallas(
                choice, accept_key, jobs.gpu_demand, jobs.mem_demand,
                gpu_free, mem_free, act, interpret=interp,
            )

        def fence_minrank(gf, mf, rankf_eff):
            _, act = pk.tile_activity(rankf_eff < RANK_INF * 0.5, J)
            return pk.fence_minrank_pallas(
                gf, mf, jobs.gpu_demand, jobs.mem_demand, rankf_eff, act,
                interpret=interp,
            )
    else:

        def tile_activity(active_j):
            return None, None  # jnp path evaluates densely (same values)

        def round_bids(u, gf, mf, rankf_eff, minrank, alias, act):
            del alias, act
            return _round_bids_jnp(
                S, u, gf, mf, jobs.gpu_demand, jobs.mem_demand, rankf_eff,
                minrank, jobs.current_node, N,
                q_lo, q_scale, q_max, node_idx_bits,
            )

        accept_pass = None

        def fence_minrank(gf, mf, rankf_eff):
            return _fence_minrank(
                gf, mf, jobs.gpu_demand, jobs.mem_demand, rankf_eff
            )

    def run_rounds(assigned, gpu_free, mem_free, rounds0, rankf_base,
                   round_cap):
        """Greedy rounds to a fixpoint from the given state; jobs whose
        ``rankf_base`` is RANK_INF may never bid (the fill pass uses this
        to fence unwound gang members). ``round_cap`` is the absolute
        round budget for THIS invocation (the fill pass brings its own —
        sharing the main budget would skip the fill exactly when the
        main loop exhausts it, the contended regime that needs it most).
        """

        def cond(state):
            # `progress` already conjoins last round's accepts with the
            # post-round pending check (computed in body, where it fuses
            # with neighboring ops — a separate reduce here would cost
            # its own dispatch per iteration)
            assigned, gpu_free, mem_free, rounds, progress = state
            return progress & (rounds < round_cap)

        def body(state):
            assigned, gpu_free, mem_free, rounds, _ = state
            # Placed/invalid jobs fold into the fence rank so the round
            # ops need no separate unassigned input.
            rankf_eff = jnp.where(assigned < 0, rankf_base, RANK_INF)
            u = v_g * gpu_free + v_m * mem_free  # [N] live best-fit pressure
            minrank = fence_minrank(gpu_free, mem_free, rankf_eff)
            # Conservative superset of jobs that can produce a non-BIG bid
            # this round: the fence admits rank r on SOME node only when
            # r <= max finite minrank, and incumbents may always bid home.
            # Everything outside this set yields all-BIG bid panels, so
            # the Pallas path skips their J tiles (compute AND the S DMA)
            # with bit-identical output. -1 fallback when no node has a
            # finite fence (nothing unplaced is feasible anywhere): only
            # home bidders can act.
            max_minrank = jnp.max(
                jnp.where(minrank < RANK_INF * 0.5, minrank, -1.0)
            )
            active_j = (rankf_eff < RANK_INF * 0.5) & (
                (rankf_eff <= max_minrank) | (jobs.current_node >= 0)
            )
            alias, act = tile_activity(active_j)
            prim, alt = round_bids(
                u, gpu_free, mem_free, rankf_eff, minrank, alias, act
            )
            has1 = prim != BIG
            choice1 = jnp.where(has1, prim & node_mask, N)

            if accept_pass is not None:
                accept1, used_g1, used_m1 = accept_pass(
                    choice1, gpu_free, mem_free, act
                )
            else:
                accept1, used_g1, used_m1 = _dense_accept(
                    choice1, accept_key, jobs.gpu_demand, jobs.mem_demand,
                    gpu_free, mem_free, N,
                )
            assigned = jnp.where(accept1, choice1, assigned)
            gpu_free = gpu_free - used_g1
            mem_free = mem_free - used_m1

            # Second-chance pass: conflict losers immediately bid their
            # alternate node against the updated capacities, inside the
            # same round. Settlement tails (a few hundred losers
            # re-bidding one node per round) dominated the round count;
            # this halves them for one extra accept pass of vector ops.
            # Incumbents whose PRIMARY bid was their home node sit the
            # pass out: hopping to an alternate the instant home is
            # contested is exactly the churn the move-hysteresis exists
            # to prevent — they re-bid next round, and only relocate once
            # home is genuinely infeasible for them. Together with the
            # home-bid fence exemption (see ``is_home`` in the bid ops),
            # measured survivor moves under 10% churn drop from ~7.7% to
            # ~0.2%.
            home_bid = (jobs.current_node >= 0) & (
                choice1 == jobs.current_node
            )
            retry = has1 & ~accept1 & (alt != BIG) & ~home_bid
            choice2 = jnp.where(retry, alt & node_mask, N)
            if accept_pass is not None:
                accept2, used_g2, used_m2 = accept_pass(
                    choice2, gpu_free, mem_free, act
                )
            else:
                accept2, used_g2, used_m2 = _dense_accept(
                    choice2, accept_key, jobs.gpu_demand, jobs.mem_demand,
                    gpu_free, mem_free, N,
                )
            assigned = jnp.where(accept2, choice2, assigned)
            # Progress: any bid implies >=1 accept (a contested node's
            # winner in the first pass always fits — it bid against these
            # capacities), so a no-accept round means no unplaced job had
            # a biddable node: fixpoint.
            return (
                assigned,
                gpu_free - used_g2,
                mem_free - used_m2,
                rounds + 1,
                (jnp.any(accept1) | jnp.any(accept2))
                & jnp.any((assigned < 0) & jobs.valid),
            )

        return lax.while_loop(
            cond, body,
            # initial progress = anything pending at all (one-time
            # reduce; keeps the no-op invocation at zero rounds)
            (assigned, gpu_free, mem_free, rounds0,
             jnp.any((assigned < 0) & jobs.valid)),
        )

    # Seed joint-fitting incumbents as already placed (all accel
    # flavors; `seeded` is static so fresh solves trace none of this).
    # Stability rationale: without seeding, a re-solve makes incumbents
    # RACE arrivals for their own homes — the mega path's cross-window
    # serialization lost that race outright (measured 4.9% survivor
    # moves under the 10% churn bench), and the pipelined path's
    # home-bid-exemption racing still leaked ~0.2%. Seeding holds every
    # joint-fitting incumbent's seat up front on both paths (measured
    # 0.0% moves); the squat inversion it re-admits — a seated
    # low-priority incumbent keeping capacity that leaves a
    # higher-priority job unplaceable — is undone by the preemption
    # repair below. A node whose incumbents no longer jointly fit
    # releases ALL of them to re-bid.
    if seeded:
        n_iota_seed = jnp.arange(N, dtype=jnp.int32)
        at_home = (jobs.current_node >= 0) & jobs.valid

        def _seat_sums(_):
            on_node = (
                jobs.current_node[None, :] == n_iota_seed[:, None]
            ) & at_home[None, :]
            return (
                jnp.sum(
                    jnp.where(on_node, jobs.gpu_demand[None, :], 0.0),
                    axis=1,
                ),
                jnp.sum(
                    jnp.where(on_node, jobs.mem_demand[None, :], 0.0),
                    axis=1,
                ),
            )

        # cond-skipped when the request carried placements but all
        # rows are -1: the two [N, J] seat-sum reduces cost ~0.15ms
        # at the headline shape
        used_g, used_m = lax.cond(
            jnp.any(at_home),
            _seat_sums,
            lambda _: (
                jnp.zeros((N,), jnp.float32),
                jnp.zeros((N,), jnp.float32),
            ),
            0,
        )
        ok_node = (used_g <= gf_valid + _EPS) & (
            used_m <= nodes.mem_free + _EPS
        )
        seated = at_home & ok_node[
            jnp.clip(jobs.current_node, 0, N - 1)
        ]
        asg_init = jnp.where(seated, jobs.current_node, -1)
        gf_seed = gf_valid - jnp.where(ok_node, used_g, 0.0)
        mf_seed = nodes.mem_free - jnp.where(ok_node, used_m, 0.0)
    else:
        asg_init = jnp.full((J,), -1, jnp.int32)
        gf_seed = gf_valid
        mf_seed = nodes.mem_free

    # One solve-to-fixpoint closure per accel flavor — the seeding and
    # preemption repair drive whichever main loop is selected through
    # the same interface: (assigned, gf_eff, mf) -> (assigned, gf, mf,
    # rounds, capped). gf_eff arrives with invalid nodes folded to -1.
    if accel in ("mega", "mega-interpret", "mega-jnp"):
        # Round-fusion main loop: every settlement round of every
        # priority window runs inside ONE pallas_call (or its jnp twin),
        # with the window's S slice VMEM-resident — see pallas_kernels'
        # mega section for the algorithmic divergence from the
        # pipelined-fence loop.
        from kubeinfer_tpu.solver import pallas_kernels as pk

        mega_fn = (
            pk.mega_rounds_jnp
            if accel == "mega-jnp"
            else functools.partial(
                pk.mega_solve_pallas, interpret=accel == "mega-interpret"
            )
        )

        def resolve_fn(a, gf_eff, mf_):
            return mega_fn(
                S, jobs.gpu_demand, jobs.mem_demand, accept_key, rankf,
                jobs.current_node, a, jobs.valid, gf_eff, mf_,
                v_g, v_m,
                max_rounds=max_rounds, q_lo=q_lo, q_scale=q_scale,
                q_max=q_max, node_idx_bits=node_idx_bits,
            )
    else:

        def resolve_fn(a, gf_eff, mf_):
            # Pipelined rounds; budget exhaustion is the round counter
            # hitting the cap (one global loop, unlike mega's
            # summed-across-windows diagnostic)
            a2, g2, m2, r2, _ = run_rounds(
                a, gf_eff, mf_, jnp.int32(0), rankf,
                jnp.int32(max_rounds),
            )
            return a2, g2, m2, r2, r2 >= max_rounds

    assigned, gpu_free, mem_free, rounds, mega_capped = resolve_fn(
        asg_init, gf_seed, mf_seed
    )

    # The repair (like the seeding it repairs) exists only on seeded
    # compiles — fresh solves trace none of it.
    if seeded:
        # Preemption repair: seeding holds incumbents' homes before
        # anyone bids, which re-admits the squat inversion — a seated
        # low-priority incumbent keeping capacity that leaves a HIGHER-
        # priority job unplaceable. (Jobs placed by the main loop cannot
        # cause this: an unplaced job reached a fixpoint where no node
        # was feasible, and capacities only shrink.) When that exact
        # case occurs, unseat the lower-rank seats on the victim job's
        # best reclaimable node and re-run the (now mostly-seeded,
        # cheap) solve; the evictees re-bid like churn departures. Each
        # iteration rescues the highest-priority stranded job — the
        # accept key's (rank, demand-desc, index) order picks it.
        # Termination is made monotone by the ``ever`` mask: only
        # never-yet-unseated seats are victimizable, and every
        # productive iteration marks >= 1 new seat (any(can) requires
        # nonzero freeable demand), so the loop runs at most #seated
        # iterations — a job rescued back onto its own seat cannot be
        # re-victimized (which doubles as repeat-churn protection for
        # evictees), and unseating can never cycle. The it < J cap is a
        # pure backstop. Exit property (fuzz-tested): the top-priority
        # unplaced job cannot be fitted by unseating any single node's
        # victimizable lower-rank seats.
        def _preempt_repair(args):
            assigned, gpu_free, mem_free, rounds, capped, it, _, ever = args
            unpl = jobs.valid & (assigned < 0)
            BIGK = jnp.int32(0x7FFFFFFF)
            jkey = jnp.where(unpl, accept_key, BIGK)
            j_star = jnp.argmin(jkey).astype(jnp.int32)
            d_star = jobs.gpu_demand[j_star]
            md_star = jobs.mem_demand[j_star]
            r_star = rankf[j_star]
            on_seat = seated & (assigned == jobs.current_node) & ~ever
            victim = on_seat & (rankf > r_star)
            vic_on = (
                jobs.current_node[None, :] == n_iota_seed[:, None]
            ) & victim[None, :]
            freed_g = jnp.sum(
                jnp.where(vic_on, jobs.gpu_demand[None, :], 0.0), axis=1
            )
            freed_m = jnp.sum(
                jnp.where(vic_on, jobs.mem_demand[None, :], 0.0), axis=1
            )
            can = (
                nodes.valid
                & (d_star <= gpu_free + freed_g + _EPS)
                & (md_star <= mem_free + freed_m + _EPS)
                & (freed_g + freed_m > 0.0)
            )
            scol = lax.dynamic_slice(
                S, (jnp.int32(0), j_star), (N, 1)
            )[:, 0]
            n_star = jnp.argmin(
                jnp.where(can, scol, jnp.float32(3.4e38))
            ).astype(jnp.int32)

            def _unseat_and_resolve(args):
                (
                    assigned, gpu_free, mem_free, rounds, capped, it, _,
                    ever,
                ) = args
                unseat = victim & (jobs.current_node == n_star)
                ever = ever | unseat
                assigned = jnp.where(unseat, -1, assigned)
                gpu_free = jnp.where(
                    n_iota_seed == n_star, gpu_free + freed_g, gpu_free
                )
                mem_free = jnp.where(
                    n_iota_seed == n_star, mem_free + freed_m, mem_free
                )
                assigned, gpu_free, mem_free, r2, capped2 = resolve_fn(
                    assigned,
                    jnp.where(nodes.valid, gpu_free, -1.0),
                    mem_free,
                )
                # the re-solve can itself exhaust its round budget; the
                # repair/fill safety net must see that, not the stale
                # first-run flag
                return (
                    assigned, gpu_free, mem_free, rounds + r2,
                    capped | capped2, it + jnp.int32(1), jnp.bool_(True),
                    ever,
                )

            # No reclaimable node fits the TOP stranded job: stop (the
            # progress flag ends the loop) rather than burn a sweep for
            # a guaranteed-identical assignment. Lower-ranked stranded
            # jobs are not attempted past a stuck top job — they would
            # demand even more reclaim.
            return lax.cond(
                jnp.any(can), _unseat_and_resolve,
                lambda a: (*a[:6], jnp.bool_(False), a[7]),
                (assigned, gpu_free, mem_free, rounds, capped, it,
                 jnp.bool_(True), ever),
            )

        def _repair_cond(args):
            assigned, _, _, _, _, it, progress, ever = args
            unpl_now = jobs.valid & (assigned < 0)
            min_unpl_rank = jnp.min(
                jnp.where(unpl_now, rankf, RANK_INF)
            )
            squat = jnp.any(
                seated
                & (assigned == jobs.current_node)
                & ~ever
                & (rankf > min_unpl_rank)
            )
            # the #seated bound comes from the ever-mask monotonicity
            # argument above; the explicit cap is a backstop, not a
            # budget
            return squat & progress & (it < jnp.int32(J))

        (
            assigned, gpu_free, mem_free, rounds, mega_capped, _, _, _
        ) = lax.while_loop(
            _repair_cond, _preempt_repair,
            (assigned, gpu_free, mem_free, rounds, mega_capped,
             jnp.int32(0), jnp.bool_(True), jnp.zeros((J,), bool)),
        )

    # Repair + fill run only when some gang member is unplaced — the
    # exact trigger for an unwind. When every gang is complete, repair is
    # an identity (keep all; recomputed capacity equals the loop-tracked
    # capacity on valid nodes) and the fill pass would just burn one
    # no-progress round, so the cond skips ~0.2ms off the common
    # all-placed solve with bit-identical output.
    def _repair_and_fill(args):
        assigned, gpu_free, mem_free, rounds = args
        assigned, gpu_free, mem_free = _gang_repair(p, assigned)
        # Fill pass: gang repair RETURNS capacity after the fixpoint,
        # which can leave feasible non-gang jobs stranded (found by the
        # property fuzz). Re-run the rounds with every unwound gang
        # member fenced — only non-gang jobs may claim the freed
        # capacity, so no new repair is ever needed and the non-gang
        # fixpoint guarantee holds for the FINAL capacities. The budget
        # is one round per fillable job plus one: every progress round
        # places >=1 job, so the loop reaches its fixpoint before this
        # cap can bind (a fixed cap would silently re-strand capacity in
        # the worst case — one freed node contested by more small jobs
        # than the cap, settling ~1 per round).
        rankf_fill = jnp.where(
            (jobs.gang_id >= 0) & (assigned < 0), RANK_INF, rankf
        )
        gf_fill = jnp.where(nodes.valid, gpu_free, -1.0)
        fillable = (assigned < 0) & jobs.valid & (jobs.gang_id < 0)
        if accel in ("mega", "mega-interpret", "mega-jnp"):
            # Fill through the mega kernel too: at the 50k soak shape
            # the pipelined fill (48 J tiles x several rounds) dominated
            # the whole device solve. The current assignment seeds the
            # kernel (asg_init) and ``may_bid`` restricts bidding to the
            # fillable set, so the kernel's output IS the merged result
            # (the round math never unassigns a placed job); the
            # per-window cap is W+1 — every progress round places >= 1
            # job, so the in-kernel while reaches its fixpoint first,
            # preserving the fill's completeness guarantee (a 64-cap
            # could re-strand a node contested by more small jobs than
            # the cap).
            from kubeinfer_tpu.solver import pallas_kernels as pk

            fill_fn = (
                pk.mega_rounds_jnp
                if accel == "mega-jnp"
                else functools.partial(
                    pk.mega_solve_pallas,
                    interpret=accel == "mega-interpret",
                )
            )
            asg_f, gpu_free, mem_free, r_f, _ = fill_fn(
                S, jobs.gpu_demand, jobs.mem_demand, accept_key,
                rankf_fill, jobs.current_node, assigned, fillable,
                gf_fill, mem_free, v_g, v_m,
                max_rounds=pk.mega_window(N, J) + 1, q_lo=q_lo,
                q_scale=q_scale, q_max=q_max,
                node_idx_bits=node_idx_bits,
            )
            # the fill is seeded with the current assignment, so its
            # output IS the merged result
            assigned = asg_f
            rounds = rounds + r_f
        else:
            assigned, gpu_free, mem_free, rounds, _ = run_rounds(
                assigned, gf_fill, mem_free, rounds, rankf_fill,
                rounds + jnp.sum(fillable.astype(jnp.int32)) + 1,
            )
        return assigned, gpu_free, mem_free, rounds

    incomplete_gang = jnp.any(
        (jobs.gang_id >= 0) & jobs.valid & (assigned < 0)
    )
    # The fill must also run when the main loop exited on its round
    # budget rather than at a fixpoint (progress still possible): the
    # old unconditional fill rescued exactly that regime with its fresh
    # budget, and skipping it would strand placeable jobs. A clean
    # fixpoint exit with complete gangs is the only case where skipping
    # is provably a no-op.
    budget_capped = mega_capped & jnp.any(
        (assigned < 0) & jobs.valid
    )
    assigned, gpu_free, mem_free, rounds = lax.cond(
        incomplete_gang | budget_capped,
        _repair_and_fill,
        lambda args: args,
        (assigned, gpu_free, mem_free, rounds),
    )
    gpu_free = jnp.where(nodes.valid, gpu_free, 0.0)
    placed = jnp.sum((assigned >= 0) & jobs.valid).astype(jnp.int32)
    return Assignment(assigned, gpu_free, mem_free, rounds, placed)


def _gang_repair(p: Problem, assigned: jax.Array):
    """Unwind incompletely-placed gangs (all-or-nothing) and recompute
    capacity from scratch. Gang ids must be < 2^16 (the hi/lo byte split
    below aliases larger ids); the pack layer's _densify_gangs guarantees
    dense ids in [0, J) with J <= 65536. -1 marks non-gang.

    Scatter-free AND [J, J]-free: segment_sum lowers to scatters, which
    TPUs serialize (measured ~0.3ms here at 12288 jobs), and the earlier
    [J, J] broadcast-compare membership counts cost ~0.16ms of VPU time
    (and risk materializing ~1.2GB on CPU backends if XLA doesn't fuse —
    advisor r2). Instead the dense id splits into hi/lo bytes and the
    per-job counts become two narrow MXU matmuls over [J, 256] one-hots:
      count[j] = sum_k w[k]·[gid_k == gid_j]
               = e_hi[j]^T (OH^T (OL ∘ w)) e_lo[j]
    — a gather-free one-hot sandwich (the same trick the cache-affinity
    scoring uses, _static_cost_t). 0/1 products are exact in bf16 and
    counts < 2^24 are exact in the f32 accumulator, so results are
    bit-identical to the broadcast-compare form.
    """
    jobs, nodes = p.jobs, p.nodes
    N = nodes.valid.shape[0]
    in_gang = (jobs.gang_id >= 0) & jobs.valid
    gid = jnp.where(in_gang, jobs.gang_id, -1)

    hi = (gid >> 8).astype(jnp.int32)
    lo = (gid & 255).astype(jnp.int32)
    slots = jnp.arange(256, dtype=jnp.int32)
    oh_hi = (
        in_gang[:, None] & (hi[:, None] == slots[None, :])
    ).astype(jnp.bfloat16)  # [J, 256]
    oh_lo = (
        in_gang[:, None] & (lo[:, None] == slots[None, :])
    ).astype(jnp.bfloat16)
    placed_w = (assigned >= 0).astype(jnp.bfloat16)
    # need and got share the hi-side contraction: RHS carries both weight
    # columns (1 for membership, placed for got) side by side.
    rhs = jnp.concatenate([oh_lo, oh_lo * placed_w[:, None]], axis=1)
    table = jax.lax.dot_general(
        oh_hi, rhs, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [256, 512]: [h, l] membership counts | placed counts
    # f32 on purpose: table holds counts up to J, and bf16's 8 mantissa
    # bits only represent integers exactly up to 256. Each output row has
    # at most one nonzero product (oh_hi rows are one-hot), so f32 is
    # exact.
    back = jax.lax.dot_general(
        oh_hi.astype(jnp.float32), table, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [J, 512]: row j holds table[hi_j, :]
    lo_f = oh_lo.astype(jnp.float32)
    need = jnp.sum(back[:, :256] * lo_f, axis=1).astype(jnp.int32)
    got = jnp.sum(back[:, 256:] * lo_f, axis=1).astype(jnp.int32)
    keep = (~in_gang) | (got == need)
    assigned = jnp.where(keep, assigned, -1)

    n_iota = jnp.arange(N, dtype=jnp.int32)
    placed_on = assigned[None, :] == n_iota[:, None]  # [N, J]; -1 matches none
    used_gpu = jnp.sum(jnp.where(placed_on, jobs.gpu_demand[None, :], 0.0), axis=1)
    used_mem = jnp.sum(jnp.where(placed_on, jobs.mem_demand[None, :], 0.0), axis=1)
    return assigned, nodes.gpu_free - used_gpu, nodes.mem_free - used_mem


def _auction_tiebreak(J: int, N: int) -> jax.Array:
    """Deterministic per-(job, node) i31 hash for selection tie-breaking
    (see the price-war notes in solve_auction). Computed once per solve
    and shared verbatim by both loop implementations — identical integer
    ops make the twin/kernel choice invisible to outcomes."""
    _n2 = lax.broadcasted_iota(jnp.int32, (J, N), 1)
    _j2 = lax.broadcasted_iota(jnp.int32, (J, N), 0)
    _h2 = _j2 * jnp.int32(-1640531527) + _n2 * jnp.int32(40503)
    _h2 = _h2 ^ (_h2 >> 13)
    _h2 = _h2 * jnp.int32(-1274126529)
    return (_h2 ^ (_h2 >> 16)) & jnp.int32(0x7FFFFFFF)


def _auction_accel(accel: str, J: int, N: int) -> str:
    """Pick the auction loop implementation: '' = jnp while_loop twin,
    'pallas'/'interpret' = the one-launch kernel (pk.auction_solve).

    Same vocabulary as _resolve_accel so callers don't need a second
    knob: any Pallas-flavored greedy accel opts the auction into its
    fused loop too; 'jnp'/'mega-jnp' keep the GSPMD-safe twin. Mosaic
    wants J%8 sublanes / N%128 lanes and the VMEM-resident benefit
    field must fit (auction_fits)."""
    from kubeinfer_tpu.solver import pallas_kernels as pk

    aligned = J % 8 == 0 and N % 128 == 0 and pk.auction_fits(J, N)
    if accel == "auto":
        if aligned and jax.default_backend() == "tpu":
            return "pallas"
        return ""
    if accel in ("pallas", "mega", "interpret", "mega-interpret"):
        # An explicit Pallas request on an ineligible shape fails loudly
        # (mirrors _resolve_accel): a silent twin fallback would make
        # kernel parity tests vacuous and mislabel bench timings.
        if not aligned:
            raise ValueError(
                f"accel={accel!r} requested but the auction kernel needs "
                f"J%8==0, N%128==0 and a VMEM-resident [J,N] field; got "
                f"J={J} N={N} (fits={pk.auction_fits(J, N)}). Use "
                "accel='jnp' or 'auto'."
            )
        return "interpret" if accel in ("interpret", "mega-interpret") \
            else "pallas"
    return ""


def _auction_loop_jnp(
    benefit: jax.Array,  # f32[J, N]; -INFEASIBLE marks infeasible
    tiebreak: jax.Array,  # i32[J, N] from _auction_tiebreak
    valid: jax.Array,  # bool[J]
    eps: jax.Array,
    max_iters: int,
) -> tuple[jax.Array, jax.Array]:
    """The Jacobi auction loop under XLA — the jnp twin of
    ``pk.auction_solve`` (bit-identical, see its docstring) and the code
    path for GSPMD-sharded solves and unaligned shapes. Returns
    (assigned i32[J], iters i32)."""
    J, N = benefit.shape
    NEG = -INFEASIBLE
    n_iota = jnp.arange(N, dtype=jnp.int32)

    def cond(state):
        assigned, owner, prices, it, progress, pending_best, stale = state
        pending = jnp.any((assigned < 0) & valid)
        return progress & pending & (it < max_iters) & (stale < _STALE_ITERS)

    def body(state):
        assigned, owner, prices, it, _, pending_best, stale = state
        unassigned = (assigned < 0) & valid
        value = jnp.where(
            unassigned[:, None], benefit - prices[None, :], NEG
        )
        best_v = jnp.max(value, axis=1)
        near = value >= best_v[:, None] - _TIE_TOL
        best_n = jnp.argmax(
            jnp.where(near, tiebreak, jnp.int32(-1)), axis=1
        ).astype(jnp.int32)
        second_v = jnp.max(
            jnp.where(n_iota[None, :] == best_n[:, None], NEG, value),
            axis=1,
        )
        can_bid = unassigned & (best_v > NEG * 0.5)
        # classic bid: price rise = value margin + eps
        bid = jnp.where(
            can_bid, prices[best_n] + (best_v - second_v) + eps, NEG
        )

        # Per-node highest bid wins; ties broken by lowest job index.
        # Scatter-free: the old [J, N] bid matrix built by .at[].set was
        # a TPU-serialized scatter per iteration (the same lesson as the
        # greedy accept, _dense_accept) — one broadcast-compare against
        # the bid targets feeds both reductions instead.
        mine = best_n[None, :] == n_iota[:, None]  # [N, J]
        bids_on = jnp.where(mine & can_bid[None, :], bid[None, :], NEG)
        win_bid = jnp.max(bids_on, axis=1)
        winner = jnp.argmax(bids_on, axis=1).astype(jnp.int32)
        node_has_winner = win_bid > NEG * 0.5

        # Evict previous owners of re-won nodes. Non-events are routed
        # to a sentinel slot J so scatters never collide on a clipped
        # index 0.
        evicted_owner = jnp.where(node_has_winner, owner, -1)
        evict_idx = jnp.where(evicted_owner >= 0, evicted_owner, J)
        evict_mask = jnp.zeros((J + 1,), bool).at[evict_idx].set(True)[:J]
        assigned = jnp.where(evict_mask, -1, assigned)

        owner = jnp.where(node_has_winner, winner, owner)
        prices = jnp.where(node_has_winner, win_bid, prices)
        # Each job bids on exactly one node, so winners are distinct
        # jobs; sentinel routing keeps no-winner nodes from clobbering
        # job 0.
        win_idx = jnp.where(node_has_winner, winner, J)
        won_node = (
            jnp.full((J + 1,), -1, jnp.int32)
            .at[win_idx]
            .set(jnp.arange(N, dtype=jnp.int32))[:J]
        )
        assigned = jnp.where(won_node >= 0, won_node, assigned)
        # Stagnation tracking: a war iteration evicts as many as it
        # places, so the pending count is the monotone progress signal
        n_pending = jnp.sum(((assigned < 0) & valid).astype(jnp.int32))
        improved = n_pending < pending_best
        return (
            assigned, owner, prices, it + 1, jnp.any(can_bid),
            jnp.minimum(n_pending, pending_best),
            jnp.where(improved, 0, stale + 1),
        )

    init = (
        jnp.full((J,), -1, jnp.int32),
        jnp.full((N,), -1, jnp.int32),
        jnp.zeros((N,), jnp.float32),
        jnp.int32(0),
        jnp.bool_(True),
        jnp.int32(J + 1),
        jnp.int32(0),
    )
    assigned, _, _, iters, _, _, _ = lax.while_loop(cond, body, init)
    return assigned, iters


@functools.partial(jax.jit, static_argnames=("max_iters", "accel"))
def solve_auction(
    p: Problem,
    weights: ScoreWeights = ScoreWeights(),
    eps: float = 0.01,
    max_iters: int = 512,
    accel: str = "auto",
) -> Assignment:
    """Auction assignment (policy ``jax-auction``): one replica per node.

    Feasible means the whole remaining node capacity satisfies the demand;
    each node hosts at most one replica. Within-eps-optimal total cost for
    the jobs it places (standard auction guarantee: J*eps of optimal).

    Priority does NOT influence auction outcomes (a per-job constant in the
    benefit cancels out of the bid increments): when preemption matters,
    use ``jax-greedy`` (priority-gated rounds) or ``native-greedy``
    (priority-sorted serial pass).

    Capacity freed by the post-solve gang repair is re-offered in the
    SAME solve (r2 verdict item 7 closed the former leave-idle
    relaxation): a fenced greedy fill runs over the repaired capacities
    with only unplaced NON-gang jobs eligible — a restricted sub-problem
    through solve_greedy itself, so the non-gang fixpoint guarantee
    ("no feasible non-gang job left unplaced") holds for the final
    capacities here exactly as it does on the greedy path.
    """
    jobs, nodes = p.jobs, p.nodes
    J = jobs.valid.shape[0]
    N = nodes.valid.shape[0]
    static_cost = _static_cost_t(p, weights).T  # auction math is job-major
    feas = (
        (jobs.gpu_demand[:, None] <= nodes.gpu_free[None, :] + _EPS)
        & (jobs.mem_demand[:, None] <= nodes.mem_free[None, :] + _EPS)
        & nodes.valid[None, :]
        & jobs.valid[:, None]
    )
    # benefit: higher is better; strictly bounded so -INF marks infeasible
    inv_gpu_cap = 1.0 / jnp.maximum(nodes.gpu_capacity, 1.0)
    inv_mem_cap = 1.0 / jnp.maximum(nodes.mem_capacity, 1.0)
    fit_cost = _fit_cost(
        nodes.gpu_free, nodes.mem_free, p, weights, inv_gpu_cap, inv_mem_cap
    )
    benefit = jnp.where(feas, -(static_cost + fit_cost), -INFEASIBLE)

    # Price-war handling — three mechanisms against the fixed-eps war,
    # which left 5 of 1000 jobs unplaced on the whole-node 1k x 1k case.
    # (1) Selection tie-breaking: a parallel (Jacobi) auction on a
    # homogeneous fleet is degenerate — identical benefit rows make every
    # job's argmax the same first index, ONE bid wins per iteration, and a
    # 1000-identical-jobs instance needs ~1000 iterations (the r3 995/1000
    # under-placement was exactly the max_iters cutoff of that war). A
    # deterministic per-(job, node) hash picks among values within
    # _TIE_TOL of the job's best instead, spreading one iteration's bids
    # across ~63% of the tied tier (measured: 256-identical converges in
    # 6 iterations vs the 1000+ cap). Tied bids are all true argmaxes, so
    # the J*eps bound only degrades by the tolerance: J*(eps+_TIE_TOL).
    # (2) Stagnation exit (below): model-pocket overflow — 25 jobs whose
    # model is cached on 20 nodes — is a genuine +eps-per-bid war (each
    # overflow job must push the whole pocket's prices past the cache
    # gap, ~20*5.0/eps bids, measured as a 500+-iteration plateau of 5
    # roving jobs on the r3 bench instance). The war's own end state is
    # "overflow jobs land on non-hit nodes", which is exactly what the
    # completeness fill produces, so the loop exits after _STALE_ITERS
    # iterations without a net placement and hands the stragglers to the
    # fill instead of burning the budget on price flattening.
    # Two rejected alternatives, tried and measured: Bertsekas eps-scaling
    # (coarse-to-fine phases, prices kept, assignment reset) collapses
    # under a parallel Jacobi auction — the phase restart leaves a single
    # roving unassigned job serially re-flattening the coarse phase's
    # price spread at +eps per iteration (599 iters on the 256-identical
    # instance whose single-phase solve takes 6); and tier-jump margins
    # (bid against the best value below the tied tier) break the eviction
    # signal, because tiers are per-job — a job that overpays its tier in
    # one jump prices out a second job whose only hit node it took
    # (measured: 2x the optimal Hungarian cost on the oracle test).
    tiebreak = _auction_tiebreak(J, N)
    mode = _auction_accel(accel, J, N)
    if mode:
        from kubeinfer_tpu.solver import pallas_kernels as pk

        assigned, iters = pk.auction_solve(
            benefit, tiebreak, jobs.valid, eps,
            max_iters=max_iters, stale_iters=_STALE_ITERS,
            tie_tol=_TIE_TOL, neg=-float(INFEASIBLE),
            interpret=(mode == "interpret"),
        )
    else:
        assigned, iters = _auction_loop_jnp(
            benefit, tiebreak, jobs.valid, eps, max_iters
        )

    # The fill runs whenever ANY valid job is unplaced — either a gang
    # member (whose unwind frees capacity the fill re-offers) or a plain
    # straggler: the greedy fill is the completeness guarantee (no
    # feasible job left unplaced — e.g. a perfect-matching instance
    # always ends at placed == J even if the auction exits on its
    # iteration budget or the stagnation cutoff mid-price-war). Fill
    # placements sit outside the J*eps bound, which applies to the
    # auction-placed jobs.
    needs_fill = jnp.any(jobs.valid & (assigned < 0))
    assigned, gpu_free, mem_free = _gang_repair(p, assigned)

    def _fill(args):
        from dataclasses import replace as _replace

        assigned, gpu_free, mem_free = args
        fillable = (assigned < 0) & jobs.valid & (jobs.gang_id < 0)
        sub = Problem(
            jobs=_replace(jobs, valid=fillable),
            nodes=_replace(nodes, gpu_free=gpu_free, mem_free=mem_free),
        )
        # accel threads through: a GSPMD-sharded auction caller passes
        # 'jnp' (sharded.py) and the fill must not embed Pallas kernels,
        # which cannot partition under GSPMD (advisor r3)
        out = solve_greedy(sub, weights, accel=accel)
        assigned = jnp.where(
            fillable & (out.node >= 0), out.node, assigned
        )
        return assigned, out.gpu_free, out.mem_free

    assigned, gpu_free, mem_free = lax.cond(
        needs_fill, _fill, lambda args: args,
        (assigned, gpu_free, mem_free),
    )
    placed = jnp.sum((assigned >= 0) & jobs.valid).astype(jnp.int32)
    return Assignment(assigned, gpu_free, mem_free, iters, placed)


def solve(
    p: Problem,
    policy: str = "jax-greedy",
    weights: ScoreWeights = ScoreWeights(),
    accel: str = "auto",
    seeded: bool = True,
) -> Assignment:
    """Dispatch by schedulerPolicy value (JAX policies only).

    ``native-greedy`` is the serial C++ baseline owned by the controller's
    backend layer, not this module — routing it here would silently run the
    wrong scorer, so it's rejected loudly, as is any unknown policy.

    ``accel`` selects the greedy round-op implementation (see
    ``_resolve_accel``); GSPMD-sharded callers must pass ``'jnp'``.
    """
    if policy == "jax-auction":
        return solve_auction(p, weights, accel=accel)
    if policy == "jax-greedy":
        return solve_greedy(p, weights, accel=accel, seeded=seeded)
    raise ValueError(
        f"unknown JAX solver policy {policy!r}; 'native-greedy' is dispatched "
        "by the controller's SchedulerBackend layer, not the JAX solver"
    )
