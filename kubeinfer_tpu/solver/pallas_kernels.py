"""Pallas TPU kernels for the greedy solver's round loop.

Why these exist: the round loop is a handful of [N, J] reductions whose
producers are broadcasts of [J]/[N] vectors. Under plain XLA each reduction
materializes its producer to HBM (measured ~1.3ms/round at 12288x1024 on a
v5e — ~8 full HBM round-trips), because multi-consumer broadcast producers
defeat reduction fusion. Here each round becomes:

- ONE ``bid`` kernel: tiles the resident [N, J] cost field S through VMEM
  (TILE_N=128 sublanes x TILE_J lanes), fusing feasibility, the per-node
  priority fence, static-bound cost quantization, and the packed
  (cost | node) i32 min — S is read from HBM at most once per round and
  nothing [N, J]-sized is ever written back. The J axis is tiled so VMEM
  holds at most [128, 1024] f32 per block regardless of the job bucket —
  the 50k-job soak shape would otherwise blow the 16MB VMEM scoped limit.
  The fence minimum over ALL jobs (``minrank``) therefore arrives as an
  input (it only reads vectors; the caller computes it as a fused jnp
  reduction).
- TWO ``accept`` passes (first chance + second chance), each a verdict
  kernel (per-node bidder totals + winner + fit verdicts + consumed
  capacity in one sweep — the [TILE_N, TILE_J] broadcast lives only in
  VMEM, accumulating across J tiles) feeding a ``flags`` kernel (the
  per-job accept bit, ``core._dense_accept``'s [N, J] broadcast-compare
  + any). Fusing the fit/consumed [N]-vector math into the verdict sweep
  removes ~6 XLA fusions per accept from the dispatch-bound round.
- ONE ``fence`` kernel: the per-node fence minimum (``core._fence_minrank``),
  an [N, J] feasibility broadcast + rank min — under XLA another full
  [N, J] VPU pass per round even though its inputs are vectors.

Per-J-tile early-out (the round-3 speedup): every kernel takes a
scalar-prefetched per-tile activity vector. The priority fence means only
one fence class (~1/4 of jobs, when the backend priority-sorts the job
axis) can bid in any round, and late rounds are straggler tails of a few
hundred jobs — so most J tiles provably produce no bids (all-BIG output /
zero accept contribution). Inactive tiles skip their compute, and the bid
kernel also skips the S HBM read itself: its S BlockSpec index_map routes
an inactive tile to the previous active tile's block, and Mosaic's
pipeline elides the DMA when consecutive grid steps map to the same block
(measured on v5e: 11/12 tiles aliased -> ~8x less bid-kernel time).
Activity is computed from the same fence/placed vectors the kernels
already consume, so skipping is bit-identical to the dense evaluation
(an inactive tile's jobs all fail the in-kernel ``allowed`` mask anyway).

The jnp reference implementations live in ``core.py`` (`_round_bids_jnp`,
`_accept_reduce_jnp`) and remain the code path for CPU tests, sharded
(GSPMD) solves, and bucket shapes not divisible by 128. ``interpret=True``
runs these kernels on CPU for parity tests.

Design refs: /opt/skills/guides/pallas_guide.md (grid/BlockSpec, iota,
reduction patterns). No reference-repo counterpart exists: the reference
scheduler has no placement solver at all (SURVEY.md §0).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE_N = 128
# 1024 measured best on v5e: full HBM bandwidth on the S sweep (746GB/s vs
# 521GB/s at 512 — per-grid-step overhead bites below 1024) while keeping
# the early-out granularity fine enough that one fence class spans ~3 of
# 12 tiles at the 12288-job bucket.
MAX_TILE_J = 1024
# Plain Python scalars: module-level jnp constants would be captured by the
# kernel closures, which pallas_call rejects ("captures constants"). Packed
# values are non-negative int32 (i31): Mosaic has no unsigned reductions.
_I32MAX = 0x7FFFFFFF
_EPS = 1e-4
# Large-but-finite sentinel for "this job may not bid" (placed/invalid);
# finite so `rank <= minrank` comparisons stay well-defined.
RANK_INF = 1e9


def _require_aligned(N: int, J: int) -> None:
    """All round kernels share the same layout contract: node axis a
    multiple of TILE_N, job axis 128-lane aligned."""
    if N % TILE_N or J % 128:
        raise ValueError(
            f"pallas round kernels need 128-aligned axes, got N={N} J={J}; "
            "use accel='jnp' for unaligned bucket shapes"
        )


def _tile_j(J: int) -> int:
    """Largest J tile <= MAX_TILE_J that divides the bucket (buckets are
    128-aligned)."""
    if J <= MAX_TILE_J:
        return J
    for t in (MAX_TILE_J, 768, 512, 384, 256, 128):
        if J % t == 0:
            return t
    raise ValueError(f"no J tile divides {J}")


def tile_activity(
    active_j: jax.Array,  # bool[J] "this job may produce a bid"
    J: int,
) -> tuple[jax.Array, jax.Array]:
    """Per-J-tile (alias, act) vectors for the scalar-prefetch early-out.

    ``act[t]`` is 1 iff any job in tile t is active. ``alias[t]`` is the
    S-block index the bid kernel should load for tile t: t itself when
    active, else the nearest active tile at or before t (falling back to
    0 for a leading inactive run) — consecutive grid steps then map to
    the same block and Mosaic skips the DMA entirely.
    """
    tj = _tile_j(J)
    tiles = J // tj
    act = jnp.any(active_j.reshape(tiles, tj), axis=1)
    t_iota = jnp.arange(tiles, dtype=jnp.int32)
    alias = jnp.maximum(
        jax.lax.cummax(jnp.where(act, t_iota, jnp.int32(-1))), 0
    )
    return alias.astype(jnp.int32), act.astype(jnp.int32)


def _bid_kernel(
    alias_ref,  # i32[tiles_j] scalar-prefetch: S block to load per tile
    act_ref,  # i32[tiles_j] scalar-prefetch: 1 = tile has potential bidders
    d_ref,  # [1, TILE_J] f32 gpu demand
    md_ref,  # [1, TILE_J] f32 mem demand
    rankf_ref,  # [1, TILE_J] f32 fence rank, RANK_INF when may-not-bid
    cur_ref,  # [1, TILE_J] i32 incumbent node index, -1 = none
    gf_ref,  # [TILE_N, 1] f32 gpu free (invalid nodes pre-folded to -1)
    mf_ref,  # [TILE_N, 1] f32 mem free
    u_ref,  # [TILE_N, 1] f32 live best-fit pressure
    minrank_ref,  # [TILE_N, 1] f32 per-node fence minimum (over ALL jobs)
    s_ref,  # [TILE_N, TILE_J] f32 resident cost field tile (aliased when
    #         inactive — contents must not be read then)
    out_ref,  # [8, TILE_J] i32 per-16-node-group packed (cost | node) mins
    *,
    q_lo: float,
    q_scale: float,
    q_max: float,
    node_idx_bits: int,
):
    del alias_ref  # consumed by the S BlockSpec index_map only
    tn = pl.program_id(0)
    tj = pl.program_id(1)
    big = jnp.int32(_I32MAX)
    rank_inf = jnp.float32(RANK_INF)

    # Inactive tile: every job in it fails the `allowed` mask below (its
    # rank exceeds every node's fence minimum and it has no home-bid
    # exemption — see core's activity rule), so the dense result is
    # all-BIG. Emit that directly; the S block under s_ref is an aliased
    # stand-in whose DMA the pipeline already skipped.
    @pl.when(act_ref[tj] == 0)
    def _inactive():
        out_ref[:] = jnp.full_like(out_ref, big)

    @pl.when(act_ref[tj] != 0)
    def _active():
        d = d_ref[:]
        md = md_ref[:]
        rankf = rankf_ref[:]
        gf = gf_ref[:]
        mf = mf_ref[:]

        feas = (d <= gf + _EPS) & (md <= mf + _EPS)  # [TILE_N, TILE_J]
        q = jnp.clip((s_ref[:] + u_ref[:] - q_lo) * q_scale, 0.0, q_max)
        n_glob = tn * TILE_N + jax.lax.broadcasted_iota(
            jnp.int32, feas.shape, 0
        )
        # Per-node priority fence: bid only if no higher-priority unplaced
        # job finds this node feasible anywhere in [0, J). RANK_INF rows
        # drop out. Incumbents are exempt on their OWN node
        # (core._round_bids_jnp twin).
        is_home = cur_ref[:] == n_glob
        allowed = (
            feas
            & ((rankf <= minrank_ref[:]) | is_home)
            & (rankf < rank_inf * 0.5)
        )
        packed = jnp.where(
            allowed,
            (q.astype(jnp.int32) << node_idx_bits) | n_glob,
            big,
        )
        # Eight 16-node group mins per tile: the TPU output block needs
        # >= 8 sublanes anyway, and finer groups give the second-chance
        # pass better alternates. Even a single-tile problem (N=128) has
        # 7 other groups.
        out_ref[:] = jnp.min(
            packed.reshape(8, TILE_N // 8, packed.shape[1]), axis=1
        )


def bid_reduce_pallas(
    s_t: jax.Array,  # [N, J] resident cost field
    u: jax.Array,  # [N]
    gf_eff: jax.Array,  # [N] (invalid nodes folded to -1)
    mf: jax.Array,  # [N]
    d: jax.Array,  # [J]
    md: jax.Array,  # [J]
    rankf_eff: jax.Array,  # [J] (RANK_INF when may-not-bid)
    minrank: jax.Array,  # [N] fence minimum over all jobs
    current_node: jax.Array,  # i32[J] incumbent node index, -1 = none
    tile_alias: jax.Array,  # i32[tiles_j] S block per tile (see
    #                         tile_activity)
    tile_act: jax.Array,  # i32[tiles_j] 1 = tile may produce bids
    *,
    q_lo: float,
    q_scale: float,
    q_max: float,
    node_idx_bits: int,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """At most one S read -> (primary, alternate) packed i32 bids per job.

    The alternate is the best node outside the primary's 16-node group —
    a cross-group second choice for the solver's second-chance pass.
    Group mins match core._round_bids_jnp exactly (parity-tested).
    Inactive J tiles (``tile_act`` 0) emit BIG without touching HBM.
    """
    N, J = s_t.shape
    _require_aligned(N, J)
    tiles_n = N // TILE_N
    tile_j = _tile_j(J)
    tiles_j = J // tile_j
    kern = functools.partial(
        _bid_kernel,
        q_lo=q_lo,
        q_scale=q_scale,
        q_max=q_max,
        node_idx_bits=node_idx_bits,
    )
    # grid (tn, tj): every (tn, tj) writes a disjoint output block, so
    # grid order is free; tj innermost keeps S reads sequential per node
    # tile AND makes aliased (inactive) tiles consecutive with the active
    # block they point at, which is what lets the pipeline elide their
    # DMAs.
    row = pl.BlockSpec(
        (1, tile_j), lambda tn, tj, alias, act: (0, tj),
        memory_space=pltpu.VMEM,
    )
    col = pl.BlockSpec(
        (TILE_N, 1), lambda tn, tj, alias, act: (tn, 0),
        memory_space=pltpu.VMEM,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(tiles_n, tiles_j),
        in_specs=[
            row,  # d
            row,  # md
            row,  # rankf
            row,  # current_node
            col,  # gf
            col,  # mf
            col,  # u
            col,  # minrank
            pl.BlockSpec(
                (TILE_N, tile_j),
                lambda tn, tj, alias, act: (tn, alias[tj]),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=pl.BlockSpec(
            (8, tile_j), lambda tn, tj, alias, act: (tn, tj),
            memory_space=pltpu.VMEM,
        ),
    )
    per_group = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((8 * tiles_n, J), jnp.int32),
        interpret=interpret,
    )(
        tile_alias,
        tile_act,
        d.reshape(1, J),
        md.reshape(1, J),
        rankf_eff.reshape(1, J),
        current_node.reshape(1, J),
        gf_eff.reshape(N, 1),
        mf.reshape(N, 1),
        u.reshape(N, 1),
        minrank.reshape(N, 1),
        s_t,
    )
    return bid_select_pallas(
        per_group, tile_alias, tile_act, interpret=interpret
    )


def _accept_verdict_kernel(
    act_ref,  # i32[tiles_j] scalar-prefetch: 1 = tile may hold bidders
    ch_ref,  # [1, TILE_J] i32 chosen node (N = no bid)
    key_ref,  # [1, TILE_J] i32 accept key
    d_ref,  # [1, TILE_J] f32 gpu demand
    md_ref,  # [1, TILE_J] f32 mem demand
    gf_ref,  # [TILE_N, 1] f32 gpu free (the capacities bids fit against)
    mf_ref,  # [TILE_N, 1] f32 mem free
    ug_ref,  # [TILE_N, 1] f32 out: capacity consumed (gpu)
    um_ref,  # [TILE_N, 1] f32 out: capacity consumed (mem)
    okall_ref,  # [TILE_N, 1] i32 out: node accepts all bidders
    okwin_ref,  # [TILE_N, 1] i32 out: node accepts its winner
    win_ref,  # [TILE_N, 1] i32 out: winning key
    tg_scr,  # [TILE_N, 1] f32 scratch: bidder gpu total
    tm_scr,  # [TILE_N, 1] f32 scratch
    wd_scr,  # [TILE_N, 1] f32 scratch: winner gpu demand
    wmd_scr,  # [TILE_N, 1] f32 scratch
    *,
    tiles_j: int,
):
    """Accept totals + fit verdicts + consumed capacity in ONE sweep —
    the accept_reduce kernel plus the ~6 inter-kernel [N]-vector fusions
    (fits_all/fits_win/used_*) that each cost dispatch latency in the
    round's critical path (the pipelined solve is launch-bound, not
    bandwidth-bound: many small kernels per round)."""
    tn = pl.program_id(0)
    tj = pl.program_id(1)
    big = jnp.int32(_I32MAX)

    @pl.when(tj == 0)
    def _init():
        tg_scr[:] = jnp.zeros_like(tg_scr)
        tm_scr[:] = jnp.zeros_like(tm_scr)
        win_ref[:] = jnp.full_like(win_ref, big)
        wd_scr[:] = jnp.zeros_like(wd_scr)
        wmd_scr[:] = jnp.zeros_like(wmd_scr)

    @pl.when(act_ref[tj] != 0)
    def _accum():
        ch = ch_ref[:]
        key = key_ref[:]
        n_glob = tn * TILE_N + jax.lax.broadcasted_iota(
            jnp.int32, (TILE_N, ch.shape[1]), 0
        )
        mine = ch == n_glob
        tg = jnp.sum(jnp.where(mine, d_ref[:], 0.0), axis=1, keepdims=True)
        tm = jnp.sum(jnp.where(mine, md_ref[:], 0.0), axis=1, keepdims=True)
        win = jnp.min(jnp.where(mine, key, big), axis=1, keepdims=True)
        new_win = jnp.minimum(win_ref[:], win)
        winner = mine & (key == new_win)
        wd = jnp.sum(jnp.where(winner, d_ref[:], 0.0), axis=1, keepdims=True)
        wmd = jnp.sum(
            jnp.where(winner, md_ref[:], 0.0), axis=1, keepdims=True
        )
        take = win < win_ref[:]
        tg_scr[:] = tg_scr[:] + tg
        tm_scr[:] = tm_scr[:] + tm
        win_ref[:] = new_win
        wd_scr[:] = jnp.where(take, wd, wd_scr[:])
        wmd_scr[:] = jnp.where(take, wmd, wmd_scr[:])

    @pl.when(tj == tiles_j - 1)
    def _verdicts():
        gf = gf_ref[:]
        mf = mf_ref[:]
        fits_all = (tg_scr[:] <= gf + _EPS) & (tm_scr[:] <= mf + _EPS)
        has_win = win_ref[:] != big
        fits_win = (
            has_win
            & (wd_scr[:] <= gf + _EPS)
            & (wmd_scr[:] <= mf + _EPS)
        )
        okall_ref[:] = fits_all.astype(jnp.int32)
        okwin_ref[:] = fits_win.astype(jnp.int32)
        ug_ref[:] = jnp.where(
            fits_all, tg_scr[:], jnp.where(fits_win, wd_scr[:], 0.0)
        )
        um_ref[:] = jnp.where(
            fits_all, tm_scr[:], jnp.where(fits_win, wmd_scr[:], 0.0)
        )


def accept_phase_pallas(
    choice: jax.Array,  # i32[J] chosen node (N sentinel = no bid)
    accept_key: jax.Array,  # i32[J]
    d: jax.Array,  # f32[J]
    md: jax.Array,  # f32[J]
    gpu_free: jax.Array,  # f32[N]
    mem_free: jax.Array,  # f32[N]
    tile_act: jax.Array,  # i32[tiles_j]
    *,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """(accept bool[J], used_gpu f32[N], used_mem f32[N]) for one accept
    pass: the verdict kernel (totals + fits + consumed capacity in one
    sweep) feeds the flags kernel directly — no [N]-vector glue between
    launches. Parity twin of core._dense_accept."""
    J = choice.shape[0]
    N = gpu_free.shape[0]
    _require_aligned(N, J)
    tiles_n = N // TILE_N
    tile_j = _tile_j(J)
    tiles_j = J // tile_j
    row = pl.BlockSpec(
        (1, tile_j), lambda tn, tj, act: (0, tj), memory_space=pltpu.VMEM
    )
    col = pl.BlockSpec(
        (TILE_N, 1), lambda tn, tj, act: (tn, 0), memory_space=pltpu.VMEM
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(tiles_n, tiles_j),
        in_specs=[row, row, row, row, col, col],
        out_specs=[col] * 5,
        scratch_shapes=[
            pltpu.VMEM((TILE_N, 1), jnp.float32),
            pltpu.VMEM((TILE_N, 1), jnp.float32),
            pltpu.VMEM((TILE_N, 1), jnp.float32),
            pltpu.VMEM((TILE_N, 1), jnp.float32),
        ],
    )
    kern = functools.partial(_accept_verdict_kernel, tiles_j=tiles_j)
    ug, um, okall, okwin, win = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((N, 1), jnp.float32),
            jax.ShapeDtypeStruct((N, 1), jnp.float32),
            jax.ShapeDtypeStruct((N, 1), jnp.int32),
            jax.ShapeDtypeStruct((N, 1), jnp.int32),
            jax.ShapeDtypeStruct((N, 1), jnp.int32),
        ],
        interpret=interpret,
    )(
        tile_act,
        choice.reshape(1, J),
        accept_key.reshape(1, J),
        d.reshape(1, J),
        md.reshape(1, J),
        gpu_free.reshape(N, 1),
        mem_free.reshape(N, 1),
    )
    accept = accept_flags_pallas(
        choice, accept_key, okall[:, 0], okwin[:, 0], win[:, 0], tile_act,
        interpret=interpret,
    )
    return accept, ug[:, 0], um[:, 0]


def _bid_select_kernel(
    alias_ref,  # i32[tiles_j] scalar-prefetch: per_group block per tile
    act_ref,  # i32[tiles_j] scalar-prefetch: 1 = tile may hold bids
    pg_ref,  # [G, TILE_J] i32 per-16-node-group packed mins
    prim_ref,  # [1, TILE_J] i32 out
    alt_ref,  # [1, TILE_J] i32 out
):
    del alias_ref
    tj = pl.program_id(0)
    big = jnp.int32(_I32MAX)

    @pl.when(act_ref[tj] == 0)
    def _inactive():
        prim_ref[:] = jnp.full_like(prim_ref, big)
        alt_ref[:] = jnp.full_like(alt_ref, big)

    @pl.when(act_ref[tj] != 0)
    def _active():
        pg = pg_ref[:]
        prim = jnp.min(pg, axis=0, keepdims=True)
        # Exclude the primary's group by VALUE, not argmin (Mosaic has no
        # i32 argmin): packed bids embed the node index and each group
        # covers a disjoint 16-node range, so a non-BIG group min is
        # globally unique per column — value exclusion selects exactly
        # the argmin group. All-BIG columns stay BIG either way.
        alt_ref[:] = jnp.min(
            jnp.where(pg == prim, big, pg), axis=0, keepdims=True
        )
        prim_ref[:] = prim


def bid_select_pallas(
    per_group: jax.Array,  # i32[G, J] per-16-node-group packed mins
    tile_alias: jax.Array,  # i32[tiles_j]
    tile_act: jax.Array,  # i32[tiles_j]
    *,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """(primary, alternate) per job from the bid kernel's group mins.

    The jnp form is three reductions (min, argmin, masked min) over the
    same [G, J] producer — three HBM passes under XLA since per_group is
    a materialized kernel output. One Pallas sweep reads it once, and
    inactive J tiles (all-BIG columns) skip their read via the same
    alias trick the bid kernel uses. Must match the tail of
    core._round_bids_jnp bit-for-bit (parity-tested).
    """
    G, J = per_group.shape
    tile_j = _tile_j(J)
    tiles_j = J // tile_j
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(tiles_j,),
        in_specs=[
            pl.BlockSpec(
                (G, tile_j), lambda tj, alias, act: (0, alias[tj]),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=[
            pl.BlockSpec(
                (1, tile_j), lambda tj, alias, act: (0, tj),
                memory_space=pltpu.VMEM,
            ),
        ] * 2,
    )
    prim, alt = pl.pallas_call(
        _bid_select_kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((1, J), jnp.int32),
            jax.ShapeDtypeStruct((1, J), jnp.int32),
        ],
        interpret=interpret,
    )(tile_alias, tile_act, per_group)
    return prim[0], alt[0]


def _fence_kernel(
    act_ref,  # i32[tiles_j] scalar-prefetch: 1 = tile has unplaced jobs
    d_ref,  # [1, TILE_J] f32 gpu demand
    md_ref,  # [1, TILE_J] f32 mem demand
    rankf_ref,  # [1, TILE_J] f32 fence rank (RANK_INF = placed/invalid)
    gf_ref,  # [TILE_N, 1] f32 gpu free
    mf_ref,  # [TILE_N, 1] f32 mem free
    out_ref,  # [TILE_N, 1] f32 out: per-node fence minimum
):
    tj = pl.program_id(1)
    rank_inf = jnp.float32(RANK_INF)

    @pl.when(tj == 0)
    def _init():
        out_ref[:] = jnp.full_like(out_ref, rank_inf)

    # A tile whose jobs are all placed/invalid contributes only RANK_INF
    # (its rankf rows are RANK_INF), so skipping it is exact.
    @pl.when(act_ref[tj] != 0)
    def _accum():
        feas = (d_ref[:] <= gf_ref[:] + _EPS) & (md_ref[:] <= mf_ref[:] + _EPS)
        part = jnp.min(
            jnp.where(feas, rankf_ref[:], rank_inf), axis=1, keepdims=True
        )
        out_ref[:] = jnp.minimum(out_ref[:], part)


def fence_minrank_pallas(
    gpu_free: jax.Array,  # f32[N]
    mem_free: jax.Array,  # f32[N]
    gpu_demand: jax.Array,  # f32[J]
    mem_demand: jax.Array,  # f32[J]
    rankf_eff: jax.Array,  # f32[J] (RANK_INF = placed/invalid)
    tile_act: jax.Array,  # i32[tiles_j] 1 = tile has unplaced jobs
    *,
    interpret: bool = False,
) -> jax.Array:
    """Per-node fence minimum — Pallas twin of ``core._fence_minrank``.

    Skips J tiles whose jobs are all placed (their ranks are RANK_INF and
    cannot lower any node's minimum). With the job axis priority-sorted,
    placed jobs become a contiguous prefix as fence classes settle, so
    late rounds reduce over a small suffix instead of all J.
    """
    N = gpu_free.shape[0]
    J = gpu_demand.shape[0]
    _require_aligned(N, J)
    tiles_n = N // TILE_N
    tile_j = _tile_j(J)
    tiles_j = J // tile_j
    row = pl.BlockSpec(
        (1, tile_j), lambda tn, tj, act: (0, tj), memory_space=pltpu.VMEM
    )
    col = pl.BlockSpec(
        (TILE_N, 1), lambda tn, tj, act: (tn, 0), memory_space=pltpu.VMEM
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(tiles_n, tiles_j),
        in_specs=[row, row, row, col, col],
        out_specs=col,
    )
    out = pl.pallas_call(
        _fence_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((N, 1), jnp.float32),
        interpret=interpret,
    )(
        tile_act,
        gpu_demand.reshape(1, J),
        mem_demand.reshape(1, J),
        rankf_eff.reshape(1, J),
        gpu_free.reshape(N, 1),
        mem_free.reshape(N, 1),
    )
    return out[:, 0]


# --- Round-fusion mega-kernel (class-serialized greedy) ---------------------
#
# The pipelined round loop above is launch-bound, not bandwidth-bound:
# ~47 XLA fusions + 7 Pallas launches per round, next to which the actual
# S traffic is small. The fix is to
# stop paying per-round launches at all: serialize the priority fence classes
# (the job axis arrives priority-sorted from backends.py, so a fence class is
# a contiguous column window) and run EVERY settlement round of a class
# inside one grid step of ONE pallas_call, with the class's S window resident
# in VMEM and the capacity vectors resident across grid steps.
#
# Windows are VMEM-budget-sized, not priority-aligned, so a window can mix
# priority levels; the per-node fence therefore still runs INSIDE the
# window — as one [N,1] reduce over the resident block per round, costing
# nothing next to the old standalone fence kernel + launch. Cross-window
# inversion is prevented by the serialization itself (earlier windows hold
# all strictly-higher priority ranks when the job axis is sorted). The
# separate fence kernel, its launch, and the activity vectors disappear.
# The home-bid fence exemption is KEPT (an incumbent may always bid its own
# node): a fence-free-for-incumbents round is what holds survivor moves at
# ~0.2% under churn — dropping it was tried and measured at 6.1% moves on
# the 10k bench shape — at the price of the same documented inversion the
# pipelined path accepts (see _mega_round_math). The result is NOT
# bit-identical to the pipelined algorithm (later windows see
# post-settlement capacities instead of bidding early on unfenced nodes —
# if anything a closer match to serial FFD). It keeps the same hard
# guarantees: no overcommit ever, at exit no unplaced job finds any node
# feasible (capacities only shrink, so earlier windows' fixpoints survive
# later consumption), and no job is fenced out by an equal-or-lower rank.
#
# Parity contract: the kernel body and the pure-jnp twin (mega_rounds_jnp)
# share _mega_round_math, so interpret-mode output is bit-identical to the
# twin by construction (f32 demand sums are dyadic rationals — order-safe).

# VMEM budget for the resident S window. The round loop's live temporaries
# (packed bids, masks, accept reductions) cost ~5x the S window itself, so
# the whole kernel wants ~6-7x this in scoped VMEM — the explicit
# vmem_limit below raises Mosaic's 16MB default (v5e has 128MB physical
# VMEM; measured stack need at W=1024, N=1024 is ~27MB).
# Measured on v5e at 10k x 1k (scripts/mega_timing.py), final fenced
# kernel: W=1024 1.43ms / W=2048 ~1.20ms / W=3072 1.78ms — fewer, wider
# windows amortize per-round reduction latency until the pass cost (and,
# past W=2048, mixed-rank fence serialization) dominates. The fence-free
# prototype ranked the same W ordering at 1.15 / 1.00 / 1.15.
_MEGA_S_BYTES = 8 * 1024 * 1024
_MEGA_VMEM_LIMIT = 100 * 1024 * 1024


def mega_window(N: int, J: int) -> int | None:
    """Class-window width for the mega path: the largest 128-multiple
    dividing J whose [N, W] f32 S window fits the VMEM budget. None when
    no window fits (huge N) — callers fall back to the pipelined path.

    Unlike the tiled round kernels, mega takes the whole node axis in one
    block, so N only needs f32 sublane alignment (N % 8), not TILE_N.
    The one bucket below 128 (J=64) gets a single 64-wide window — a
    twin/interpret-only shape (Mosaic lanes want 128; `_resolve_accel`
    never routes it to the real kernel)."""
    if N % 8:
        return None
    fit = _MEGA_S_BYTES // (4 * N)
    wmax = min(J, fit // 128 * 128)
    if J % 128 == 0 and wmax >= 128:
        for w in range(wmax, 0, -128):
            if J % w == 0:
                return w
    if J == 64 and fit >= J:
        return J  # the one sub-128 bucket: a single 64-wide window
    return None  # N too large for any window: pipelined fallback


def _mega_round_math(
    Sq,  # [N, W] resident PRE-QUANTIZED cost window: (S - q_lo) * q_scale,
    #      computed once per window entry — saves an [N, W] ALU pass per
    #      round vs renormalizing S each time
    d,  # [1, W] gpu demand
    md,  # [1, W] mem demand
    key,  # [1, W] i32 accept key (rank | demand desc | index)
    rank,  # [1, W] f32 fence rank (class-compressed crank; RANK_INF for
    #        invalid jobs)
    cur,  # [1, W] i32 incumbent node index (-1 = none)
    may,  # [1, W] bool job may ever bid (valid)
    asg,  # [1, W] i32 assigned node, -1 = unplaced
    gf,  # [N, 1] gpu free (invalid nodes folded to -1)
    mf,  # [N, 1] mem free
    vg,  # [N, 1] fit-pressure weights (w_gpu / cap)
    vm,  # [N, 1]
    *,
    q_scale: float,
    q_max: float,
    node_idx_bits: int,
):
    """One serialized-class settlement round on resident values.

    Shared verbatim by the Mosaic kernel body and the jnp twin — parity by
    construction. Returns (asg, gf, mf, progress): in-window per-node
    priority fence (windows can mix fence classes — VMEM sizes them, not
    priority boundaries), bid (packed masked min over nodes), per-node
    joint-fit/winner accept (core._dense_accept's rule), capacity update.
    ``progress`` is False at the window fixpoint — additionally cut short
    when no unplaced demand fits the largest free node (saves the
    all-infeasible discovery round on exhausted-capacity windows, e.g.
    most of the 50k soak's tail)."""
    big = jnp.int32(_I32MAX)
    rank_inf = jnp.float32(RANK_INF)
    N = Sq.shape[0]
    unpl = may & (asg < 0)  # [1, W]
    feas = (d <= gf + _EPS) & (md <= mf + _EPS) & unpl  # [N, W]
    # Per-node fence over the resident window: job j may bid node n only
    # if no unplaced higher-rank job finds n feasible. The [N, W] fence
    # reduce only runs while the UNPLACED set actually spans more than
    # one rank — [1, W] min/max reduces detect that per round, so
    # single-class windows and straggler tails (conflict losers are
    # almost always one rank) skip it entirely.
    rank_eff = jnp.where(unpl, rank, rank_inf)
    r_lo = jnp.min(rank_eff)
    r_hi = jnp.max(jnp.where(unpl, rank, -rank_inf))
    minrank = jax.lax.cond(
        r_lo < r_hi,
        lambda: jnp.min(
            jnp.where(feas, rank_eff, rank_inf), axis=1, keepdims=True
        ),
        lambda: jnp.full((feas.shape[0], 1), rank_inf, jnp.float32),
    )
    n_glob = jax.lax.broadcasted_iota(jnp.int32, feas.shape, 0)
    # Home-bid fence exemption (same trade the pipelined path makes,
    # core._round_bids_jnp): an incumbent may always bid its OWN node —
    # rank-ordered acceptance there still lets a same-node higher-rank
    # bidder win, but without the exemption every fenced round strands
    # incumbents whose nodes interest a higher class, and survivor moves
    # under 10% churn measured 6.1% (BENCH r4 pre-fix) vs the ~0.2%
    # stability contract (BASELINE config 4). The cost is the one known
    # inversion: an incumbent's early home-grab can deflect a
    # higher-rank job that only discovers the node a round later.
    feas = feas & ((rank_eff <= minrank) | (cur == n_glob))
    # live best-fit pressure, pre-scaled into quantized units ([N, 1])
    uq = (vg * gf + vm * mf) * q_scale
    q = jnp.clip(Sq + uq, 0.0, q_max)
    packed = jnp.where(feas, (q.astype(jnp.int32) << node_idx_bits) | n_glob, big)
    prim = jnp.min(packed, axis=0, keepdims=True)  # [1, W]
    node_mask = jnp.int32((1 << node_idx_bits) - 1)
    choice = jnp.where(prim != big, prim & node_mask, jnp.int32(N))
    mine = choice == n_glob  # [N, W]; sentinel N matches no row
    tg = jnp.sum(jnp.where(mine, d, 0.0), axis=1, keepdims=True)  # [N, 1]
    tm = jnp.sum(jnp.where(mine, md, 0.0), axis=1, keepdims=True)
    win = jnp.min(jnp.where(mine, key, big), axis=1, keepdims=True)
    fits_all = (tg <= gf + _EPS) & (tm <= mf + _EPS)
    # Unlike the pipelined accept (whose second-chance pass re-checks
    # against post-first-pass capacities), every mega bid is made against
    # exactly the capacities this accept checks, so a contested node's
    # single winner always fits — no separate winner-fit test. One ``ok``
    # mask then drives the accept flags AND the consumed-capacity sums in
    # the same sweep (the pipelined kernels need separate winner-demand
    # reductions because their flags kernel runs in another launch).
    ok = mine & (fits_all | (key == win))
    accept = jnp.any(ok, axis=0, keepdims=True)
    used_g = jnp.sum(jnp.where(ok, d, 0.0), axis=1, keepdims=True)
    used_m = jnp.sum(jnp.where(ok, md, 0.0), axis=1, keepdims=True)
    asg = jnp.where(accept, choice, asg)
    gf = gf - used_g
    mf = mf - used_m
    # Fixpoint detection: accepts this round AND something still unplaced
    # AND the smallest remaining gpu demand fits the roomiest node (a cheap
    # O(N)+O(W) necessary condition for any further bid).
    still = may & (asg < 0)
    min_d = jnp.min(jnp.where(still, d, jnp.float32(3.4e38)))
    progress = (
        jnp.any(accept)
        & jnp.any(still)
        & (min_d <= jnp.max(gf) + _EPS)
    )
    return asg, gf, mf, progress


def _mega_kernel(
    d_ref,  # [1, W] f32 gpu demand (class window)
    md_ref,  # [1, W] f32 mem demand
    key_ref,  # [1, W] i32 accept key
    rank_ref,  # [1, W] f32 fence rank (RANK_INF for invalid)
    cur_ref,  # [1, W] i32 incumbent node index (-1 = none)
    asg0_ref,  # [1, W] i32 seeded assignment (-1 = unplaced) — churn
    #            re-solves seat joint-fitting incumbents up front
    may_ref,  # [1, W] i32 job validity (1 = may bid)
    gf0_ref,  # [N, 1] f32 starting gpu free (invalid nodes folded to -1)
    mf0_ref,  # [N, 1] f32 starting mem free
    vg_ref,  # [N, 1] f32 fit-pressure weights
    vm_ref,  # [N, 1] f32
    s_ref,  # [N, W] f32 resident cost window for this class
    asg_ref,  # [1, W] i32 out: assigned node (-1 unplaced)
    gf_ref,  # [N, 1] f32 out: free capacity, resident across classes
    mf_ref,  # [N, 1] f32 out
    rounds_ref,  # [1, 1] i32 out (SMEM): total settlement rounds
    capped_ref,  # [1, 1] i32 out (SMEM): 1 = some window hit max_rounds
    #              with progress still possible (budget exhaustion signal)
    *,
    max_rounds: int,
    q_lo: float,
    q_scale: float,
    q_max: float,
    node_idx_bits: int,
):
    c = pl.program_id(0)

    @pl.when(c == 0)
    def _init():
        gf_ref[:] = gf0_ref[:]
        mf_ref[:] = mf0_ref[:]
        rounds_ref[0, 0] = 0
        capped_ref[0, 0] = 0

    d = d_ref[:]
    md = md_ref[:]
    key = key_ref[:]
    rank = rank_ref[:]
    cur = cur_ref[:]
    may = may_ref[:] != 0
    Sq = (s_ref[:] - q_lo) * q_scale  # once per window, not per round
    vg = vg_ref[:]
    vm = vm_ref[:]

    def cond(carry):
        _, _, _, r, prog = carry
        return prog & (r < max_rounds)

    def body(carry):
        asg, gf, mf, r, _ = carry
        asg, gf, mf, prog = _mega_round_math(
            Sq, d, md, key, rank, cur, may, asg, gf, mf, vg, vm,
            q_scale=q_scale, q_max=q_max,
            node_idx_bits=node_idx_bits,
        )
        return asg, gf, mf, r + jnp.int32(1), prog

    gf_in = gf_ref[:]
    mf_in = mf_ref[:]
    asg0 = asg0_ref[:]
    unpl0 = may & (asg0 < 0)
    init_prog = jnp.any(unpl0) & (
        jnp.min(jnp.where(unpl0, d, jnp.float32(3.4e38)))
        <= jnp.max(gf_in) + _EPS
    )
    asg, gf, mf, r, prog = jax.lax.while_loop(
        cond, body, (asg0, gf_in, mf_in, jnp.int32(0), init_prog)
    )
    asg_ref[:] = asg
    gf_ref[:] = gf
    mf_ref[:] = mf
    rounds_ref[0, 0] = rounds_ref[0, 0] + r
    # prog surviving the loop exit means the budget bound, not the
    # fixpoint — the caller's repair/fill safety net keys off this.
    capped_ref[0, 0] = capped_ref[0, 0] | prog.astype(jnp.int32)


def mega_solve_pallas(
    s_t: jax.Array,  # [N, J] resident cost field (priority-sorted J axis)
    d: jax.Array,  # f32[J]
    md: jax.Array,  # f32[J]
    accept_key: jax.Array,  # i32[J]
    rankf: jax.Array,  # f32[J] fence rank (RANK_INF for invalid)
    current_node: jax.Array,  # i32[J] incumbent node (-1 = none)
    asg_init: jax.Array,  # i32[J] seeded assignment (-1 = unplaced);
    #                       gf_eff/mf must already be net of seated jobs
    may_bid: jax.Array,  # bool[J] (valid jobs)
    gf_eff: jax.Array,  # f32[N] (invalid nodes folded to -1)
    mf: jax.Array,  # f32[N]
    vg: jax.Array,  # f32[N]
    vm: jax.Array,  # f32[N]
    *,
    max_rounds: int,
    q_lo: float,
    q_scale: float,
    q_max: float,
    node_idx_bits: int,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Whole greedy main loop in ONE pallas_call.

    Grid steps are contiguous windows of the priority-sorted job axis;
    each step runs its window's settlement rounds to a fixpoint over the
    VMEM-resident S window (with the per-node fence computed in-window)
    while the capacity vectors stay resident in revisited output blocks.
    Returns (assigned i32[J], gpu_free f32[N], mem_free f32[N],
    rounds i32, capped bool). ``max_rounds`` is a PER-WINDOW budget;
    ``capped`` reports any window exiting on it with progress still
    possible. Twin: ``mega_rounds_jnp``.
    """
    N, J = s_t.shape
    W = mega_window(N, J)
    if W is None:
        raise ValueError(f"no mega window for N={N} J={J}")
    n_classes = J // W
    row = pl.BlockSpec((1, W), lambda c: (0, c), memory_space=pltpu.VMEM)
    const_col = pl.BlockSpec(
        (N, 1), lambda c: (0, 0), memory_space=pltpu.VMEM
    )
    smem_scalar = pl.BlockSpec(
        (1, 1), lambda c: (0, 0), memory_space=pltpu.SMEM
    )
    kern = functools.partial(
        _mega_kernel,
        max_rounds=max_rounds,
        q_lo=q_lo,
        q_scale=q_scale,
        q_max=q_max,
        node_idx_bits=node_idx_bits,
    )
    asg, gf, mfo, rounds, capped = pl.pallas_call(
        kern,
        grid=(n_classes,),
        in_specs=[
            row,  # d
            row,  # md
            row,  # key
            row,  # rank
            row,  # cur
            row,  # asg0
            row,  # may
            const_col,  # gf0
            const_col,  # mf0
            const_col,  # vg
            const_col,  # vm
            pl.BlockSpec((N, W), lambda c: (0, c), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            row,
            const_col,
            const_col,
            smem_scalar,
            smem_scalar,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, J), jnp.int32),
            jax.ShapeDtypeStruct((N, 1), jnp.float32),
            jax.ShapeDtypeStruct((N, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_MEGA_VMEM_LIMIT
        ),
    )(
        d.reshape(1, J),
        md.reshape(1, J),
        accept_key.reshape(1, J),
        rankf.reshape(1, J),
        current_node.reshape(1, J),
        asg_init.reshape(1, J),
        may_bid.astype(jnp.int32).reshape(1, J),
        gf_eff.reshape(N, 1),
        mf.reshape(N, 1),
        vg.reshape(N, 1),
        vm.reshape(N, 1),
        s_t,
    )
    return asg[0], gf[:, 0], mfo[:, 0], rounds[0, 0], capped[0, 0] != 0


def mega_rounds_jnp(
    s_t: jax.Array,  # [N, J]
    d: jax.Array,  # f32[J]
    md: jax.Array,
    accept_key: jax.Array,
    rankf: jax.Array,
    current_node: jax.Array,
    asg_init: jax.Array,
    may_bid: jax.Array,
    gf_eff: jax.Array,
    mf: jax.Array,
    vg: jax.Array,
    vm: jax.Array,
    *,
    max_rounds: int,
    q_lo: float,
    q_scale: float,
    q_max: float,
    node_idx_bits: int,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Pure-jnp twin of ``mega_solve_pallas`` — identical class windows,
    identical round math (shared _mega_round_math), bit-identical output.
    The CPU/parity path for the class-serialized algorithm."""
    N, J = s_t.shape
    W = mega_window(N, J)
    if W is None:
        raise ValueError(f"no mega window for N={N} J={J}")
    n_classes = J // W
    d2 = d.reshape(1, J)
    md2 = md.reshape(1, J)
    key2 = accept_key.reshape(1, J)
    rank2 = rankf.reshape(1, J)
    cur2 = current_node.reshape(1, J)
    asg02 = asg_init.reshape(1, J)
    may2 = may_bid.reshape(1, J)
    gf0 = gf_eff.reshape(N, 1)
    mf0 = mf.reshape(N, 1)
    vg2 = vg.reshape(N, 1)
    vm2 = vm.reshape(N, 1)

    def class_body(c, carry):
        asg_full, gf, mf_c, rounds, capped = carry
        col = c * W
        Sw = (
            jax.lax.dynamic_slice(s_t, (0, col), (N, W)) - q_lo
        ) * q_scale
        dw = jax.lax.dynamic_slice(d2, (0, col), (1, W))
        mdw = jax.lax.dynamic_slice(md2, (0, col), (1, W))
        keyw = jax.lax.dynamic_slice(key2, (0, col), (1, W))
        rankw = jax.lax.dynamic_slice(rank2, (0, col), (1, W))
        curw = jax.lax.dynamic_slice(cur2, (0, col), (1, W))
        asg0w = jax.lax.dynamic_slice(asg02, (0, col), (1, W))
        mayw = jax.lax.dynamic_slice(may2, (0, col), (1, W))

        def cond(carry):
            _, _, _, r, prog = carry
            return prog & (r < max_rounds)

        def body(carry):
            asg, gf, mf_c, r, _ = carry
            asg, gf, mf_c, prog = _mega_round_math(
                Sw, dw, mdw, keyw, rankw, curw, mayw, asg, gf, mf_c,
                vg2, vm2,
                q_scale=q_scale, q_max=q_max,
                node_idx_bits=node_idx_bits,
            )
            return asg, gf, mf_c, r + jnp.int32(1), prog

        unpl0 = mayw & (asg0w < 0)
        init_prog = jnp.any(unpl0) & (
            jnp.min(jnp.where(unpl0, dw, jnp.float32(3.4e38)))
            <= jnp.max(gf) + _EPS
        )
        asg, gf, mf_c, r, prog = jax.lax.while_loop(
            cond, body, (asg0w, gf, mf_c, jnp.int32(0), init_prog)
        )
        asg_full = jax.lax.dynamic_update_slice(asg_full, asg, (0, col))
        return asg_full, gf, mf_c, rounds + r, capped | prog

    asg_full, gf, mf_out, rounds, capped = jax.lax.fori_loop(
        0, n_classes, class_body,
        (
            jnp.full((1, J), -1, jnp.int32),
            gf0,
            mf0,
            jnp.int32(0),
            jnp.bool_(False),
        ),
    )
    return asg_full[0], gf[:, 0], mf_out[:, 0], rounds, capped


def _accept_flags_kernel(
    act_ref,  # i32[tiles_j] scalar-prefetch: 1 = tile has bidders
    ch_ref,  # [1, TILE_J] i32 chosen node (N = no bid)
    key_ref,  # [1, TILE_J] i32 accept key
    all_ref,  # [TILE_N, 1] i32 node accepts all bidders (fits_all)
    winok_ref,  # [TILE_N, 1] i32 node accepts its winner (fits_win)
    winkey_ref,  # [TILE_N, 1] i32 winning key per node
    acc_ref,  # [1, TILE_J] i32 out: job's bid accepted
):
    tn = pl.program_id(1)  # inner: accumulate into the resident out block
    tj = pl.program_id(0)

    @pl.when((tn == 0) & (act_ref[tj] == 0))
    def _inactive():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(act_ref[tj] != 0)
    def _active():
        ch = ch_ref[:]
        n_glob = tn * TILE_N + jax.lax.broadcasted_iota(
            jnp.int32, (TILE_N, ch.shape[1]), 0
        )
        mine = ch == n_glob
        ok = (all_ref[:] != 0) | (
            (winok_ref[:] != 0) & (winkey_ref[:] == key_ref[:])
        )
        hit = jnp.any(mine & ok, axis=0, keepdims=True).astype(jnp.int32)

        @pl.when(tn == 0)
        def _init():
            acc_ref[:] = hit

        @pl.when(tn != 0)
        def _accum():
            acc_ref[:] = acc_ref[:] | hit


def accept_flags_pallas(
    choice: jax.Array,  # i32[J]
    accept_key: jax.Array,  # i32[J]
    fits_all: jax.Array,  # bool[N]
    fits_win: jax.Array,  # bool[N]
    win_key: jax.Array,  # i32[N]
    tile_act: jax.Array,  # i32[tiles_j]
    *,
    interpret: bool = False,
) -> jax.Array:
    """Per-job accept bit — the Pallas twin of ``core._dense_accept``'s
    [N, J] broadcast-compare + any() (which XLA runs as a full second
    [N, J] VPU pass per accept). Grid is (tj, tn) with tn INNER so the
    [1, TILE_J] output block stays VMEM-resident across the node sweep
    (accumulating across a non-innermost dim would round-trip the block
    through HBM each step — and Pallas does not guarantee read-back of
    prior contents for non-consecutive revisits)."""
    J = choice.shape[0]
    N = fits_all.shape[0]
    _require_aligned(N, J)
    tiles_n = N // TILE_N
    tile_j = _tile_j(J)
    tiles_j = J // tile_j
    row = pl.BlockSpec(
        (1, tile_j), lambda tj, tn, act: (0, tj), memory_space=pltpu.VMEM
    )
    col = pl.BlockSpec(
        (TILE_N, 1), lambda tj, tn, act: (tn, 0), memory_space=pltpu.VMEM
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(tiles_j, tiles_n),
        in_specs=[row, row, col, col, col],
        out_specs=row,
    )
    acc = pl.pallas_call(
        _accept_flags_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((1, J), jnp.int32),
        interpret=interpret,
    )(
        tile_act,
        choice.reshape(1, J),
        accept_key.reshape(1, J),
        fits_all.astype(jnp.int32).reshape(N, 1),
        fits_win.astype(jnp.int32).reshape(N, 1),
        win_key.reshape(N, 1),
    )
    return acc[0] != 0


# --- auction: the whole Jacobi loop in one launch ------------------------
#
# solve_auction's lax.while_loop costs ~40us of per-iteration launch /
# serialization overhead under XLA (measured r4: 4.78ms for 118 iterations
# at 1kx1k — the same dispatch-bound profile the greedy round loop had
# before the mega kernel). Here the loop runs INSIDE one pallas_call with
# the [J, N] benefit field VMEM-resident; every per-iteration product
# ([J, N] value/bid masks) lives and dies in VMEM. The jnp twin is
# core._auction_loop_jnp — bit-identical by construction: every float is
# either copied through a selection (max/min/where picks) or produced by
# the exact expression the twin uses (bid = price + (best_v - second_v)
# + eps), and all tie-breaks resolve to lowest-index in both.
#
# Scatter-free by necessity (Mosaic has no scatter): the twin's two
# .at[].set scatters (evictions, won-node writeback) become
# broadcast-compare + lane reductions over [J, N] — the same trade the
# accept-verdict kernels made (module docstring).

# Per-iteration live set: benefit + tiebreak inputs plus ~4 [J, N]
# selection temporaries Mosaic keeps concurrently (value, near/tb,
# bids_on, the evict/won compares). 12x input bytes is a conservative
# ceiling under the raised 100MB scoped limit.
_AUCTION_TEMPS = 12


def auction_fits(J: int, N: int) -> bool:
    """True when the one-launch auction's VMEM working set fits."""
    return _AUCTION_TEMPS * J * N * 4 <= _MEGA_VMEM_LIMIT


def _auction_kernel(
    eps_ref,  # SMEM f32 (1,1): runtime bid increment
    benefit_ref,  # VMEM f32 [J, N]; -1e9 marks infeasible
    tiebreak_ref,  # VMEM i32 [J, N] hash (core.py computes it once)
    valid_ref,  # VMEM i32 [J, 1]
    asg_ref,  # out VMEM i32 [J, 1]
    iters_ref,  # out SMEM i32 (1,1)
    *,
    max_iters: int,
    stale_iters: int,
    tie_tol: float,
    neg: float,
):
    J, N = benefit_ref.shape
    benefit = benefit_ref[...]
    tiebreak = tiebreak_ref[...]
    valid = valid_ref[...] != 0  # [J, 1]
    eps = eps_ref[0, 0]
    NEG = jnp.float32(neg)
    n2 = jax.lax.broadcasted_iota(jnp.int32, (J, N), 1)
    j2 = jax.lax.broadcasted_iota(jnp.int32, (J, N), 0)

    def cond(state):
        asg, owner, prices, it, progress, pending_best, stale = state
        pending = jnp.any((asg < 0) & valid)
        return (
            (progress != 0)
            & pending
            & (it < max_iters)
            & (stale < stale_iters)
        )

    def body(state):
        asg, owner, prices, it, _, pending_best, stale = state
        unassigned = (asg < 0) & valid  # [J, 1]
        value = jnp.where(unassigned, benefit - prices, NEG)  # [J, N]
        best_v = jnp.max(value, axis=1, keepdims=True)  # [J, 1]
        near = value >= best_v - jnp.float32(tie_tol)
        tb = jnp.where(near, tiebreak, -1)
        tb_max = jnp.max(tb, axis=1, keepdims=True)
        # argmax(tb, axis=1) with lowest-index ties, scatter-free
        best_n = jnp.min(
            jnp.where(tb == tb_max, n2, N), axis=1, keepdims=True
        )
        at_best = n2 == best_n  # [J, N]: job j's single bid target
        second_v = jnp.max(
            jnp.where(at_best, NEG, value), axis=1, keepdims=True
        )
        can_bid = unassigned & (best_v > NEG * 0.5)  # [J, 1]
        price_at_best = jnp.max(
            jnp.where(at_best, jnp.broadcast_to(prices, (J, N)), NEG),
            axis=1, keepdims=True,
        )  # gather prices[best_n] as a lane selection
        bid = jnp.where(
            can_bid, price_at_best + (best_v - second_v) + eps, NEG
        )  # [J, 1]

        bids_on = jnp.where(at_best & can_bid, bid, NEG)  # [J, N]
        win_bid = jnp.max(bids_on, axis=0, keepdims=True)  # [1, N]
        winner = jnp.min(
            jnp.where(bids_on == win_bid, j2, J), axis=0, keepdims=True
        )  # [1, N]: highest bid, lowest job index on float ties
        node_has_winner = win_bid > NEG * 0.5  # [1, N]

        # twin's eviction scatter: job j is evicted iff some re-won node
        # listed it as owner
        evict = (
            jnp.max(
                jnp.where(node_has_winner & (owner == j2), 1, 0),
                axis=1, keepdims=True,
            )
            > 0
        )  # [J, 1]
        asg = jnp.where(evict, -1, asg)
        owner = jnp.where(node_has_winner, winner, owner)
        prices = jnp.where(node_has_winner, win_bid, prices)
        # twin's won-node scatter: each winning job finds its (unique)
        # node by lane reduction
        won_node = jnp.min(
            jnp.where(node_has_winner & (winner == j2), n2, N),
            axis=1, keepdims=True,
        )  # [J, 1]
        asg = jnp.where(won_node < N, won_node, asg)
        n_pending = jnp.sum(((asg < 0) & valid).astype(jnp.int32))
        improved = n_pending < pending_best
        return (
            asg, owner, prices,
            it + jnp.int32(1),
            jnp.any(can_bid).astype(jnp.int32),
            jnp.minimum(n_pending, pending_best),
            jnp.where(improved, jnp.int32(0), stale + jnp.int32(1)),
        )

    init = (
        jnp.full((J, 1), -1, jnp.int32),
        jnp.full((1, N), -1, jnp.int32),
        jnp.zeros((1, N), jnp.float32),
        jnp.int32(0),
        jnp.int32(1),
        jnp.int32(J + 1),
        jnp.int32(0),
    )
    asg, _, _, it, _, _, _ = jax.lax.while_loop(cond, body, init)
    asg_ref[...] = asg
    iters_ref[0, 0] = it


def auction_solve(
    benefit: jax.Array,  # f32[J, N]
    tiebreak: jax.Array,  # i32[J, N]
    valid: jax.Array,  # bool[J]
    eps: jax.Array,  # f32 scalar (traced — a tunable request field)
    *,
    max_iters: int,
    stale_iters: int,
    tie_tol: float,
    neg: float,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """One-launch auction loop. Returns (assigned i32[J], iters i32).

    Twin: ``core._auction_loop_jnp`` (bit-identical; parity test in
    tests/test_solver_core.py). Callers gate on ``auction_fits`` and the
    J%8 / N%128 Mosaic layout requirements (core._auction_accel)."""
    J, N = benefit.shape
    kern = functools.partial(
        _auction_kernel,
        max_iters=max_iters,
        stale_iters=stale_iters,
        tie_tol=tie_tol,
        neg=neg,
    )
    full = pl.BlockSpec((J, N), lambda: (0, 0), memory_space=pltpu.VMEM)
    col = pl.BlockSpec((J, 1), lambda: (0, 0), memory_space=pltpu.VMEM)
    smem = pl.BlockSpec((1, 1), lambda: (0, 0), memory_space=pltpu.SMEM)
    asg, iters = pl.pallas_call(
        kern,
        in_specs=[smem, full, full, col],
        out_specs=[col, smem],
        out_shape=[
            jax.ShapeDtypeStruct((J, 1), jnp.int32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_MEGA_VMEM_LIMIT
        ),
    )(
        jnp.asarray(eps, jnp.float32).reshape(1, 1),
        benefit,
        tiebreak,
        valid.astype(jnp.int32).reshape(J, 1),
    )
    return asg[:, 0], iters[0, 0]


# --- Batched request routing: masked score row-argmax (router tier) ---------
#
# The fleet router's batched route solve (solver/routing.py) reduces to one
# primitive repeated every round: for each request row, the argmax over
# replicas of ``match_depth + per-replica bias`` under a hard eligibility
# mask, ties broken by the LOWEST replica index (the replica axis arrives
# name-sorted, so lowest index == lowest name — the router's documented
# tie-break). Under XLA the [B, R] score broadcast materializes per round;
# here it lives only in VMEM tiles, same rationale as the bid kernel above.
#
# Parity contract: the kernel and ``route_pick_jnp`` are bit-identical BY
# ARGUMENT, not by shared closure — the only arithmetic is one f32 add
# (match + bias, identical op in both); everything else is comparisons.
# A lexicographic max on (value, -index) is order-associative, so the
# kernel's sequential tile reduction (strict ``>`` keeps the earlier
# tile on equal values; within a tile the first index of the tile max
# wins) selects exactly the first index of the global row max — which is
# what the twin computes directly. tests/test_router_solver.py holds the
# bit-identity under interpret mode.

# Finite "-inf" for masked entries: Mosaic reductions over true -inf are
# fine, but a finite sentinel keeps the "no eligible replica" row exactly
# representable and comparable on both paths. Any real score is
# match + bias >= -(alpha * pressure_clip + stale + gamma) >> this.
ROUTE_NEG = -3e38


def _route_pick_kernel(
    match_ref,  # [TB, TR] i32 match depth in blocks; -1 = ineligible
    bias_ref,  # [1, TR] f32 per-replica bias (pressure/stale/price folded)
    active_ref,  # [TB, 1] i32 1 = row still unassigned this round
    val_ref,  # [TB, 1] f32 out: running row max
    idx_ref,  # [TB, 1] i32 out: running argmax (global replica index)
):
    tr = pl.program_id(1)
    neg = jnp.float32(ROUTE_NEG)

    @pl.when(tr == 0)
    def _init():
        val_ref[:] = jnp.full_like(val_ref, neg)
        idx_ref[:] = jnp.full_like(idx_ref, -1)

    ok = (match_ref[:] >= 0) & (active_ref[:] != 0)
    s = jnp.where(ok, match_ref[:].astype(jnp.float32) + bias_ref[:], neg)
    part_v = jnp.max(s, axis=1, keepdims=True)
    r_iota = (
        jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        + tr * s.shape[1]
    )
    part_i = jnp.min(
        jnp.where(s == part_v, r_iota, jnp.int32(_I32MAX)),
        axis=1, keepdims=True,
    )
    # strict >: an equal value in a LATER tile must not displace the
    # earlier (lower-index) holder. An all-masked tile has part_v == neg
    # and can never beat the init value, so idx stays -1 for dead rows.
    better = part_v > val_ref[:]
    idx_ref[:] = jnp.where(better, part_i, idx_ref[:])
    val_ref[:] = jnp.where(better, part_v, val_ref[:])


def route_pick_jnp(
    match: jax.Array,  # i32[B, R]; -1 = ineligible
    bias: jax.Array,  # f32[R]
    active: jax.Array,  # bool[B]
) -> tuple[jax.Array, jax.Array]:
    """jnp twin of ``route_pick_pallas``: (row max f32[B], first-index
    argmax i32[B], -1 when the row has no eligible replica)."""
    B, R = match.shape
    neg = jnp.float32(ROUTE_NEG)
    ok = (match >= 0) & active[:, None]
    s = jnp.where(ok, match.astype(jnp.float32) + bias[None, :], neg)
    v = jnp.max(s, axis=1)
    r_iota = jax.lax.broadcasted_iota(jnp.int32, (B, R), 1)
    idx = jnp.min(
        jnp.where(s == v[:, None], r_iota, jnp.int32(_I32MAX)), axis=1
    )
    idx = jnp.where(v > neg, idx, -1).astype(jnp.int32)
    return v, idx


def route_pick_pallas(
    match: jax.Array,  # i32[B, R]; -1 = ineligible
    bias: jax.Array,  # f32[R]
    active: jax.Array,  # bool[B]
    *,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Masked row argmax — Pallas form of ``route_pick_jnp`` (see the
    section comment for why the two are bit-identical by argument)."""
    B, R = match.shape
    if B % 8 or R % 128:
        raise ValueError(
            f"route_pick_pallas needs B%8==0 and R%128==0, got B={B} "
            f"R={R}; use accel='jnp' for unaligned route buckets"
        )
    # problem.py buckets are all multiples of 64; 64 is the one bucket
    # below the 128 sublane tile (f32 min tile is (8, 128), so 64 rows
    # are legal — just a shorter block).
    tb = 128 if B % 128 == 0 else 64 if B % 64 == 0 else 8
    tr = _tile_j(R)
    row = pl.BlockSpec((1, tr), lambda b, r: (0, r), memory_space=pltpu.VMEM)
    blk = pl.BlockSpec(
        (tb, tr), lambda b, r: (b, r), memory_space=pltpu.VMEM
    )
    col = pl.BlockSpec((tb, 1), lambda b, r: (b, 0), memory_space=pltpu.VMEM)
    val, idx = pl.pallas_call(
        _route_pick_kernel,
        grid=(B // tb, R // tr),
        in_specs=[blk, row, col],
        out_specs=[col, col],
        out_shape=[
            jax.ShapeDtypeStruct((B, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, 1), jnp.int32),
        ],
        interpret=interpret,
    )(match, bias.reshape(1, R), active.astype(jnp.int32).reshape(B, 1))
    return val[:, 0], idx[:, 0]
