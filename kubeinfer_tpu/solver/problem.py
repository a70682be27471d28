"""Static-shape tensor encoding of a scheduling problem.

Jobs and nodes arrive as Python lists that vary per reconcile tick; XLA wants
static shapes. Both axes are padded up to bucketed sizes (powers of two) so
the jitted solver compiles once per bucket pair and is reused across ticks
(SURVEY.md §7 hard part 2). Padding rows/columns are marked invalid and can
never be chosen.

Structure-of-arrays layout: each job field is one contiguous vector, so the
solver's [J, N] broadcasts are pure vectorized ops on the MXU/VPU — no
ragged per-job structures anywhere on device.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

# Bucket sizes for both axes: powers of two plus 1.5x midpoints, so padding
# overhead stays <= 50% while keeping the jit-cache small. Smallest 64 keeps
# tiny test problems cheap; largest covers the 50k-job soak (BASELINE.json
# config 5).
BUCKETS = (
    64, 128, 256, 384, 512, 768, 1024, 1536, 2048, 3072, 4096, 6144,
    8192, 12288, 16384, 24576, 32768, 49152, 65536,
)

# Max distinct model ids participating in cache-affinity scoring per solve.
# Models beyond the table share slot 0 ("no affinity"); static so the
# node-cache bitmap has a fixed shape.
MAX_MODELS = 256

GIB = float(1024**3)


def bucket_size(n: int) -> int:
    """Smallest bucket >= n (>= 1)."""
    for b in BUCKETS:
        if n <= b:
            return b
    raise ValueError(f"problem axis {n} exceeds max bucket {BUCKETS[-1]}")


@dataclass
class JobSet:
    """Padded job-side arrays (length J). One row per *replica* to place.

    ``gang_id`` couples rows into all-or-nothing groups (gang scheduling,
    BASELINE.json config 3); -1 = no gang. ``current_node`` is the incumbent
    placement (-1 = none) feeding the hysteresis term so full re-solves under
    churn don't thrash placements (config 4, SURVEY.md §7 hard part 4).
    """

    gpu_demand: jax.Array  # f32[J] chips requested (fractional allowed)
    mem_demand: jax.Array  # f32[J] accelerator memory, GiB
    priority: jax.Array  # f32[J] higher = more important
    gang_id: jax.Array  # i32[J] -1 = no gang
    model_id: jax.Array  # i32[J] slot in the model table (0 = none)
    current_node: jax.Array  # i32[J] incumbent node index, -1 = unplaced
    valid: jax.Array  # bool[J] padding mask

    def tree_flatten(self):  # registered below
        return (
            (self.gpu_demand, self.mem_demand, self.priority, self.gang_id,
             self.model_id, self.current_node, self.valid),
            None,
        )


@dataclass
class NodeSet:
    """Padded node-side arrays (length N).

    ``cached`` is the node x model bitmap behind cache-affinity scoring: a
    replica whose model already sits on a node's disk is cheaper there (the
    tensor form of the reference's shared-cache goal — its coordinator /
    follower plane exists to create exactly these cache hits).
    ``topology`` holds (group) coordinates for affinity scoring
    (BASELINE.json config 5).
    """

    gpu_free: jax.Array  # f32[N]
    mem_free: jax.Array  # f32[N] GiB
    gpu_capacity: jax.Array  # f32[N] total chips (normalizes fit scoring)
    mem_capacity: jax.Array  # f32[N] total GiB
    topology: jax.Array  # i32[N] topology group id
    cached: jax.Array  # bool[N, MAX_MODELS]
    valid: jax.Array  # bool[N]


@dataclass
class Problem:
    """One tick's scheduling problem, fully on device.

    Deliberately carries NO true (unpadded) counts: the ``valid`` masks
    are the on-device truth, and host-side callers track their own true
    sizes (e.g. ``SolveRequest.num_jobs``). An earlier revision kept true
    counts here as pytree metadata — which keyed the jit cache, so every
    distinct job count recompiled the solver and defeated bucketing.
    """

    jobs: JobSet
    nodes: NodeSet


jax.tree_util.register_dataclass(
    JobSet,
    data_fields=["gpu_demand", "mem_demand", "priority", "gang_id", "model_id",
                 "current_node", "valid"],
    meta_fields=[],
)
jax.tree_util.register_dataclass(
    NodeSet,
    data_fields=["gpu_free", "mem_free", "gpu_capacity", "mem_capacity",
                 "topology", "cached", "valid"],
    meta_fields=[],
)
jax.tree_util.register_dataclass(
    Problem,
    data_fields=["jobs", "nodes"],
    meta_fields=[],
)


@dataclass
class JobRow:
    """Host-side description of one replica to place (pre-encoding)."""

    gpu: float = 0.0
    mem_gib: float = 0.0
    priority: float = 0.0
    gang: int = -1
    model: str = ""
    current_node: int = -1


@dataclass
class NodeRow:
    """Host-side description of one node (pre-encoding)."""

    gpu_free: float = 0.0
    mem_free_gib: float = 0.0
    topology: int = 0
    cached_models: Sequence[str] = field(default_factory=tuple)
    gpu_capacity: float = 0.0  # 0 => same as gpu_free
    mem_capacity_gib: float = 0.0  # 0 => same as mem_free_gib


def _densify_gangs(gang: np.ndarray) -> np.ndarray:
    """Remap arbitrary gang ids to dense [0, n_gangs) so they always fit the
    solver's segment-op bound (gang ids must be < J; see _gang_repair).
    Without this, ids >= J would clip together and merge distinct gangs."""
    out = np.full(gang.shape, -1, np.int32)
    mask = gang >= 0
    if mask.any():
        _, inverse = np.unique(gang[mask], return_inverse=True)
        out[mask] = inverse.astype(np.int32)
    return out


def _padded_sizes(
    J_true: int, N_true: int, job_multiple: int, node_multiple: int
) -> tuple[int, int]:
    """Bucketed padded axis sizes, rounded up to the mesh-axis multiples —
    shared by the dict-based and direct-pack encoders so their layouts
    can never desync."""
    J = bucket_size(max(J_true, 1))
    N = bucket_size(max(N_true, 1))
    J = -(-J // max(job_multiple, 1)) * max(job_multiple, 1)
    N = -(-N // max(node_multiple, 1)) * max(node_multiple, 1)
    return J, N


def _clamp_model_ids(jm: np.ndarray) -> np.ndarray:
    """Out-of-table model slots collapse to 0 ("no affinity") rather than
    letting a downstream clip manufacture false cache hits for whichever
    model owns slot MAX_MODELS-1."""
    return np.where((jm >= 0) & (jm < MAX_MODELS), jm, 0)


def _prep_padded_arrays(
    *,
    job_gpu: np.ndarray,
    job_mem_gib: np.ndarray,
    job_priority: np.ndarray | None = None,
    job_gang: np.ndarray | None = None,
    job_model: np.ndarray | None = None,
    job_current_node: np.ndarray | None = None,
    node_gpu_free: np.ndarray,
    node_mem_free_gib: np.ndarray,
    node_gpu_capacity: np.ndarray | None = None,
    node_mem_capacity_gib: np.ndarray | None = None,
    node_topology: np.ndarray | None = None,
    node_cached: np.ndarray | None = None,
    job_multiple: int = 1,
    node_multiple: int = 1,
    job_perm: np.ndarray | None = None,
) -> tuple[dict[str, np.ndarray], dict[str, np.ndarray], int, int, int, int]:
    """Shared host-side prep: bucket, pad, densify. Returns numpy fields
    (jobs dict, nodes dict) + (J_true, N_true, J, N).

    ``job_perm`` reorders the job axis during the padding copy (one fused
    fancy-index per field instead of a separate pre-permutation pass) —
    the backend's priority sort uses this; see backends.py.
    """
    J_true = int(job_gpu.shape[0])
    N_true = int(node_gpu_free.shape[0])
    J, N = _padded_sizes(J_true, N_true, job_multiple, node_multiple)

    def padj(a, fill, dtype):
        out = np.full(J, fill, dtype)
        if job_perm is None:
            out[:J_true] = a
        else:
            out[:J_true] = np.asarray(a)[job_perm]
        return out

    def padn(a, fill, dtype):
        out = np.full(N, fill, dtype)
        out[:N_true] = a
        return out

    cached = np.zeros((N, MAX_MODELS), bool)
    if node_cached is not None:
        cached[:N_true, : node_cached.shape[1]] = node_cached
    jvalid = np.zeros(J, bool)
    jvalid[:J_true] = True
    nvalid = np.zeros(N, bool)
    nvalid[:N_true] = True

    zeros_j = np.zeros(J_true, np.float32)
    jobs = {
        "gpu_demand": padj(job_gpu, 0, np.float32),
        "mem_demand": padj(job_mem_gib, 0, np.float32),
        "priority": padj(
            job_priority if job_priority is not None else zeros_j,
            0, np.float32,
        ),
        "gang_id": padj(
            _densify_gangs(np.asarray(job_gang, np.int32))
            if job_gang is not None
            else np.full(J_true, -1),
            -1, np.int32,
        ),
        "model_id": padj(
            # Out-of-table slots collapse to 0 ("no affinity") rather than
            # letting jnp.take's clip manufacture false cache hits for
            # whichever model owns slot MAX_MODELS-1.
            _clamp_model_ids(np.asarray(job_model))
            if job_model is not None
            else np.zeros(J_true),
            0, np.int32,
        ),
        "current_node": padj(
            job_current_node
            if job_current_node is not None
            else np.full(J_true, -1),
            -1, np.int32,
        ),
        "valid": jvalid,
    }
    nodes = {
        "gpu_free": padn(node_gpu_free, 0, np.float32),
        "mem_free": padn(node_mem_free_gib, 0, np.float32),
        "gpu_capacity": padn(
            node_gpu_capacity if node_gpu_capacity is not None else node_gpu_free,
            0, np.float32,
        ),
        "mem_capacity": padn(
            node_mem_capacity_gib
            if node_mem_capacity_gib is not None
            else node_mem_free_gib,
            0, np.float32,
        ),
        "topology": padn(
            node_topology if node_topology is not None else np.zeros(N_true),
            0, np.int32,
        ),
        "cached": cached,
        "valid": nvalid,
    }
    return jobs, nodes, J_true, N_true, J, N


def encode_problem_arrays(
    *,
    job_gpu: np.ndarray,
    job_mem_gib: np.ndarray,
    job_priority: np.ndarray | None = None,
    job_gang: np.ndarray | None = None,
    job_model: np.ndarray | None = None,  # i32 model slots (0 = none)
    job_current_node: np.ndarray | None = None,
    node_gpu_free: np.ndarray,
    node_mem_free_gib: np.ndarray,
    node_gpu_capacity: np.ndarray | None = None,
    node_mem_capacity_gib: np.ndarray | None = None,
    node_topology: np.ndarray | None = None,
    node_cached: np.ndarray | None = None,  # bool [N, MAX_MODELS]
    job_multiple: int = 1,
    node_multiple: int = 1,
) -> Problem:
    """Vectorized fast path: pack pre-built numpy arrays (one np.pad + one
    device_put per field). This is what the reconciler and benchmarks use —
    O(J+N) numpy ops, no per-object Python loop. ``encode_problem`` below is
    the convenience row-based wrapper for small problems and tests.

    ``job_multiple``/``node_multiple`` round the padded axis up to a multiple
    of a mesh axis size, so shards stay equal-sized when the problem is
    placed on a device mesh whose axis does not divide the bucket (buckets
    are all multiples of 64, so powers of two <= 64 never need this)."""
    jobs, nodes, J_true, N_true, _, _ = _prep_padded_arrays(
        job_gpu=job_gpu, job_mem_gib=job_mem_gib, job_priority=job_priority,
        job_gang=job_gang, job_model=job_model,
        job_current_node=job_current_node,
        node_gpu_free=node_gpu_free, node_mem_free_gib=node_mem_free_gib,
        node_gpu_capacity=node_gpu_capacity,
        node_mem_capacity_gib=node_mem_capacity_gib,
        node_topology=node_topology, node_cached=node_cached,
        job_multiple=job_multiple, node_multiple=node_multiple,
    )
    return Problem(
        jobs=JobSet(**{k: jnp.asarray(v) for k, v in jobs.items()}),
        nodes=NodeSet(**{k: jnp.asarray(v) for k, v in nodes.items()}),
    )


# --- single-buffer packing (one host->device transfer per solve) -----------
#
# Every device_put is a dispatch of its own, and 14 field transfers per
# solve are 14 of them next to one solve. The packed path lays the whole problem into ONE contiguous f32 buffer
# (i32/bool regions bitcast — no value conversion) and unpacks with free
# slices/bitcasts inside the jitted solve.
#
# Layout, in 4-byte words (J/N are the padded bucket sizes):
#   [0,   3J) job f32 fields: gpu_demand, mem_demand, priority
#   [3J,  7J) job i32 fields: gang_id, model_id, current_node, valid
#   [7J, 7J+4N) node f32 fields: gpu_free, mem_free, gpu_capacity,
#               mem_capacity
#   [7J+4N, 7J+6N) node i32 fields: topology, valid
#   [7J+6N, 7J+6N+N*MAX_MODELS/4) cached bitmap, uint8[N, MAX_MODELS]

_CACHED_WORDS = MAX_MODELS // 4  # f32 words per node of cached bitmap


def packed_words(J: int, N: int) -> int:
    return 7 * J + 6 * N + N * _CACHED_WORDS


def pack_problem_arrays(
    *,
    job_gpu: np.ndarray,
    job_mem_gib: np.ndarray,
    job_priority: np.ndarray | None = None,
    job_gang: np.ndarray | None = None,
    job_model: np.ndarray | None = None,
    job_current_node: np.ndarray | None = None,
    node_gpu_free: np.ndarray,
    node_mem_free_gib: np.ndarray,
    node_gpu_capacity: np.ndarray | None = None,
    node_mem_capacity_gib: np.ndarray | None = None,
    node_topology: np.ndarray | None = None,
    node_cached: np.ndarray | None = None,
    job_multiple: int = 1,
    node_multiple: int = 1,
    job_perm: np.ndarray | None = None,
) -> tuple[np.ndarray, int, int, int, int]:
    """Host-side packing; same kwargs as ``encode_problem_arrays``.

    Returns ``(buf f32[packed_words], J_true, N_true, J, N)``.

    Fields are written DIRECTLY into their buffer slices (one zeroed
    allocation, one copy per field) rather than materializing 14 padded
    intermediates and copying them again — the pack sits inside the
    headline pack+solve latency, and the double-copy was ~half its cost.
    ``job_perm`` applies the backend's priority permutation during the
    field copy (see backends.py).
    """
    J_true = int(job_gpu.shape[0])
    N_true = int(node_gpu_free.shape[0])
    J, N = _padded_sizes(J_true, N_true, job_multiple, node_multiple)

    # np.empty + explicit pad fills: np.zeros would page-fault the whole
    # buffer lazily on first write; the pad tails are a fraction of it
    buf = np.empty(packed_words(J, N), np.float32)
    i32 = buf.view(np.int32)

    def putf(o, a, pad=0.0):
        dst = buf[o : o + J]
        dst[J_true:] = pad
        if a is None:
            dst[:J_true] = pad
        else:
            a = np.asarray(a)
            dst[:J_true] = a[job_perm] if job_perm is not None else a

    putf(0, job_gpu)
    putf(J, job_mem_gib)
    putf(2 * J, job_priority)
    gang = i32[3 * J : 4 * J]
    gang[J_true:] = -1
    if job_gang is not None:
        gang[:J_true] = _densify_gangs(
            np.asarray(job_gang, np.int32)[job_perm]
            if job_perm is not None
            else np.asarray(job_gang, np.int32)
        )
    else:
        gang[:J_true] = -1
    model = i32[4 * J : 5 * J]
    model[J_true:] = 0
    if job_model is not None:
        jm = np.asarray(job_model)
        if job_perm is not None:
            jm = jm[job_perm]
        model[:J_true] = _clamp_model_ids(jm)
    else:
        model[:J_true] = 0
    cur = i32[5 * J : 6 * J]
    cur[:] = -1
    if job_current_node is not None:
        jc = np.asarray(job_current_node, np.int32)
        cur[:J_true] = jc[job_perm] if job_perm is not None else jc
    jv = i32[6 * J : 7 * J]
    jv[:J_true] = 1
    jv[J_true:] = 0
    o = 7 * J

    def putn(off, a, fallback=None):
        dst = buf[off : off + N]
        dst[N_true:] = 0.0
        dst[:N_true] = a if a is not None else fallback

    putn(o, node_gpu_free)
    putn(o + N, node_mem_free_gib)
    putn(o + 2 * N, node_gpu_capacity, node_gpu_free)
    putn(o + 3 * N, node_mem_capacity_gib, node_mem_free_gib)
    topo = i32[o + 4 * N : o + 5 * N]
    topo[N_true:] = 0
    if node_topology is not None:
        topo[:N_true] = node_topology
    else:
        topo[:N_true] = 0
    nv = i32[o + 5 * N : o + 6 * N]
    nv[:N_true] = 1
    nv[N_true:] = 0
    cached = buf[o + 6 * N :].view(np.uint8).reshape(N, MAX_MODELS)
    if node_cached is not None:
        nc = np.asarray(node_cached)
        w = nc.shape[1]
        cached[:N_true, :w] = nc
        cached[:N_true, w:] = 0
        cached[N_true:] = 0
    else:
        cached[:] = 0
    return buf, J_true, N_true, J, N


def unpack_problem(buf: jax.Array, J: int, N: int) -> Problem:
    """Jittable inverse of ``pack_problem_arrays`` (slices + bitcasts only;
    XLA fuses these into the consumers, so unpacking is effectively free).
    """
    from jax import lax

    def f32(o, n):
        return lax.slice(buf, (o,), (o + n,))

    def i32(o, n):
        return lax.bitcast_convert_type(f32(o, n), jnp.int32)

    gpu_d, mem_d, prio = f32(0, J), f32(J, J), f32(2 * J, J)
    gang, model, cur = i32(3 * J, J), i32(4 * J, J), i32(5 * J, J)
    jvalid = i32(6 * J, J) != 0
    o = 7 * J
    gpu_f, mem_f = f32(o, N), f32(o + N, N)
    gpu_c, mem_c = f32(o + 2 * N, N), f32(o + 3 * N, N)
    topo = i32(o + 4 * N, N)
    nvalid = i32(o + 5 * N, N) != 0
    cached = lax.bitcast_convert_type(
        f32(o + 6 * N, N * _CACHED_WORDS).reshape(N, _CACHED_WORDS),
        jnp.uint8,
    ).reshape(N, MAX_MODELS) != 0
    return Problem(
        jobs=JobSet(
            gpu_demand=gpu_d, mem_demand=mem_d, priority=prio,
            gang_id=gang, model_id=model, current_node=cur, valid=jvalid,
        ),
        nodes=NodeSet(
            gpu_free=gpu_f, mem_free=mem_f, gpu_capacity=gpu_c,
            mem_capacity=mem_c, topology=topo, cached=cached, valid=nvalid,
        ),
    )


def encode_problem(
    jobs: Sequence[JobRow],
    nodes: Sequence[NodeRow],
) -> tuple[Problem, dict[str, int]]:
    """Pack host-side rows into padded device arrays.

    Returns the Problem plus the model-name -> slot table used (so callers
    can interpret cache stats). Encoding is plain numpy — O(J + N + cache
    entries) host work — then one transfer per field.
    """
    J = bucket_size(max(len(jobs), 1))
    N = bucket_size(max(len(nodes), 1))

    model_table: dict[str, int] = {}

    def model_slot(name: str) -> int:
        if not name:
            return 0
        if name not in model_table:
            if len(model_table) + 1 >= MAX_MODELS:
                return 0  # table full: no affinity signal for this model
            model_table[name] = len(model_table) + 1  # slot 0 reserved: none
        return model_table[name]

    gpu_d = np.zeros(J, np.float32)
    mem_d = np.zeros(J, np.float32)
    prio = np.zeros(J, np.float32)
    gang = np.full(J, -1, np.int32)
    model = np.zeros(J, np.int32)
    cur = np.full(J, -1, np.int32)
    jvalid = np.zeros(J, bool)
    for i, j in enumerate(jobs):
        gpu_d[i] = j.gpu
        mem_d[i] = j.mem_gib
        prio[i] = j.priority
        gang[i] = j.gang
        model[i] = model_slot(j.model)
        cur[i] = j.current_node
        jvalid[i] = True
    gang[: len(jobs)] = _densify_gangs(gang[: len(jobs)])

    gpu_f = np.zeros(N, np.float32)
    mem_f = np.zeros(N, np.float32)
    gpu_c = np.zeros(N, np.float32)
    mem_c = np.zeros(N, np.float32)
    topo = np.zeros(N, np.int32)
    cached = np.zeros((N, MAX_MODELS), bool)
    nvalid = np.zeros(N, bool)
    for i, n in enumerate(nodes):
        gpu_f[i] = n.gpu_free
        mem_f[i] = n.mem_free_gib
        gpu_c[i] = n.gpu_capacity or n.gpu_free
        mem_c[i] = n.mem_capacity_gib or n.mem_free_gib
        topo[i] = n.topology
        for m in n.cached_models:
            s = model_slot(m)
            if s:
                cached[i, s] = True
        nvalid[i] = True

    problem = Problem(
        jobs=JobSet(
            gpu_demand=jnp.asarray(gpu_d),
            mem_demand=jnp.asarray(mem_d),
            priority=jnp.asarray(prio),
            gang_id=jnp.asarray(gang),
            model_id=jnp.asarray(model),
            current_node=jnp.asarray(cur),
            valid=jnp.asarray(jvalid),
        ),
        nodes=NodeSet(
            gpu_free=jnp.asarray(gpu_f),
            mem_free=jnp.asarray(mem_f),
            gpu_capacity=jnp.asarray(gpu_c),
            mem_capacity=jnp.asarray(mem_c),
            topology=jnp.asarray(topo),
            cached=jnp.asarray(cached),
            valid=jnp.asarray(nvalid),
        ),
    )
    return problem, model_table
