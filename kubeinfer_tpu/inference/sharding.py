"""Sharding for the native runtime: TP param specs + SP forward +
the continuous batcher's device layout.

Tensor parallel (the reference's ``--tensor-parallel-size`` is a
pass-through flag to external vLLM, vllm.go:57-61; here TP is real):
attention heads and ffn columns shard over the ``tp`` mesh axis. With
column-parallel (q/k/v/gate/up) then row-parallel (o/down) weights, the
only collectives GSPMD must insert are the two per-block psums of the
standard Megatron layout — we annotate the params and let the partitioner
do exactly that (scaling-book recipe: annotate, don't hand-schedule).

:class:`EngineLayout` extends the same recipe to the serving engine's
paged state: params per :func:`param_specs`, the shared KV block pool
``[num_blocks, n_kv, block_size, D]`` sharded along ``n_kv`` (each
device holds its own heads' slice of EVERY block — block indices stay
logical and host bookkeeping never sees the layout), everything else
replicated. The engine's jits (admit, chunk, decode window) take the
placed arrays and GSPMD propagates — one extra compiled executable per
layout, no trace changes.

Sequence parallel: ``forward_sequence_parallel`` runs the whole decoder
under ``shard_map`` with the sequence axis sharded over ``sp``, swapping
the dense attention for ring attention (ring_attention.py). Weights are
replicated across ``sp``; activations never materialize the full
sequence on one device — this is the long-context path.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kubeinfer_tpu.inference.config import ModelConfig
from kubeinfer_tpu.inference.kv_blocks import page_axes
from kubeinfer_tpu.inference.model import Params, forward
from kubeinfer_tpu.inference.ring_attention import ring_attention


def order_devices_ici(devices) -> list:
    """Devices reordered along a boustrophedon walk of the chip grid so
    consecutive ranks are ICI neighbors (the ordering make_axis_mesh's
    docstring deferred).

    ``jax.devices()`` enumerates TPU chips in row-major coordinate
    order, so the wrap from the end of one row to the start of the next
    puts consecutive mesh ranks on chips a full row apart — every
    collective then pays a multi-hop detour on exactly the axis that is
    supposed to be latency-critical. The snake walk flips direction on
    alternate rows (and alternate planes, for 3D slices), keeping every
    consecutive pair one ICI hop apart; cores on the same chip sort
    adjacent, which is tighter still. Devices without chip coords
    (CPU/virtual meshes, the 8-device test mesh) keep their enumeration
    order — on those platforms there is no topology to respect and the
    stable order keeps layouts reproducible.
    """
    coords = [getattr(d, "coords", None) for d in devices]
    if any(c is None for c in coords):
        return list(devices)
    sizes = [max(c[i] for c in coords) + 1 for i in range(len(coords[0]))]

    def snake_rank(c) -> int:
        # walk dims slowest-to-fastest (TPU coords are (x, y, z): z is
        # the slowest axis); a dim entered at an odd index reverses the
        # next-faster dim, which is what makes row ends adjacent
        rank, flip = 0, False
        for i in reversed(range(len(sizes))):
            v = (sizes[i] - 1 - c[i]) if flip else c[i]
            rank = rank * sizes[i] + v
            flip = (v % 2) == 1
        return rank

    return sorted(
        devices,
        key=lambda d: (snake_rank(d.coords),
                       getattr(d, "core_on_chip", 0)),
    )


def mesh_device_array(devices, dp: int, tp: int, sp: int):
    """ICI-ordered ``(dp, tp, sp)`` device array with ``tp`` ranks
    adjacent on the physical chain.

    A plain ``reshape(dp, tp, sp)`` makes ``sp`` the fastest-varying
    axis; filling ``(dp, sp, tp)`` and transposing instead puts
    consecutive ``tp`` ranks on consecutive chain positions — the tp
    axis carries the per-layer Megatron psums (two per block, every
    step), while sp/dp collectives are per-request-scale, so tp gets
    the single-hop neighbors. When sp == 1 the transpose is the
    identity and the array matches the historical layout exactly.
    Factored from make_inference_mesh so topology tests can drive it
    with fake devices.
    """
    import numpy as np

    ordered = order_devices_ici(devices)[: dp * tp * sp]
    return np.asarray(ordered).reshape(dp, sp, tp).transpose(0, 2, 1)


def make_inference_mesh(
    tp: int = 1, sp: int = 1, dp: int | None = None
) -> Mesh:
    """(dp, tp, sp) mesh over the available devices (dp fills the rest),
    ICI-ordered so adjacent tp ranks sit on adjacent devices
    (order_devices_ici / mesh_device_array)."""
    devices = jax.devices()
    if dp is None:
        dp = len(devices) // (tp * sp)
    n = dp * tp * sp
    if n > len(devices) or n < 1:
        raise ValueError(
            f"mesh dp={dp} tp={tp} sp={sp} needs {n} devices, have "
            f"{len(devices)}"
        )
    return Mesh(
        mesh_device_array(devices, dp, tp, sp),
        axis_names=("dp", "tp", "sp"),
    )


def make_axis_mesh(axis_name: str, n: int) -> Mesh:
    """1-D mesh over the first ``n`` devices in ICI order (shared by the
    pp/ep constructors — one place for device-count checks and the
    locality ordering)."""
    import numpy as np

    devices = jax.devices()
    if n > len(devices):
        raise ValueError(
            f"{axis_name}={n} needs {n} devices, have {len(devices)}"
        )
    return Mesh(
        np.asarray(order_devices_ici(devices)[:n]).reshape(n),
        axis_names=(axis_name,),
    )


def param_specs(cfg: ModelConfig) -> Params:
    """PartitionSpec tree matching init_params' layout (Megatron TP)."""
    layer = {
        "input_layernorm": P(),
        "post_attention_layernorm": P(),
        "q_proj": P(None, "tp"),  # column parallel: heads shard
        "k_proj": P(None, "tp"),
        "v_proj": P(None, "tp"),
        "o_proj": P("tp", None),  # row parallel: psum after
    }
    if cfg.num_local_experts > 0:
        # Mixtral family under TP: every expert's ffn shards exactly like
        # the dense mlp (column-parallel gate/up, row-parallel down) with
        # the expert-stacked leading axis replicated; expert parallelism
        # over an ``ep`` axis is the separate moe.moe_block_ep path.
        layer["moe"] = {
            "router": P(),
            "gate_proj": P(None, None, "tp"),
            "up_proj": P(None, None, "tp"),
            "down_proj": P(None, "tp", None),
        }
    else:
        layer["gate_proj"] = P(None, "tp")
        layer["up_proj"] = P(None, "tp")
        layer["down_proj"] = P("tp", None)
    if cfg.qkv_bias:  # biases follow their projection's output sharding
        layer["q_bias"] = P("tp")
        layer["k_bias"] = P("tp")
        layer["v_bias"] = P("tp")
    return {
        "embed_tokens": P(None, None),  # replicated (small vs the ffn)
        "layers": [layer] * cfg.num_hidden_layers,
        "norm": P(),
        "lm_head": P(None, "tp"),  # vocab-sharded logits
    }


def expand_quant_specs(specs: Params, params: Params) -> Params:
    """Grow a param_specs tree to match weight-quantized leaves: where
    ``params`` carries a quant dict, the weight's spec applies to the
    int8 codes and the f32 scale plane shards along the weight's OUT
    axis (per-column storage, so there is no tile/tp divisibility
    coupling). Placement only — no new programs, same as the rest of
    the TP layout. Uses tree.map's prefix rule: ``specs`` is a prefix
    of ``params``, so a P leaf meets the whole quant subtree."""

    def one(spec, leaf):
        if isinstance(leaf, dict) and "qw" in leaf:
            out_axis = spec[-1] if len(spec) else None
            return {"qw": spec, "scale": P(out_axis)}
        return spec

    return jax.tree.map(one, specs, params)


def param_placer(mesh: Mesh | None, cfg: ModelConfig):
    """``place(name, piece)`` for the builders that make the tree piece
    by piece (model.init_params, weights.params_from_state_dict): puts
    one top-level piece — ``"layer"``, ``"embed_tokens"``, ``"norm"``
    or ``"lm_head"`` — on its param_specs shards the moment it exists.
    The builder's device then holds one layer at a time, never the
    whole tree: shard_params on a finished tree needs the whole model
    on one device first, which a 7B model at bf16 does not fit.
    Without a mesh the piece stays where it was built."""
    if mesh is None:
        return lambda name, piece: piece
    specs = param_specs(cfg)

    def place(name: str, piece):
        spec = specs["layers"][0] if name == "layer" else specs[name]
        return jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
            piece, expand_quant_specs(spec, piece),
        )

    return place


def shard_params(params: Params, mesh: Mesh, cfg: ModelConfig) -> Params:
    """Place a FINISHED param pytree onto the mesh per param_specs
    (trees that already fit one device; see param_placer)."""
    specs = param_specs(cfg)
    if "lm_head" not in params:
        specs = dict(specs)
        specs.pop("lm_head")
    specs = expand_quant_specs(specs, params)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, specs
    )


@dataclasses.dataclass(frozen=True)
class EngineLayout:
    """Device layout of the continuous batcher: mesh + placements for
    params, the paged KV pool, and the rest of the slot state.

    ``tp == 1`` is the degenerate single-device layout: no mesh exists,
    ``shard_params``/``shard_state`` return their inputs untouched, and
    the engine is byte-for-byte the pre-sharding engine — same arrays,
    same traces, same compile cache. Under ``tp > 1`` the layout only
    PLACES arrays; it never rewrites the engine's programs. Params
    follow :func:`param_specs` (Megatron column/row parallel), the
    per-layer pool ``[num_blocks, n_kv, block_size, D]`` shards along
    ``n_kv`` (dim 1: kv_blocks.page_axes says where), and every other
    SlotState leaf — block tables,
    sampling knobs, PRNG keys — replicates. Because the ``num_blocks``
    axis is whole on every device, the host's i32 block tables resolve
    per-device KV shards unchanged: a table entry names the same
    logical block everywhere, each device just gathers/scatters its own
    heads' slice of it. That is the whole reason BlockPool/RadixCache
    never learn about the layout.

    Token parity with tp=1 is by dominance, not bit-exactness of the
    logits: GSPMD's psum reduces partial products in a different order
    than the unsharded contraction, so logits can differ in the last
    ulps — but the sampling noise is position-folded (identical across
    layouts) and argmax/gumbel-pick decisions ride logit GAPS, which
    the parity suite pins greedy and sampled across admits, windows,
    and preemption cycles.
    """

    tp: int = 1
    mesh: Mesh | None = None

    def __post_init__(self) -> None:
        if self.tp < 1:
            raise ValueError(f"tp must be >= 1, got {self.tp}")
        if (self.mesh is None) != (self.tp == 1):
            raise ValueError(
                "EngineLayout carries a mesh exactly when tp > 1 "
                f"(tp={self.tp}, mesh={'set' if self.mesh else 'None'})"
            )

    @classmethod
    def build(cls, tp: int = 1) -> "EngineLayout":
        """The CLI/bench constructor: tp=1 stays meshless (zero
        behavior change), tp>1 builds the ICI-ordered serving mesh."""
        if tp <= 1:
            return cls()
        return cls(tp=tp, mesh=make_inference_mesh(tp=tp, sp=1, dp=1))

    @property
    def sharded(self) -> bool:
        return self.mesh is not None

    @property
    def mesh_devices(self) -> int:
        """Device count under the layout (1 when unsharded) — what the
        kubeinfer_mesh_devices gauge reports."""
        return 1 if self.mesh is None else self.mesh.size

    def check_model(self, cfg: ModelConfig) -> None:
        """Divisibility the layout needs: every device must own whole
        heads. n_kv % tp == 0 keeps the pool shards real (a device with
        zero KV heads would still pay every collective); GQA ratios
        where n_kv == tp (one KV head per device) are the floor."""
        if not self.sharded:
            return
        if cfg.num_attention_heads % self.tp:
            raise ValueError(
                f"tp={self.tp} must divide num_attention_heads="
                f"{cfg.num_attention_heads}"
            )
        if cfg.num_key_value_heads % self.tp:
            raise ValueError(
                f"tp={self.tp} must divide num_key_value_heads="
                f"{cfg.num_key_value_heads} (KV pool shards along n_kv)"
            )

    def shard_params(self, params: Params, cfg: ModelConfig) -> Params:
        """Place params per param_specs; identity when unsharded."""
        if not self.sharded:
            return params
        return shard_params(params, self.mesh, cfg)

    def pool_sharding(self) -> NamedSharding:
        """[num_blocks, n_kv, block_size, D]: heads shard, blocks stay
        whole per device so logical table indices resolve everywhere."""
        return NamedSharding(self.mesh, P(*page_axes(1, "tp")))

    def scale_sharding(self) -> NamedSharding:
        """[num_blocks, n_kv] int8 dequant scales: shard along n_kv
        exactly like the pool — each device holds its own heads'
        scales for EVERY block, so the kernel's scale prefetch never
        crosses devices."""
        return NamedSharding(self.mesh, P(None, "tp"))

    def tail_sharding(self) -> NamedSharding:
        """[n_slots, 2, n_kv, block_size, D] bf16 tail pairs: n_kv
        shards with the pool (dim 2); slots and the 2-slot tail axis
        stay whole per device."""
        return NamedSharding(self.mesh, P(*page_axes(2, "tp")))

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def shard_state(self, state):
        """Place a stepper.SlotState; identity when unsharded. The
        placement is the jit contract: decode_window/_admit_slot donate
        this pytree, and jax compiles one executable per distinct input
        sharding — which is exactly the one-shape-per-(bucket, layout)
        discipline the profiler pins."""
        if not self.sharded:
            return state
        pool = self.pool_sharding()
        scale = self.scale_sharding()
        tail = self.tail_sharding()
        rep = self.replicated()
        kv_names = ("caches_k", "caches_v", "scales_k", "scales_v",
                    "tails_k", "tails_v")
        placed = {
            f.name: jax.device_put(getattr(state, f.name), rep)
            for f in dataclasses.fields(state)
            if f.name not in kv_names
        }
        return dataclasses.replace(
            state,
            caches_k=[jax.device_put(c, pool) for c in state.caches_k],
            caches_v=[jax.device_put(c, pool) for c in state.caches_v],
            scales_k=[jax.device_put(s, scale) for s in state.scales_k],
            scales_v=[jax.device_put(s, scale) for s in state.scales_v],
            tails_k=[jax.device_put(t, tail) for t in state.tails_k],
            tails_v=[jax.device_put(t, tail) for t in state.tails_v],
            **placed,
        )


def forward_tensor_parallel(
    params: Params, tokens: jax.Array, cfg: ModelConfig, mesh: Mesh
) -> jax.Array:
    """Jit the standard forward with TP-sharded params; GSPMD inserts the
    Megatron psums. ``params`` should already be placed (shard_params) —
    then this is zero-copy; unplaced params are placed on trace."""

    @functools.partial(jax.jit, static_argnames=("cfg",))
    def fwd(p, t, cfg: ModelConfig):
        from kubeinfer_tpu.inference.model import attention

        # attn_fn pinned to the dense einsum path: GSPMD partitions
        # einsums across the mesh, but the default forward's causal
        # flash kernel is a Pallas custom call that GSPMD cannot
        # partition — under a sharded jit it would replicate (or fail
        # to lower) instead of sharding over heads.
        out, _ = forward(p, t, cfg, attn_fn=attention)
        return jax.lax.with_sharding_constraint(
            out, NamedSharding(mesh, P("dp", None, None))
        )

    tokens = jax.device_put(
        tokens, NamedSharding(mesh, P("dp", None))
    )
    return fwd(shard_params(params, mesh, cfg), tokens, cfg)


def forward_sequence_parallel(
    params: Params, tokens: jax.Array, cfg: ModelConfig, mesh: Mesh
) -> jax.Array:
    """Causal LM forward with the SEQUENCE axis sharded over ``sp``.

    The full decoder body runs per-shard under shard_map (pointwise over
    T except attention, which is the ring). RoPE positions are global:
    each shard computes them from its axis index. T must divide by the
    sp axis size.
    """
    B, T = tokens.shape
    sp = mesh.shape["sp"]
    if T % sp:
        raise ValueError(f"sequence length {T} must divide by sp={sp}")
    T_loc = T // sp

    def body(p, t_local):
        r = jax.lax.axis_index("sp")
        positions = (
            r * T_loc + jnp.arange(T_loc, dtype=jnp.int32)[None, :]
        )
        positions = jnp.broadcast_to(positions, t_local.shape)

        def ring_fn(q, k, v, mask):  # model's mask is local-only: ignore;
            # causality comes from global positions inside the ring
            del mask
            return ring_attention(q, k, v, axis_name="sp")

        # the local mask arg is unused by ring_fn but must have the
        # local shape for the (ignored) broadcast in attention()'s twin
        local_mask = jnp.ones((t_local.shape[0], T_loc, T_loc), bool)
        out, _ = forward(
            p, t_local, cfg, positions=positions, attn_mask=local_mask,
            attn_fn=ring_fn,
        )
        return out

    shard_fwd = jax.jit(
        shard_map(
            functools.partial(body),
            mesh=mesh,
            in_specs=(param_specs_replicated(cfg, params), P(None, "sp")),
            out_specs=P(None, "sp", None),
        )
    )
    return shard_fwd(params, tokens)


def param_specs_replicated(cfg: ModelConfig, params: Params) -> Params:
    """All-replicated spec tree (shard_map in_specs for the SP path)."""
    specs = jax.tree.map(lambda _: P(), params)
    return specs
