"""Main-path kernels compiled for a TPU v5e that is described, not attached.

Interpret mode runs a Pallas kernel as jnp ops and accepts what Mosaic
refuses: a scalar bitcast, a tile that overflows scoped VMEM, a slice off
the (8, 128) tiling. The TPU's compiler is installed with libtpu and
compiles for a topology description without a chip, so these cases hold
every kernel the serving path and the solver launch to "the chip's
compiler takes it at published widths" at no chip time. A compile that
passes is not a chip run: results and times come from chip_smoke.py.

This is the only file that describes the chip. Only one process may hold
libtpu, so the description happens inside the module-scoped fixtures
below — never at import, in a skipif, in parametrize arguments or in
conftest — and every compile runs in this test's own process: under
``-n 6 --dist loadfile`` all workers import this file and exactly one
runs it.
"""

from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from kubeinfer_tpu.inference.kv_blocks import pool_shape
from kubeinfer_tpu.observability.stepprof import KERNEL_NAMES


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_compile_cache():
    """A compile for a described device is written to the persistent
    cache but cannot be read back without the chip: the next run would
    warn and compile again. Keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


BF16, I8, I32, F32 = jnp.bfloat16, jnp.int8, jnp.int32, jnp.float32

# published attention widths: (query heads, kv heads, head_dim)
QWEN2_7B = (28, 4, 128)
GEMMA_2B = (8, 1, 256)
LLAMA3_8B = (32, 8, 128)
QWEN3_NEXT = (16, 2, 256)  # its full-attention layers: group 8


def _ragged(heads, T, S):
    """Chunked-prefill attention: a T-token chunk against an S-slot
    cache. qwen2-7b runs at T=128: its 7-row GQA group is not sublane
    aligned and Mosaic takes ~25 s over the T=256 tile, ~5 s here."""
    from kubeinfer_tpu.inference.flash_attention import (
        flash_attention_ragged,
    )

    nq, nkv, D = heads
    return flash_attention_ragged, (
        ((1, T, nq, D), BF16), ((1, S, nkv, D), BF16),
        ((1, S, nkv, D), BF16), ((), I32), ((1,), I32),
    )


# the server's pool at --batch-slots 8 --max-model-len 4096
_B, _BS, _MB = 8, 128, 32
_NB = 1 + 2 * _B * _MB


def _decode_blocks(T, heads=QWEN2_7B, B=_B, NB=_NB):
    from kubeinfer_tpu.inference.flash_attention import (
        decode_attention_blocks,
    )

    nq, nkv, D = heads
    pool = (pool_shape(NB, _BS, nkv, D), BF16)
    return decode_attention_blocks, (
        ((B, T, nq, D), BF16), pool, pool, ((B, _MB), I32), ((B,), I32),
    )


def _moe_grouped_matmul(M, K, N, E=128):
    """One projection of Qwen3-Next's held experts: M sorted (row,
    expert) pairs, 128 of the 512 experts."""
    from kubeinfer_tpu.inference.moe import grouped_matmul

    return grouped_matmul, (
        ((M, K), BF16), ((E, K, N), BF16), ((E,), I32))


def _gdn_decode_step(B, H=32, D=128):
    from kubeinfer_tpu.inference.gdn import gdn_decode_step

    vec = ((B, H, D), F32)
    return gdn_decode_step, (
        vec, vec, vec, ((B, H), F32), ((B, H), F32), ((B, H, D, D), F32))


def _decode_blocks_q8(T):
    from kubeinfer_tpu.inference.flash_attention import (
        decode_attention_blocks_q8,
    )

    nq, nkv, D = QWEN2_7B
    pool = (pool_shape(_NB, _BS, nkv, D), I8)
    scales = ((_NB, nkv), F32)
    tail = ((_B, *pool_shape(2, _BS, nkv, D)), BF16)
    return decode_attention_blocks_q8, (
        ((_B, T, nq, D), BF16), pool, pool, scales, scales, tail, tail,
        ((_B, _MB), I32), ((_B,), I32),
    )


# every call the qwen2-7b step programs issue: decode's 8 live rows, the
# 64/128/256/512-row admit buckets and prefill chunk x gate/up, down,
# q/o, k/v. Each compiles at the tiles weight_quant.quant_matmul_tiles
# picks for it, under the VMEM limit the kernel states.
QUANT_MATMUL_ROWS = (8, 64, 128, 256, 512)
QUANT_MATMUL_PROJECTIONS = (
    (3584, 18944), (18944, 3584), (3584, 3584), (3584, 512))


def _quant_matmul(M, K, N):
    from kubeinfer_tpu.inference.weight_quant import quant_matmul

    return quant_matmul, (((M, K), BF16), ((K, N), I8), ((N,), F32))


def _route_pick(B, R):
    from kubeinfer_tpu.solver.pallas_kernels import route_pick_pallas

    return route_pick_pallas, (
        ((B, R), I32), ((R,), F32), ((B,), jnp.bool_),
    )


def _packed_solve(J, N, policy, accel):
    """The whole jitted solve the scheduler backend dispatches, on the
    single packed buffer, with the accel pinned (``auto`` would ask
    jax.default_backend(), which is the CPU here)."""
    from kubeinfer_tpu.scheduler.backends import _packed_solver
    from kubeinfer_tpu.solver.problem import packed_words

    fn = functools.partial(
        _packed_solver(), J=J, N=N, policy=policy, accel=accel,
        seeded=False,
    )
    return fn, (((packed_words(J, N),), F32),)


CASES = {
    "ragged-qwen2-7b": lambda: _ragged(QWEN2_7B, 128, 4096),
    "ragged-gemma-2b": lambda: _ragged(GEMMA_2B, 512, 4096),
    "ragged-llama-3-8b": lambda: _ragged(LLAMA3_8B, 512, 4096),
    "decode-blocks-T1": lambda: _decode_blocks(1),
    "decode-blocks-T5": lambda: _decode_blocks(5),
    # qwen3-next-80b at --batch-slots 64: one block of pool a slot
    "decode-blocks-qwen3-next": lambda: _decode_blocks(
        1, QWEN3_NEXT, B=64, NB=1 + 64 * _MB),
    "moe-gmm-decode-640x2048x512": lambda: _moe_grouped_matmul(
        640, 2048, 512),
    "moe-gmm-prefill-2560x512x2048": lambda: _moe_grouped_matmul(
        2560, 512, 2048),
    "gdn-decode-step-64": lambda: _gdn_decode_step(64),
    "decode-blocks-q8-T1": lambda: _decode_blocks_q8(1),
    "decode-blocks-q8-T5": lambda: _decode_blocks_q8(5),
    **{
        f"quant-matmul-{M}x{K}x{N}":
            functools.partial(_quant_matmul, M, K, N)
        for M in QUANT_MATMUL_ROWS for K, N in QUANT_MATMUL_PROJECTIONS
    },
    "route-pick-256x128": lambda: _route_pick(256, 128),
    "solve-mega-12288x1024": lambda: _packed_solve(
        12288, 1024, "jax-greedy", "mega"),
    "solve-auction-1024x1024": lambda: _packed_solve(
        1024, 1024, "jax-auction", "pallas"),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_compiles_for_v5e(case, one_chip, no_compile_cache):
    fn, operands = CASES[case]()
    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        for shape, dtype in operands
    ]
    compiled = jax.jit(fn).lower(*args).compile()
    # a router that fell to its dense branch would compile too
    assert "tpu_custom_call" in compiled.as_text()


# --- the pool stays where it lies -------------------------------------------
# decode_attention_blocks reads the pool as stored, and the token write
# is one in-place scatter of n_kv rows a token. Either can come back as
# a relayout of the whole pool without a test on the CPU noticing: a
# transpose in front of the kernel, or a scatter spelled so that the
# TPU compiler wants its operand token-major (it then copies the pool
# there and back, every step). Only the optimised HLO shows it.

# (query heads, kv heads, head_dim, hidden, slots, pool blocks): the
# served geometry of each benchmark configuration's full-attention layer
POOLS = {
    "qwen2-7b": (*QWEN2_7B, 3584, 8, 513),
    "qwen3-next": (*QWEN3_NEXT, 2048, 64, 2049),
}
_RELAYOUTS = re.compile(
    r" (copy|copy-start|copy-done|transpose|fusion)\(")


def _pool_sized(text, count):
    """Instructions of an optimised HLO module that copy, transpose or
    fuse to a result of ``count`` elements, other than the in-place
    update (a fusion around the scatter)."""
    bodies, name = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\{$", line)
        if head:
            name = head.group(1)
            bodies[name] = []
        elif name is not None:
            bodies[name].append(line)
    found = []
    for lines in bodies.values():
        for line in lines:
            op = _RELAYOUTS.search(line)
            if not op or " = " not in line:
                continue
            result = line[line.index(" = "):op.start()]
            sizes = [
                functools.reduce(int.__mul__, map(int, dims.split(",")))
                for dims in re.findall(r"\w\[([\d,]+)\]", result)]
            if count not in sizes:
                continue
            called = re.search(r"calls=%?([\w.\-]+)", line)
            update = called and any(
                " scatter(" in x or " dynamic-update-slice(" in x
                for x in bodies.get(called.group(1), ()))
            if op.group(1) != "fusion" or not update:
                found.append(line.strip()[:160])
    return found


@pytest.mark.parametrize("model", sorted(POOLS))
def test_decode_window_leaves_the_pool_where_it_lies(
        model, one_chip, no_compile_cache, monkeypatch):
    import dataclasses

    from kubeinfer_tpu.inference import flash_attention as fa
    from kubeinfer_tpu.inference.config import PRESETS
    from kubeinfer_tpu.inference.model import init_params
    from kubeinfer_tpu.inference.stepper import (
        decode_window,
        init_slot_state,
    )

    # the router asks jax.default_backend(), which is the CPU here
    monkeypatch.setattr(fa, "decode_blocks_available", lambda bs, D: True)
    nq, nkv, D, hidden, slots, blocks = POOLS[model]
    cfg = dataclasses.replace(
        PRESETS["qwen2-7b"], num_hidden_layers=1, vocab_size=1024,
        hidden_size=hidden, intermediate_size=1024,
        num_attention_heads=nq, num_key_value_heads=nkv,
        head_dim_override=D)

    def placed(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = placed(jax.eval_shape(functools.partial(
        init_params, cfg, dtype=BF16), jax.random.PRNGKey(0)))
    state = placed(jax.eval_shape(functools.partial(
        init_slot_state, cfg, slots, _MB * _BS, BF16, blocks, _BS)))
    pool = state.caches_k[0]
    assert pool.shape == pool_shape(blocks, _BS, nkv, D)
    text = decode_window.lower(params, state, cfg, 1).compile().as_text()
    assert "decode_attention_blocks" in text
    assert _pool_sized(text, pool.size) == []
    # the parent's spelling of the read is what the guard is for
    seen = jax.jit(lambda p: p.transpose(0, 2, 1, 3)).lower(
        pool).compile().as_text()
    assert _pool_sized(seen, pool.size)


# --- the names the device profile carries ----------------------------------
# stepprof.KERNEL_NAMES is what a reader of the profile matches; a
# Pallas kernel's name exists only in a TPU compile, so it is held
# here, at small shapes (the kernel's name does not depend on them).

_SMALL = (8, 2, 128)  # query heads, kv heads, head_dim


def _named_kernel(name):
    from kubeinfer_tpu.inference import flash_attention as fa
    from kubeinfer_tpu.inference import weight_quant as wq

    nq, nkv, D = _SMALL
    q1 = ((2, 1, nq, D), BF16)
    dense = ((2, 256, nkv, D), BF16)
    qT = ((1, 128, nq, D), BF16)
    kvT = ((1, 256, nkv, D), BF16)
    pages = pool_shape(9, 128, nkv, D)
    pool, pool8 = (pages, BF16), (pages, I8)
    table, lens = ((2, 4), I32), ((2,), I32)
    scales = ((9, nkv), F32)
    tail = ((2, *pool_shape(2, 128, nkv, D)), BF16)
    return {
        "quant_matmul": (wq.quant_matmul, (
            ((8, 256), BF16), ((256, 256), I8), ((256,), F32))),
        "decode_attention": (fa.decode_attention, (q1, dense, dense, lens)),
        "decode_attention_blocks": (fa.decode_attention_blocks, (
            q1, pool, pool, table, lens)),
        "decode_attention_blocks_q8": (fa.decode_attention_blocks_q8, (
            q1, pool8, pool8, scales, scales, tail, tail, table, lens)),
        "flash_attention": (fa.flash_attention, (
            qT, kvT, kvT, ((1, 128, 256), jnp.bool_))),
        "flash_attention_ragged": (fa.flash_attention_ragged, (
            qT, kvT, kvT, ((), I32), ((1,), I32))),
        "moe_grouped_matmul": _moe_grouped_matmul(128, 128, 128, E=4),
        "gdn_decode_step": _gdn_decode_step(2, H=8),
    }[name]


@pytest.mark.parametrize("name", KERNEL_NAMES)
def test_kernel_carries_its_name_for_v5e(name, one_chip, no_compile_cache):
    fn, operands = _named_kernel(name)
    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        for shape, dtype in operands
    ]
    text = jax.jit(fn).lower(*args).compile().as_text()
    # the custom call's instruction is named after the kernel, which is
    # the head of the HLO line a TPU trace prints for the event, and
    # its op_name ends .../<name>/pallas_call
    assert re.search(
        rf'%{name}(\.\d+)? = [^\n]*custom_call_target="tpu_custom_call"',
        text)
    assert f'/{name}/pallas_call"' in text
