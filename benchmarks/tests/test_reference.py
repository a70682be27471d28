"""The program's forward against the plain reference, at a small size
on the CPU: qwen2 widths in miniature (biases on, 4 query heads on 2 KV
heads), float32 and int8 weights."""

import json
import os
import sys

import pytest

from lib import cell as cells

sys.path.insert(0, cells.CHECKOUT)


@pytest.mark.parametrize("weight_dtype,tol", [
    # float32 against float32: rounding order only
    ("bf16", 2e-4),
    # the program multiplies in the activation type after an exact int8
    # cast and scales the accumulator; the reference scales the weights
    # first: same numbers, another order
    ("int8", 2e-4),
])
def test_program_forward_matches_reference(weight_dtype, tol):
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    from kubeinfer_tpu.inference.config import ModelConfig
    from kubeinfer_tpu.inference.model import forward, init_params
    from reference import qwen2

    with open(os.path.join(cells.ROOT, "configs",
                           "tiny-rehearsal.json")) as f:
        conf = json.load(f)
    conf["model_type"] = "qwen2"  # biases on, as the cells' model has
    cfg = ModelConfig.from_hf_dict(conf)
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32,
                         weight_dtype=weight_dtype)
    # zero-init biases would hide a dropped bias: make them count
    for i, lp in enumerate(params["layers"]):
        for n in "qkv":
            b = lp[n + "_bias"]
            lp[n + "_bias"] = 0.1 * jax.random.normal(
                jax.random.PRNGKey(10 * i + ord(n)), b.shape, b.dtype)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (24,), 0,
                                cfg.vocab_size)
    want = qwen2.forward(params, tokens, conf)
    with jax.default_matmul_precision("highest"):
        got, _ = forward(params, tokens[None], cfg)
    assert got.shape[1:] == want.shape
    assert float(jnp.max(jnp.abs(got[0].astype(jnp.float32) - want))) < tol


# --- the served weights and the comparison a run's ``correct`` rests on

DEEPER = dict(model_type="qwen2", hidden_size=128, intermediate_size=384,
              num_hidden_layers=8, vocab_size=512)
TEST_LIMIT = 0.05  # at this size: the program reads under 0.0003 on six
# seeds, the int4 control 0.228 at the least


def _deeper():
    with open(os.path.join(cells.ROOT, "configs",
                           "tiny-rehearsal.json")) as f:
        conf = json.load(f)
    conf.update(DEEPER)
    return conf


@pytest.mark.parametrize("weight_dtype", ["bf16", "int8"])
def test_reference_makes_the_weights_the_program_serves(weight_dtype):
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    from kubeinfer_tpu.inference.config import ModelConfig
    from kubeinfer_tpu.inference.model import init_params
    from reference import qwen2

    conf = _deeper()
    params = init_params(ModelConfig.from_hf_dict(conf),
                         jax.random.PRNGKey(conf["assumed"]["weights_seed"]),
                         dtype=jnp.bfloat16, weight_dtype=weight_dtype)
    k_embed, k_layers, k_head = qwen2.weight_keys(
        conf["assumed"]["weights_seed"])
    for i in (0, 5):
        mine = qwen2.make_layer(k_layers, i, conf, weight_dtype)
        theirs = params["layers"][i]
        assert set(mine) == set(theirs)
        same = jax.tree.map(lambda a, b: bool((a == b).all())
                            and a.dtype == b.dtype, mine, theirs)
        assert all(jax.tree.leaves(same)), (i, same)
    ends = qwen2.make_ends(k_embed, k_head, conf)
    for name, leaf in ends.items():
        assert bool((leaf == params[name]).all()), name


@pytest.mark.parametrize("seed", range(6))
def test_the_program_reads_under_the_limit_and_the_control_over(seed):
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    import numpy as np

    from kubeinfer_tpu.inference.config import ModelConfig
    from kubeinfer_tpu.inference.model import forward, init_params
    from reference import compare

    conf = _deeper()
    cfg = ModelConfig.from_hf_dict(conf)
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16,
                         weight_dtype="int8")
    toks = np.random.default_rng(seed).integers(0, 512, (4, 96))
    logits, _ = forward(params, jnp.asarray(toks), cfg)
    greedy = np.asarray(jnp.argmax(logits, -1))
    # the program's greedy token after each of some prefixes, as a
    # served answer of one token
    served = [{"index": 100 * i + p, "prompt": toks[i, :p + 1].tolist(),
               "tokens": [int(greedy[i, p])]}
              for i in range(4) for p in range(40, 96, 8)]
    ref = compare.run(conf, served, "int8")
    low = compare.run(conf, served, "int4")
    program = max(compare.gaps_of(lg, jnp.asarray(s["tokens"]))[0]
                  for lg, s in zip(ref, served))
    control = max(compare.gaps_of(lg, jnp.argmax(lo, -1))[0]
                  for lg, lo in zip(ref, low))
    assert program < TEST_LIMIT < control
    assert control >= 3 * max(program, 1e-3)
