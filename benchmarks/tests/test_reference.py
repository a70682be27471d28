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
