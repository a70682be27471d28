"""sizes/<model_type>.py: the bytes lib/model_size.py gave before it
moved, pinned, and the operations step.mfu prices a token at."""

import os
import sys

import pytest

from lib import cell as cells

sys.path.insert(0, cells.CHECKOUT)


@pytest.mark.parametrize("config,weight_dtype,want", [
    ("qwen2-7b-w8", "int8", 8_711_506_944),
    ("qwen2-7b-w8", "bf16", 15_231_233_024),
    ("tiny-rehearsal", "int8", 144_000),
    ("tiny-rehearsal", "bf16", 213_632),
])
def test_param_bytes_are_what_they_were(config, weight_dtype, want):
    conf = cells.load_json("configs", config + ".json")
    got = cells.sizes(conf["model_type"]).param_bytes(conf, weight_dtype)
    assert got == want


def test_int8_bytes_are_the_smokes():
    import chip_smoke

    conf = cells.load_json("configs", "qwen2-7b-w8.json")
    assert cells.sizes("qwen2").param_bytes(conf, "int8") == \
        chip_smoke.expected_param_bytes()


def test_unknown_weight_type_is_an_error():
    conf = cells.load_json("configs", "qwen2-7b-w8.json")
    with pytest.raises(ValueError):
        cells.sizes("qwen2").param_bytes(conf, "fp4")


def test_flops_per_token_is_two_a_matmul_weight():
    conf = cells.load_json("configs", "qwen2-7b-w8.json")
    price = cells.sizes("qwen2").flops_per_token(conf)
    H, F, V = 3584, 18944, 152064
    layer = H * H + 2 * H * 512 + H * H + 3 * H * F
    assert price == {"layers": 2 * 28 * layer, "head": 2 * H * V}
    # biases and norms are no matmul: llama's price is qwen2's
    assert cells.sizes("llama").flops_per_token(conf) == price
