"""Qwen3-Next's files: sizes/qwen3_next.py is the server's own count,
reference/qwen3_next.py makes the weights the program serves and tells
a sound server from the control, and the two kernels' operation counts
read the HLO lines a v5e compile prints."""

import json
import os
import sys

import pytest

from lib import cell as cells
from lib.context import Context
from lib.peaks import peaks_for

sys.path.insert(0, cells.CHECKOUT)

SMALL = dict(
    name="small", model_type="qwen3_next", vocab_size=512, hidden_size=128,
    intermediate_size=256, num_hidden_layers=8, num_attention_heads=4,
    num_key_value_heads=2, head_dim=32, full_attention_interval=4,
    linear_conv_kernel_dim=4, linear_key_head_dim=32,
    linear_num_key_heads=2, linear_num_value_heads=4,
    linear_value_head_dim=32, moe_intermediate_size=64, num_experts=4,
    num_experts_per_tok=4, shared_expert_intermediate_size=64,
    norm_topk_prob=True, partial_rotary_factor=0.25, rms_norm_eps=1e-6,
    rope_theta=1e7, tie_word_embeddings=False,
    expert_parallel={"size": 4, "rank": 2}, max_position_embeddings=512,
    weight_dtype="bf16", assumed={"weights_seed": 0},
)
TEST_LIMIT = 0.15  # at this size: see the test that uses them
TEST_MEAN_LIMIT = 0.001


def _program(dtype):
    import jax

    from kubeinfer_tpu.inference.config import ModelConfig
    from kubeinfer_tpu.inference.model import init_params

    cfg = ModelConfig.from_hf_dict(SMALL)
    return cfg, init_params(cfg, jax.random.PRNGKey(0), dtype=dtype)


def test_param_bytes_of_the_cell_are_pinned():
    conf = cells.load_json("configs", "qwen3-next-80b-ep4.json")
    size = cells.sizes("qwen3_next")
    assert size.param_bytes(conf, "bf16") == 7_334_503_424
    with pytest.raises(ValueError):
        size.param_bytes(conf, "int8")
    # 10 pairs a token of which a quarter are held here
    price = size.flops_per_token(conf)
    assert price["head"] == 2 * 2048 * 37984
    every = sum(size.layer_matrices(conf, i)["every"] for i in range(8))
    assert price["layers"] == 2 * (every + 8 * 2.5 * 3 * 2048 * 512)


def test_param_bytes_are_the_servers_count():
    """kubeinfer_model_param_bytes is the sum of the leaves' bytes
    (ContinuousEngine.model_param_bytes): checks/identity.py holds a
    run to this equality."""
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    _, params = _program(jnp.bfloat16)
    served = sum(x.nbytes for x in jax.tree.leaves(params))
    assert cells.sizes("qwen3_next").param_bytes(SMALL, "bf16") == served


def test_reference_makes_the_weights_the_program_serves():
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    from reference import qwen3_next as ref

    _, params = _program(jnp.bfloat16)
    k_embed, k_layers, k_head = ref.weight_keys(0)
    names = {"gate_proj": "experts_gate", "up_proj": "experts_up",
             "down_proj": "experts_down", "shared_gate_proj": "shared_gate",
             "shared_up_proj": "shared_up",
             "shared_down_proj": "shared_down", "norm": "gdn_norm"}
    for i in (0, 3, 6):
        mine = ref.make_layer(k_layers, i, SMALL, "bf16")
        theirs = params["layers"][i]
        assert bool(mine["is_full"]) == ("q_proj" in theirs)
        flat = {**theirs, **theirs["moe"], **theirs.get("linear_attn", {})}
        for name, leaf in flat.items():
            if isinstance(leaf, dict):
                continue
            want = mine[names.get(name, name)]
            assert leaf.dtype == want.dtype and bool((leaf == want).all()), \
                (i, name)
    for name, leaf in ref.make_ends(k_embed, k_head, SMALL).items():
        assert bool((leaf == params[name]).all()), name


@pytest.mark.parametrize("seed", range(2))
def test_the_program_reads_under_the_limits_and_the_controls_over(seed):
    """The comparison a run's ``correct`` rests on, at a small size: the
    program as served (bfloat16) generates greedily, reference/
    compare.py reads its tokens under both limits, the structural
    control (the first layer's decay left out) over the limit on the
    widest gap, and the next weight precision below the served one
    (int8) over the limit on the mean, where its widest gap lies too
    close to a sound run's to tell them apart. Readings at this size,
    widest / mean: the program 0.020-0.024 / 0.00025-0.00038, int8
    0.043-0.087 / 0.0020-0.0023, int4 0.77-0.86 / 0.24, no_decay
    1.16-1.23 / 0.43. (The recurrent state kept in bfloat16 reads
    0.024-0.038 / 0.00034 here, inside the program's own rounding:
    checks/recurrent_state.py holds it by its bytes.)"""
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    import numpy as np

    from kubeinfer_tpu.inference.model import forward
    from reference import compare

    mean_gap = cells.check("reference_mean").mean_gap
    cfg, params = _program(jnp.bfloat16)
    step = jax.jit(lambda t: jnp.argmax(forward(params, t, cfg)[0], -1))
    L, P = 192, 40
    toks = np.zeros((3, L), np.int32)
    toks[:, :P] = np.random.default_rng(seed).integers(0, 512, (3, P))
    for p in range(P - 1, L - 1):  # causal: the zeros behind p reach no row
        toks[:, p + 1] = np.asarray(step(jnp.asarray(toks)))[:, p]
    served = [{"index": i, "prompt": toks[i, :P].tolist(),
               "tokens": toks[i, P:].tolist()} for i in range(3)]
    ref = compare.run(SMALL, served, "bf16")

    def read(tokens):
        doc = {"requests": [{"gaps": compare.gaps_of(lg, t)}
                            for lg, t in zip(ref, tokens)]}
        return max(max(r["gaps"]) for r in doc["requests"]), mean_gap(doc)

    def control(name):
        return read([jnp.argmax(lo, -1)
                     for lo in compare.run(SMALL, served, name)])

    program = read([jnp.asarray(s["tokens"]) for s in served])
    no_decay, int8 = control("no_decay"), control("int8")
    assert program[0] < TEST_LIMIT < no_decay[0]
    assert no_decay[0] >= 3 * program[0]
    assert program[1] < TEST_MEAN_LIMIT < int8[1]
    assert int8[1] >= 3 * program[1]
    # the state's rounding is really applied (a cast there and back is
    # taken out by the TPU's compiler: reduce_precision is not)
    assert control("state_bf16")[1] > 0


def test_the_mean_check_reads_the_comparison_the_reference_check_left(
        tmp_path):
    check = cells.check("reference_mean")

    class Run:
        out, compared = str(tmp_path), {}

        class cell:
            spec = {"reference": {"mean_gap_limit": 0.005}}

    assert "no comparison" in check.after_exit(Run)[0]
    (tmp_path / "reference_served.json").write_text("[]")
    for gaps, wrong in (([0.0, 0.004, 0.0, 0.008], 0),
                        ([0.0, 0.02, 0.0, 0.02], 1)):
        (tmp_path / "reference_out.json").write_text(json.dumps(
            {"requests": [{"gaps": gaps[:2]}, {"gaps": gaps[2:]}]}))
        assert len(check.after_exit(Run)) == wrong
        assert Run.compared["logit_gap_mean"] == [sum(gaps) / 4, 0.005]


# the custom-call lines of a v5e compile (tests/test_chip_compile.py
# compiles the same shapes), cut to what the patterns read
GMM_LINE = (
    "%moe_grouped_matmul.1 = bf16[640,512]{1,0:T(8,128)(2,1)} custom-call("
    "%add_clamp_fusion, %get-tuple-element.43, %dynamic_slice.0, "
    "%pad_add_fusion, %x.1, /*index=5*/%w.1), custom_call_target="
    '"tpu_custom_call", operand_layout_constraints={s32[132]{0}, '
    "s32[132]{0}, s32[1]{0}, s32[129]{0}, bf16[640,2048]{1,0}, "
    "bf16[128,2048,512]{2,1,0}}")
GDN_LINE = (
    "%gdn_decode_step.1 = (f32[64,32,128,128]{3,2,1,0:T(8,128)}, "
    "f32[64,32,128]{2,1,0:T(8,128)}) custom-call(%pad.2, %copy.6), "
    'custom_call_target="tpu_custom_call", operand_layout_constraints={'
    "f32[64,32,8,128]{3,2,1,0}, f32[64,32,128,128]{3,2,1,0}}")


def test_grouped_matmul_counts_reached_experts_and_held_rows():
    oc = cells.opcount("moe_grouped_matmul")
    assert oc.shapes_from_hlo(GMM_LINE) == (640, 2048, 512, 128)
    ops, moved = oc.count(640, 2048, 512, 128, reached=90.5, rows=160.0)
    assert moved == 2 * (90.5 * 2048 * 512 + 160 * 2048 + 160 * 512)
    assert ops == 2 * 160 * 2048 * 512
    # a counter that runs past the shape is not hidden: it has to show
    assert oc.count(640, 2048, 512, 128, reached=500, rows=9999)[1] > \
        oc.count(640, 2048, 512, 128, reached=128, rows=640)[1]
    assert oc.shapes_from_hlo("%fusion.3 = f32[8]{0} fusion()") is None


def test_gdn_step_counts_the_decoding_rows_state_once_in_and_once_out():
    oc = cells.opcount("gdn_decode_step")
    assert oc.shapes_from_hlo(GDN_LINE) == (64, 32, 128, 128)
    # 11.5 of the 64 slots decoding: the idle slots need nothing
    ops, moved = oc.count(64, 32, 128, 128, rows=11.5)
    state = 11.5 * 32 * 128 * 128 * 4
    assert 2 * state < moved < 1.02 * 2 * state
    assert ops == 7 * 11.5 * 32 * 128 * 128


def _page(reached, held, calls, row_steps=0, steps=0):
    return (f"kubeinfer_moe_experts_reached_total {reached}\n"
            f"kubeinfer_moe_held_pairs_total {held}\n"
            f"kubeinfer_moe_calls_total {calls}\n"
            f"kubeinfer_engine_decode_row_steps_total {row_steps}\n"
            f"kubeinfer_engine_decode_steps_total {steps}\n")


def test_counted_roofline_takes_its_means_from_the_window():
    m = cells.load_json("layer_metrics", "kernel.moe_matmul_roofline.json")
    args = dict(m["reader"])
    read = cells.reader(args.pop("kind"))
    # 100 calls of 2 ms each; 64 experts and 128 rows a call
    trace = {"devices": {"/device:TPU:0": {
        "busy_s": 1.0, "window_s": 1.0,
        "ops": [[GMM_LINE, 0.2, 100]]}}}
    ctx = Context(seconds=51, setup_s=0, trace=trace,
                  peaks=peaks_for("TPU v5 lite"),
                  scrapes=[(0, _page(0, 0, 0)),
                           (51, _page(6400, 12800, 100))])
    weights = 2 * (64 * 2048 * 512 + 128 * 2048 + 128 * 512)
    want = 100.0 * (100 * weights / 819e9) / 0.2
    assert read(args, ctx) == pytest.approx(want)
    # a server without the counters (the parent) has nothing to read
    ctx.scrapes = [(0, ""), (51, "")]
    assert read(args, ctx) is None


def test_gdn_roofline_counts_the_rows_that_decoded():
    """600 calls of 0.4 ms each (6 layers, 100 steps) with 16 of the 64
    slots decoding: a kernel that walks every slot reads a quarter of
    what it moves."""
    m = cells.load_json("layer_metrics", "kernel.gdn_step_roofline.json")
    args = dict(m["reader"])
    read = cells.reader(args.pop("kind"))
    trace = {"devices": {"/device:TPU:0": {
        "busy_s": 1.0, "window_s": 1.0,
        "ops": [[GDN_LINE, 0.24, 600]]}}}
    ctx = Context(seconds=51, setup_s=0, trace=trace,
                  peaks=peaks_for("TPU v5 lite"),
                  scrapes=[(0, _page(0, 0, 0)),
                           (51, _page(0, 0, 0, row_steps=1600, steps=100))])
    _, moved = cells.opcount("gdn_decode_step").count(
        64, 32, 128, 128, rows=16.0)
    assert read(args, ctx) == pytest.approx(
        100.0 * (600 * moved / 819e9) / 0.24)
    assert 20 < read(args, ctx) < 25
    ctx.scrapes = [(0, ""), (51, "")]
    assert read(args, ctx) is None


def test_recurrent_state_bytes_are_the_servers_count(monkeypatch):
    """checks/recurrent_state.py holds a run to this equality: the
    engine's count of what its linear-attention layers hold is the
    configuration's, a float32 state and a 16-bit tail a layer and
    slot; a state kept in bfloat16 would read about half and fail."""
    pytest.importorskip("jax")
    import jax.numpy as jnp

    from kubeinfer_tpu.inference.stepper import init_slot_state
    from lib import client

    cfg, _ = _program(jnp.bfloat16)
    st = init_slot_state(cfg, 3, 64, jnp.bfloat16, num_blocks=4,
                         block_size=16)
    served = sum(x.nbytes for x in (*st.gdn_state, *st.gdn_conv))
    assert all(x.dtype == jnp.float32 for x in st.gdn_state)
    conf = dict(SMALL, server_args=["--batch-slots", "3"])
    want = cells.sizes("qwen3_next").recurrent_state_bytes(conf, 3)
    assert want == served == 6 * 3 * (4 * 4 * 32 * 32 + 2 * 3 * 256)

    check = cells.check("recurrent_state")

    class Run:
        config, url, compared = conf, "http://x", {}

    for got, wrong in ((served, 0), (served // 2, 1)):
        monkeypatch.setattr(client, "http", lambda *a, **k: (
            200, f"kubeinfer_recurrent_state_bytes {got}\n"))
        assert len(check.before_window(Run)) == wrong
        assert Run.compared["recurrent_state_bytes"] == [got, want]


def test_the_cell_asks_the_reference_and_warms_both_buckets():
    spec = cells.load_json("workloads", "qwen3-next-80b-ep4.gen.json")
    traffic = cells.load_json("traffic", "gen.json")
    assert spec["checks"] == ["identity", "recurrent_state", "reference",
                              "reference_mean"]
    assert spec["reference"]["mean_gap_limit"] < spec["reference"][
        "gap_limit"]
    lens = sorted(w["prompt_len"] for w in spec["warmup"])
    lo, hi = traffic["prompt_len"]["min"], traffic["prompt_len"]["max"]
    assert lo > 64 and lens[0] <= 128 < lens[1] <= hi == 256
    # nothing reused for a model with recurrent layers: no hit expected
    assert not any(w.get("expect_hit") for w in spec["warmup"])
    assert spec["rate_req_s"] <= 0.6 * spec["knee_req_s"] * 1.001
