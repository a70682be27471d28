"""A run's served tokens against the plain reference, on the device the
run has just left: ``python3 benchmarks/reference/compare.py --config
<file> --served <file> --out <file> [--control <weight type>]``.

``served`` is a list of {"index", "prompt", "tokens"}: prompts as they
were sent and the tokens the server answered, greedy. The reference
(``reference/<model_type>.py``, found by the configuration's
``model_type``) runs once over each prompt with its served tokens,
layer by layer so that a model the chip only just holds fits: one
layer's weights are made from the seed, every sequence goes through
it, and they are dropped. For every served token it reads the gap: the
reference's best logit at that position less the reference's logit of
the served token, 0 where they are the same token. A sound server's
gaps are the rounding of its arithmetic; a wrong token's gap is the
spread of the logits.

``--control int4`` puts the reference itself in the program's place,
its weights in that lower type: at each position the token it puts
first is read for its gap in the same way. It is what a limit must
fail (PERF.md, section 2).

Nothing of the program is imported and nothing it made is read.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

PAD = 256  # sequences are padded to a multiple, to compile few shapes
ROWS = 256  # and so are the served positions, which go through the head


def gaps_of(logits, tokens):
    """best - logit[token] for each row, as a list of floats."""
    import jax.numpy as jnp

    rows = jnp.arange(logits.shape[0])
    return (jnp.max(logits, -1) - logits[rows, tokens]).tolist()


def run(conf: dict, served: list[dict], weight_dtype: str) -> list:
    """For each served request the logits f32[n, V] at its n served
    positions, from weights of ``weight_dtype``."""
    import jax
    import jax.numpy as jnp

    ref = importlib.import_module("reference." + conf["model_type"])
    seed = conf["assumed"]["weights_seed"]
    k_embed, k_layers, k_head = ref.weight_keys(seed)
    seqs = [r["prompt"] + r["tokens"][:-1] for r in served]
    T = -(-max(len(s) for s in seqs) // PAD) * PAD
    toks = jnp.asarray([s + [0] * (T - len(s)) for s in seqs], jnp.int32)

    make = jax.jit(lambda k, i: ref.make_layer(k, i, conf, weight_dtype))
    step = jax.jit(lambda h, lp: ref.layer(h, lp, conf))
    with jax.default_matmul_precision("highest"):
        ends = jax.jit(lambda a, b: ref.make_ends(a, b, conf))(k_embed,
                                                                k_head)
        # causal: the padding behind a sequence's end reaches no row
        # before it
        embed = jax.jit(ref.embed)
        hs = [embed(ends, t) for t in toks]
        for i in range(conf["num_hidden_layers"]):
            lp = make(k_layers, jnp.int32(i))
            hs = [step(h, lp) for h in hs]
        # the served positions of every request in one call of one
        # shape: which row of which sequence, padded to a multiple
        where = [(n, len(r["prompt"]) - 1 + j) for n, r in enumerate(served)
                 for j in range(len(r["tokens"]))]
        pad = -len(where) % ROWS
        seq, pos = (jnp.asarray(x + (0,) * pad, jnp.int32)
                    for x in zip(*where))
        head = jax.jit(lambda e, h: ref.logits(e, h, conf))
        rows = head(ends, jnp.stack(hs)[seq, pos])
    out, at = [], 0
    for r in served:
        out.append(rows[at:at + len(r["tokens"])])
        at += len(r["tokens"])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--served", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--control", default="",
                    help="a weight type below the configuration's")
    args = ap.parse_args(argv)
    with open(args.config, encoding="utf-8") as f:
        conf = json.load(f)
    with open(args.served, encoding="utf-8") as f:
        served = json.load(f)

    t0 = time.monotonic()
    import jax
    import jax.numpy as jnp

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", os.path.join(
            os.path.dirname(ROOT), ".jax_cache"))
    logits = run(conf, served, conf["weight_dtype"])
    doc = {"device": jax.devices()[0].device_kind, "requests": []}
    for r, lg in zip(served, logits):
        doc["requests"].append({
            "index": r["index"], "prompt_len": len(r["prompt"]),
            "gaps": gaps_of(lg, jnp.asarray(r["tokens"], jnp.int32))})
    if args.control:
        lower = run(conf, served, args.control)
        for row, lg, lo in zip(doc["requests"], logits, lower):
            row["control_gaps"] = gaps_of(lg, jnp.argmax(lo, -1))
    doc["gap_max"] = max(g for r in doc["requests"] for g in r["gaps"])
    doc["tokens"] = sum(len(r["gaps"]) for r in doc["requests"])
    if args.control:
        doc["control"] = args.control
        doc["control_gap_max"] = max(
            g for r in doc["requests"] for g in r["control_gaps"])
    doc["seconds"] = time.monotonic() - t0
    with open(args.out, "w", encoding="utf-8") as f:
        json.dump(doc, f)
    print(json.dumps({k: v for k, v in doc.items() if k != "requests"}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
