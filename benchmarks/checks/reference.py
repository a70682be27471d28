"""The served tokens against the plain reference, once the window has
closed, the peak memory is read and the child has left the chips.

The cell's file gives ``reference``: {"requests": how many of the
judged requests to compare, "gap_limit": the widest gap a served
token's logit may lie below the reference's best}. The sample is drawn
from the run's seed and always holds the longest request, so that the
longest context the window served is among what is compared.
reference/compare.py does the arithmetic in a process of its own (this
one never imports jax); its time is no part of ``setup_s``.
"""

import json
import os
import random
import subprocess
import sys

from lib import schedule
from lib.cell import CHECKOUT, ROOT

LIMIT_S = 300.0


def sample(window, k: int, seed: int):
    ok = sorted((r for r in window if r.ok and r.tokens),
                key=lambda r: r.index)
    if not ok:
        return []
    longest = max(ok, key=lambda r: (r.prompt_len + len(r.tokens), r.index))
    rest = [r for r in ok if r is not longest]
    picked = random.Random(f"reference/{seed}").sample(
        rest, min(k - 1, len(rest)))
    return sorted([longest] + picked, key=lambda r: r.index)


def after_exit(run):
    spec = run.cell.spec["reference"]
    vocab = run.config["vocab_size"]
    by_index = {q.index: q for q in run.requests}
    served = [{"index": r.index, "tokens": r.tokens,
               "prompt": schedule.prompt_tokens(by_index[r.index],
                                                run.seed, vocab)}
              for r in sample(run.window, spec["requests"], run.seed)]
    if not served:
        return ["reference: no answered request to compare"]
    paths = {k: os.path.join(run.out, f"reference_{k}.json")
             for k in ("served", "out")}
    with open(paths["served"], "w", encoding="utf-8") as f:
        json.dump(served, f)
    cmd = [sys.executable, os.path.join(ROOT, "reference", "compare.py"),
           "--config", os.path.join(ROOT, "configs",
                                    run.config["name"] + ".json"),
           "--served", paths["served"], "--out", paths["out"]]
    try:
        r = subprocess.run(cmd, cwd=CHECKOUT, timeout=LIMIT_S,
                           stdout=subprocess.DEVNULL, stderr=subprocess.PIPE,
                           text=True)
    except subprocess.TimeoutExpired:
        return [f"reference: no result in {LIMIT_S:.0f} s"]
    if r.returncode != 0:
        return [f"reference: compare.py exited {r.returncode}: "
                f"{r.stderr[-400:]}"]
    with open(paths["out"], encoding="utf-8") as f:
        doc = json.load(f)
    run.notes.append(f"reference: {doc['tokens']} served tokens of "
                     f"{len(served)} requests in {doc['seconds']:.1f} s")
    run.compared["logit_gap_max"] = [doc["gap_max"], spec["gap_limit"]]
    if not doc["gap_max"] <= spec["gap_limit"]:
        return [f"reference: a served token's logit lies {doc['gap_max']:.4f} "
                f"below the reference's best, the limit is "
                f"{spec['gap_limit']}"]
    return []
