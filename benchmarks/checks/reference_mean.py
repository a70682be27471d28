"""The served tokens against the plain reference, by the MEAN of what
checks/reference.py holds by its maximum: list this check behind that
one, whose comparison it reads (``reference_out.json``, every served
token's gap below the reference's best) and does not run again.

The cell's file gives ``reference``: {"mean_gap_limit": the widest mean
over the compared tokens}. The widest gap of a run is one token's luck
and tells a sound server from a wrong one; the mean over some 2,300
tokens moves a tenth from run to run and tells the served precision
from the next one below it, whose widest gap lies too close to a sound
run's (PERF.md, section 2, has the readings of both).
"""

import json
import os


def mean_gap(doc: dict, key: str = "gaps") -> float:
    """Mean of ``key`` over every compared token of compare.py's
    result (``control_gaps``: the same for its --control)."""
    gaps = [g for r in doc["requests"] for g in r[key]]
    return sum(gaps) / len(gaps)


def after_exit(run):
    limit = run.cell.spec["reference"]["mean_gap_limit"]
    served, out = (os.path.join(run.out, f"reference_{k}.json")
                   for k in ("served", "out"))
    # this run's comparison, not one an earlier run left behind
    if not (os.path.isfile(out) and os.path.isfile(served)
            and os.path.getmtime(out) >= os.path.getmtime(served)):
        return ["reference_mean: checks/reference.py left no comparison "
                "of this run to read"]
    with open(out, encoding="utf-8") as f:
        mean = mean_gap(json.load(f))
    run.compared["logit_gap_mean"] = [mean, limit]
    if not mean <= limit:
        return [f"reference_mean: the served tokens' logits lie {mean:.5f} "
                f"below the reference's best in the mean, the limit is "
                f"{limit}"]
    return []
