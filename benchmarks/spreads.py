"""Spread of each metric over sets of runs, the way the bounds are set:
``python benchmarks/spreads.py <set a files...> -- <set b files...>``.

Each file holds one run's output; its last line is the result. For each
metric: each set's median and spread (distance between the quartiles by
``statistics.quantiles(n=4)`` over the median), the wider of the two,
five times that (the bound it asks for), and how far the second set's
median lies from the first's.
"""

from __future__ import annotations

import json
import statistics
import sys

from lib.stats import spread


def last_result(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        lines = [ln for ln in f.read().splitlines() if ln.startswith("{")]
    return json.loads(lines[-1])


def main(argv) -> int:
    args = argv[1:]
    cut = args.index("--") if "--" in args else len(args)
    sets = [s for s in (args[:cut], args[cut + 1:]) if s]
    table: dict = {}
    for i, files in enumerate(sets):
        for path in files:
            doc = last_result(path)
            if not doc.get("correct"):
                print(f"{path}: correct is {doc.get('correct')}")
            for name, m in doc["metrics"].items():
                table.setdefault(name, [[] for _ in sets])[i].append(
                    m["value"])
    for name, per_set in table.items():
        row = {"metric": name}
        spreads = []
        for i, values in enumerate(per_set):
            row[f"median_{i}"] = statistics.median(values)
            row[f"n_{i}"] = len(values)
            if len(values) >= 2:
                spreads.append(spread(values))
                row[f"spread_{i}"] = spreads[-1]
        if spreads:
            row["widest"] = max(spreads)
            row["bound_asked"] = 5 * max(spreads)
        if len(per_set) == 2 and all(per_set):
            row["second_vs_first"] = row["median_1"] / row["median_0"] - 1
        print(json.dumps(row))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
