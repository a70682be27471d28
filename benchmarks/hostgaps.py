"""Idle time of the device by what the host was doing meanwhile:
``python benchmarks/hostgaps.py <trace dir> [--save sample.json]``.

Reads the one ``.xplane.pb`` under the trace directory that
``run.py --trace 1`` leaves in ``.bench_out/<cell>/trace`` and prints,
one JSON object a line: (1) the clock check of every decode window
(dispatch span before module event before readback's end), (2) idle
time by the innermost ``engine.*`` span that covers each gap, in
seconds and as a share of all idle time, ``unattributed`` among them,
for all gaps and for those over 1 ms, with the longest gaps and their
owners, (3) self time of every ``engine.*`` span per scheduler pass.
The arithmetic is lib/hostspans.py. Run it after the server has gone:
it holds itself to the CPU, as lib/trace.py's run does.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"  # never ask for the chip

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from lib import hostspans  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--save", help="also write the sample (spans, busy "
                    "stretches, modules) to this file")
    args = ap.parse_args(argv)
    sample = hostspans.extract(args.trace_dir)
    if args.save:
        with open(args.save, "w", encoding="utf-8") as f:
            json.dump(sample, f)
    if not sample["spans"]:
        print("hostgaps.py: no engine.pass span in the trace: the "
              "program writes none, or no profiler session ran",
              file=sys.stderr)
        return 1
    rep = hostspans.report(sample)
    for name, dev in rep["devices"].items():
        print(json.dumps({"device": name, "clock_check":
                          dev.pop("clock_check")}))
        print(json.dumps({"device": name, **dev}))
    print(json.dumps({"self_time_ms_per_pass":
                      rep["self_time_ms_per_pass"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
