"""The child: owns the chips and runs the program's own entry point.

It registers the configuration file's model under the configuration's
name, installs two signals that start and stop the device profiler, and
calls ``kubeinfer_tpu.inference.server.main`` with flags only. ``main``
installs SIGTERM/SIGINT alone, so the profiler signals survive it.
Weights come from ``PRNGKey(0)`` inside ``main``; the run's seed makes
the traffic, in the parent.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import threading

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)  # lib/
sys.path.insert(0, os.path.dirname(HERE))  # the checkout: kubeinfer_tpu/


def _profiler_thread(start: threading.Event, stop: threading.Event,
                     trace_dir: str) -> None:
    """Signal handlers only set events; the profiler calls, which take
    seconds, run here and never inside a handler."""
    import jax

    start.wait()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # the device's lines are what is read
    opts.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    with open(os.path.join(trace_dir, "started"), "w") as f:
        f.write("1")
    stop.wait()
    jax.profiler.stop_trace()
    with open(os.path.join(trace_dir, "done"), "w") as f:
        f.write("1")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True, help="configuration file")
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--out", required=True, help="directory for "
                    "device.json and the trace")
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearsal only: a cell of BENCHMARK.json never "
                    "passes this")
    args = ap.parse_args(argv)
    with open(args.config, encoding="utf-8") as f:
        conf = json.load(f)

    from kubeinfer_tpu.inference.config import PRESETS, ModelConfig
    from kubeinfer_tpu.utils.compile_cache import enable_compile_cache
    from lib.peaks import DEVICE_PEAKS

    # the file's top level is the source's config.json, so the
    # program's own reading of such a file builds the model
    PRESETS[conf["name"]] = ModelConfig.from_hf_dict(conf)

    enable_compile_cache()  # before the first compile, as main() does
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    with open(os.path.join(args.out, "device.json"), "w") as f:
        json.dump(device, f)
    if not args.allow_cpu:
        if device["platform"] != "tpu" or device["kind"] not in DEVICE_PEAKS:
            print(f"serve.py: {device} is not a TPU of a kind in the "
                  "peaks table", file=sys.stderr)
            return 3
        if device["count"] < conf["chips"]:
            print(f"serve.py: {device['count']} chip(s), the "
                  f"configuration needs {conf['chips']}", file=sys.stderr)
            return 3

    trace_dir = os.path.join(args.out, "trace")
    os.makedirs(trace_dir, exist_ok=True)
    start, stop = threading.Event(), threading.Event()
    threading.Thread(target=_profiler_thread, daemon=True,
                     args=(start, stop, trace_dir)).start()
    signal.signal(signal.SIGUSR1, lambda *_: start.set())
    signal.signal(signal.SIGUSR2, lambda *_: stop.set())

    from kubeinfer_tpu.inference import server

    return server.main([
        "--model", conf["name"], "--random-init",
        "--host", "127.0.0.1", "--port", str(args.port),
        *[str(a) for a in conf["server_args"]],
    ])


if __name__ == "__main__":
    sys.exit(main())
