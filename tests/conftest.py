"""Test configuration: force an 8-device virtual CPU mesh BEFORE jax imports.

Multi-chip TPU hardware is not available in CI; all sharding tests run on
8 virtual CPU devices (the same code path pjit/shard_map take on a real TPU
mesh — only the device kind differs). Must run before any test module
imports jax. Explicit assignment (not setdefault): on a machine with an
accelerator the ambient platform is that accelerator, and the tests are
written for eight CPU devices.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# If anything imported jax before this file ran, its config has already
# read JAX_PLATFORMS and the env assignment above alone is inert. Update
# the live config too (backends are still uninitialized at collection
# time, so this takes effect; if it ever runs too late, the assertion
# below catches it).
import jax

jax.config.update("jax_platforms", "cpu")
assert jax.devices()[0].platform == "cpu", (
    f"tests must run on the virtual CPU mesh, got {jax.devices()}"
)
assert len(jax.devices()) == 8, jax.devices()

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

# Persistent XLA compilation cache: the suite is jit-compile-bound (the
# top tests are 30-50s of pure compile), and the cache is keyed by HLO
# hash so reuse across runs is sound even as code changes (changed
# programs simply miss). Same rule as the program itself
# (utils/compile_cache.py): JAX_COMPILATION_CACHE_DIR if set, else
# <checkout>/.jax_cache.
from kubeinfer_tpu.utils.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)


def subprocess_pythonpath() -> str:
    """PYTHONPATH for spawned subprocesses: the repo first, then
    whatever the environment already had."""
    rest = os.environ.get("PYTHONPATH", "")
    return REPO_ROOT + (os.pathsep + rest if rest else "")


# --- suite tiering (r4 verdict item 7) -------------------------------------
# Component markers are derived from the module name so they can never
# drift from the file layout; `slow` is opted into per-test where the
# compile cost lives (the suite is compile-bound, not run-bound, so
# slowness is a property of individual jit programs, not components).
# `make test-fast` runs `-m "not slow"`; CI's full tier runs everything.

_COMPONENT_BY_PREFIX = (
    (("test_solver", "test_problem", "test_backends", "test_sharded",
      "test_distributed", "test_multiprocess"),
     "solver"),
    (("test_inference", "test_flash", "test_sampling"),
     "inference"),
    # resilience layer + fault-injection scenarios (`make test-chaos`);
    # pure controlplane work — runs under the same virtual CPU mesh
    (("test_chaos", "test_resilience"), "chaos"),
    # invariant linter + racecheck sentinel (kubeinfer_tpu/analysis/);
    # the sanitizer file covers the lockset detector + schedule fuzzer;
    # the protocol files cover the lifecycle spec (lint + replay oracle)
    (("test_static_analysis", "test_concurrency_sanitizer",
      "test_protocol"), "analysis"),
    # fleet router: scoring/summary round-trips + proxy; its chaos
    # scenario carries an explicit @pytest.mark.chaos on top
    (("test_router",), "router"),
    # tracing + serving latency breakdown (kubeinfer_tpu/observability/)
    (("test_observability",), "observability"),
)


def pytest_collection_modifyitems(config, items):
    import pytest

    for item in items:
        mod = item.module.__name__.rsplit(".", 1)[-1]
        for prefixes, marker in _COMPONENT_BY_PREFIX:
            if mod.startswith(prefixes):
                item.add_marker(getattr(pytest.mark, marker))
                break
        else:
            item.add_marker(pytest.mark.controlplane)


# --- concurrency sanitizer arming (ISSUE 9) ---------------------------------
# Every chaos-marked test (test_chaos, test_resilience, and router chaos
# scenarios) runs at KUBEINFER_RACECHECK=2: tracked locks feed the
# lock-order graph AND guard()-registered objects feed the Eraser
# lockset detector. Teardown fails the test on either oracle — a race
# the schedule happened not to lose is still a finding.
#
# ISSUE 17 adds a third oracle to the same fixture: a ProtocolMonitor
# streams every FlightRecorder.note through the request lifecycle spec
# (analysis/protocol.py) as it happens, so an illegal transition is a
# failure even when the bounded ring has already evicted the evidence.
# Legality-only at teardown: chains may legitimately end non-terminal
# (a test that stops mid-flight without sweeping, spec-group requests
# that never occupy a slot), so completeness is asserted only where a
# test knows its expected request set (protocol.assert_conformant).

import pytest  # noqa: E402 — after the jax mesh setup above


@pytest.fixture(autouse=True)
def _sanitizer_armed(request, monkeypatch):
    if request.node.get_closest_marker("chaos") is None:
        yield
        return
    monkeypatch.setenv("KUBEINFER_RACECHECK", "2")
    from kubeinfer_tpu.analysis import lockset, protocol, racecheck
    from kubeinfer_tpu.observability import flightrecorder

    racecheck.REGISTRY.reset()
    lockset.REGISTRY.reset()
    mon = protocol.ProtocolMonitor()
    prev = flightrecorder.get_monitor()
    flightrecorder.set_monitor(mon)
    try:
        yield
    finally:
        flightrecorder.set_monitor(prev)
    cycles = racecheck.REGISTRY.cycles()
    assert not cycles, f"lock-order cycles (deadlock potential): {cycles}"
    races = lockset.REGISTRY.races()
    assert not races, (
        "lockset data races:\n" + lockset.REGISTRY.render()
    )
    mon.assert_clean()
