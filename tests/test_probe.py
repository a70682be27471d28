"""Hardware probe tests (runs on the virtual CPU mesh)."""

from __future__ import annotations

from kubeinfer_tpu.agent.probe import (
    probe_accelerators,
    probe_accelerators_in_child,
    probe_host_memory,
)


def test_probe_sees_local_devices():
    info = probe_accelerators()
    assert info is not None
    # conftest forces an 8-device virtual CPU mesh
    assert info.count == 8
    assert info.platform == "cpu"


def test_probe_in_child_matches_in_process(monkeypatch):
    """The agent's probe: same answer as the in-process one, from a
    child that has exited (the parent never holds a device for it)."""
    from tests.conftest import subprocess_pythonpath

    monkeypatch.setenv("PYTHONPATH", subprocess_pythonpath())
    assert probe_accelerators_in_child() == probe_accelerators()


def test_probe_in_child_failure_is_none(monkeypatch):
    # a child that cannot bring a backend up reports nothing, and the
    # agent falls back to its configured capacity
    monkeypatch.setenv("JAX_PLATFORMS", "no-such-platform")
    assert probe_accelerators_in_child() is None


def test_probe_host_memory_on_linux():
    mem = probe_host_memory()
    assert mem is not None
    total, avail = mem
    assert total > 0 and 0 < avail <= total
