"""Analyzer self-tests + the tier-1 gate (ISSUE 2 acceptance).

Fixture snippets inject one violation per rule and assert the analyzer
catches exactly it; known-good twins assert the matching idiom stays
clean (the false-positive budget is zero — a noisy linter gets
suppressed wholesale and stops being a gate). The final test runs the
real analyzer over the real repo surface and asserts zero unsuppressed
findings, which is what makes `make lint` failures reproduce in tier-1.
"""

from __future__ import annotations

import subprocess
import sys
import textwrap
import threading
from pathlib import Path

import pytest

from kubeinfer_tpu.analysis import racecheck
from kubeinfer_tpu.analysis.core import analyze_paths, analyze_source

REPO = Path(__file__).resolve().parent.parent


def run_src(src: str, path: str = "pkg/sample.py", **kw):
    return analyze_source(textwrap.dedent(src), path, **kw)


def rules_of(findings):
    return [f.rule for f in findings]


# --- jit-host-sync ----------------------------------------------------------


def test_item_inside_jit_flagged():
    fs = run_src(
        """
        import jax

        @jax.jit
        def f(x):
            return x.item()
        """
    )
    assert rules_of(fs) == ["jit-host-sync"]


def test_int_cast_on_traced_flagged_static_arg_clean():
    fs = run_src(
        """
        import jax
        from functools import partial

        @partial(jax.jit, static_argnames=("n",))
        def f(x, n):
            k = int(n)       # static: resolved at trace time
            y = int(x + 1)   # traced: crashes under trace
            return k + y
        """
    )
    assert len(fs) == 1 and fs[0].rule == "jit-host-sync"
    assert "int()" in fs[0].message


def test_np_asarray_of_traced_inside_jit_flagged():
    fs = run_src(
        """
        import jax, numpy as np

        @jax.jit
        def f(x):
            return np.asarray(x)
        """
    )
    assert rules_of(fs) == ["jit-host-sync"]


def test_device_get_inside_jit_flagged():
    fs = run_src(
        """
        import jax

        @jax.jit
        def f(x):
            return jax.device_get(x)
        """
    )
    assert rules_of(fs) == ["jit-host-sync"]


def test_shape_read_is_clean():
    fs = run_src(
        """
        import jax, jax.numpy as jnp

        @jax.jit
        def f(x):
            b = x.shape[0]          # static metadata, not data
            return jnp.zeros((b, int(x.ndim)))
        """
    )
    assert fs == []


def test_closure_constant_is_trace_time():
    # float() of a module-level jnp constant is legal inside jit: the
    # closure is concrete at trace time (solver INFEASIBLE pattern)
    fs = run_src(
        """
        import jax, jax.numpy as jnp

        BIG = jnp.float32(1e9)

        @jax.jit
        def f(x):
            return x * float(BIG)
        """
    )
    assert fs == []


# --- jit-traced-branch ------------------------------------------------------


def test_if_on_traced_flagged():
    fs = run_src(
        """
        import jax, jax.numpy as jnp

        @jax.jit
        def f(x):
            if x > 0:
                return x
            return -x
        """
    )
    assert rules_of(fs) == ["jit-traced-branch"]


def test_while_on_traced_flagged():
    fs = run_src(
        """
        import jax

        @jax.jit
        def f(x):
            while x < 10:
                x = x + 1
            return x
        """
    )
    assert rules_of(fs) == ["jit-traced-branch"]


def test_is_none_branch_clean():
    fs = run_src(
        """
        import jax, jax.numpy as jnp

        @jax.jit
        def f(x, key=None):
            if key is None:
                key = jax.random.PRNGKey(0)
            return x, key
        """
    )
    assert fs == []


def test_branch_on_static_arg_clean():
    fs = run_src(
        """
        import jax
        from functools import partial

        @partial(jax.jit, static_argnames=("flag",))
        def f(x, flag):
            if flag:
                return x * 2
            return x
        """
    )
    assert fs == []


# --- jit-dynamic-shape ------------------------------------------------------


def test_nonzero_without_size_flagged_with_size_clean():
    fs = run_src(
        """
        import jax, jax.numpy as jnp

        @jax.jit
        def f(x):
            bad = jnp.nonzero(x)
            ok = jnp.nonzero(x, size=8, fill_value=-1)
            return bad, ok
        """
    )
    assert rules_of(fs) == ["jit-dynamic-shape"]


def test_unique_flagged():
    fs = run_src(
        """
        import jax, jax.numpy as jnp

        @jax.jit
        def f(x):
            return jnp.unique(x)
        """
    )
    assert rules_of(fs) == ["jit-dynamic-shape"]


def test_boolean_mask_index_flagged_where_clean():
    fs = run_src(
        """
        import jax, jax.numpy as jnp

        @jax.jit
        def f(x):
            bad = x[x > 0]
            ok = jnp.where(x > 0, x, 0.0)   # three-arg where is static
            return bad, ok
        """
    )
    assert rules_of(fs) == ["jit-dynamic-shape"]


def test_single_arg_where_flagged():
    fs = run_src(
        """
        import jax, jax.numpy as jnp

        @jax.jit
        def f(x):
            return jnp.where(x > 0)
        """
    )
    assert rules_of(fs) == ["jit-dynamic-shape"]


def test_per_row_cache_scatter_clean():
    # the ragged-decode cache write (model.decoder_layer): the batched
    # .at[rows, offset].set scatter and the vmapped per-row
    # dynamic_update_slice are both static-shape — traced values feed
    # the INDICES, never the output shape
    fs = run_src(
        """
        import jax, jax.numpy as jnp

        @jax.jit
        def f(cache, new, offset):
            rows = jnp.arange(cache.shape[0])
            ck = cache.at[rows, offset].set(new[:, 0])
            cv = jax.vmap(
                lambda c, n, o: jax.lax.dynamic_update_slice(
                    c, n, (o, 0, 0)
                )
            )(cache, new, offset)
            return ck, cv
        """
    )
    assert fs == []


# --- host-sync boundary rule ------------------------------------------------


def test_jit_result_readback_flagged_outside_jit():
    fs = run_src(
        """
        import jax, numpy as np

        @jax.jit
        def step(x):
            return x * 2

        def serve(x):
            y = step(x)
            return np.asarray(y)
        """
    )
    assert rules_of(fs) == ["host-sync"]


def test_boundary_rule_off_for_test_files():
    src = """
        import jax, numpy as np

        @jax.jit
        def step(x):
            return x * 2

        def test_step():
            assert np.asarray(step(1.0)) == 2.0
        """
    assert run_src(src, path="tests/test_sample.py") == []
    assert rules_of(run_src(src, path="pkg/mod.py")) == ["host-sync"]


def test_cross_file_jit_registry():
    # bench.py pattern: the jit decorator lives in another file; the
    # caller must still see a device value
    fs = run_src(
        """
        import numpy as np
        from pkg.solver import solve

        def bench():
            out = solve(1.0)
            return np.asarray(out)
        """,
        jit_registry={"solve": (frozenset(), frozenset())},
    )
    assert rules_of(fs) == ["host-sync"]


# --- suppressions -----------------------------------------------------------


def test_allow_same_line_suppresses():
    fs = run_src(
        """
        import jax

        @jax.jit
        def f(x):
            return x.item()  # lint: allow[jit-host-sync] fixture: deliberate
        """
    )
    assert fs == []


def test_allow_preceding_comment_line_suppresses():
    fs = run_src(
        """
        import jax

        @jax.jit
        def f(x):
            # lint: allow[jit-host-sync] fixture: deliberate sync
            return x.item()
        """
    )
    assert fs == []


def test_bare_allow_is_a_finding():
    fs = run_src(
        """
        import jax

        @jax.jit
        def f(x):
            return x.item()  # lint: allow[jit-host-sync]
        """
    )
    assert rules_of(fs) == ["lint-bare-allow"]


def test_unknown_rule_in_allow_is_a_finding():
    fs = run_src("x = 1  # lint: allow[no-such-rule] reason here\n")
    assert rules_of(fs) == ["lint-unknown-rule"]


def test_allow_in_docstring_is_not_a_suppression():
    fs = run_src(
        '''
        def f():
            """Docs may mention `# lint: allow[jit-host-sync]` freely."""
            return 1
        '''
    )
    assert fs == []


def test_allow_only_matches_named_rule():
    fs = run_src(
        """
        import jax

        @jax.jit
        def f(x):
            return x.item()  # lint: allow[jit-dynamic-shape] wrong rule named
        """
    )
    # the misnamed allow now ALSO surfaces as a stale suppression: the
    # named rule never fires on that line
    assert rules_of(fs) == ["jit-host-sync", "unused-suppression"]


# --- lock-discipline --------------------------------------------------------


def test_unlocked_write_flagged():
    fs = run_src(
        """
        import threading

        class C:
            def __init__(self):
                self._lock = threading.Lock()
                self._n = 0

            def locked_inc(self):
                with self._lock:
                    self._n += 1

            def racy_inc(self):
                self._n += 1
        """
    )
    assert rules_of(fs) == ["lock-discipline"]
    assert "racy_inc" in fs[0].message


def test_init_writes_and_all_locked_clean():
    fs = run_src(
        """
        import threading

        class C:
            def __init__(self):
                self._lock = threading.Lock()
                self._n = 0
                self._replay()

            def _replay(self):
                # reachable only from __init__: pre-sharing writes
                self._n = 10

            def inc(self):
                with self._lock:
                    self._n += 1
        """
    )
    assert fs == []


def test_always_locked_helper_propagates():
    # batching._admit shape: helper's own body shows no lock, but every
    # call site holds it
    fs = run_src(
        """
        import threading

        class C:
            def __init__(self):
                self._lock = threading.Lock()
                self._n = 0

            def _bump(self):
                self._n += 1

            def inc(self):
                with self._lock:
                    self._bump()

            def inc2(self):
                with self._lock:
                    self._bump()
        """
    )
    assert fs == []


def test_mutator_call_counts_as_write():
    fs = run_src(
        """
        import threading

        class C:
            def __init__(self):
                self._lock = threading.Lock()
                self._items = []

            def locked_add(self, x):
                with self._lock:
                    self._items.append(x)

            def racy_add(self, x):
                self._items.append(x)
        """
    )
    assert rules_of(fs) == ["lock-discipline"]


def test_event_methods_are_exempt():
    # threading.Event is internally synchronized; set/clear anywhere is
    # fine even if one call site happens to hold a lock
    fs = run_src(
        """
        import threading

        class C:
            def __init__(self):
                self._lock = threading.Lock()
                self._flag = threading.Event()

            def locked_set(self):
                with self._lock:
                    self._flag.set()

            def free_clear(self):
                self._flag.clear()
        """
    )
    assert fs == []


def test_module_level_global_discipline():
    fs = run_src(
        """
        import threading

        _lock = threading.Lock()
        _cache = None

        def fill():
            global _cache
            with _lock:
                _cache = 1

        def racy_fill():
            global _cache
            _cache = 2
        """
    )
    assert rules_of(fs) == ["lock-discipline"]
    assert "racy_fill" in fs[0].message


# --- racecheck runtime sentinel ---------------------------------------------


def test_make_lock_unarmed_is_plain(monkeypatch):
    monkeypatch.delenv("KUBEINFER_RACECHECK", raising=False)
    lk = racecheck.make_lock("t.plain")
    assert not isinstance(lk, racecheck.TrackedLock)
    with lk:
        pass


def test_make_lock_armed_is_tracked(monkeypatch):
    monkeypatch.setenv("KUBEINFER_RACECHECK", "1")
    lk = racecheck.make_lock("t.tracked")
    assert isinstance(lk, racecheck.TrackedLock)


def test_lock_order_inversion_reports_cycle(monkeypatch):
    monkeypatch.setenv("KUBEINFER_RACECHECK", "1")
    racecheck.REGISTRY.reset()
    a = racecheck.make_lock("t.A")
    b = racecheck.make_lock("t.B")
    with a:
        with b:
            pass
    with b:
        with a:  # inverted: the deadlock-potential edge
            pass
    cycles = racecheck.REGISTRY.cycles()
    assert cycles, "inverted acquisition order must produce a cycle"
    assert {"t.A", "t.B"} <= set(cycles[0])
    racecheck.REGISTRY.reset()


def test_consistent_order_is_acyclic(monkeypatch):
    monkeypatch.setenv("KUBEINFER_RACECHECK", "1")
    racecheck.REGISTRY.reset()
    a = racecheck.make_lock("t.A2")
    b = racecheck.make_lock("t.B2")
    for _ in range(3):
        with a:
            with b:
                pass
    assert racecheck.REGISTRY.cycles() == []
    rep = racecheck.REGISTRY.report()
    assert ("t.A2", "t.B2") in rep["edges"]
    assert rep["hold_max_s"]["t.A2"] >= 0.0
    racecheck.REGISTRY.reset()


def test_tracked_condition_wait_notify(monkeypatch):
    monkeypatch.setenv("KUBEINFER_RACECHECK", "1")
    racecheck.REGISTRY.reset()
    cond = racecheck.make_condition("t.cond")
    hits = []

    def waiter():
        with cond:
            while not hits:
                cond.wait(timeout=5.0)
            hits.append("woke")

    t = threading.Thread(target=waiter)
    t.start()
    with cond:
        hits.append("go")
        cond.notify()
    t.join(timeout=5.0)
    assert not t.is_alive() and hits == ["go", "woke"]
    racecheck.REGISTRY.reset()


def test_cross_thread_edges_detect_inversion(monkeypatch):
    monkeypatch.setenv("KUBEINFER_RACECHECK", "1")
    racecheck.REGISTRY.reset()
    a = racecheck.make_lock("t.X")
    b = racecheck.make_lock("t.Y")

    def t1():
        with a:
            with b:
                pass

    def t2():
        with b:
            with a:
                pass

    # run serially so both orders are observed without actually deadlocking
    th = threading.Thread(target=t1)
    th.start()
    th.join()
    th = threading.Thread(target=t2)
    th.start()
    th.join()
    assert racecheck.REGISTRY.cycles()
    racecheck.REGISTRY.reset()


# --- log-discipline ---------------------------------------------------------


def test_bare_print_in_library_flagged():
    fs = run_src(
        """
        def handler(x):
            print("served", x)
        """
    )
    assert rules_of(fs) == ["log-discipline"]


def test_basic_config_in_library_flagged():
    fs = run_src(
        """
        import logging

        def setup():
            logging.basicConfig(level=logging.INFO)
        """
    )
    assert rules_of(fs) == ["log-discipline"]


def test_module_logger_is_clean():
    fs = run_src(
        """
        import logging

        log = logging.getLogger(__name__)

        def handler(x):
            log.info("served %s", x)
        """
    )
    assert fs == []


def test_cli_entrypoints_exempt():
    src = """
    import logging

    def main():
        logging.basicConfig(level=logging.INFO)
        print("ready")
    """
    for path in ("pkg/__main__.py", "pkg/ctl.py", "bench.py",
                 "__graft_entry__.py", "chip_smoke.py", "scripts/tool.py",
                 "tests/test_thing.py"):
        assert run_src(src, path=path) == []
    assert rules_of(run_src(src, path="pkg/server.py")) == [
        "log-discipline", "log-discipline",
    ]


def test_shadowed_print_is_not_the_builtin():
    fs = run_src(
        """
        def render(print):
            print("not the builtin")

        class W:
            def print(self):
                pass

            def go(self):
                self.print()
        """
    )
    assert fs == []


def test_log_discipline_allow_suppresses():
    fs = run_src(
        """
        def main():
            # lint: allow[log-discipline] process entrypoint owns stdout
            print("ready")
        """
    )
    assert fs == []


# --- metric-name ------------------------------------------------------------


def test_metric_missing_prefix_flagged():
    fs = run_src(
        """
        from kubeinfer_tpu.metrics.registry import Counter

        c = Counter("requests_total", "requests")
        """
    )
    assert rules_of(fs) == ["metric-name"]
    assert "kubeinfer_" in fs[0].message


def test_counter_without_total_flagged():
    fs = run_src(
        """
        from kubeinfer_tpu.metrics.registry import Counter

        c = Counter("kubeinfer_requests", "requests")
        """
    )
    assert rules_of(fs) == ["metric-name"]
    assert "_total" in fs[0].message


def test_histogram_without_unit_flagged():
    fs = run_src(
        """
        from kubeinfer_tpu.metrics.registry import Histogram

        h = Histogram("kubeinfer_request_latency", "latency")
        """
    )
    assert rules_of(fs) == ["metric-name"]


def test_gauge_without_quantity_suffix_flagged():
    fs = run_src(
        """
        from kubeinfer_tpu.metrics.registry import Gauge

        g = Gauge("kubeinfer_goodput", "tokens per second")
        """
    )
    assert rules_of(fs) == ["metric-name"]


def test_computed_metric_name_flagged():
    fs = run_src(
        """
        from kubeinfer_tpu.metrics.registry import Counter

        def make(component):
            return Counter(f"kubeinfer_{component}_total", "per component")
        """
    )
    assert rules_of(fs) == ["metric-name"]
    assert "literal" in fs[0].message


def test_compliant_collectors_clean():
    fs = run_src(
        """
        from kubeinfer_tpu.metrics.registry import (
            Counter, Gauge, Histogram,
        )

        c = Counter("kubeinfer_requests_total", "requests")
        h = Histogram("kubeinfer_request_seconds", "latency")
        g1 = Gauge("kubeinfer_ready_replicas", "replicas")
        g2 = Gauge("kubeinfer_stale_seconds", "staleness")
        g3 = Gauge("kubeinfer_goodput_tokens_per_second", "goodput")
        """
    )
    assert fs == []


def test_collections_counter_not_matched():
    fs = run_src(
        """
        import collections

        hist = collections.Counter(["a", "b", "a"])
        """
    )
    assert fs == []


def test_metric_name_rule_off_for_test_files():
    src = """
    from kubeinfer_tpu.metrics.registry import Counter

    c = Counter("t_total", "fixture counter")
    """
    assert run_src(src, path="tests/test_metrics.py") == []
    assert rules_of(run_src(src, path="pkg/server.py")) == ["metric-name"]


# --- metric-label -----------------------------------------------------------


def test_label_case_and_high_cardinality_flagged():
    fs = run_src(
        """
        from kubeinfer_tpu.metrics.registry import Counter

        c = Counter("kubeinfer_req_total", "reqs",
                    labels=("Kind", "request_id"))
        """
    )
    assert rules_of(fs) == ["metric-label", "metric-label"]
    msgs = " ".join(f.message for f in fs)
    assert "'Kind'" in msgs and "high-cardinality" in msgs


def test_histogram_positional_labels_checked():
    # Histogram's constructor takes buckets as positional 2, pushing the
    # labels tuple to positional 3 — the pass must look there, not at 2.
    fs = run_src(
        """
        from kubeinfer_tpu.metrics.registry import Histogram

        h = Histogram("kubeinfer_wait_seconds", "wait", (0.1, 1.0),
                      ("trace_id",))
        """
    )
    assert rules_of(fs) == ["metric-label"]
    assert "high-cardinality" in fs[0].message


def test_computed_label_set_flagged_literal_clean():
    fs = run_src(
        """
        from kubeinfer_tpu.metrics.registry import Gauge

        LABELS = ("kind",)
        g = Gauge("kubeinfer_queue_depth", "depth", labels=LABELS)
        ok = Gauge("kubeinfer_pool_free", "free", labels=("kind", "node"))
        """
    )
    assert rules_of(fs) == ["metric-label"]
    assert "literal tuple/list" in fs[0].message


# --- blocking-under-lock ----------------------------------------------------


def test_sleep_under_lock_flagged_direct():
    fs = run_src(
        """
        import time
        from kubeinfer_tpu.analysis.racecheck import make_lock

        class Poller:
            def __init__(self):
                self._lock = make_lock("poller")

            def wait(self):
                with self._lock:
                    time.sleep(0.5)
        """
    )
    assert rules_of(fs) == ["blocking-under-lock"]
    assert "time.sleep()" in fs[0].message
    # direct findings land on the blocking line itself
    assert fs[0].line == 11


def test_transitive_block_lands_on_call_under_lock():
    fs = run_src(
        """
        import subprocess
        from kubeinfer_tpu.analysis.racecheck import make_lock

        class Builder:
            def __init__(self):
                self._lock = make_lock("builder")

            def _compile(self):
                subprocess.run(["cc", "x.c"])

            def build(self):
                with self._lock:
                    self._compile()
        """
    )
    assert rules_of(fs) == ["blocking-under-lock"]
    # the suppression/fix point is where the lock scope is chosen — the
    # call line — not the callee's subprocess line
    assert fs[0].line == 14
    assert "_compile()" in fs[0].message


def test_jit_dispatch_under_lock_flagged_via_registry():
    fs = run_src(
        """
        from kubeinfer_tpu.analysis.racecheck import make_lock

        class Engine:
            def __init__(self):
                self._lock = make_lock("engine")

            def admit(self, x):
                with self._lock:
                    return step_fn(x)
        """,
        jit_registry={"step_fn": frozenset()},
    )
    assert rules_of(fs) == ["blocking-under-lock"]
    assert "jit dispatch" in fs[0].message


def test_blocking_outside_lock_and_init_clean():
    fs = run_src(
        """
        import time
        from kubeinfer_tpu.analysis.racecheck import make_lock

        class Warmup:
            def __init__(self):
                self._lock = make_lock("warm")
                with self._lock:
                    # nothing shares the object mid-__init__
                    time.sleep(0.01)

            def tick(self):
                time.sleep(0.1)
                with self._lock:
                    self.n = 1
        """
    )
    assert fs == []


def test_blockcheck_off_for_test_files():
    src = """
    import time
    from kubeinfer_tpu.analysis.racecheck import make_lock

    _mu = make_lock("fixture")

    def poll():
        with _mu:
            time.sleep(0.01)
    """
    assert run_src(src, path="tests/test_fixture.py") == []
    assert rules_of(run_src(src, path="pkg/poll.py")) == [
        "blocking-under-lock"]


def test_blocking_under_lock_allow_suppresses():
    fs = run_src(
        """
        import time
        from kubeinfer_tpu.analysis.racecheck import make_lock

        class S:
            def __init__(self):
                self._lock = make_lock("s")

            def settle(self):
                with self._lock:
                    # lint: allow[blocking-under-lock] 10ms debounce is the accepted ceiling
                    time.sleep(0.01)
        """
    )
    assert fs == []


# --- unused-suppression -----------------------------------------------------


def test_stale_allow_is_a_finding():
    fs = run_src(
        """
        # lint: allow[jit-host-sync] left behind after a refactor
        x = 1
        """
    )
    assert rules_of(fs) == ["unused-suppression"]
    # lands on the comment's own line — that's the line to delete
    assert fs[0].line == 2
    assert "allow[jit-host-sync]" in fs[0].message


def test_consumed_allow_is_not_stale():
    fs = run_src(
        """
        import jax

        @jax.jit
        def f(x):
            # lint: allow[jit-host-sync] fixture: proving consumption
            return x.item()
        """
    )
    assert fs == []


def test_unused_suppression_is_unsuppressable():
    # allow[unused-suppression] neither hides the stale finding nor is
    # itself exempt from staleness — both comment lines get reported
    fs = run_src(
        """
        # lint: allow[unused-suppression] trying to hide staleness
        # lint: allow[metric-name] stale after rename
        x = 1
        """
    )
    assert rules_of(fs) == ["unused-suppression", "unused-suppression"]


def test_bare_and_unknown_allows_not_double_reported():
    # bare/unknown allows already carry their own meta finding; the
    # staleness pass must not pile a second finding on the same comment
    fs = run_src(
        """
        # lint: allow[jit-host-sync]
        x = 1
        y = 2  # lint: allow[not-a-rule] reasoned but bogus
        """
    )
    assert rules_of(fs) == ["lint-bare-allow", "lint-unknown-rule"]


# --- racecheck reservoir + cycle determinism --------------------------------


def test_hold_stats_reservoir_bounded_and_deterministic():
    a = racecheck._HoldStats("pool.lock")
    b = racecheck._HoldStats("pool.lock")
    for i in range(500):
        a.add(float(i))
        b.add(float(i))
    assert a.count == 500
    assert a.max == 499.0
    assert len(a.samples) == a.CAP
    # name-seeded replacement RNG: which samples survive is a pure
    # function of the duration sequence, so two identical runs agree
    assert a.samples == b.samples
    # a different lock name seeds differently (same sequence, different
    # survivors) — proves the seed actually comes from the name
    c = racecheck._HoldStats("store.lock")
    for i in range(500):
        c.add(float(i))
    assert c.samples != a.samples


def test_cycle_report_independent_of_edge_insertion_order():
    def build(order):
        reg = racecheck._Registry()
        locks = {n: racecheck.TrackedLock(n) for n in "abc"}
        for outer, inner in order:
            reg.on_acquired(locks[outer])
            reg.on_acquired(locks[inner])
            reg.on_released(locks[inner])
            reg.on_released(locks[outer])
        return reg.cycles()

    fwd = build([("a", "b"), ("b", "c"), ("c", "a")])
    rev = build([("c", "a"), ("b", "c"), ("a", "b")])
    assert fwd == rev == [["a", "b", "c", "a"]]


# --- the tier-1 gate --------------------------------------------------------


def test_repo_surface_has_zero_unsuppressed_findings():
    paths = [REPO / p for p in
             ("kubeinfer_tpu", "tests", "scripts", "bench.py",
              "__graft_entry__.py", "chip_smoke.py")]
    findings, nfiles = analyze_paths([p for p in paths if p.exists()])
    assert nfiles > 50, "scan surface collapsed — path wiring broke"
    msgs = "\n".join(f.render() for f in findings)
    assert not findings, f"unsuppressed analysis findings:\n{msgs}"


def test_cli_exit_codes(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(
        "import jax\n\n@jax.jit\ndef f(x):\n    return x.item()\n"
    )
    proc = subprocess.run(
        [sys.executable, "-m", "kubeinfer_tpu.analysis", str(bad)],
        capture_output=True, text=True, cwd=REPO,
    )
    assert proc.returncode == 1
    # grep/editor-clickable format: file:line rule message
    assert f"{bad}:5 jit-host-sync" in proc.stdout
    good = tmp_path / "good.py"
    good.write_text("x = 1\n")
    proc = subprocess.run(
        [sys.executable, "-m", "kubeinfer_tpu.analysis", str(good)],
        capture_output=True, text=True, cwd=REPO,
    )
    assert proc.returncode == 0
