"""Where JAX keeps compiled programs between processes, and what
compiling cost this process.

A cold start compiles every step program and kernel (minutes for a 7B
model); the persistent cache turns a restart into reads. The cache key
includes the directory's path, so the path is fixed per checkout: never
a temp name, a pid or a timestamp, any of which would make every entry
a miss.

A warm start still traces and lowers every program, fetches the ones
the cache kept and compiles again the ones it did not (those that took
less than ``jax_persistent_cache_min_compile_time_secs``).
:func:`watch_compiles` counts each of those from JAX's own
``jax.monitoring`` events, so the server can say where its start-up
went (docs/OBSERVABILITY.md, "Start-up").
"""

from __future__ import annotations

import os
import threading

from kubeinfer_tpu.analysis.racecheck import make_lock
from kubeinfer_tpu.observability import tracing

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; call before the first
    compile. ``JAX_COMPILATION_CACHE_DIR`` wins when set — JAX reads it
    itself, so nothing is touched. Otherwise the cache lives in
    ``<checkout>/.jax_cache``. Returns the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


# jax 0.9: jax/_src/dispatch.py:60-62, compiler.py:435-452,
# compilation_cache.py:283. Each stage runs inside
# ``dispatch.log_elapsed_time``, which announces its start as a scalar
# event of the same name and its end as a duration.
_STAGE_OF = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_FETCH = "/jax/compilation_cache/cache_retrieval_time_sec"
_HIT = "/jax/compilation_cache/cache_hits"
_WRITTEN = "/jax/compilation_cache/cache_misses"  # an entry written

_TRACER = tracing.get_tracer("jax-compile")


class CompileWatch:
    """Process totals of JAX's compile events.

    A stage's seconds count only the outermost stage open on a thread:
    tracing a program traces the jitted functions it calls, and a trace
    may run a small eager program, so the nested durations are already
    inside the outer one. The three stages then add up to the wall time
    a thread spent compiling. Programs count every backend compile:
    ``fetched`` came out of the persistent cache, ``kept`` were written
    to it, ``unkept`` are the rest, which every start compiles again.
    Each backend compile is also a ``compile.backend`` span, all under
    one trace, carrying JAX's ``fun_name`` and whether it was fetched.
    """

    def __init__(self) -> None:
        self._lock = make_lock("utils.CompileWatch._lock")
        self._tls = threading.local()
        self._seconds = {stage: 0.0 for stage in _STAGE_OF.values()}
        self._fetch_s = 0.0
        self._backend = 0
        self._fetched = 0
        self._kept = 0
        self._root = tracing.new_root_context()

    def totals(self) -> dict[str, float]:
        """Monotonic totals: ``<stage>_s`` for trace / lower / backend,
        ``fetch_s``, and the programs ``fetched`` / ``kept`` /
        ``unkept``."""
        with self._lock:
            out = {f"{k}_s": v for k, v in self._seconds.items()}
            out.update(fetch_s=self._fetch_s, fetched=self._fetched,
                       kept=self._kept,
                       unkept=self._backend - self._fetched - self._kept)
        return out

    # --- jax.monitoring listeners (run on the compiling thread) ---

    def _on_start(self, event: str, value, **_kw) -> None:
        if event in _STAGE_OF:
            self._tls.depth = getattr(self._tls, "depth", 0) + 1

    def _on_duration(self, event: str, secs: float, **kw) -> None:
        if event == _FETCH:
            with self._lock:
                self._fetch_s += secs
            return
        stage = _STAGE_OF.get(event)
        if stage is None:
            return
        depth = max(getattr(self._tls, "depth", 1) - 1, 0)
        self._tls.depth = depth
        with self._lock:
            if depth == 0:
                self._seconds[stage] += secs
            if stage == "backend":
                self._backend += 1
        if stage == "backend":
            fetched = getattr(self._tls, "fetched", False)
            self._tls.fetched = False
            end = tracing.now()
            _TRACER.record_span(
                "compile.backend", end - secs, end, parent=self._root,
                fun_name=str(kw.get("fun_name", "")), fetched=fetched)

    def _on_event(self, event: str, **_kw) -> None:
        if event == _HIT:
            self._tls.fetched = True
            with self._lock:
                self._fetched += 1
        elif event == _WRITTEN:
            with self._lock:
                self._kept += 1


_WATCH: CompileWatch | None = None
_WATCH_LOCK = make_lock("utils.compile_cache._WATCH_LOCK")


def watch_compiles() -> CompileWatch:
    """Register the listeners of a process-wide :class:`CompileWatch`
    with ``jax.monitoring``, once; later calls return the same watch.
    Call before the first compile that should count."""
    global _WATCH
    with _WATCH_LOCK:
        if _WATCH is None:
            import jax.monitoring as mon

            watch = CompileWatch()
            mon.register_scalar_listener(watch._on_start)
            mon.register_event_duration_secs_listener(watch._on_duration)
            mon.register_event_listener(watch._on_event)
            _WATCH = watch
        return _WATCH


def compile_watch() -> CompileWatch | None:
    """The watch :func:`watch_compiles` registered, None before."""
    return _WATCH
