"""OpenAI-compatible HTTP server over the native engine.

``python -m kubeinfer_tpu.inference.server`` accepts the SAME CLI surface
the agent's runtime launcher builds for vLLM (runtime.py build_args —
--model/--host/--port/--tensor-parallel-size/--dtype/[--max-model-len]),
so switching a workload to the native TPU runtime is just
``RUNTIME_KIND=native`` (or ``runtime: native`` in the LLMService spec) —
lifecycle code is untouched.

Endpoints (the surface the reference's mock pins, testdata
vllm-mock/mock_server.py, plus real generation):

- ``GET  /health``            → OK
- ``GET  /v1/models``         → OpenAI-style model list
- ``POST /v1/completions``    → {model, prompt: str|[int], max_tokens,
                                temperature, seed} → completion

String prompts need tokenizer files next to the weights (loaded via
``transformers`` AutoTokenizer); token-id prompts always work (and are
what the tests and the e2e slice use). ``--random-init`` serves a
randomly initialized preset config — the demo/e2e mode that needs no
weights and no network, the role the reference's vllm-mock image plays,
except it really generates.

Reproducibility contract: a completion is a deterministic function of
(prompt, seed, sampling params) — independent of what else is in
flight. Greedy requests are trivially so; sampled requests hold it
because slot-path sampling keys are per-slot, derived from each
request's own seed and folded with the token's output index — with or
without a ``--draft-model`` (stepper.spec_accept emits the target's own
draws).
"""

from __future__ import annotations

import argparse
import json
import logging
import signal
import sys
import threading
import time
from http.server import ThreadingHTTPServer
from urllib.parse import parse_qs, urlparse

from kubeinfer_tpu.analysis.racecheck import make_lock
from kubeinfer_tpu.metrics.registry import (
    Counter, Gauge, Histogram, Registry,
)
from kubeinfer_tpu.observability import tracing
from kubeinfer_tpu.observability.slo import SLOMonitor
from kubeinfer_tpu.observability.stepprof import PHASES
from kubeinfer_tpu.utils.httpbase import BaseEndpointHandler, token_matches

log = logging.getLogger(__name__)

_TRACER = tracing.get_tracer("inference-server")


def _serving_metrics(registry: Registry):
    """Serving-side collectors (vLLM exposes the equivalents; the
    control plane's collector set lives in metrics/registry.py — these
    are per-inference-server and ride its own /metrics endpoint)."""
    return {
        "requests": Counter(
            "kubeinfer_inference_requests_total",
            "Completion requests by outcome and decode route",
            labels=("route", "outcome"), registry=registry,
        ),
        "prompt_tokens": Counter(
            "kubeinfer_inference_prompt_tokens_total",
            "Prompt tokens received", registry=registry,
        ),
        "completion_tokens": Counter(
            "kubeinfer_inference_completion_tokens_total",
            "Tokens generated", registry=registry,
        ),
        "latency": Histogram(
            "kubeinfer_inference_request_seconds",
            "End-to-end completion latency",
            buckets=(0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0,
                     60.0, 120.0),
            labels=("route",), registry=registry,
        ),
        # per-request latency breakdown (the vLLM request-metrics plane
        # equivalents): TTFT/queue-wait come from the batcher's own
        # request timeline when the continuous route served the request
        # (t_submit/t_admit/t_first, batching.py _Request); routes with
        # no internal timeline degrade to end-to-end figures — same
        # family, split by the route label
        "ttft": Histogram(
            "kubeinfer_inference_ttft_seconds",
            "Time to first generated token (queue wait + admission + "
            "prefill on the continuous route; end-to-end elsewhere)",
            buckets=(0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
                     10.0, 30.0),
            labels=("route",), registry=registry,
        ),
        "tpot": Histogram(
            "kubeinfer_inference_time_per_output_token_seconds",
            "Mean decode time per generated token after the first",
            buckets=(0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                     0.25, 0.5, 1.0),
            labels=("route",), registry=registry,
        ),
        "queue_wait": Histogram(
            "kubeinfer_inference_queue_wait_seconds",
            "Submit-to-admission wait in the continuous batcher",
            buckets=(0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0,
                     30.0),
            labels=("route",), registry=registry,
        ),
        # paged speculative decoding (batching.py verify windows, on
        # with --draft-model): the engine's monotonic ints convert
        # to Prometheus counters by delta at scrape time under the kv
        # lock, same discipline as the radix counters below; the ratio
        # gauge is cumulative accepted/proposed so dashboards read the
        # acceptance rate without a PromQL rate-quotient
        "spec_draft_tokens": Counter(
            "kubeinfer_spec_draft_tokens_total",
            "Draft tokens proposed by paged verify windows",
            registry=registry,
        ),
        "spec_accepted_tokens": Counter(
            "kubeinfer_spec_accepted_tokens_total",
            "Proposed draft tokens the target accepted at a window "
            "boundary",
            registry=registry,
        ),
        "spec_rollbacks": Counter(
            "kubeinfer_spec_rollbacks_total",
            "Verify windows that rejected at least one draft token "
            "for some row",
            registry=registry,
        ),
        "spec_acceptance_ratio": Gauge(
            "kubeinfer_spec_acceptance_ratio",
            "Cumulative accepted/proposed draft tokens (0 until the "
            "first window)",
            registry=registry,
        ),
        # paged-KV pool + radix prefix cache (batching.kv_cache_stats):
        # gauges snapshot pool occupancy; the cache counters are
        # Prometheus counters fed by delta at scrape time so restarts
        # of the batcher never make them go backwards mid-series
        "kv_blocks_in_use": Gauge(
            "kubeinfer_kv_blocks_in_use",
            "KV pool blocks referenced by live slots or the prefix cache",
            registry=registry,
        ),
        "kv_blocks_free": Gauge(
            "kubeinfer_kv_blocks_free",
            "KV pool blocks on the free list",
            registry=registry,
        ),
        # device layout of the continuous batcher (sharding.EngineLayout):
        # capacity dashboards need the tp degree next to the pool gauges —
        # a tp=4 replica's blocks_in_use counts LOGICAL blocks whose bytes
        # are split 4 ways, so per-device headroom math divides by tp
        "tp_degree": Gauge(
            "kubeinfer_engine_tp_degree",
            "Tensor-parallel degree of the serving engine's device "
            "layout (1 = unsharded)",
            registry=registry,
        ),
        "mesh_devices": Gauge(
            "kubeinfer_mesh_devices",
            "Devices in the serving mesh (1 when unsharded)",
            registry=registry,
        ),
        "kv_shard_blocks_in_use": Gauge(
            "kubeinfer_kv_shard_blocks_in_use",
            "KV pool blocks referenced per tensor-parallel shard; block "
            "indices are logical, so every shard references the same "
            "block set and holds n_kv/tp heads of each",
            labels=("shard",), registry=registry,
        ),
        "prefix_hits": Counter(
            "kubeinfer_prefix_cache_hits_total",
            "Admits that reused >= 1 cached prefix block",
            registry=registry,
        ),
        "prefix_misses": Counter(
            "kubeinfer_prefix_cache_misses_total",
            "Admits that prefilled from token 0",
            registry=registry,
        ),
        "prefix_evictions": Counter(
            "kubeinfer_prefix_cache_evictions_total",
            "Radix-cache nodes evicted (LRU) to free pool blocks",
            registry=registry,
        ),
        # step-level engine efficiency (batching.StepProfiler): goodput
        # separates "tokens the device produced for someone" from the
        # padded work static shapes force; occupancy/padding-waste say
        # WHY goodput moved (empty slots vs bucket padding). Gauges
        # snapshot the profiler's sliding-window summary at scrape time.
        "goodput": Gauge(
            "kubeinfer_engine_goodput_tokens_per_second",
            "Live (non-padding) tokens produced per second, sliding "
            "window over profiler steps",
            registry=registry,
        ),
        "occupancy": Gauge(
            "kubeinfer_engine_batch_occupancy",
            "Mean live-rows / n_slots over recent decode dispatches",
            registry=registry,
        ),
        "padding_waste": Gauge(
            "kubeinfer_engine_padding_waste_frac",
            "Padded / (live + padded) tokens over recent dispatches",
            registry=registry,
        ),
        "queue_depth": Gauge(
            "kubeinfer_engine_queue_depth",
            "Requests waiting for a slot (submit queue + holdover)",
            registry=registry,
        ),
        "step_duration": Histogram(
            "kubeinfer_engine_step_duration_seconds",
            "Device dispatch wall time by phase (prefill/decode/verify/"
            "spec/chunk; a chunk's is the dispatch alone)",
            buckets=(0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
                     0.1, 0.15, 0.2, 0.25, 0.5, 1.0, 5.0, 30.0),
            labels=("phase",), registry=registry,
        ),
        # counters where the work happens (stepprof.StepProfiler totals
        # and the admit path's token accounting): monotonic engine ints
        # converted by delta at scrape time, like the radix counters
        "dispatches": Counter(
            "kubeinfer_engine_dispatches_total",
            "Device dispatches by phase",
            labels=("phase",), registry=registry,
        ),
        "decode_steps": Counter(
            "kubeinfer_engine_decode_steps_total",
            "Model steps run by decode and verify windows (sum of K)",
            registry=registry,
        ),
        "decode_row_steps": Counter(
            "kubeinfer_engine_decode_row_steps_total",
            "Rows that were decoding, summed over the model steps of "
            "decode and verify windows (over decode_steps_total: the "
            "mean live rows of a step)",
            registry=registry,
        ),
        "prefill_tokens": Counter(
            "kubeinfer_engine_prefill_tokens_total",
            "Prompt tokens of admitted requests: computed (run through "
            "the prefill programs), cached (taken from the radix "
            "cache), padded (bucket padding computed for nothing)",
            labels=("kind",), registry=registry,
        ),
        "prefix_refused": Counter(
            "kubeinfer_prefix_cache_refused_total",
            "Admissions whose prefix lookup was refused: "
            "recurrent_state (the model has linear-attention layers, "
            "whose state the cached blocks do not carry)",
            labels=("reason",), registry=registry,
        ),
        # routed experts (moe.STATS): summed on the device inside the
        # step programs over every MoE call, read back with a decode
        # window's tokens
        "moe_routed_pairs": Counter(
            "kubeinfer_moe_routed_pairs_total",
            "(token, expert) pairs the router chose for real rows",
            registry=registry,
        ),
        "moe_held_pairs": Counter(
            "kubeinfer_moe_held_pairs_total",
            "Routed pairs whose expert this replica holds (the rest "
            "belong to other expert-parallel ranks and add nothing here)",
            registry=registry,
        ),
        "moe_experts_reached": Counter(
            "kubeinfer_moe_experts_reached_total",
            "Held experts that at least one row reached, summed over "
            "MoE calls (only these experts' weights are read)",
            registry=registry,
        ),
        "moe_max_pairs": Counter(
            "kubeinfer_moe_busiest_expert_pairs_total",
            "Pairs of the busiest held expert, summed over MoE calls "
            "(over held_pairs / held experts: the load imbalance)",
            registry=registry,
        ),
        "moe_calls": Counter(
            "kubeinfer_moe_calls_total",
            "MoE calls (one per routed layer per model step)",
            registry=registry,
        ),
        "recurrent_state_bytes": Gauge(
            "kubeinfer_recurrent_state_bytes",
            "Resident bytes of the linear-attention layers' per-slot "
            "recurrent state and convolution tails",
            registry=registry,
        ),
        "admission_wait": Histogram(
            "kubeinfer_engine_admission_wait_seconds",
            "A first admission's queue wait split at the first decode "
            "window boundary after submit: window (the window in "
            "flight) and backlog (slots, pool, another admit); the two "
            "sum to kubeinfer_inference_queue_wait_seconds",
            buckets=(0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0,
                     30.0),
            labels=("stage",), registry=registry,
        ),
        "compiles": Counter(
            "kubeinfer_engine_compiles_total",
            "Device dispatches that hit a first-seen (phase, bucket) "
            "shape (jit compile proxy)",
            registry=registry,
        ),
        # preemptive scheduling + chunked prefill (batching.py): the
        # monotonic engine counters convert by delta at scrape time
        # like the radix counters above; the depth gauges snapshot the
        # scheduler's instantaneous backlog
        "preemptions": Counter(
            "kubeinfer_preemptions_total",
            "Decoding rows parked (blocks cached to the radix trie) to "
            "admit an SLO-pressured waiter",
            registry=registry,
        ),
        "resumes": Counter(
            "kubeinfer_preemption_resumes_total",
            "Parked rows readmitted (radix warm-resume)",
            registry=registry,
        ),
        "chunks": Counter(
            "kubeinfer_prefill_chunks_total",
            "Intermediate chunked-prefill dispatches (excludes the "
            "finalizing bucket dispatch)",
            registry=registry,
        ),
        "chunk_queue": Gauge(
            "kubeinfer_prefill_chunk_queue_depth",
            "Chunked prefills in flight (slot reserved, row not yet "
            "decoding)",
            registry=registry,
        ),
        "parked": Gauge(
            "kubeinfer_parked_requests",
            "Preempted requests awaiting readmission",
            registry=registry,
        ),
        # SLO burn rates (observability/slo.py): burn 1.0 = spending
        # budget exactly at the sustainable rate; the window label keeps
        # the short/long pair an alerting rule needs in one series
        "slo_burn": Gauge(
            "kubeinfer_slo_burn_rate",
            "Error-budget burn rate per objective and window",
            labels=("slo", "window"), registry=registry,
        ),
        "slo_budget": Gauge(
            "kubeinfer_slo_budget_remaining",
            "Signed remaining budget fraction over the longest window",
            labels=("slo",), registry=registry,
        ),
        # disaggregated prefill/decode (disagg/): the KV transfer plane
        # observed from BOTH ends — direction=export counts blocks/bytes
        # served at /kv/blocks, direction=import counts blocks/bytes
        # landed via ContinuousEngine.import_prefix; fallbacks are the
        # paths that degraded to local prefill (token-identical, so a
        # fallback is a latency event, never a correctness one)
        "kv_stream_blocks": Counter(
            "kubeinfer_kv_stream_blocks_total",
            "KV blocks streamed over the transfer plane",
            labels=("direction",), registry=registry,
        ),
        "kv_stream_bytes": Counter(
            "kubeinfer_kv_stream_bytes_total",
            "Wire bytes streamed over the KV transfer plane",
            labels=("direction",), registry=registry,
        ),
        "kv_stream_seconds": Histogram(
            "kubeinfer_kv_stream_seconds",
            "KV transfer-plane operation latency (export = serve the "
            "blob; import = fetch + verify + scatter)",
            buckets=(0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
                     1.0, 2.5, 5.0),
            labels=("direction",), registry=registry,
        ),
        "disagg_fallbacks": Counter(
            "kubeinfer_disagg_fallbacks_total",
            "Disaggregated-prefill requests that fell back to local "
            "prefill, by reason",
            labels=("reason",), registry=registry,
        ),
        "kv_pool_bytes": Gauge(
            "kubeinfer_kv_pool_bytes",
            "Resident bytes of the paged KV pool (pages + quant scales "
            "+ bf16 tail buffers), summed across the mesh",
            registry=registry,
        ),
        "model_param_bytes": Gauge(
            "kubeinfer_model_param_bytes",
            "Resident bytes of the model parameters (int8 pages + f32 "
            "scale planes under weight_dtype=int8), summed across the "
            "mesh",
            registry=registry,
        ),
        # what the device itself reports, per local device: the two
        # static gauges above are shape arithmetic, these include
        # activations, compiler scratch and whatever else the process
        # holds — and under tp they show whether the weights spread
        "device_bytes_in_use": Gauge(
            "kubeinfer_device_bytes_in_use",
            "Device memory this process holds now, per local device "
            "(memory_stats bytes_in_use; absent where the backend "
            "reports no stats)",
            labels=("device",), registry=registry,
        ),
        "device_peak_bytes_in_use": Gauge(
            "kubeinfer_device_peak_bytes_in_use",
            "High-water mark of device memory held by this process, "
            "per local device (memory_stats peak_bytes_in_use)",
            labels=("device",), registry=registry,
        ),
        "requests_shed": Counter(
            "kubeinfer_requests_shed_total",
            "Completion requests refused at the admission door, by "
            "reason (queue_depth_limit = graceful load shedding; the "
            "client got 503 + Retry-After, never a queue slot)",
            labels=("reason",), registry=registry,
        ),
        "kv_quant_blocks": Counter(
            "kubeinfer_kv_quant_blocks_total",
            "KV blocks quantized to int8 on commit (admit-time fills "
            "plus decode/verify boundary crossings; imports excluded)",
            registry=registry,
        ),
        # live-session migration (drain/evacuate/rebalance): sessions
        # handed off with a resume prefix, chunks streamed while decode
        # continued, and the export-cache evictions that tell an
        # operator a slow importer is losing blobs between chunks.
        # Fallbacks are the paths that degraded to (partial) re-prefill
        # — token-identical by the determinism contract, so every one
        # is a latency event, never a correctness one.
        "migrations": Counter(
            "kubeinfer_migrations_total",
            "Live sessions completed as migrated (drain handed them to "
            "the router with a resume prefix)",
            registry=registry,
        ),
        "migration_chunks": Counter(
            "kubeinfer_migration_chunks_total",
            "KV chunks streamed out by drain passes while decode "
            "continued on the source",
            registry=registry,
        ),
        "migration_fallbacks": Counter(
            "kubeinfer_migration_fallbacks_total",
            "Migration resume paths that degraded to (partial) local "
            "re-prefill, by reason",
            labels=("reason",), registry=registry,
        ),
        "kv_export_evictions": Counter(
            "kubeinfer_kv_export_evictions_total",
            "Export-cache blobs evicted (entry cap or bytes budget) "
            "before being pulled",
            registry=registry,
        ),
        "draining": Gauge(
            "kubeinfer_engine_draining_state",
            "1 while the engine refuses new admissions (drain in "
            "progress)",
            registry=registry,
        ),
    }


class InferenceServer:
    def __init__(self, engine, model_id: str, tokenizer=None,
                 host: str = "127.0.0.1", port: int = 8000,
                 continuous=None, sp=None,
                 tls_cert: str = "", tls_key: str = "",
                 token: str = "", slo=None,
                 kv_export_budget_mb: float = 0.0) -> None:
        self.engine = engine
        self.continuous = continuous  # ContinuousEngine | None
        self.sp = sp  # SPEngine | None (sequence-parallel long prompts)
        self.model_id = model_id
        self.tokenizer = tokenizer
        # bearer token guarding /debug/* only: traces and flight
        # recorder dumps carry prompt lengths and scheduling detail,
        # /metrics stays open like every scrape target. Empty = open
        # (tests, pod-network-only deployments) — same contract as the
        # store's debug endpoints (httpstore.py).
        self._token = token
        self.slo = slo if slo is not None else SLOMonitor()
        self.registry = Registry()
        self.metrics = _serving_metrics(self.registry)
        # disaggregated-prefill export staging (disagg/export.py):
        # prefill-only completions park their wire-encoded KV here,
        # keyed by deepest prefix fingerprint, until a decode replica
        # pulls it from /kv/blocks. Only meaningful with a continuous
        # engine (the paged pool is what gets exported).
        self.kv_exports = None
        if continuous is not None:
            from kubeinfer_tpu.disagg.export import KVExportCache

            # --kv-export-budget-mb: migration chunks are much larger
            # than prefill exports, so the cache is byte-bounded too
            # (0 = entry cap only, the pre-migration behavior)
            self.kv_exports = KVExportCache(
                max_bytes=(
                    int(kv_export_budget_mb * (1 << 20))
                    if kv_export_budget_mb > 0 else None
                ),
            )
            # live-session migration: the engine's drain pass streams
            # committed-KV chunks through this sink (scheduler thread,
            # off the engine lock); they land in the same export cache
            # /kv/blocks already serves, keyed by each chunk's own
            # deepest fingerprint — the target's chunked importer needs
            # no new endpoint
            continuous.migration_sink = self._export_migration_chunk
            # fleet identity: engine spans inherit this server's
            # model_id unless the engine was already named — model_id
            # is the name the router registers the replica under, so
            # fleetview's per-replica attribution lines up across the
            # server and engine halves of one hop
            if getattr(continuous, "replica_name", None) is None:
                continuous.replica_name = model_id
        # last-seen monotonic kv_cache_stats counters, for the
        # delta-to-Counter conversion at scrape time; guarded because
        # ThreadingHTTPServer can run concurrent /metrics scrapes
        self._kv_last: dict[str, int] = {}
        # profiler replay cursor: each step record feeds the duration
        # histogram exactly once across concurrent scrapes
        self._prof_seq = -1
        self._kv_lock = make_lock("server.InferenceServer._kv_lock")
        server = self

        class Handler(BaseEndpointHandler):
            def _authed(self) -> bool:
                if not server._token:
                    return True
                got = self.headers.get("Authorization", "")
                return token_matches(got, server._token)

            def do_GET(self):
                path = self.path.split("?", 1)[0]
                if path.startswith("/debug/") and not self._authed():
                    self.respond(401, "application/json",
                                 json.dumps({"error": "unauthorized"}))
                    return
                if path == "/health":
                    self.respond(200, "text/plain", "OK")
                elif path == "/metrics":
                    server._refresh_spec_metrics()
                    # unauthenticated by design: the inference server
                    # binds inside the pod network; the manager's
                    # token-guarded endpoint is the cluster-facing one
                    self.respond(
                        200, "text/plain; version=0.0.4",
                        server.registry.render(),
                    )
                elif path == "/v1/models":
                    self.respond(200, "application/json", json.dumps({
                        "object": "list",
                        "data": [{
                            "id": server.model_id,
                            "object": "model",
                            "owned_by": "kubeinfer-tpu",
                        }],
                    }))
                elif path == "/debug/spans":
                    # recorded spans as Chrome trace-event JSON —
                    # save the body and open it in Perfetto
                    # (docs/OBSERVABILITY.md); ?trace_id= narrows to
                    # one request's trace. Engine counter tracks
                    # (occupancy / queue depth / kv blocks) merge in as
                    # their own process group so the curves render next
                    # to the span timeline.
                    q = parse_qs(urlparse(self.path).query)
                    tid = (q.get("trace_id") or [None])[0]
                    doc = tracing.RECORDER.to_chrome_trace(tid)
                    server._merge_counter_tracks(doc)
                    self.respond(
                        200, "application/json", json.dumps(doc),
                    )
                elif path == "/cache/summary":
                    # unauthenticated like /metrics: fingerprints are
                    # one-way hashes of block keys — no prompt content
                    # is recoverable — and the fleet router polls this
                    # from inside the pod network
                    serving = (
                        server.continuous.stats_summary()
                        if server.continuous is not None else {}
                    )
                    self.respond(200, "application/json", json.dumps({
                        "model": server.model_id,
                        "serving": serving,
                    }))
                elif path == "/kv/blocks":
                    # disaggregated-prefill transfer plane: serve one
                    # exported prefix by content address (deepest
                    # rolling fingerprint). Unauthenticated like
                    # /cache/summary — the fleet's pod network — and
                    # self-verifying on the wire (sha256 in the header,
                    # wire.py), so a torn read never reaches a pool.
                    q = parse_qs(urlparse(self.path).query)
                    try:
                        fp = int((q.get("fp") or [""])[0])
                    except ValueError:
                        self.respond(400, "application/json", json.dumps(
                            {"error": "fp must be an integer fingerprint"}
                        ))
                        return
                    blob = (
                        server.kv_exports.get(fp)
                        if server.kv_exports is not None else None
                    )
                    if blob is None:
                        # evicted from the export LRU (or never made):
                        # the importer falls back to local prefill
                        self.respond(404, "application/json", json.dumps(
                            {"error": "no export for fingerprint"}
                        ))
                        return
                    try:
                        hdr = json.loads(blob[:blob.find(b"\n")])
                        nblocks = int(hdr.get("blocks", 0))
                    except ValueError:
                        nblocks = 0
                    # count BEFORE the socket write: the importer's very
                    # next request may scrape /metrics, and the counters
                    # must already reflect the blob it just received
                    server.metrics["kv_stream_blocks"].inc(
                        "export", by=nblocks
                    )
                    server.metrics["kv_stream_bytes"].inc(
                        "export", by=len(blob)
                    )
                    t0 = time.perf_counter()
                    self.respond(200, "application/octet-stream", blob)
                    server.metrics["kv_stream_seconds"].observe(
                        "export", time.perf_counter() - t0
                    )
                elif path == "/debug/flightrecorder":
                    # ?since= is an exactly-once cursor (events with
                    # seq > since only), same contract as
                    # StepProfiler.snapshot: a long-run drainer passes
                    # its last-seen seq each poll instead of refetching
                    # (and re-counting) the whole ring
                    q = parse_qs(urlparse(self.path).query)
                    try:
                        since = int((q.get("since") or ["-1"])[0])
                    except ValueError:
                        self.respond(400, "application/json", json.dumps(
                            {"error": "since must be an integer seq"}
                        ))
                        return
                    fl = (server.continuous.flight.to_dict(since)
                          if server.continuous is not None
                          else {"capacity": 0, "recorded": 0,
                                "events": []})
                    self.respond(200, "application/json", json.dumps(fl))
                elif path == "/debug/slo":
                    self.respond(
                        200, "application/json",
                        json.dumps(server.slo.snapshot()),
                    )
                else:
                    self.respond(404, "text/plain", "not found\n")

            def do_POST(self):
                path = self.path.split("?", 1)[0]
                n = int(self.headers.get("Content-Length", 0))
                raw = self.rfile.read(n)
                if path == "/admin/drain":
                    # guarded like /debug/*: draining is disruptive (a
                    # replica stops admitting), so it shares the bearer
                    # token; empty token = open, same contract
                    if not self._authed():
                        self.respond(401, "application/json",
                                     json.dumps({"error": "unauthorized"}))
                        return
                    try:
                        body = json.loads(raw or b"{}")
                    except ValueError:
                        body = {}
                    if not isinstance(body, dict):
                        body = {}
                    try:
                        resp = server.drain(
                            resume=bool(body.get("resume", False)),
                            timeout_s=float(body.get("timeout_s", 30.0)),
                        )
                    except ValueError as e:
                        self.respond(400, "application/json", json.dumps(
                            {"error": {"message": str(e),
                                       "type": "invalid_request_error"}}
                        ))
                        return
                    self.respond(200, "application/json",
                                 json.dumps(resp))
                    return
                if path != "/v1/completions":
                    self.respond(404, "text/plain", "not found\n")
                    return
                # server-side span joins the caller's trace when a
                # traceparent header arrived; otherwise this request
                # starts a fresh trace
                with _TRACER.span(
                    "http POST /v1/completions",
                    parent=self.trace_context(),
                ) as sp:
                    try:
                        try:
                            body = json.loads(raw or b"{}")
                        except ValueError:
                            # malformed JSON never reaches complete(); count
                            # it here or a flood of garbage 400s shows zero
                            # in requests_total
                            server.metrics["requests"].inc("invalid", "invalid")
                            raise
                        resp = server.complete(body)
                        sp.set(status=200)
                        self.respond(200, "application/json", json.dumps(resp))
                    except ValueError as e:
                        sp.set(status=400)
                        self.respond(400, "application/json", json.dumps(
                            {"error": {"message": str(e), "type": "invalid_request_error"}}
                        ))
                    except Exception as e:  # keep the serving thread alive
                        if server._is_overload_error(e):
                            # graceful load shedding: valid request, no
                            # queue room — 503 with a Retry-After hint
                            # so well-behaved clients back off instead
                            # of hammering the door
                            sp.set(status=503)
                            self.respond(
                                503, "application/json",
                                json.dumps({"error": {
                                    "message": str(e),
                                    "type": "overloaded",
                                }}),
                                headers={"Retry-After": str(max(
                                    1, int(getattr(
                                        e, "retry_after_s", 1.0))))},
                            )
                            return
                        if server._is_draining_error(e):
                            # the request is valid; THIS replica just
                            # won't take it — 503 with a typed body so
                            # the router marks the replica draining and
                            # routes elsewhere instead of relaying an
                            # error to the client
                            sp.set(status=503)
                            self.respond(503, "application/json", json.dumps(
                                {"error": {"message": str(e), "type": "draining"}}
                            ))
                            return
                        log.exception("completion failed")
                        sp.set(status=500)
                        self.respond(500, "application/json", json.dumps(
                            {"error": {"message": str(e), "type": "server_error"}}
                        ))

        from kubeinfer_tpu.utils.httpbase import wrap_server_tls

        self._httpd = wrap_server_tls(
            ThreadingHTTPServer((host, port), Handler), tls_cert, tls_key
        )
        self._httpd.daemon_threads = True
        self._thread: threading.Thread | None = None

    @property
    def port(self) -> int:
        return self._httpd.server_address[1]

    # -- request handling --------------------------------------------------

    def _encode(self, prompt) -> list[int]:
        if isinstance(prompt, list):
            if not all(isinstance(t, int) for t in prompt):
                raise ValueError("prompt list must contain token ids (ints)")
            return prompt
        if isinstance(prompt, str):
            if self.tokenizer is None:
                raise ValueError(
                    "string prompts require tokenizer files next to the "
                    "model weights; this server was started without them — "
                    "send token ids instead"
                )
            return self.tokenizer.encode(prompt)
        raise ValueError("prompt must be a string or a list of token ids")

    def _decode(self, ids: list[int]) -> str:
        if self.tokenizer is None:
            return " ".join(str(i) for i in ids)
        return self.tokenizer.decode(ids)

    def _refresh_spec_metrics(self) -> None:
        """Scrape-time refresh of the speculation gauges and the
        paged-KV collectors from the batcher's counters (they mutate in
        the scheduler thread; gauges snapshot rather than double-count,
        and the monotonic radix counters convert to Prometheus counters
        by delta under _kv_lock so concurrent scrapes never double-add).
        SLO gauges refresh even without a continuous engine — every
        route feeds _observe_breakdown, so the burn rates are
        meaningful for per-request-only servers too."""
        import jax

        for d in jax.local_devices():
            stats = d.memory_stats() or {}
            if "bytes_in_use" in stats:
                self.metrics["device_bytes_in_use"].set(
                    str(d.id), stats["bytes_in_use"]
                )
            if "peak_bytes_in_use" in stats:
                self.metrics["device_peak_bytes_in_use"].set(
                    str(d.id), stats["peak_bytes_in_use"]
                )
        snap = self.slo.snapshot()
        for name, obj in snap["objectives"].items():
            for w, d in obj["windows"].items():
                self.metrics["slo_burn"].set(name, f"{w}s", d["burn_rate"])
            self.metrics["slo_budget"].set(name, obj["budget_remaining"])
        if self.continuous is None:
            return
        stats = self.continuous.kv_cache_stats()
        self.metrics["kv_blocks_in_use"].set(stats["blocks_in_use"])
        self.metrics["kv_blocks_free"].set(stats["blocks_free"])
        self.metrics["kv_pool_bytes"].set(stats["pool_bytes"])
        self.metrics["recurrent_state_bytes"].set(
            self.continuous.recurrent_state_bytes)
        self.metrics["model_param_bytes"].set(
            self.continuous.model_param_bytes
        )
        layout = self.continuous.layout
        self.metrics["tp_degree"].set(layout.tp)
        self.metrics["mesh_devices"].set(layout.mesh_devices)
        # one series per shard, all reporting the same logical count:
        # the pool's bookkeeping is layout-agnostic (kv_blocks.py), so a
        # shard's referenced-block set IS the pool's — the per-shard
        # fan-out exists so dashboards aggregating by device see the
        # sharded pool instead of inferring it from tp_degree
        for shard in range(layout.tp):
            self.metrics["kv_shard_blocks_in_use"].set(
                str(shard), stats["blocks_in_use"]
            )
        summary = self.continuous.stats_summary()
        self.metrics["goodput"].set(summary["goodput_tokens_per_sec"])
        self.metrics["occupancy"].set(summary["batch_occupancy"])
        self.metrics["padding_waste"].set(summary["padding_waste_frac"])
        self.metrics["queue_depth"].set(summary["queue_depth"])
        self.metrics["draining"].set(
            1.0 if summary.get("draining") else 0.0
        )
        sched = self.continuous.scheduler_stats()
        self.metrics["chunk_queue"].set(sched["chunk_queue"])
        self.metrics["parked"].set(sched["parked"])
        with self._kv_lock:
            for key, name in (
                ("hits", "prefix_hits"),
                ("misses", "prefix_misses"),
                ("evictions", "prefix_evictions"),
                ("quant_blocks", "kv_quant_blocks"),
            ):
                delta = stats[key] - self._kv_last.get(key, 0)
                # unconditional inc: a zero delta still materializes
                # the sample, so the series exists (at 0) from the
                # first scrape rather than popping into existence on
                # its first event
                self.metrics[name].inc(by=delta)
                self._kv_last[key] = stats[key]
            # scheduler counters ride the same delta-to-Counter
            # conversion (the engine's ints are monotonic per process;
            # _kv_last keys are disjoint from the radix ones)
            for key, name in (
                ("preempted", "preemptions"),
                ("resumed", "resumes"),
                ("chunks", "chunks"),
                ("spec_draft_tokens", "spec_draft_tokens"),
                ("spec_accepted_tokens", "spec_accepted_tokens"),
                ("spec_rollbacks", "spec_rollbacks"),
                ("migrated", "migrations"),
                ("migration_chunks", "migration_chunks"),
                ("decode_steps", "decode_steps"),
                ("decode_row_steps", "decode_row_steps"),
            ):
                delta = sched[key] - self._kv_last.get(key, 0)
                self.metrics[name].inc(by=delta)
                self._kv_last[key] = sched[key]
            for name, totals, labels in (
                ("dispatches", sched["dispatches"], PHASES),
                ("prefill_tokens", sched["prefill_tokens"],
                 ("computed", "cached", "padded")),
                ("prefix_refused", sched["prefix_refused"],
                 ("recurrent_state",)),
            ):
                for label in labels:
                    key = f"{name}.{label}"
                    total = totals.get(label, 0)
                    self.metrics[name].inc(
                        label, by=total - self._kv_last.get(key, 0)
                    )
                    self._kv_last[key] = total
            for key, total in sched["moe"].items():
                self.metrics["moe_" + key].inc(
                    by=total - self._kv_last.get("moe." + key, 0))
                self._kv_last["moe." + key] = total
            # a reader of deltas sums phases (decode + verify windows
            # per decode_steps_total): each needs its series at 0
            for phase in PHASES:
                self.metrics["step_duration"].ensure(phase)
            for stage in ("window", "backlog"):
                self.metrics["admission_wait"].ensure(stage)
            if self.kv_exports is not None:
                # export-cache evictions ride the same delta-to-Counter
                # conversion (the cache's int is monotonic per process)
                ev = self.kv_exports.stats()["evictions"]
                self.metrics["kv_export_evictions"].inc(
                    by=ev - self._kv_last.get("export_evictions", 0)
                )
                self._kv_last["export_evictions"] = ev
            # ratio from the cumulative ints, not the deltas: a scrape
            # landing between windows would otherwise read 0/0 and
            # flap the gauge to zero
            self.metrics["spec_acceptance_ratio"].set(
                sched["spec_accepted_tokens"]
                / max(sched["spec_draft_tokens"], 1)
            )
            # profiler replay under the same lock: the cursor advance
            # and the histogram observes must be atomic per scrape or a
            # concurrent scrape double-counts the same step records
            self.metrics["compiles"].inc(by=0)
            recs = self.continuous.profiler.snapshot(
                since_seq=self._prof_seq
            )
            for r in recs:
                self.metrics["step_duration"].observe(r.phase, r.dur_s)
                if r.compiled:
                    self.metrics["compiles"].inc()
            if recs:
                self._prof_seq = recs[-1].seq

    def _merge_counter_tracks(self, doc: dict) -> None:
        """Append the engine's counter tracks (batch occupancy, padded
        tokens, queue depth, kv blocks) to a Chrome trace doc as one
        extra process group, so Perfetto shows the efficiency curves
        under the span timeline they explain. No-op without a
        continuous engine."""
        if self.continuous is None:
            return
        events = doc.get("traceEvents", [])
        pid = max((e.get("pid", 0) for e in events), default=0) + 1
        events.append({
            "ph": "M", "name": "process_name", "pid": pid, "tid": 0,
            "args": {"name": "engine-counters"},
        })
        events.extend(self.continuous.profiler.counter_events(pid))
        events.extend(self.continuous.flight.counter_events(pid))
        doc["traceEvents"] = events

    def complete(self, body: dict) -> dict:
        # mutable holder: _complete records the chosen route the moment
        # it picks one, so exceptions thrown DURING generation still
        # carry their route label (a local set via the return tuple
        # would be lost exactly when the per-route error breakdown
        # matters)
        route_box = {"route": "invalid"}
        t0 = time.perf_counter()
        # replica attr = model_id: every in-process server records into
        # the shared RECORDER, and fleetview attributes a merged
        # trace's hops to replicas by this attr (model_id is the name
        # the router registers the replica under in the fleet benches)
        with _TRACER.span("server.complete", replica=self.model_id) as span:
            try:
                resp = self._complete(body, route_box)
            except ValueError:
                self.metrics["requests"].inc(route_box["route"], "invalid")
                raise
            except Exception as e:
                if self._is_overload_error(e):
                    self.metrics["requests_shed"].inc("queue_depth_limit")
                    outcome = "shed"
                elif self._is_draining_error(e):
                    outcome = "draining"
                else:
                    outcome = "error"
                self.metrics["requests"].inc(route_box["route"], outcome)
                raise
            finally:
                span.set(route=route_box["route"])
        route = route_box["route"]
        dur = time.perf_counter() - t0
        self.metrics["requests"].inc(route, "ok")
        self.metrics["latency"].observe(route, dur)
        self.metrics["prompt_tokens"].inc(
            by=resp["usage"]["prompt_tokens"]
        )
        self.metrics["completion_tokens"].inc(
            by=resp["usage"]["completion_tokens"]
        )
        ttft, tpot = self._observe_breakdown(
            route, dur, resp["usage"]["completion_tokens"],
            route_box.get("timing"),
        )
        # non-OpenAI extension: the serving timeline as the SERVER saw
        # it. The fleet router/bench compare replicas by TTFT/TPOT, and
        # a client-side wall clock would fold proxy+network time into
        # the very signal being compared.
        resp["kubeinfer"] = {
            "route": route,
            "ttft_ms": round(ttft * 1e3, 3),
            "tpot_ms": round(tpot * 1e3, 3),
        }
        resp["kubeinfer"].update(route_box.get("ext") or {})
        return resp

    def _observe_breakdown(self, route: str, total_s: float, n_out: int,
                           req=None) -> tuple[float, float]:
        """Derived latency-breakdown histograms. The continuous route
        hands back its ``_Request`` (``timing`` in the route box) whose
        t_submit/t_admit/t_first/t_done were stamped by the scheduler
        itself; routes without an internal timeline degrade to
        end-to-end TTFT and mean-per-token TPOT — the route label keeps
        the populations separable on dashboards. Returns the observed
        ``(ttft, tpot)`` seconds so complete() can echo them to the
        client (the disagg bench compares decode-replica TPOT tails
        across fleet topologies from this echo)."""
        ttft = total_s
        decode_s = None
        if req is not None and req.t_submit:
            if req.t_admit:
                wait = max(0.0, req.t_admit - req.t_submit)
                self.metrics["queue_wait"].observe(route, wait)
                self.slo.observe("queue_wait", wait)
                # the same wait, by what was waited for; observed here
                # so both halves cover exactly queue_wait's population
                self.metrics["admission_wait"].observe(
                    "window", req.wait_window_s)
                self.metrics["admission_wait"].observe(
                    "backlog", req.wait_backlog_s)
            end = req.t_done or req.t_submit + total_s
            if req.t_first:
                ttft = max(0.0, req.t_first - req.t_submit)
                decode_s = max(0.0, end - req.t_first)
        self.metrics["ttft"].observe(route, ttft)
        self.slo.observe("ttft", ttft)
        if decode_s is not None and n_out > 1:
            tpot = decode_s / (n_out - 1)
        else:
            tpot = total_s / max(1, n_out)
        self.metrics["tpot"].observe(route, tpot)
        self.slo.observe("tpot", tpot)
        return ttft, tpot

    def _maybe_import_prefix(self, ids: list[int], base_url: str) -> None:
        """Pull this prompt's exported KV prefix from ``base_url`` and
        land it in the local pool + radix cache. Best-effort: every
        failure increments a fallback reason and the request proceeds
        with a local (token-identical) prefill. Runs lock-free on the
        serving HTTP thread — the network fetch here is exactly the
        blocking surface the admit path must never hold a lock across,
        so it happens before routing, and the scatter itself is staged
        to the scheduler thread (batching.import_prefix)."""
        from kubeinfer_tpu.disagg.client import import_remote_prefix
        from kubeinfer_tpu.inference.kv_blocks import prefix_fingerprints

        eng = self.continuous
        fps = prefix_fingerprints(ids, eng.block_size)
        if not fps:
            return  # sub-block prompt: nothing a prefill replica can ship
        advertised = set(
            eng.cache_summary().get("fingerprints", [])
        )
        if fps[-1] in advertised:
            return  # already warm locally (earlier import or admit)
        t0 = time.perf_counter()
        # the ledger's "stream" phase: the span brackets the network
        # fetch + verify + staged scatter, parented under the active
        # server.complete span so it joins the request's trace
        with _TRACER.span("server.kv_import", kind="prefix",
                          replica=self.model_id) as sp:
            imported, reason, wire_bytes = import_remote_prefix(
                eng, ids, base_url,
            )
            sp.set(blocks=imported,
                   **({"fallback": reason} if reason else {}))
        if imported > 0:
            self.metrics["kv_stream_blocks"].inc("import", by=imported)
            self.metrics["kv_stream_bytes"].inc("import", by=wire_bytes)
            self.metrics["kv_stream_seconds"].observe(
                "import", time.perf_counter() - t0
            )
        else:
            self.metrics["disagg_fallbacks"].inc(reason or "unknown")

    def _is_draining_error(self, e: BaseException) -> bool:
        """Lazy-typed check: batching pulls jax, and this module must
        stay importable in weightless tools — the class only exists to
        be raised once a continuous engine does, so the import here
        never runs before batching is loaded anyway."""
        if self.continuous is None:
            return False
        from kubeinfer_tpu.inference.batching import EngineDrainingError

        return isinstance(e, EngineDrainingError)

    def _is_overload_error(self, e: BaseException) -> bool:
        """Shed-at-the-door twin of _is_draining_error (same lazy-typed
        import rationale); distinct because the HTTP answer differs —
        overload carries Retry-After, drain does not recover."""
        if self.continuous is None:
            return False
        from kubeinfer_tpu.inference.batching import EngineOverloadedError

        return isinstance(e, EngineOverloadedError)

    def _export_migration_chunk(self, chunk: dict) -> None:
        """Engine migration sink (scheduler thread, OFF the engine
        lock): wire-encode one streamed chunk and park it in the export
        cache keyed by the chunk's own deepest fingerprint — exactly
        where ``/kv/blocks`` serves from, so the target's chunked
        importer (disagg.client.import_remote_chain) needs no new
        endpoint. Chunk 0 encodes as plain v1/v2 (start_block=0); later
        chunks ride wire v3. Raising here is fine: the engine treats a
        sink failure as 'hand the session off with what already
        streamed'."""
        from kubeinfer_tpu.disagg.wire import encode_payload

        blob = encode_payload(
            chunk["pages_k"], chunk["pages_v"],
            chunk["fingerprints"], chunk["block_size"],
            scales_k=chunk.get("scales_k"),
            scales_v=chunk.get("scales_v"),
            kv_dtype=chunk.get("kv_dtype", "bf16"),
            start_block=chunk["start_block"],
        )
        # export blocks/bytes are counted when /kv/blocks serves the
        # blob (count-before-respond there); counting the put too would
        # double-book the direction=export series
        self.kv_exports.put(int(chunk["fingerprints"][-1]), blob)

    def drain(self, resume: bool = False,
              timeout_s: float = 30.0) -> dict:
        """``POST /admin/drain``: stop admitting, migrate-or-complete
        every live session, report. Three callers share this one
        mechanism: scale-down (the reconciler drains before deleting
        the pod), fault evacuation (SLO-burn-triggered), and hot-replica
        rebalancing (``resume=True`` — hand the sessions off, then
        rejoin the fleet). Blocks up to ``timeout_s``; a false
        ``drained`` means sessions are still live (the caller retries
        or escalates to a hard kill, which the fallback path absorbs
        token-identically)."""
        if self.continuous is None:
            raise ValueError("drain requires the continuous batcher")
        eng = self.continuous
        before = eng.migrated_total
        eng.drain()
        drained = eng.wait_drained(timeout_s)
        sched = eng.scheduler_stats()
        out = {
            "drained": bool(drained),
            "draining": True,
            "migrated": int(eng.migrated_total - before),
            "migration_chunks_total": int(sched["migration_chunks"]),
            "migration_blocks_total": int(sched["migration_blocks"]),
            "exports": (
                self.kv_exports.stats()
                if self.kv_exports is not None else {}
            ),
        }
        if resume and drained:
            eng.undrain()
            out["draining"] = False
        return out

    def _maybe_import_chain(self, tokens: list[int],
                            base_url: str) -> None:
        """Chunked warm-import of a migrated session's KV chain from
        the SOURCE replica before the resume admit. Best-effort like
        ``_maybe_import_prefix``, but failures count under the
        migration fallback counter — a partial import is still a win
        (the resume re-prefills only past the last verified chunk), so
        blocks/bytes are recorded even when a reason is."""
        from kubeinfer_tpu.disagg.client import import_remote_chain
        from kubeinfer_tpu.inference.kv_blocks import prefix_fingerprints

        eng = self.continuous
        fps = prefix_fingerprints(tokens, eng.block_size)
        if not fps:
            return
        advertised = set(
            eng.cache_summary().get("fingerprints", [])
        )
        if fps[-1] in advertised:
            return  # whole chain already warm (bounce-back resume)
        t0 = time.perf_counter()
        # same stream-phase span as _maybe_import_prefix: one name for
        # both import shapes so ledger joins need a single rule
        with _TRACER.span("server.kv_import", kind="chain",
                          replica=self.model_id) as sp:
            imported, reason, wire_bytes = import_remote_chain(
                eng, tokens, base_url,
                chunk_blocks=getattr(eng, "migration_chunk_blocks", 4),
            )
            sp.set(blocks=imported,
                   **({"fallback": reason} if reason else {}))
        if imported > 0:
            self.metrics["kv_stream_blocks"].inc("import", by=imported)
            self.metrics["kv_stream_bytes"].inc("import", by=wire_bytes)
            self.metrics["kv_stream_seconds"].observe(
                "import", time.perf_counter() - t0
            )
        if reason is not None:
            self.metrics["migration_fallbacks"].inc(reason)

    def _complete(self, body: dict, route_box: dict) -> dict:
        prompt = body.get("prompt")
        if prompt is None:
            raise ValueError("'prompt' is required")
        ids = self._encode(prompt)
        max_tokens = int(body.get("max_tokens", 16))
        if not (0 <= max_tokens <= 4096):
            raise ValueError(
                "max_tokens must be in [0, 4096] (0 = prefill-only)"
            )
        temperature = float(body.get("temperature", 0.0))
        top_k = int(body.get("top_k", 0))
        top_p = float(body.get("top_p", 1.0))
        rep_penalty = float(body.get("repetition_penalty", 1.0))
        if top_k < 0:
            raise ValueError("top_k must be >= 0 (0 disables)")
        if not (0.0 < top_p <= 1.0):
            raise ValueError("top_p must be in (0, 1]")
        if rep_penalty <= 0.0:
            raise ValueError("repetition_penalty must be > 0")
        seed = int(body.get("seed", 0))
        eos_id = -1
        if self.tokenizer is not None and self.tokenizer.eos_token_id is not None:
            eos_id = int(self.tokenizer.eos_token_id)

        # live-session migration resume (router-injected): a source
        # replica drained mid-generation and handed back its tokens-so-
        # far (and optionally where to pull the streamed KV chain from)
        resume = body.get("kubeinfer_resume")
        resume_tokens: list[int] = []
        if resume is not None:
            if not isinstance(resume, dict):
                raise ValueError("kubeinfer_resume must be an object")
            rt = resume.get("tokens") or []
            if not (
                isinstance(rt, list)
                and all(isinstance(t, int) for t in rt)
            ):
                raise ValueError(
                    "kubeinfer_resume.tokens must be token ids (ints)"
                )
            resume_tokens = [int(t) for t in rt]

        # disaggregated decode side: the router annotates the forwarded
        # body with the prefill replica that just produced this prompt's
        # KV; pull it into the local pool BEFORE routing so the
        # continuous admit below sees a warm radix cache. Runs on this
        # HTTP thread with no engine locks held (the scatter is staged
        # to the scheduler thread) — the new blocking surface the lint
        # would flag lives in _maybe_import_prefix, off-lock by design.
        kv_source = body.get("kubeinfer_kv_source")
        if (
            isinstance(kv_source, str) and kv_source
            and max_tokens > 0
            and self.continuous is not None
            and self.continuous.fits(len(ids), max_tokens)
        ):
            self._maybe_import_prefix(ids, kv_source)

        if max_tokens == 0:
            # prefill-only mode (disaggregated prefill role): run the
            # prompt through the continuous batcher's normal admit path
            # — the SAME code that serves interleaved prefills, so the
            # exported pages are bit-identical to what a local prefill
            # would have produced — and park the wire-encoded KV in the
            # export cache for a decode replica to pull. This branch
            # outranks every other route: sp/engine have no exportable
            # paged pool.
            if not (
                self.continuous is not None
                and self.continuous.fits(len(ids), 0)
            ):
                raise ValueError(
                    "max_tokens=0 (prefill-only) requires the continuous "
                    "batcher and a prompt that fits its cache"
                )
            route_box["route"] = "prefill"
            req = self.continuous.serve(
                ids, max_new_tokens=0, eos_id=eos_id,
                temperature=temperature, seed=seed,
                top_k=top_k, top_p=top_p,
                repetition_penalty=rep_penalty,
                export_kv=True,
            )
            gen: list[int] = []
            route_box["timing"] = req
            if req.kv_export is not None and self.kv_exports is not None:
                from kubeinfer_tpu.disagg.wire import (
                    WireError, encode_payload,
                )

                exp = req.kv_export
                try:
                    blob = encode_payload(
                        exp["pages_k"], exp["pages_v"],
                        exp["fingerprints"], exp["block_size"],
                        scales_k=exp.get("scales_k"),
                        scales_v=exp.get("scales_v"),
                        kv_dtype=exp.get("kv_dtype", "bf16"),
                    )
                except WireError:
                    # capture raced an empty/partial prefill (e.g. the
                    # prompt had no full block); the importer will fall
                    # back to local prefill — latency, not correctness
                    log.exception("kv export encode failed; skipping")
                else:
                    fp = exp["fingerprints"][-1]
                    self.kv_exports.put(fp, blob)
                    route_box["ext"] = {"kv_export": {
                        "fingerprint": int(fp),
                        "blocks": len(exp["fingerprints"]),
                        "bytes": len(blob),
                    }}
        elif resume_tokens:
            # resume MUST ride the continuous batcher: only its
            # position-folded key schedule reproduces the source's
            # sampling stream mid-generation (park/readmit invariant);
            # the sp/per-request engines would re-draw
            if not (
                self.continuous is not None
                and self.continuous.fits(len(ids), max_tokens)
            ):
                raise ValueError(
                    "kubeinfer_resume requires the continuous batcher "
                    "and a prompt that fits its cache"
                )
            route_box["route"] = "resume"
            if len(resume_tokens) >= max_tokens or (
                eos_id >= 0 and resume_tokens[-1] == eos_id
            ):
                # degenerate tail: the source finished the generation
                # before the hand-off completed — answer directly, no
                # zero-budget admit
                gen = resume_tokens[:max_tokens]
            else:
                src = resume.get("kv_source")
                if isinstance(src, str) and src:
                    # committed chain only — full blocks of the
                    # effective prompt MINUS the last token (the
                    # source's committed-blocks rule: the newest
                    # token's KV never streamed)
                    self._maybe_import_chain(
                        (ids + resume_tokens)[:-1], src,
                    )
                req = self.continuous.serve(
                    ids, max_new_tokens=max_tokens, eos_id=eos_id,
                    temperature=temperature, seed=seed,
                    top_k=top_k, top_p=top_p,
                    repetition_penalty=rep_penalty,
                    resume_tokens=resume_tokens,
                )
                gen = req.out_tokens
                route_box["timing"] = req
                if req.migrated is not None:
                    # drained AGAIN mid-resume (rolling rebalance):
                    # the router chains another hop off this ext
                    route_box["ext"] = {"migrated": dict(req.migrated)}
        elif self.sp is not None and self.sp.fits(len(ids), max_tokens):
            # long prompts shard their prefill over the mesh's sp axis
            # (ring attention; sp_engine.py) and decode from the
            # handed-off KV — the route that makes >single-chip-prefill
            # contexts servable. Short prompts fall through: the
            # collective traffic isn't worth it below --sp-min-prompt.
            route_box["route"] = "sp"
            out = self.sp.generate(
                [ids], max_new_tokens=max_tokens, eos_id=eos_id,
                temperature=temperature, seed=seed,
                top_k=top_k, top_p=top_p,
                repetition_penalty=rep_penalty,
            )
            gen = out.tokens[0, : out.lengths[0]].tolist()
        elif (
            self.continuous is not None
            and self.continuous.fits(len(ids), max_tokens)
        ):
            # requests ride the shared continuous-batching slots (greedy
            # and sampled alike — slots carry per-request temperature and
            # PRNG state): concurrent clients decode together instead of
            # serializing. Requests beyond slot width (long context) fall
            # through to the per-request engine, which serves the model's
            # full context.
            route_box["route"] = "continuous"
            req = self.continuous.serve(
                ids, max_new_tokens=max_tokens, eos_id=eos_id,
                temperature=temperature, seed=seed,
                top_k=top_k, top_p=top_p,
                repetition_penalty=rep_penalty,
            )
            gen = req.out_tokens
            # hand the scheduler-stamped timeline to complete() for the
            # TTFT/TPOT/queue-wait histograms
            route_box["timing"] = req
            if req.migrated is not None:
                # the engine drained under this request: out_tokens is
                # a PREFIX of the answer; the ext tells the router to
                # re-route with these as the resume prefix (and pull
                # the streamed chain from this replica's /kv/blocks)
                route_box["ext"] = {"migrated": dict(req.migrated)}
        else:
            if self.engine.cfg.recurrent:
                raise ValueError(
                    "a model with linear-attention layers is served by "
                    "the continuous batcher alone, and this request "
                    "does not fit a slot of it"
                )
            route_box["route"] = "engine"
            out = self.engine.generate(
                [ids], max_new_tokens=max_tokens, eos_id=eos_id,
                temperature=temperature, seed=seed,
                top_k=top_k, top_p=top_p,
                repetition_penalty=rep_penalty,
            )
            gen = out.tokens[0, : out.lengths[0]].tolist()
        # "stop" iff the sequence actually terminated on EOS — including
        # EOS landing exactly on the max_tokens-th token (a length-based
        # test would mislabel that and invite clients to auto-continue a
        # finished sequence)
        stopped = eos_id >= 0 and bool(gen) and gen[-1] == eos_id
        finish = "stop" if stopped else "length"
        if (route_box.get("ext") or {}).get("migrated") is not None:
            # partial generation by design — neither EOS nor budget;
            # the router treats this as "continue elsewhere", a client
            # seeing it raw knows the tokens are a prefix
            finish = "migrated"
        # continuous-engine routes carry the engine's flight-recorder
        # request id (_Request.rid) in the completion id, so a client
        # report cross-references straight into a /debug/flightrecorder
        # dump's per-request chain (detail key `req`, see
        # analysis/protocol.py); batch-generate routes have no rid
        rid = getattr(route_box.get("timing"), "rid", None)
        return {
            "id": "cmpl-kubeinfer" if rid is None
            else f"cmpl-kubeinfer-{rid}",
            "object": "text_completion",
            "model": self.model_id,
            "choices": [{
                "index": 0,
                "text": self._decode(gen),
                "tokens": gen,
                "finish_reason": finish,
            }],
            "usage": {
                "prompt_tokens": len(ids),
                "completion_tokens": len(gen),
                "total_tokens": len(ids) + len(gen),
            },
        }

    # -- lifecycle ----------------------------------------------------------

    def start(self) -> "InferenceServer":
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, daemon=True,
            name=f"inference-server-{self.port}",
        )
        self._thread.start()
        return self

    def stop(self) -> None:
        # shutdown() handshakes with serve_forever and BLOCKS FOREVER if
        # the serve loop never ran — callers that used complete()
        # directly (tests, the multichip dryrun) still get a clean close
        if self._thread is not None:
            self._httpd.shutdown()
        self._httpd.server_close()


def _load_tokenizer(model_dir: str):
    try:
        from transformers import AutoTokenizer

        return AutoTokenizer.from_pretrained(model_dir)
    except Exception as e:
        log.warning("no tokenizer loaded from %s (%s); id-only mode", model_dir, e)
        return None


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(prog="kubeinfer-inference-server")
    # flag surface = runtime.py build_args (vllm.go:93-112 parity)
    p.add_argument("--model", required=True,
                   help="model dir (HF snapshot) or preset name with "
                        "--random-init")
    p.add_argument("--host", default="0.0.0.0")
    p.add_argument("--port", type=int, default=8000)
    p.add_argument("--tensor-parallel-size", type=int, default=1)
    p.add_argument("--sequence-parallel-size", type=int, default=1,
                   help="shard long-prompt prefill over this many mesh "
                        "devices via ring attention (sp_engine.py); "
                        "requests below --sp-min-prompt keep the normal "
                        "routes")
    p.add_argument("--sp-min-prompt", type=int, default=1024,
                   help="minimum prompt length (tokens) routed through "
                        "the sequence-parallel engine")
    p.add_argument("--gpu-memory-utilization", type=float, default=0.9)
    p.add_argument("--dtype", default="auto",
                   choices=["auto", "bfloat16", "float32"])
    p.add_argument("--max-model-len", type=int, default=0)
    p.add_argument("--random-init", action="store_true",
                   help="serve a randomly initialized --model preset "
                        "(demo/e2e mode; no weights needed)")
    p.add_argument("--batch-slots", type=int, default=8,
                   help="continuous-batching decode slots shared by "
                        "concurrent requests, greedy and sampled alike "
                        "(0 disables; over-slot-width requests use the "
                        "per-request engine)")
    p.add_argument("--prefill-chunk-blocks", type=int, default=4,
                   help="split each prefill into chunks of this many KV "
                        "blocks interleaved with decode steps, so a long "
                        "cold prompt never stalls the decode batch for "
                        "more than one chunk (0 = whole-suffix prefill)")
    p.add_argument("--migration-chunk-blocks", type=int, default=4,
                   help="KV blocks streamed per drain pass during live-"
                        "session migration; decode windows run between "
                        "chunks, so the stream chases the decode head "
                        "instead of stalling it")
    p.add_argument("--kv-export-budget-mb", type=float, default=0.0,
                   help="byte budget for the KV export cache (prefill "
                        "exports + migration chunks); 0 = entry cap "
                        "only. Evictions past the budget count under "
                        "kubeinfer_kv_export_evictions_total")
    p.add_argument("--kv-dtype", default="bf16",
                   choices=("bf16", "int8"),
                   help="paged KV pool dtype: int8 quantizes blocks on "
                        "commit (per-block-per-head scales, dequant in "
                        "the attention kernel) for ~2x the resident "
                        "slots at equal HBM; disagg peers must match")
    p.add_argument("--weight-dtype", default="bf16",
                   choices=("bf16", "int8"),
                   help="model weight precision: int8 quantizes the "
                        "projection matmul weights at LOAD time "
                        "(per-tile absmax scales, dequant fused into "
                        "the matmul) for ~2x model capacity at equal "
                        "HBM; embeddings, norms, and lm_head stay in "
                        "--dtype. Composes with --tensor-parallel-size "
                        "(scale planes shard with their weights)")
    p.add_argument("--queue-depth-limit", type=int, default=0,
                   help="shed completion submits with 503 + Retry-After "
                        "once waiting work (queue + holdover + parked) "
                        "reaches this depth, counted under "
                        "kubeinfer_requests_shed_total (0 = unbounded "
                        "queueing, the pre-shedding behavior)")
    p.add_argument("--preemption-slo", default="",
                   metavar="THRESHOLD_S[:BURN_LIMIT]",
                   help="park the youngest decoding row (KV cached to "
                        "the radix trie, token-identical warm resume) "
                        "when a waiter exceeds THRESHOLD_S and the "
                        "queue-wait burn rate reaches BURN_LIMIT "
                        "(default 1.0); empty disables preemption")
    p.add_argument("--draft-model", default="",
                   help="draft model dir (HF snapshot) or preset name "
                        "(with --random-init) enabling speculative "
                        "decoding: the draft runs inside the continuous "
                        "batcher's paged batch, K-query verify windows "
                        "with accept/rollback at the window boundary, "
                        "for every slot-served request (greedy, sampled "
                        "and repetition-penalty alike; tokens are the "
                        "target's own). Must share the target's "
                        "vocabulary; needs --batch-slots > 0")
    p.add_argument("--speculation-depth", type=int, default=4,
                   help="draft tokens proposed per verify window")
    p.add_argument("--tls-cert-file", default="",
                   help="serve completions over TLS (PEM cert; key via "
                        "--tls-key-file)")
    p.add_argument("--tls-key-file", default="")
    p.add_argument("--debug-token-file", default="",
                   help="file holding the bearer token required on "
                        "/debug/* (spans, flight recorder, SLO); empty "
                        "leaves them open")
    p.add_argument("--flight-capacity", type=int, default=512,
                   help="flight-recorder ring size (scheduler "
                        "decisions kept for /debug/flightrecorder); "
                        "long load runs raise it so post-mortems and "
                        "the ?since= cursor don't lose events between "
                        "polls")
    p.add_argument("--span-sample-every", type=int, default=1,
                   help="record spans for 1 in N traces (head "
                        "sampling, whole traces kept or dropped "
                        "together; 1 = record all). Sampled-out "
                        "requests still count in every metric — only "
                        "span recording is gated")
    p.add_argument("--slo", action="append", default=[],
                   metavar="NAME:THRESHOLD_S:OBJECTIVE",
                   help="SLO objective, repeatable (e.g. ttft:0.5:0.99 "
                        "= 99%% of requests see first token in 500ms); "
                        "names: ttft, tpot, queue_wait. Default: loose "
                        "built-ins (observability/slo.py)")
    args = p.parse_args(argv)
    if args.draft_model and args.batch_slots <= 0:
        raise SystemExit(
            "--draft-model requires the continuous batcher "
            "(--batch-slots > 0): the draft proposes inside the paged "
            "batch's verify windows, and no other route speculates"
        )
    # lint: allow[log-discipline] main() is the process entrypoint and owns root logging config
    logging.basicConfig(level=logging.INFO)
    if args.span_sample_every != 1:
        # process-global on purpose: the keep/drop verdict must agree
        # across every tracer in this process or ledgers shear mid-hop
        tracing.set_span_sampling(args.span_sample_every)

    from kubeinfer_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax
    import jax.numpy as jnp

    from kubeinfer_tpu.inference.config import PRESETS
    from kubeinfer_tpu.inference.engine import Engine
    from kubeinfer_tpu.inference.model import init_params

    dtype = {"auto": jnp.bfloat16, "bfloat16": jnp.bfloat16,
             "float32": jnp.float32}[args.dtype]
    mesh = None
    if args.tensor_parallel_size > 1 or args.sequence_parallel_size > 1:
        # GSPMD partitions the jitted forward over tp, and the SP
        # engine shard_maps prefill over sp. Built BEFORE the weights:
        # under tp every piece of the tree is placed on its shards as
        # it is made (``mesh=`` below), so the whole model never sits
        # on one device — at bf16 a 7B tree is 15 GB and one 16 GB
        # chip cannot hold it even for the moment before resharding.
        from kubeinfer_tpu.inference.sharding import make_inference_mesh

        mesh = make_inference_mesh(
            tp=args.tensor_parallel_size,
            sp=args.sequence_parallel_size, dp=1,
        )
    tp_mesh = mesh if args.tensor_parallel_size > 1 else None
    tokenizer = None
    if args.random_init:
        # --model may be a preset name or (when the lifecycle layer passes
        # a cache dir, e.g. the mock-download e2e flow) any path: fall
        # back to the CI-sized preset.
        cfg = PRESETS.get(args.model)
        if cfg is None:
            log.info("--random-init: %r is not a preset; using 'tiny'",
                     args.model)
            cfg = PRESETS["tiny"]
        try:
            cfg.check_serving(
                weight_dtype=args.weight_dtype, kv_dtype=args.kv_dtype,
                tp=args.tensor_parallel_size,
                sp=args.sequence_parallel_size,
                speculation=bool(args.draft_model),
            )
        except ValueError as e:
            raise SystemExit(str(e)) from None
        params = init_params(cfg, jax.random.PRNGKey(0), dtype=dtype,
                             weight_dtype=args.weight_dtype,
                             mesh=tp_mesh)
    else:
        from kubeinfer_tpu.inference.weights import load_pretrained

        params, cfg = load_pretrained(args.model, dtype=dtype,
                                      weight_dtype=args.weight_dtype,
                                      mesh=tp_mesh)
        tokenizer = _load_tokenizer(args.model)
    if args.weight_dtype == "int8" and args.sequence_parallel_size > 1:
        # the SP engine shard_maps with manual param_specs and has no
        # quantized-leaf path; refusing beats silently serving a
        # broken long-prompt route
        raise SystemExit(
            "--weight-dtype int8 does not compose with "
            "--sequence-parallel-size > 1 yet"
        )
    if args.max_model_len > 0:
        max_cache = args.max_model_len
    else:
        max_cache = cfg.max_position_embeddings

    sp_engine = None
    if args.sequence_parallel_size > 1:
        from kubeinfer_tpu.inference.sp_engine import SPEngine

        sp_engine = SPEngine(
            params, cfg, mesh, max_cache_len=max_cache,
            min_prompt=args.sp_min_prompt,
        )

    engine = Engine(params, cfg, max_cache_len=max_cache)
    spec_draft = None
    if args.draft_model:
        # placed by ContinuousEngine (replicated under tp: the draft is
        # small, and replication spares it the target's head-count
        # divisibility)
        if args.random_init:
            dcfg = PRESETS.get(args.draft_model)
            if dcfg is None:
                raise SystemExit(
                    f"--draft-model {args.draft_model!r} is not a preset "
                    "(with --random-init the draft must name one)"
                )
            dparams = init_params(dcfg, jax.random.PRNGKey(1), dtype=dtype)
        else:
            from kubeinfer_tpu.inference.weights import load_pretrained

            dparams, dcfg = load_pretrained(args.draft_model, dtype=dtype)
        spec_draft = (dparams, dcfg)
    continuous = None
    if args.batch_slots > 0:
        from kubeinfer_tpu.inference.batching import (
            ContinuousEngine, PreemptionPolicy,
        )

        preemption = None
        if args.preemption_slo:
            preemption = PreemptionPolicy.parse(args.preemption_slo)
        layout = None
        if args.tensor_parallel_size > 1:
            # the real --tensor-parallel path (the reference forwards
            # the flag to external vLLM, vllm.go:57-61; we own the
            # partition): reuse the (dp, tp, sp) mesh built above so
            # the batcher, the per-request engine, and the draft all
            # place onto the same devices
            from kubeinfer_tpu.inference.sharding import EngineLayout

            layout = EngineLayout(tp=args.tensor_parallel_size, mesh=mesh)
        continuous = ContinuousEngine(
            params, cfg, n_slots=args.batch_slots,
            cache_len=min(max_cache, 4096),
            prefill_chunk_blocks=args.prefill_chunk_blocks,
            preemption=preemption,
            layout=layout,
            spec_draft=spec_draft,
            spec_k=args.speculation_depth,
            kv_dtype=args.kv_dtype,
            weight_dtype=args.weight_dtype,
            queue_depth_limit=args.queue_depth_limit,
            migration_chunk_blocks=args.migration_chunk_blocks,
            flight_capacity=args.flight_capacity,
        )
        continuous.start()
    debug_token = ""
    if args.debug_token_file:
        with open(args.debug_token_file, encoding="utf-8") as f:
            debug_token = f.read().strip()
    slo = None
    if args.slo:
        from kubeinfer_tpu.observability.slo import SLOObjective

        slo = SLOMonitor(
            objectives=tuple(SLOObjective.parse(s) for s in args.slo)
        )
    srv = InferenceServer(
        engine, model_id=args.model, tokenizer=tokenizer,
        host=args.host, port=args.port, continuous=continuous,
        sp=sp_engine,
        tls_cert=args.tls_cert_file, tls_key=args.tls_key_file,
        token=debug_token, slo=slo,
        kv_export_budget_mb=args.kv_export_budget_mb,
    ).start()
    log.info("native inference server on %s:%d (model %s)",
             args.host, srv.port, args.model)

    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda *_: stop.set())
    signal.signal(signal.SIGINT, lambda *_: stop.set())
    while not stop.is_set():
        stop.wait(0.5)
    srv.stop()
    if continuous is not None:
        continuous.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
