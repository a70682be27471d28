# Developer entrypoints (reference Makefile parity: test / test-e2e /
# lint / build / run targets, Makefile:44-250).

PY ?= python
# Tests run on a forced virtual CPU mesh (tests/conftest.py); bench and
# chip-smoke need a TPU and fail without one.

.PHONY: all native test test-fast test-chaos test-e2e bench bench-quick \
        bench-full chip-smoke lint sanitize verify-flight trace-demo \
        envelope run-manager run-agent docker-build clean

all: native lint test-fast

native:
	$(MAKE) -C native

test: native
	$(PY) -m pytest tests/ -q -x --ignore=tests/test_process_e2e.py

# Developer default: skip the explicitly slow-marked compile-heaviest
# tests (pyproject markers; ~6min of jit compiles). CI and pre-round
# gates run the full `test` tier.
test-fast: native
	$(PY) -m pytest tests/ -q -x --ignore=tests/test_process_e2e.py -m "not slow"

test-e2e: native
	$(PY) -m pytest tests/test_process_e2e.py tests/test_e2e_slice.py -q -x

# Resilience tier: RetryPolicy/breaker units + deterministic
# fault-injection scenarios (tests/test_chaos.py). Part of `test` too;
# this target is the focused loop when iterating on failure handling.
# Chaos-marked tests arm KUBEINFER_RACECHECK=2 via conftest, so the
# lockset race detector, lock-order graph, AND the lifecycle
# ProtocolMonitor (analysis/protocol.py) run as teardown oracles.
test-chaos:
	$(PY) -m pytest tests/ -q -x -m chaos

# Concurrency sanitizer (docs/ANALYSIS.md): 8 seeded deterministic
# schedules per fuzz scenario with the lockset detector and the live
# protocol monitor armed, then the chaos tier under the same oracles.
# Bounded: the fuzzer serializes tiny in-process scenarios (~seconds),
# no jit compiles involved.
sanitize:
	$(PY) -m kubeinfer_tpu.analysis.schedfuzz --schedules 8
	$(PY) -m pytest tests/ -q -x -m chaos

bench: native
	$(PY) bench.py

bench-quick: native
	$(PY) bench.py --quick

bench-full: native
	$(PY) bench.py --full

# Proof of life on one TPU v5e: solver, serving kernels and the inference
# server at qwen2-7b widths, each checked against its reference. Exits
# non-zero without a TPU. `$(PY) chip_smoke.py --chips 4` is the
# tensor-parallel comparison on a four-chip host.
chip-smoke:
	$(PY) chip_smoke.py

# Offline leg of the lifecycle verifier: replay the newest bench flight
# dump (bench.py serving_trace_bench writes bench_flight.json) against
# the protocol spec. Exit 1 = illegal transition (both event sites
# reported), exit 2 = no dump yet — run `make bench` first.
verify-flight:
	@f=$$(ls -t bench_flight*.json 2>/dev/null | head -1); \
	if [ -z "$$f" ]; then \
		echo "verify-flight: no bench_flight*.json (run 'make bench' first)" >&2; \
		exit 2; \
	fi; \
	echo "replaying $$f"; \
	$(PY) -m kubeinfer_tpu.analysis protocol "$$f"

# Syntax (compileall) + invariant analyzer (kubeinfer_tpu/analysis/):
# jit purity, static shapes under jit, lock discipline. Exits non-zero
# on any unsuppressed `file:line rule message` finding; the same scan
# is a tier-1 gate via tests/test_static_analysis.py.
lint:
	$(PY) -m compileall -q kubeinfer_tpu tests scripts bench.py __graft_entry__.py chip_smoke.py
	$(PY) -m kubeinfer_tpu.analysis kubeinfer_tpu tests scripts bench.py __graft_entry__.py chip_smoke.py

# One traced serving request on the virtual CPU mesh; writes a
# Perfetto-loadable Chrome trace JSON (docs/OBSERVABILITY.md walks the
# span model). The module forces JAX_PLATFORMS=cpu itself.
trace-demo:
	JAX_PLATFORMS=cpu $(PY) -m kubeinfer_tpu.observability

# Fleet-envelope smoke (envelope observatory PR): the tiny-preset
# open-loop sweep + knee detection + joined-ledger pins, seconds on the
# virtual CPU mesh. Same tests run in tier-1 via the auto-applied
# observability marker; the O(1e5)-request full sweep is slow-marked
# and excluded here.
envelope:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_observability_envelope.py \
		-q -m "not slow"

# local quickstart helpers (see README)
run-manager:
	$(PY) -m kubeinfer_tpu.manager --tick-interval 0.5

run-agent:
	STORE_ADDR=http://127.0.0.1:18080 KUBEINFER_DOWNLOADER=mock \
	MODEL_PATH=/tmp/kubeinfer-models NODE_NAME=$${NODE_NAME:-node-0} \
	$(PY) -m kubeinfer_tpu.agent

docker-build:
	docker build -t kubeinfer-tpu:latest .

clean:
	$(MAKE) -C native clean
	find . -name __pycache__ -type d -exec rm -rf {} +
