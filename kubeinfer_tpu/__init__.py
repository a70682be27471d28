"""kubeinfer_tpu — a TPU-native distributed AI inference job scheduler.

A brand-new framework with the capabilities of Moore-Z/kubeinfer (a Kubernetes
operator scheduling distributed LLM inference workloads; see
/root/reference), re-designed TPU-first:

- ``api``          — job/service resource types (parity with reference
                     api/v1/llmservice_types.go:25-98, plus ``schedulerPolicy``).
- ``solver``       — the accelerated scheduling core: batched jobs x nodes
                     feasibility/cost tensors solved under ``jax.jit``
                     (the component the reference lacks entirely; placement
                     there is delegated to kube-scheduler,
                     internal/controller/llmservice_controller.go:193-312).
- ``parallel``     — device-mesh sharding of the solver (pjit/shard_map) for
                     multi-chip scale-out over ICI/DCN.
- ``controlplane`` — in-memory versioned object store with watches and
                     Leases: the coordination bus (the reference uses the
                     K8s API server for this role) and the envtest-equivalent
                     test control plane.
- ``controller``   — batching reconciler + pluggable SchedulerBackend
                     (parity with internal/controller/llmservice_controller.go,
                     re-architected from per-CR serial to per-tick batched).
- ``agent``        — lease election, coordinator/follower model distribution,
                     inference-runtime lifecycle, node-state reporting
                     (parity with cmd/agent + internal/agent/*).
- ``models``       — learned placement cost model (flax) usable as a solver
                     scoring policy; the flagship jittable model.
- ``metrics``      — Prometheus collectors (parity with pkg/metrics/metrics.go
                     plus solve-latency/placement-quality instrumentation).
- ``native``       — C++ tier: serial greedy baseline scorer (the >=100x
                     comparison baseline) and fast host-side helpers, via
                     ctypes.
- ``utils``        — clock abstraction (real + simulated), logging, env config.
"""

from kubeinfer_tpu.observability import tracing as _tracing

__version__ = "0.1.0"

# when this process first imported the package: where the server's
# ``import`` start-up phase begins (inference/server.py, ``main``)
IMPORTED_AT = _tracing.now()
