"""repro.serve — continuous-batching inference on top of the paged-KV
model interface (Model.init_paged_cache / Model.paged_step).

  engine.Engine        one fused mixed prefill+decode call per step —
                       or N decode steps per dispatch entirely on
                       device (steps_per_dispatch: on-device sampling,
                       stop conditions, packed (B, N) token readback);
                       pipelined dispatch; pins to a mesh slice's lead
                       device
  kv_cache             block pool allocator + per-sequence block tables;
                       sliding-window block reclamation
  scheduler            FCFS policy with a prefill-token budget; RequestQueue
  router               token-weighted replica placement over Topology axes
  dispatcher           ServeCluster: one Engine per fast-fabric device
                       slice + worker threads; the slow layer carries
                       only admission/results/metrics
  telemetry            metrics registry (counters/gauges/histograms with
                       labels), per-request lifecycle tracing (TTFT /
                       TPOT / e2e histograms), host spans in the JAX
                       profiler's trace, JSONL snapshot export
  faults               replica lifecycle states, health/retry policy,
                       deterministic chaos-injection plans (the
                       dispatcher's fault-tolerance knobs)
"""
from repro.serve.dispatcher import ServeCluster
from repro.serve.engine import Engine, EngineConfig, RequestResult
from repro.serve.faults import (FaultAction, FaultInjected, FaultPlan,
                                HealthConfig, NoLiveReplicas, Overloaded,
                                ReplicaKilled, ReplicaState, RetryPolicy)
from repro.serve.kv_cache import (BlockAllocator, PagedKVCache,
                                  StateSlotAllocator)
from repro.serve.router import Replica, ReplicaRouter
from repro.serve.scheduler import Request, RequestQueue, Scheduler
from repro.serve.telemetry import (Counter, Gauge, Histogram,
                                   JsonlMetricsWriter, LatencyHists,
                                   MetricsRegistry, SpanTracer, Telemetry,
                                   TraceBook)

__all__ = [
    "BlockAllocator", "Counter", "Engine", "EngineConfig", "FaultAction",
    "FaultInjected", "FaultPlan", "Gauge", "HealthConfig", "Histogram",
    "JsonlMetricsWriter", "LatencyHists", "MetricsRegistry",
    "NoLiveReplicas", "Overloaded", "PagedKVCache", "Replica",
    "ReplicaKilled", "ReplicaRouter", "ReplicaState", "Request",
    "RequestQueue", "RequestResult", "RetryPolicy", "Scheduler",
    "ServeCluster", "SpanTracer", "StateSlotAllocator", "Telemetry",
    "TraceBook",
]
