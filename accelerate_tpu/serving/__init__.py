"""Continuous-batching serving over the compiled generation stack.

Public surface:

* :class:`ServingEngine` — slot-based decode service running a FIXED set
  of compiled programs after warmup (one ``prefill_chunk`` executable of
  shape ``[1, prefill_chunk]`` for every prompt length and one decode
  tick over a pool of KV pages; one ``restore_prefix`` copy only with an
  external prefix cache); requests
  join and leave the batch mid-flight with zero recompiles, and admission
  is interleaved — at most ``prefill_chunks_per_tick`` chunk calls
  between decode ticks, so long prompts never stall active streams.
* :class:`Request` / :class:`RequestStatus` — the submit handle: streamed
  tokens, ``result()``, cancellation, timestamps; chunk-admitted requests
  pass through ``PREFILLING`` while their prompt streams into KV.
* :class:`ServingStats` — TTFT/queue-wait/throughput/occupancy counters
  plus the chunked-prefill split (chunk count/ms, backlog, prefix-cache
  hit rate/bytes) — ``engine.serving_metrics()``,
  ``Accelerator.log(include_serving=True)``.
* :class:`AdmissionQueue` / :class:`QueueFull` / :class:`QueueClosed` /
  :class:`SlotScheduler` — the bounded admission layer (FCFS, or a
  priority queue when built with a ``rank_fn``) and slot free-list.
* :class:`PriorityPolicy` / :class:`TokenBucket` /
  :class:`TenantRateLimiter` / :class:`FairShareAdmission` /
  :class:`AutoscaleConfig` / :class:`FleetAutoscaler` — the SLO control
  plane (``serving.control``): priority classes acted on by admission
  order and preemption victim selection, per-tenant rate limits and
  weighted fair share at the gateway, and supervisor-driven replica
  autoscaling over retained factories. See
  ``docs/usage_guides/slo_control.md``.
* :class:`PrefixCache` — byte-bounded LRU of chunk-aligned prefix KV
  blocks keyed by token-prefix hash chains (shared system prompts skip
  their prefill FLOPs).
* :class:`ReplicaSet` / :class:`ReplicaState` / :class:`FleetRequest` —
  N engine replicas behind one submit surface: least-loaded routing,
  per-replica health, and failover that resumes a dead replica's
  in-flight streams on a healthy one (``prompt + tokens_emitted``) with
  zero duplicated or lost tokens.
* :class:`SlicePlan` / :class:`SliceExec` — mesh-sliced tensor
  parallelism: carve ``jax.devices()`` into disjoint ``tp``-wide slices,
  each one replica of a ``ReplicaSet.from_mesh`` fleet serving sharded
  params / KV / adapter bank through the same warm executables
  (``ServingEngine(tp=...)`` for a single slice).
* :class:`ServingGateway` / :class:`GatewayConfig` /
  :class:`GatewayStats` — stdlib-only HTTP front end: ``POST
  /v1/completions`` (JSON + SSE streaming), ``/healthz`` / ``/readyz`` /
  ``/metrics`` (Prometheus text with latency histograms) /
  ``/debug/trace`` (Chrome-trace JSON), backpressure mapped to HTTP
  status codes — including 429 load shedding on *projected* KV-page
  pressure with a drain-rate-derived ``Retry-After`` — and graceful
  drain on SIGTERM.
* :class:`FleetSupervisor` / :class:`HungReplicaError` — the
  self-healing control loop: a heartbeat watchdog that fences replicas
  hung without an error, auto-restart of FAILED replicas through the
  fleet's retained engine factories (re-warm, adapter re-registration,
  exponential backoff), and a crash-loop circuit breaker that parks
  flapping replicas in ``CRASH_LOOP``. See
  ``docs/usage_guides/fault_tolerance.md``.
* :class:`ChaosSchedule` / :class:`ChaosKilled` — deterministic fault
  injection keyed on decode ticks (scripted kill / hang / slow-tick),
  the harness the fault-tolerance tests and ``bench.py
  extra.serving.chaos`` drive the supervisor with.

Every request carries a ``trace_id`` (gateway-minted or the client's
``X-Request-Id``): engines drop per-edge spans — queue wait, prefill
chunks, decode-tick ITL, preemptions, failover hops — into bounded
lock-light ring buffers (``accelerate_tpu.observability``), exported as
Chrome-trace/Perfetto JSON via ``engine.dump_trace``, ``GET
/debug/trace?id=``, or ``accelerate-tpu serve --trace-dir``; a
per-replica flight recorder keeps the last N structured events and
auto-dumps a postmortem into ``ReplicaSet.failover_reports`` when a
replica dies. See ``docs/usage_guides/observability.md``.

Multi-tenant LoRA serving (``accelerate_tpu.adapters``) plugs in through
the same surface: construct the engine with an
:class:`~..adapters.registry.AdapterBank`, register named adapters at
runtime (zero recompiles — the bank is a regular traced argument), and
pass ``adapter="name"`` to ``submit`` / the gateway's JSON body. See
``docs/usage_guides/lora.md``.

See ``docs/usage_guides/serving.md``.
"""

from .chaos import ChaosKilled, ChaosSchedule
from .control import (
    AutoscaleConfig,
    FairShareAdmission,
    FleetAutoscaler,
    PriorityPolicy,
    TenantRateLimiter,
    TokenBucket,
)
from .engine import ServingEngine
from .gateway import GatewayConfig, ServingGateway
from .mesh_exec import SliceExec, SlicePlan
from .metrics import GatewayStats, ServingStats
from .request import Request, RequestStatus
from .router import FleetRequest, ReplicaSet, ReplicaState
from .scheduler import (
    AdmissionQueue,
    PrefixCache,
    QueueClosed,
    QueueFull,
    SlotScheduler,
)
from .supervisor import FleetSupervisor, HungReplicaError

__all__ = [
    "ServingEngine",
    "ServingStats",
    "GatewayStats",
    "Request",
    "RequestStatus",
    "AdmissionQueue",
    "PrefixCache",
    "QueueFull",
    "QueueClosed",
    "SlotScheduler",
    "ReplicaSet",
    "ReplicaState",
    "FleetRequest",
    "SlicePlan",
    "SliceExec",
    "ServingGateway",
    "GatewayConfig",
    "PriorityPolicy",
    "TokenBucket",
    "TenantRateLimiter",
    "FairShareAdmission",
    "AutoscaleConfig",
    "FleetAutoscaler",
    "FleetSupervisor",
    "HungReplicaError",
    "ChaosSchedule",
    "ChaosKilled",
]
