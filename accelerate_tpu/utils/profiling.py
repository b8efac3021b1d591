"""Profiler session wrapping ``jax.profiler``.

Parity with the reference's torch.profiler integration (reference:
utils/dataclasses.py:400-503 builds torch.profiler.profile;
accelerator.py:3423-3480 exports per-rank Chrome traces). On TPU the
profiler of record is jax.profiler: XPlane traces viewable in
TensorBoard/Perfetto, capturing XLA ops, HBM usage, and ICI traffic.
"""

from __future__ import annotations

import os
import threading
import time
from typing import TYPE_CHECKING, Optional

if TYPE_CHECKING:  # pragma: no cover
    from .dataclasses import ProfileKwargs


class CompileWatcher:
    """Counts XLA compilations (and compilation-cache hits) in-process.

    Wraps the ``jax.monitoring`` listener pair the zero-recompile test
    suites used inline: the event-duration listener fires once per
    compile/trace, the plain event listener carries compilation-cache
    hits. Promoted here so the serving engine's flight recorder, the
    gateway's ``/metrics`` endpoint, and the tests all share one
    accounting of "did anything recompile".

    ``events`` lists ONLY duration-listener matches — exactly what the
    old inline listeners collected — so a zero-recompile pin is simply
    ``assert not watcher.events``. Cache hits are counted separately
    (a hit is the healthy steady state, not a recompile).

    Thread-safe; ``start``/``stop`` are idempotent and ``stop`` always
    unregisters (context-manager protocol supported)::

        with CompileWatcher() as w:
            serve_a_round()
        assert not w.events, f"recompiled: {w.events}"

    ``on_event(event_name, duration_s_or_None)`` is invoked outside the
    lock for every recorded event (compiles with their duration, cache
    hits with ``None``) — the engine uses it to mirror compile events
    into its flight recorder. Callback exceptions are swallowed: the
    listener runs inside XLA's compile path.
    """

    def __init__(self, include=("compile", "trace"), on_event=None):
        self._include = tuple(include)
        self._on_event = on_event
        self._lock = threading.Lock()
        self._events: list[tuple] = []   # (name, duration_s) compiles only
        self._cache_hits = 0
        self._registered = False
        self._dur_listener = None
        self._evt_listener = None

    def _matches(self, event: str) -> bool:
        return any(s in event for s in self._include)

    def _record(self, event: str, duration_s: Optional[float]) -> None:
        with self._lock:
            if duration_s is None:
                self._cache_hits += 1
            else:
                self._events.append((event, duration_s))
        cb = self._on_event
        if cb is not None:
            try:
                cb(event, duration_s)
            except Exception:
                pass

    def start(self) -> "CompileWatcher":
        """Register the listeners (no-op if already registered)."""
        with self._lock:
            if self._registered:
                return self
            self._registered = True

            def on_duration(event, duration_s, **kw):
                if self._matches(event):
                    self._record(event, float(duration_s))

            def on_plain(event, **kw):
                if "cache_hit" in event:
                    self._record(event, None)

            self._dur_listener = on_duration
            self._evt_listener = on_plain
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_plain)
        return self

    def stop(self) -> None:
        """Unregister the listeners (no-op if not registered)."""
        with self._lock:
            if not self._registered:
                return
            self._registered = False
            dur, evt = self._dur_listener, self._evt_listener
            self._dur_listener = self._evt_listener = None
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(dur)
        jax.monitoring.unregister_event_listener(evt)

    def __enter__(self) -> "CompileWatcher":
        return self.start()

    def __exit__(self, *exc) -> bool:
        self.stop()
        return False

    def reset(self) -> None:
        """Zero the counters without unregistering (post-warmup baseline)."""
        with self._lock:
            self._events = []
            self._cache_hits = 0

    @property
    def events(self) -> list:
        """Names of compile/trace events seen, in order (empty = no
        recompiles since ``start``/``reset``)."""
        with self._lock:
            return [name for name, _ in self._events]

    @property
    def durations(self) -> list:
        """``(event_name, duration_s)`` pairs for every compile seen."""
        with self._lock:
            return list(self._events)

    @property
    def total(self) -> int:
        """Number of compile/trace events seen."""
        with self._lock:
            return len(self._events)

    @property
    def cache_hits(self) -> int:
        """Compilation-cache hit events seen (plain-event listener)."""
        with self._lock:
            return self._cache_hits

    def counts(self) -> dict:
        """Per-event-name compile counts (``/metrics`` export)."""
        out: dict = {}
        with self._lock:
            for name, _ in self._events:
                out[name] = out.get(name, 0) + 1
        return out

    def summary(self) -> dict:
        """Scalar snapshot: total compiles, total compile seconds, hits."""
        with self._lock:
            return {
                "compile_events": len(self._events),
                "compile_secs": round(sum(d for _, d in self._events), 6),
                "compilation_cache_hits": self._cache_hits,
            }


class PipelineStats:
    """Step-time breakdown counters for the host input pipeline.

    Thread-safe: the prefetch worker records ``stage_ms`` (collate +
    host→device staging) while the training thread records ``data_wait_ms``
    (time the step loop blocked waiting for a batch) and the queue depth it
    observed. Near-zero ``data_wait_ms`` with a busy device means the
    pipeline is hidden behind compute; sustained waits mean the host is the
    bottleneck (raise ``prefetch_size``/``num_workers`` or speed up the
    producer).
    """

    def __init__(self):
        self._lock = threading.Lock()
        self.reset()

    def reset(self):
        """Zero every counter (e.g. between measurement windows)."""
        with self._lock:
            self._wait_ms_sum = 0.0
            self._wait_ms_max = 0.0
            self._wait_ms_last = 0.0
            self._wait_count = 0
            self._stage_ms_sum = 0.0
            self._stage_ms_max = 0.0
            self._stage_ms_last = 0.0
            self._stage_count = 0
            self._depth_sum = 0
            self._depth_count = 0

    def record_wait(self, ms: float):
        """One consumer-side blocking wait for the next staged batch."""
        with self._lock:
            self._wait_ms_sum += ms
            self._wait_ms_max = max(self._wait_ms_max, ms)
            self._wait_ms_last = ms
            self._wait_count += 1

    def record_stage(self, ms: float):
        """One producer-side collate+stage of a batch."""
        with self._lock:
            self._stage_ms_sum += ms
            self._stage_ms_max = max(self._stage_ms_max, ms)
            self._stage_ms_last = ms
            self._stage_count += 1

    def record_depth(self, depth: int):
        """Queue depth observed by the consumer right after a get."""
        with self._lock:
            self._depth_sum += int(depth)
            self._depth_count += 1

    def summary(self) -> dict:
        """Scalar snapshot suitable for ``Accelerator.log``/tracking payloads."""
        with self._lock:
            waits = max(1, self._wait_count)
            stages = max(1, self._stage_count)
            depths = max(1, self._depth_count)
            return {
                "data_wait_ms": round(self._wait_ms_sum / waits, 3),
                "data_wait_ms_last": round(self._wait_ms_last, 3),
                "data_wait_ms_max": round(self._wait_ms_max, 3),
                "stage_ms": round(self._stage_ms_sum / stages, 3),
                "stage_ms_last": round(self._stage_ms_last, 3),
                "stage_ms_max": round(self._stage_ms_max, 3),
                "queue_depth": round(self._depth_sum / depths, 3),
                "batches_waited": self._wait_count,
                "batches_staged": self._stage_count,
            }

    def merge(self, other: "PipelineStats") -> "PipelineStats":
        """Fold another stats object into this one (multi-loader aggregation)."""
        with other._lock:
            o = (other._wait_ms_sum, other._wait_ms_max, other._wait_ms_last, other._wait_count,
                 other._stage_ms_sum, other._stage_ms_max, other._stage_ms_last, other._stage_count,
                 other._depth_sum, other._depth_count)
        with self._lock:
            self._wait_ms_sum += o[0]
            self._wait_ms_max = max(self._wait_ms_max, o[1])
            self._wait_ms_last = o[2] or self._wait_ms_last
            self._wait_count += o[3]
            self._stage_ms_sum += o[4]
            self._stage_ms_max = max(self._stage_ms_max, o[5])
            self._stage_ms_last = o[6] or self._stage_ms_last
            self._stage_count += o[7]
            self._depth_sum += o[8]
            self._depth_count += o[9]
        return self

    class _Timer:
        __slots__ = ("_record", "_t0")

        def __init__(self, record):
            self._record = record

        def __enter__(self):
            self._t0 = time.perf_counter()
            return self

        def __exit__(self, exc_type, *exc):
            # An exhausted/failed pull is not a batch wait — don't count it.
            if exc_type is None:
                self._record((time.perf_counter() - self._t0) * 1e3)
            return False

    def time_wait(self):
        """Context manager timing a consumer wait into ``data_wait_ms``."""
        return self._Timer(self.record_wait)

    def time_stage(self):
        """Context manager timing a producer stage into ``stage_ms``."""
        return self._Timer(self.record_stage)


class ProfileSession:
    """Context manager driving a jax.profiler trace with an optional
    wait/warmup/active schedule (the reference's schedule_option).

    Usage::

        with ProfileSession(ProfileKwargs(), log_dir="/tmp/trace") as prof:
            for batch in loader:
                train_step(...)
                prof.step()
    """

    def __init__(self, kwargs: "ProfileKwargs", log_dir: Optional[str] = None,
                 pipeline_stats: Optional[PipelineStats] = None,
                 serving_stats=None, gateway_stats=None, tracer=None):
        self.kwargs = kwargs
        self.log_dir = log_dir or kwargs.output_trace_dir or "./jax_trace"
        sched = kwargs.schedule_option or {}
        self.wait = int(sched.get("wait", 0)) + int(sched.get("skip_first", 0))
        self.warmup = int(sched.get("warmup", 0))
        self.active = int(sched.get("active", 0)) or None  # None = whole block
        self._step = 0
        self._tracing = False
        # Host-side step breakdowns ride along with the device trace: pass
        # the stats objects shared with the dataloaders / serving engines
        # (or let callers attach them later via attach_pipeline_stats /
        # attach_serving_stats).
        self.pipeline_stats = pipeline_stats
        self.serving_stats = serving_stats
        self.gateway_stats = gateway_stats
        # Host-side span sink (observability.Tracer): each step() emits a
        # "train_step" span in the same Chrome-trace format the serving
        # engine uses, so a training timeline and a serving timeline can
        # be merged into one Perfetto view.
        self.tracer = tracer
        self._last_step_t: Optional[float] = None

    def _should_trace(self) -> bool:
        if self.active is None:
            return True
        start = self.wait + self.warmup
        return start <= self._step < start + self.active

    def _start(self):
        import jax

        os.makedirs(self.log_dir, exist_ok=True)
        jax.profiler.start_trace(
            self.log_dir,
            create_perfetto_link=self.kwargs.create_perfetto_link,
            create_perfetto_trace=self.kwargs.create_perfetto_trace,
        )
        self._tracing = True

    def _stop(self):
        import jax

        if self._tracing:
            jax.profiler.stop_trace()
            self._tracing = False
            if self.kwargs.on_trace_ready is not None:
                self.kwargs.on_trace_ready(self)

    def __enter__(self):
        if self._should_trace():
            self._start()
        self._last_step_t = time.monotonic()
        return self

    def attach_pipeline_stats(self, stats: PipelineStats):
        """Attach input-pipeline counters (``data_breakdown()`` and the
        ``train_step`` span's ``args`` read them)."""
        self.pipeline_stats = stats
        return self

    def attach_serving_stats(self, stats):
        """Attach serving-engine counters (``serving.metrics.ServingStats``)
        for ``serving_breakdown()``."""
        self.serving_stats = stats
        return self

    def attach_gateway_stats(self, stats):
        """Attach HTTP gateway counters (``serving.metrics.GatewayStats``)
        for ``gateway_breakdown()``."""
        self.gateway_stats = stats
        return self

    def attach_tracer(self, tracer):
        """Attach an ``observability.Tracer`` so every ``step()`` emits a
        ``train_step`` span (step-to-step wall time, with the input
        pipeline's data-wait breakdown in ``args``)."""
        self.tracer = tracer
        self._last_step_t = time.monotonic()
        return self

    def step(self):
        """Advance the schedule (reference: torch profiler .step())."""
        if self.tracer is not None:
            now = time.monotonic()
            if self._last_step_t is not None:
                args: dict = {"step": self._step}
                if self.pipeline_stats is not None:
                    s = self.pipeline_stats.summary()
                    args["data_wait_ms"] = s["data_wait_ms_last"]
                    args["stage_ms"] = s["stage_ms_last"]
                self.tracer.emit("train_step", self._last_step_t,
                                 now - self._last_step_t, cat="training",
                                 args=args)
            self._last_step_t = now
        self._step += 1
        should = self._should_trace()
        if should and not self._tracing:
            self._start()
        elif not should and self._tracing:
            self._stop()

    def data_breakdown(self) -> dict:
        """Latest input-pipeline breakdown (data_wait_ms/stage_ms/queue_depth);
        empty when no stats object is attached."""
        if self.pipeline_stats is None:
            return {}
        return self.pipeline_stats.summary()

    def serving_breakdown(self) -> dict:
        """Latest serving-engine breakdown (ttft_ms/decode_tokens_per_sec/
        slot_occupancy, prefill_chunks/prefill_backlog,
        prefix_cache_hit_rate, …); empty when no serving stats are
        attached."""
        if self.serving_stats is None:
            return {}
        return self.serving_stats.summary()

    def gateway_breakdown(self) -> dict:
        """Latest HTTP-gateway breakdown (http_requests/http_429/streams/
        tokens_streamed, …); empty when no gateway stats are attached."""
        if self.gateway_stats is None:
            return {}
        return self.gateway_stats.summary()

    def __exit__(self, *exc):
        self._stop()
        return False


def annotate(name: str):
    """Named span on the jax profiler's host plane (a bare
    ``jax.profiler.TraceAnnotation``; name it ``atpu:<what>`` like the
    serving tracer's spans, so ``chipbench.host_spans`` finds it)."""
    import jax

    return jax.profiler.TraceAnnotation(name)

