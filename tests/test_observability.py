"""Observability stack: tracing, flight recorder, CompileWatcher,
Prometheus lint, latency histograms, and fleet stats merging.

What is pinned here:

* TRACER SEMANTICS — per-thread drop-oldest rings stay bounded, clear()
  discards history without touching writers, disabled tracers cost one
  branch, trace_id filtering works, and chrome_trace()/dump() emit
  structurally valid Chrome-trace JSON (checked by validate_chrome_trace,
  which is itself tested against known-bad traces).
* FLIGHT RECORDER — bounded deque with a dropped counter, postmortem
  dump shape, tracer mirroring, JSON export.
* COMPILE WATCHER — the promoted zero-recompile probe: records real XLA
  compile events with durations, idempotent start/stop, reset between
  measurement windows, callback errors swallowed (the callback runs
  inside the XLA compile path).
* PROMETHEUS LINT — the validator accepts the gateway's exposition
  format and rejects each violation class (missing HELP/TYPE, duplicate
  families, non-cumulative or +Inf-less histograms, garbage samples).
* FLEET AGGREGATION — ServingStats.merge over an N-replica loop keeps
  counters monotone, sample buffers bounded, per-adapter tables and
  histograms intact.
* ENGINE INTEGRATION — a tracing-enabled engine serves exactly, emits
  per-request span chains, dumps a valid merged trace, keeps the
  zero-recompile steady state, and freezes a postmortem on kill().
* ONE TIMELINE — a ``tracer.span`` region is also an ``atpu:<name>``
  annotation on the jax profiler's host plane (nesting, the ``trace_id``
  stat, one line per thread; a disabled tracer leaves neither), and a
  high-rate category never evicts another category's records.
* LOOP PHASES — a chunked paged engine leaves every phase span of its
  loop, disjoint on the engine thread, and the always-on counters behind
  them (``host_us/*``, ``chunk_to_dispatch_ms``, ``emit_lag_ms``) reach
  ``summary()``, ``merge()``, ``reset()`` and a lint-clean ``/metrics``.
"""

import json
import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from accelerate_tpu.models.llama import LlamaConfig, LlamaForCausalLM  # noqa: E402
from accelerate_tpu.observability import (  # noqa: E402
    FlightRecorder,
    Tracer,
    clean_trace_id,
    lint_prometheus_text,
    merge_chrome_traces,
    new_trace_id,
    parse_sample_line,
    validate_chrome_trace,
)
from accelerate_tpu.observability.tracing import TRACE_ID_MAX_LEN  # noqa: E402
from accelerate_tpu.serving import (  # noqa: E402
    GatewayConfig,
    ServingEngine,
    ServingGateway,
    ServingStats,
)
from accelerate_tpu.serving.metrics import (  # noqa: E402
    HISTOGRAM_NAMES,
    HOST_INTERVALS,
    HOST_PHASES,
    LatencyHistogram,
)
from accelerate_tpu.utils.dataclasses import ProfileKwargs  # noqa: E402
from accelerate_tpu.utils.profiling import (  # noqa: E402
    CompileWatcher,
    ProfileSession,
)

EOS = 7


# ---------------------------------------------------------------------------
# trace ids
# ---------------------------------------------------------------------------
class TestTraceIds:
    def test_new_trace_id_shape_and_uniqueness(self):
        ids = {new_trace_id() for _ in range(64)}
        assert len(ids) == 64
        for tid in ids:
            assert len(tid) == 16
            assert clean_trace_id(tid) == tid  # round-trips its own ids

    def test_clean_accepts_reasonable_client_ids(self):
        for raw in ("abc", "a-b_c.d:e", "X" * TRACE_ID_MAX_LEN, "  padded  "):
            assert clean_trace_id(raw) == raw.strip()

    def test_clean_rejects_garbage(self):
        for raw in (None, 17, b"bytes", "", "   ", "X" * (TRACE_ID_MAX_LEN + 1),
                    "has space", "tab\tchar", "semi;colon", "sl/ash",
                    'quo"te', "new\nline"):
            assert clean_trace_id(raw) is None


# ---------------------------------------------------------------------------
# Tracer
# ---------------------------------------------------------------------------
class TestTracer:
    def test_emit_span_instant_ordering(self):
        tr = Tracer(capacity=64, name="t")
        tr.instant("first", trace_id="r1")
        with tr.span("work", trace_id="r1", args={"k": 1, "hits": 3}):
            pass
        tr.emit("manual", time.monotonic(), 0.001, trace_id="r2")
        evs = tr.events()
        assert [e[3] for e in evs] == ["first", "work", "manual"]
        # record layout: (tid, t0, dur, name, cat, trace_id, args)
        work = evs[1]
        assert work[2] > 0 and work[5] == "r1"
        assert work[6] == {"k": 1, "hits": 3}
        assert evs[0][2] is None  # instant has no duration

    def test_trace_id_filter(self):
        tr = Tracer(capacity=64)
        for i in range(6):
            tr.instant("e", trace_id="a" if i % 2 else "b")
        assert len(tr.events("a")) == 3
        assert len(tr.events("b")) == 3
        assert len(tr.events("missing")) == 0
        assert len(tr.events()) == 6

    def test_ring_bounded_drop_oldest(self):
        tr = Tracer(capacity=8)
        for i in range(30):
            tr.instant(f"e{i}")
        assert len(tr) == 8
        names = [e[3] for e in tr.events()]
        assert names == [f"e{i}" for i in range(22, 30)]  # newest survive

    def test_clear_discards_history(self):
        tr = Tracer(capacity=16)
        for _ in range(5):
            tr.instant("old")
        tr.clear()
        assert len(tr) == 0 and tr.events() == []
        tr.instant("new")
        assert [e[3] for e in tr.events()] == ["new"]

    def test_disabled_tracer_records_nothing(self):
        tr = Tracer(capacity=16, enabled=False)
        tr.instant("x")
        with tr.span("y"):
            pass
        assert len(tr) == 0

    def test_capacity_validation(self):
        with pytest.raises(ValueError):
            Tracer(capacity=0)

    def test_per_thread_rings_all_visible(self):
        tr = Tracer(capacity=64)
        barrier = threading.Barrier(4)

        def emitter(i):
            barrier.wait()
            for j in range(10):
                tr.instant(f"t{i}e{j}")

        threads = [threading.Thread(target=emitter, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        evs = tr.events()
        assert len(evs) == 40
        assert len({e[0] for e in evs}) == 4  # four distinct writer tids

    def test_chrome_trace_valid_and_typed(self):
        tr = Tracer(capacity=16, name="replica-0")
        tr.instant("hit", trace_id="r1", args={"chunk": 2})
        with tr.span("tick", trace_id="r1"):
            time.sleep(0.001)
        trace = tr.chrome_trace()
        assert validate_chrome_trace(trace) == []
        evs = trace["traceEvents"]
        meta = [e for e in evs if e["ph"] == "M"]
        assert meta and meta[0]["args"]["name"] == "replica-0"
        by_name = {e["name"]: e for e in evs if e["ph"] != "M"}
        assert by_name["hit"]["ph"] == "i"
        assert by_name["hit"]["args"] == {"chunk": 2, "trace_id": "r1"}
        assert by_name["tick"]["ph"] == "X" and by_name["tick"]["dur"] > 0

    def test_dump_roundtrip(self, tmp_path):
        tr = Tracer(capacity=16)
        tr.instant("x", trace_id="only")
        tr.instant("y", trace_id="other")
        path = tr.dump(str(tmp_path / "trace.json"), trace_id="only")
        with open(path) as f:
            loaded = json.load(f)
        assert validate_chrome_trace(loaded) == []
        names = [e["name"] for e in loaded["traceEvents"] if e["ph"] != "M"]
        assert names == ["x"]  # filtered dump

    def test_merge_chrome_traces_keeps_pid_lanes(self):
        a, b = Tracer(capacity=8, name="a"), Tracer(capacity=8, name="b")
        a.instant("ea")
        b.instant("eb")
        merged = merge_chrome_traces([a.chrome_trace(), b.chrome_trace()])
        assert validate_chrome_trace(merged) == []
        pids = {e["pid"] for e in merged["traceEvents"]}
        assert pids == {a.pid, b.pid} and a.pid != b.pid


class TestValidateChromeTrace:
    def test_rejects_known_bad_shapes(self):
        assert validate_chrome_trace({}) != []
        assert validate_chrome_trace({"traceEvents": "nope"}) != []
        bad_ph = {"traceEvents": [{"ph": "Z", "name": "x"}]}
        assert any("unknown ph" in p for p in validate_chrome_trace(bad_ph))
        missing = {"traceEvents": [{"ph": "i", "name": "x"}]}
        assert any("missing" in p for p in validate_chrome_trace(missing))
        bad_dur = {"traceEvents": [
            {"ph": "X", "name": "x", "pid": 1, "tid": 1, "ts": 0.0,
             "dur": -1.0}]}
        assert any("bad dur" in p for p in validate_chrome_trace(bad_dur))

    def test_accepts_metadata_only(self):
        trace = {"traceEvents": [
            {"name": "process_name", "ph": "M", "pid": 1, "tid": 0,
             "args": {"name": "r"}}]}
        assert validate_chrome_trace(trace) == []


# ---------------------------------------------------------------------------
# FlightRecorder
# ---------------------------------------------------------------------------
class TestFlightRecorder:
    def test_bounded_with_dropped_count(self):
        fr = FlightRecorder(capacity=4, name="r0")
        for i in range(10):
            fr.record("evt", i=i)
        assert len(fr) == 4
        snap = fr.snapshot()
        assert [e["i"] for e in snap] == [6, 7, 8, 9]
        dump = fr.dump()
        assert dump["dropped"] == 6
        assert dump["name"] == "r0" and dump["capacity"] == 4
        assert [e["kind"] for e in dump["events"]] == ["evt"] * 4
        assert fr.snapshot(last=2) == snap[-2:]

    def test_clear_resets(self):
        fr = FlightRecorder(capacity=2)
        for i in range(5):
            fr.record("e")
        fr.clear()
        assert len(fr) == 0 and fr.dump()["dropped"] == 0

    def test_capacity_validation(self):
        with pytest.raises(ValueError):
            FlightRecorder(capacity=0)

    def test_mirrors_into_tracer(self):
        tr = Tracer(capacity=16)
        fr = FlightRecorder(capacity=8, tracer=tr)
        fr.record("preemption", trace_id="r9", slot=2)
        evs = tr.events("r9")
        assert len(evs) == 1
        _, _, dur, name, cat, tid, args = evs[0]
        assert (name, cat, dur) == ("preemption", "flight", None)
        assert args["slot"] == 2

    def test_dump_json_handles_unserializable(self, tmp_path):
        fr = FlightRecorder(capacity=8)
        fr.record("fatal", error=RuntimeError("boom"))
        path = fr.dump_json(str(tmp_path / "black-box.json"))
        with open(path) as f:
            loaded = json.load(f)
        assert loaded["events"][0]["kind"] == "fatal"
        assert "boom" in loaded["events"][0]["error"]


# ---------------------------------------------------------------------------
# CompileWatcher
# ---------------------------------------------------------------------------
def _fresh_compile(c):
    """Force one real XLA compile (a fresh closure never hits the jit cache)."""
    f = jax.jit(lambda x: x * c + float(c))
    f(jnp.arange(4.0)).block_until_ready()


class TestCompileWatcher:
    def test_records_compile_events_with_durations(self):
        with CompileWatcher() as w:
            _fresh_compile(2.0)
        assert w.events, "a fresh jit must produce at least one compile event"
        assert len(w.events) == len(w.durations)
        assert all(d >= 0 for _, d in w.durations)
        assert w.total == len(w.events)
        s = w.summary()
        assert s["compile_events"] == len(w.events)
        assert s["compile_secs"] == pytest.approx(
            sum(d for _, d in w.durations), abs=1e-5)
        assert s["compilation_cache_hits"] == w.cache_hits
        assert w.counts()  # per-event-name breakdown non-empty

    def test_stop_detaches_listener(self):
        w = CompileWatcher()
        with w:
            _fresh_compile(3.0)
        before = len(w.events)
        assert before
        _fresh_compile(4.0)  # after stop: must not be observed
        assert len(w.events) == before

    def test_idempotent_start_stop_and_reset(self):
        w = CompileWatcher()
        w.start()
        w.start()  # second start registers nothing new
        _fresh_compile(5.0)
        n = len(w.events)
        assert n
        w.reset()  # zero the window without detaching
        assert w.events == [] and w.cache_hits == 0 and w.total == 0.0
        _fresh_compile(6.0)
        assert len(w.events) >= 1  # still listening after reset
        w.stop()
        w.stop()  # double-stop is a no-op

    def test_callback_fires_and_errors_are_swallowed(self):
        seen = []

        def cb(event, duration_s):
            seen.append((event, duration_s))
            raise RuntimeError("listener bug must not break compilation")

        with CompileWatcher(on_event=cb) as w:
            _fresh_compile(7.0)  # must not raise despite the bad callback
        assert w.events
        assert {e for e, _ in seen} >= set(w.events)


# ---------------------------------------------------------------------------
# Prometheus exposition lint
# ---------------------------------------------------------------------------
VALID_EXPO = """\
# HELP app_requests_total Requests served.
# TYPE app_requests_total counter
app_requests_total 42
# HELP app_latency_ms Request latency.
# TYPE app_latency_ms histogram
app_latency_ms_bucket{le="1.0"} 3
app_latency_ms_bucket{le="10.0"} 7
app_latency_ms_bucket{le="+Inf"} 9
app_latency_ms_sum 55.5
app_latency_ms_count 9
# HELP app_tokens_total Tokens by adapter.
# TYPE app_tokens_total counter
app_tokens_total{adapter="a"} 5
app_tokens_total{adapter="b"} 6
"""


class TestPromlint:
    def test_valid_body_passes(self):
        assert lint_prometheus_text(VALID_EXPO) == []

    def test_parse_sample_line(self):
        assert parse_sample_line("m 1.5") == ("m", {}, "1.5")
        name, labels, value = parse_sample_line(
            'hist_bucket{le="+Inf",route="/v1"} 9')
        assert name == "hist_bucket"
        assert labels == {"le": "+Inf", "route": "/v1"}
        assert value == "9"
        assert parse_sample_line("no value here!") is None

    @pytest.mark.parametrize("body,needle", [
        ("metric_without_help 1\n", "no # HELP"),
        ("# HELP m x\nm 1\n", "no # TYPE"),
        ("# HELP m x\n# TYPE m counter\n# HELP m again\n# TYPE m counter\nm 1\n",
         "duplicate"),
        ("# HELP m x\n# TYPE m counter\nm notanumber\n", "non-numeric"),
        ("# HELP m x\n# TYPE m wat\nm 1\n", "unknown type"),
        ("# HELP m x\n# TYPE m counter\nm 1\nm 2\n", "duplicate series"),
        ("# HELP h x\n# TYPE h histogram\n"
         'h_bucket{le="1.0"} 5\nh_bucket{le="2.0"} 3\n'
         'h_bucket{le="+Inf"} 5\nh_sum 1\nh_count 5\n', "not cumulative"),
        ("# HELP h x\n# TYPE h histogram\n"
         'h_bucket{le="1.0"} 5\nh_sum 1\nh_count 5\n', "+Inf"),
        ("# HELP h x\n# TYPE h histogram\n"
         'h_bucket{le="+Inf"} 5\nh_sum 1\nh_count 7\n', "_count"),
        ("# HELP h x\n# TYPE h histogram\n"
         'h_bucket{le="5.0"} 1\nh_bucket{le="1.0"} 1\n'
         'h_bucket{le="+Inf"} 1\nh_sum 1\nh_count 1\n', "out of order"),
    ])
    def test_each_violation_class_is_caught(self, body, needle):
        problems = lint_prometheus_text(body)
        assert any(needle in p for p in problems), (needle, problems)


# ---------------------------------------------------------------------------
# LatencyHistogram + ServingStats.merge (fleet aggregation)
# ---------------------------------------------------------------------------
class TestLatencyHistogram:
    def test_observe_and_cumulative_monotone(self):
        h = LatencyHistogram(bounds=(1.0, 10.0, 100.0))
        for v in (0.5, 5.0, 50.0, 500.0, 5.0):
            h.observe(v)
        cum = h.cumulative()
        assert cum == [(1.0, 1), (10.0, 3), (100.0, 4), ("+Inf", 5)]
        assert h.count == 5 and h.sum == pytest.approx(560.5)
        snap = h.snapshot()
        assert snap["count"] == 5 and snap["bounds"] == (1.0, 10.0, 100.0)

    def test_merge_and_copy_independent(self):
        a = LatencyHistogram(bounds=(1.0, 10.0))
        b = LatencyHistogram(bounds=(1.0, 10.0))
        a.observe(0.5)
        b.observe(5.0)
        c = a.copy()
        a.merge(b)
        assert a.count == 2 and a.cumulative()[-1] == ("+Inf", 2)
        assert c.count == 1  # copy unaffected by later merge


def _loaded_stats(i: int) -> ServingStats:
    """One replica's worth of plausible traffic, deterministic in i."""
    s = ServingStats()
    for j in range(3 + i):
        s.record_submit(queue_depth=j)
        s.record_admit(queue_wait_ms=1.0 + i, ttft_ms=10.0 * (i + 1))
        s.record_tick(active_slots=2, committed_tokens=4, max_slots=4,
                      seconds=0.002)
        s.record_prefill_chunk(ms=3.0, backlog=i)
    s.record_adapter_admit(f"tenant-{i % 2}", hit=bool(i % 2))
    s.record_adapter_tokens(f"tenant-{i % 2}", tokens=10 * (i + 1))
    return s


class TestServingStatsMerge:
    N = 5

    def test_counters_monotone_over_merge_loop(self):
        acc = ServingStats()
        prev = acc.summary()
        expected_admits = 0
        for i in range(self.N):
            acc.merge(_loaded_stats(i))
            expected_admits += 3 + i
            cur = acc.summary()
            # every pure counter only ever grows as replicas fold in
            for key in ("requests_submitted", "requests_admitted",
                        "decode_ticks", "decode_tokens", "prefill_chunks",
                        "adapter_requests", "adapter_tokens"):
                assert cur[key] >= prev[key], key
            assert cur["requests_admitted"] == expected_admits
            # histogram stays internally consistent after every merge
            for name, snap in acc.histograms().items():
                counts = [c for _, c in snap["cumulative"]]
                assert counts == sorted(counts), name
                assert snap["cumulative"][-1][0] == "+Inf"
            prev = cur
        # maxima are maxed, not summed
        assert prev["ttft_ms_max"] == pytest.approx(10.0 * self.N)
        assert prev["queue_wait_ms_max"] == pytest.approx(1.0 + self.N - 1)
        # each admit observed once into the fleet histograms
        hists = acc.histograms()
        assert hists["ttft_ms"]["count"] == expected_admits
        assert hists["queue_wait_ms"]["count"] == expected_admits
        assert set(hists) == set(HISTOGRAM_NAMES)

    def test_sample_buffers_stay_bounded(self):
        acc = ServingStats()
        per_replica = ServingStats.MAX_TTFT_SAMPLES // 2 + 100
        for i in range(4):
            s = ServingStats()
            for _ in range(per_replica):
                s.record_admit(queue_wait_ms=0.1, ttft_ms=float(i + 1))
            assert len(s._ttft_samples) <= ServingStats.MAX_TTFT_SAMPLES
            acc.merge(s)
            assert len(acc._ttft_samples) <= ServingStats.MAX_TTFT_SAMPLES
        # newest replica's samples won (drop-oldest across the merge loop)
        assert acc.summary()["ttft_ms_p50"] == pytest.approx(4.0)
        # but the sums still cover every admit ever recorded
        assert acc.summary()["requests_admitted"] == 4 * per_replica

    def test_per_adapter_survives_merge(self):
        acc = ServingStats()
        for i in range(self.N):
            acc.merge(_loaded_stats(i))
        per = acc.per_adapter()
        assert set(per) == {"tenant-0", "tenant-1"}
        # i in {0,2,4} -> tenant-0 misses; i in {1,3} -> tenant-1 hits
        assert per["tenant-0"] == {"requests": 3, "tokens": 10 + 30 + 50,
                                   "hits": 0, "misses": 3, "loads": 3,
                                   "evictions": 0}
        assert per["tenant-1"] == {"requests": 2, "tokens": 20 + 40,
                                   "hits": 2, "misses": 0, "loads": 0,
                                   "evictions": 0}
        summ = acc.summary()
        assert summ["adapter/tenant-0/requests"] == 3
        assert summ["adapters_tracked"] == 2


class TestCategoryRings:
    def test_a_busy_category_never_evicts_another(self):
        tr = Tracer(capacity=8)
        tr.instant("retire", trace_id="req-1")
        for i in range(100):
            tr.emit("sweep", float(i), 0.001, cat="phase")
        names = [e[3] for e in tr.events()]
        assert names.count("sweep") == 8 and "retire" in names
        assert [e[3] for e in tr.events("req-1")] == ["retire"]
        assert len(tr) == 9
        tr.clear()
        assert tr.events() == []


# ---------------------------------------------------------------------------
# Tracer -> jax profiler bridge: one region, two clocks
# ---------------------------------------------------------------------------
def _host_plane_lines(trace_dir):
    """``[[(name, start_ns, end_ns, stats)]]``: the ``atpu:*`` events of
    the profiler's host plane, one list per thread line that has any."""
    from jax.profiler import ProfileData

    files = sorted(trace_dir.rglob("*.xplane.pb"))
    assert files, "the profiler wrote no .xplane.pb"
    lines = []
    for plane in ProfileData.from_file(str(files[-1])).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                    dict(e.stats)) for e in line.events
                   if e.name.startswith("atpu:")]
            if evs:
                lines.append(evs)
    return lines


@pytest.fixture(scope="module")
def bridged(tmp_path_factory):
    """One profiler session (``host_tracer_level = 1``, as the benchmark
    sets it) around nested spans on two threads of an enabled tracer and
    a span of a disabled one."""
    trace_dir = tmp_path_factory.mktemp("bridge")
    on, off = Tracer(capacity=64), Tracer(capacity=64, enabled=False)

    def work(tag):
        for i in range(3):
            with on.span("outer", trace_id=f"{tag}-{i}"):
                with on.span("inner"):
                    time.sleep(0.001)

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        threads = [threading.Thread(target=work, args=(tag,))
                   for tag in ("a", "b")]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        with off.span("ghost", trace_id="never"):
            time.sleep(0.001)
    finally:
        jax.profiler.stop_trace()
    return on, off, _host_plane_lines(trace_dir)


class TestProfilerBridge:
    def test_spans_are_annotations_on_one_line_per_thread(self, bridged):
        _, _, lines = bridged
        assert len(lines) == 2
        for evs in lines:
            assert sorted(name for name, *_ in evs) == (
                ["atpu:inner"] * 3 + ["atpu:outer"] * 3)

    def test_nesting_and_trace_id_survive(self, bridged):
        _, _, lines = bridged
        tags = set()
        for evs in lines:
            outers = [e for e in evs if e[0] == "atpu:outer"]
            inners = [e for e in evs if e[0] == "atpu:inner"]
            for _, start, end, stats in inners:
                assert stats == {}            # no trace_id, no stat
                assert sum(o[1] <= start and end <= o[2]
                           for o in outers) == 1
            ids = [o[3]["trace_id"] for o in outers]
            assert len({i[0] for i in ids}) == 1    # one thread, one tag
            tags |= set(ids)
        assert tags == {f"{t}-{i}" for t in "ab" for i in range(3)}

    def test_the_same_regions_are_ring_records(self, bridged):
        on, _, _ = bridged
        evs = on.events()
        assert sorted(e[3] for e in evs) == ["inner"] * 6 + ["outer"] * 6
        assert len({e[0] for e in evs}) == 2          # two threads
        for e in evs:
            if e[3] == "inner":
                assert sum(o[3] == "outer" and o[0] == e[0]
                           and o[1] <= e[1] and e[1] + e[2] <= o[1] + o[2]
                           for o in evs) == 1

    def test_disabled_tracer_leaves_neither_record_nor_annotation(
            self, bridged):
        _, off, lines = bridged
        assert off.events() == [] and len(off) == 0
        assert not any(name == "atpu:ghost"
                       for evs in lines for name, *_ in evs)
        assert off.span("x") is off.span("y")     # one shared no-op span


# ---------------------------------------------------------------------------
# ProfileSession -> Tracer bridge (training-step spans)
# ---------------------------------------------------------------------------
class TestProfileSessionTracer:
    def test_step_emits_train_step_spans(self):
        # wait=100 keeps jax.profiler off; only the span bridge runs.
        prof = ProfileSession(
            ProfileKwargs(schedule_option={"wait": 100, "active": 1}))
        tr = Tracer(capacity=16)
        prof.attach_tracer(tr)
        for _ in range(3):
            time.sleep(0.002)
            prof.step()
        evs = tr.events()
        assert [e[3] for e in evs] == ["train_step"] * 3
        for i, ev in enumerate(evs):
            assert ev[4] == "training"
            assert ev[6]["step"] == i
            assert ev[2] >= 0.002  # step-to-step wall time, not zero
        trace = tr.chrome_trace()
        assert validate_chrome_trace(trace) == []


# ---------------------------------------------------------------------------
# Engine integration: spans, dumps, postmortem, zero-recompile with tracing
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def tiny():
    cfg = LlamaConfig.tiny(use_flash_attention=False)
    m = LlamaForCausalLM(cfg)
    params = m.init_params(jax.random.PRNGKey(0), batch_size=2, seq_len=8)
    return cfg, m, params


class TestEngineTracing:
    def test_request_span_chain_and_dump(self, tiny, tmp_path):
        _, m, params = tiny
        eng = ServingEngine(m, params, max_slots=2, max_len=48,
                            eos_token_id=EOS)
        try:
            eng.start()
            r = eng.submit(np.array([[3, 5, 7, 11]], np.int32),
                           max_new_tokens=6, trace_id="trace-req-a")
            r2 = eng.submit(np.array([[1, 4]], np.int32), max_new_tokens=4)
            r.result(timeout=120)
            r2.result(timeout=120)
            assert r2.trace_id  # engine mints when the caller didn't
            names = {e[3] for e in eng.trace_events("trace-req-a")}
            assert {"submit", "queue_wait", "first_token", "itl",
                    "retire"} <= names
            # the other request's spans never leak into this id's view
            assert all(e[5] == "trace-req-a"
                       for e in eng.trace_events("trace-req-a"))
            path = eng.dump_trace(str(tmp_path / "eng.json"))
            with open(path) as f:
                trace = json.load(f)
            assert validate_chrome_trace(trace) == []
            tids = {e["args"]["trace_id"] for e in trace["traceEvents"]
                    if e.get("args", {}).get("trace_id")}
            assert {"trace-req-a", r2.trace_id} <= tids
        finally:
            eng.shutdown(drain=False)

    def test_tracing_disabled_engine_stays_silent(self, tiny):
        _, m, params = tiny
        eng = ServingEngine(m, params, max_slots=2, max_len=48,
                            eos_token_id=EOS, tracing=False)
        try:
            eng.start()
            eng.submit(np.array([[3, 5]], np.int32),
                       max_new_tokens=4).result(timeout=120)
            assert eng.trace_events() == []
        finally:
            eng.shutdown(drain=False)

    def test_zero_recompile_steady_state_with_tracing(self, tiny, tmp_path):
        """Tracing must add no device work: once warm, traffic with varying
        prompt lengths (plus a live trace dump) compiles nothing."""
        _, m, params = tiny
        eng = ServingEngine(m, params, max_slots=2, max_len=48,
                            eos_token_id=EOS)
        try:
            eng.start()
            eng.warmup()
            with CompileWatcher() as watcher:
                handles = [
                    eng.submit(np.arange(1, n + 1, dtype=np.int32)[None, :],
                               max_new_tokens=4)
                    for n in (3, 6, 1)
                ]
                for h in handles:
                    h.result(timeout=120)
                eng.dump_trace(str(tmp_path / "steady.json"))
            assert not watcher.events
        finally:
            eng.shutdown(drain=False)

    def test_kill_freezes_postmortem(self, tiny):
        _, m, params = tiny
        eng = ServingEngine(m, params, max_slots=2, max_len=48,
                            eos_token_id=EOS)
        eng.start()
        assert eng.postmortem() is None  # healthy engine: no black box yet
        eng.submit(np.array([[3, 5, 7]], np.int32),
                   max_new_tokens=4).result(timeout=120)
        eng.kill(RuntimeError("chaos-test"))
        deadline = time.monotonic() + 30
        while eng.postmortem() is None and time.monotonic() < deadline:
            time.sleep(0.05)
        pm = eng.postmortem()
        assert pm is not None
        kinds = [e["kind"] for e in pm["events"]]
        assert "kill" in kinds and "admission" in kinds
        with pytest.raises(RuntimeError):
            eng.shutdown(drain=False)  # dead engines re-raise on shutdown


# ---------------------------------------------------------------------------
# The engine loop as phases: spans on the engine thread, always-on counters
# ---------------------------------------------------------------------------
HOST_KEYS = ([f"host_us/{p}" for p in HOST_PHASES + ("other",)]
             + [f"host_us_max/{p}" for p in HOST_PHASES]
             + [f"{i}_ms{suffix}" for i in HOST_INTERVALS
                for suffix in ("", "_max")])


def _serve_chunked(m, params, tracing=True):
    """A tiny paged engine (chunk 8) serving four prompts of one to three
    chunks (no shared prefix: 3 + 2 + 1 + 3 chunk calls) to streaming
    callers; returns it idle, not shut down."""
    eng = ServingEngine(m, params, max_slots=2, max_len=64, prefill_chunk=8,
                        eos_token_id=EOS, tracing=tracing)
    eng.start()
    got = []
    reqs = [eng.submit(np.arange(k, k + n, dtype=np.int32)[None, :],
                       max_new_tokens=5, ignore_eos=True,
                       on_token=got.append)
            for k, n in ((1, 20), (30, 9), (50, 3), (60, 17))]
    for r in reqs:
        r.result(timeout=120)
    deadline = time.monotonic() + 30
    while (eng.stats.summary()["emit_lag_count"] < len(got)
           and time.monotonic() < deadline):
        time.sleep(0.01)          # the emitter's last batch, the idle flush
    time.sleep(0.05)
    return eng, got


@pytest.fixture(scope="class")
def phased(tiny):
    _, m, params = tiny
    eng, got = _serve_chunked(m, params)
    yield eng, got
    eng.shutdown(drain=False)


class TestEnginePhases:
    def test_every_phase_leaves_a_span(self, phased):
        eng, _ = phased
        phase_names = {e[3] for e in eng.trace_events() if e[4] == "phase"}
        assert phase_names == set(HOST_PHASES)

    def test_engine_thread_spans_are_disjoint_or_nested(self, phased):
        eng, _ = phased
        evs = [e for e in eng.trace_events() if e[4] == "phase"]
        engine_tid = next(e[0] for e in evs if e[3] == "tick_launch")
        emitter_tid = next(e[0] for e in evs if e[3] == "emit")
        assert engine_tid != emitter_tid
        assert {e[3] for e in evs if e[0] == emitter_tid} == {"emit"}
        spans = sorted((e[1], e[1] + e[2], e[3]) for e in evs
                       if e[0] == engine_tid)
        assert len(spans) > 20
        for (s0, e0, n0), (s1, e1, n1) in zip(spans, spans[1:]):
            assert e0 <= s1 or e1 <= e0, (n0, n1)   # never partly overlapping

    def test_phase_spans_carry_no_args_and_no_trace_id(self, phased):
        eng, _ = phased
        assert all(e[5] is None and e[6] is None
                   for e in eng.trace_events() if e[4] == "phase")

    def test_summary_has_every_host_counter(self, phased):
        eng, got = phased
        s = eng.stats.summary()
        assert set(HOST_KEYS) <= set(s)
        assert s["prefill_chunks"] == 3 + 2 + 1 + 3
        assert s["chunk_to_dispatch_count"] == s["prefill_chunks"]
        assert s["emit_lag_count"] == len(got) == 20
        for name in ("prefill_launch", "prefill_wait", "prefill_commit",
                     "tick_launch", "tick_wait", "tick_commit", "emit"):
            assert 0 < s[f"host_us/{name}"] <= s[f"host_us_max/{name}"] * max(
                1, s["decode_ticks"]), name
        assert 0 < s["chunk_to_dispatch_ms"] <= s["chunk_to_dispatch_ms_max"]
        assert 0 < s["emit_lag_ms"] <= s["emit_lag_ms_max"]
        assert s["host_us/other"] <= s["host_us_per_tick"]

    def test_merge_and_reset_carry_them(self, phased):
        eng, _ = phased
        one = eng.stats.summary()
        merged = ServingStats().merge(eng.stats).merge(eng.stats)
        two = merged.summary()
        for key in ("chunk_to_dispatch_count", "emit_lag_count"):
            assert two[key] == 2 * one[key]
        for key in HOST_KEYS:
            if "_max" in key:
                assert two[key] == one[key], key         # maxima max
            else:                                        # sums and counts add
                assert two[key] == pytest.approx(one[key], rel=1e-3, abs=1e-3)
        merged.reset()
        cleared = merged.summary()
        assert all(cleared[key] == 0 for key in HOST_KEYS)
        assert cleared["chunk_to_dispatch_count"] == 0

    def test_metrics_exposes_the_phases_as_one_labelled_family(self, phased):
        eng, _ = phased
        gw = ServingGateway(eng, config=GatewayConfig(port=0))
        text = gw.metrics_text()
        assert lint_prometheus_text(text) == []
        for phase in HOST_PHASES + ("other",):
            assert (f'accelerate_tpu_serving_host_us{{phase="{phase}"}} '
                    in text), phase
        assert 'accelerate_tpu_serving_host_us_max{phase="prefill_wait"}' in text
        assert "host_us/" not in text
        for name in ("chunk_to_dispatch_ms", "chunk_to_dispatch_ms_max",
                     "emit_lag_ms", "emit_lag_ms_max"):
            assert f"\naccelerate_tpu_serving_{name} " in text

    def test_tracing_off_keeps_the_counters_and_drops_the_spans(self, tiny):
        _, m, params = tiny
        eng, got = _serve_chunked(m, params, tracing=False)
        try:
            assert eng.trace_events() == []
            s = eng.stats.summary()
            assert s["chunk_to_dispatch_count"] == s["prefill_chunks"] == 9
            assert s["host_us/tick_launch"] > 0 and s["emit_lag_ms"] > 0
        finally:
            eng.shutdown(drain=False)
