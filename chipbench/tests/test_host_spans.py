"""host_spans.py on synthetic events, the eight readers that rest on it on an
empty context, and a profiler trace taken on the CPU around a tiny engine."""

import json
import time

import pytest

from chipbench import harness, host_spans as hs
from chipbench import trace_reduce as tr

NEW_METRICS = ["chunk_to_dispatch_ms", "prefill_commit_us", "tick_launch_us", "tick_commit_us",
               "queue_wait_ms", "emit_lag_ms", "gateway_loop_busy", "idle_named.serve"]


def op(start, dur, name="x", line=tr.OPS_LINE):
    return tr.Event("/device:TPU:0", line, name, float(start), float(dur))


def span(name, start, dur, thread=0):
    return hs.HostEvent(thread, "python", name, float(start), float(dur))


def test_a_gap_is_split_across_the_spans_it_crosses_and_the_rest_is_unnamed():
    # device busy [0,10) [30,40) [70,80): gaps [10,30) and [40,70)
    trace = tr.Trace([op(0, 10), op(30, 10), op(70, 10)])
    host = [span("tick_launch", 0, 5),                # marks the engine thread; under no gap
            span("prefill_wait", 8, 7),               # [8,15): 5 of the first gap
            span("prefill_commit", 15, 10),           # [15,25): 10 of it; [25,30) under no span
            span("sweep", 45, 5),                     # [45,50) of the second gap
            span("tick_commit", 75, 5),               # under no gap; ends the recorded stretch
            span("emit", 10, 60, thread=1)]           # another thread: never booked
    idle = hs.idle_by_span(trace, host)
    assert idle == {"prefill_wait": pytest.approx(5e-9), "prefill_commit": pytest.approx(10e-9),
                    "sweep": pytest.approx(5e-9), hs.UNNAMED: pytest.approx(30e-9)}
    assert sum(idle.values()) == pytest.approx(50e-9)       # every idle nanosecond booked once
    table = hs.idle_table(idle)
    assert table.index("host at work") < table.index("prefill_commit") \
        < table.index("wake-up latency") < table.index("prefill_wait") < table.index("no span")


def test_idle_time_outside_the_engine_threads_recorded_stretch_is_not_judged():
    # device gaps [10,100) and [110,300); the engine thread's spans cover [50,200) only
    trace = tr.Trace([op(0, 10), op(100, 10), op(300, 10)])
    host = [span("tick_launch", 50, 10), span("tick_wait", 60, 140)]
    idle = hs.idle_by_span(trace, host)
    assert idle == {"tick_launch": pytest.approx(10e-9), "tick_wait": pytest.approx(130e-9),
                    hs.UNNAMED: pytest.approx(0.0)}


def test_nested_spans_book_to_the_innermost():
    trace = tr.Trace([op(0, 10), op(100, 10)])              # one gap [10,100)
    host = [span("tick_launch", 0, 1), span("outer", 20, 60), span("inner", 30, 20),
            span("sweep", 100, 5)]
    assert hs.innermost_segments(host[1:3]) == [(20.0, 30.0, "outer"), (30.0, 50.0, "inner"),
                                               (50.0, 80.0, "outer")]
    idle = hs.idle_by_span(trace, host)
    assert idle == {"outer": pytest.approx(40e-9), "inner": pytest.approx(20e-9),
                    hs.UNNAMED: pytest.approx(30e-9)}


def test_no_engine_thread_gives_empty_tables():
    trace = tr.Trace([op(0, 10), op(30, 10)])
    assert hs.idle_by_span(trace, [span("stage_batch", 0, 50)]) == {}
    assert hs.launch_lag(trace, []) == []
    assert hs.thread_busy_share([], None) is None
    assert hs.load("/nonexistent/file.xplane.pb") == []


def test_thread_busy_share_is_a_union_clipped_to_the_window():
    host = [span("gw.accept", 0, 10, thread=2), span("gw.route", 2, 5, thread=2),   # nested: once
            span("gw.sse_write", 20, 10, thread=2), span("gw.done", 95, 20, thread=2),
            span("tick_launch", 0, 100, thread=0)]
    assert hs.thread_of(host, hs.GATEWAY_MARK) == 2
    assert hs.thread_busy_share(host, 2, (0.0, 100.0), prefix="gw.") == pytest.approx(0.25)
    assert hs.thread_busy_share(host, 2, prefix="gw.") == pytest.approx(40 / 115)
    assert hs.thread_busy_share(host, 2, (0.0, 100.0), prefix="tick") is None


def test_launch_lag_pairs_in_order_and_falls_back_to_time_at_a_cut_trace():
    mods = [op(12, 50, "jit__paged_decode_fn(1)", tr.MODULES_LINE),     # inside its span
            op(130, 50, "jit__paged_decode_fn(1)", tr.MODULES_LINE),    # 10 after the span's end
            op(5, 3, "jit__paged_prefill_chunk_fn(2)", tr.MODULES_LINE)]
    host = [span("tick_launch", 10, 10), span("tick_launch", 100, 20), span("sweep", 0, 5)]
    lags = hs.launch_lag(tr.Trace(mods), host)
    assert [(s.start_ns, m.start_ns, lag) for s, m, lag in lags] == [(10.0, 12.0, -8.0),
                                                                     (100.0, 130.0, 10.0)]
    # the trace began after the first launch: its program has no span, the rest pair by time
    cut = hs.launch_lag(tr.Trace(mods), host[1:])
    assert [(s.start_ns, m.start_ns, lag) for s, m, lag in cut] == [(100.0, 130.0, 10.0)]


def test_the_device_clock_is_shifted_by_the_least_that_causality_allows():
    # three programs: enqueued at 100, 200, 300 on the host's clock; the device plane stamps
    # their starts 40, 15 and 38 early of that (so at least 40 late of truth) and the host hears
    # of each end 55 or more after the stamp
    modules = [(60.0, 90.0, 1), (185.0, 195.0, 2), (262.0, 290.0, 3), (400.0, 410.0, None)]
    enqueued = {1: 100.0, 2: 200.0, 3: 300.0, 9: 0.0}
    done = [145.0, 260.0, 350.0]
    assert hs.clock_skew_ns(enqueued, done, modules) == (40.0, 55.0)
    assert hs.clock_skew_ns({}, [], modules) == (0.0, None)               # nothing to go by
    assert hs.clock_skew_ns({1: 10.0}, [], [(60.0, 90.0, 1)]) == (0.0, None)   # never negative

    host = hs.HostEvents([span("tick_launch", 95, 10), span("tick_launch", 195, 10)])
    host.skew_ns = 40.0
    mods = [op(60, 30, "jit__paged_decode_fn(1)", tr.MODULES_LINE),
            op(185, 10, "jit__paged_decode_fn(1)", tr.MODULES_LINE)]
    lags = hs.launch_lag(tr.Trace(mods), host)
    assert [lag for _, _, lag in lags] == [-5.0, 20.0]       # 60+40-105, 185+40-205
    assert all(m.start_ns + 40 >= s.start_ns for s, m, _ in lags)
    assert not all(m.start_ns >= s.start_ns for s, m, _ in lags)   # the raw clocks break causality
    # the device's gap [90,185) is [130,225) to the host, judged up to the last span's end, 205:
    # 10 under the second launch, 65 under none
    idle = hs.idle_by_span(tr.Trace([op(60, 30), op(185, 10)]), host)
    assert idle == {"tick_launch": pytest.approx(10e-9), hs.UNNAMED: pytest.approx(65e-9)}


@pytest.mark.parametrize("name", NEW_METRICS)
def test_every_new_reader_returns_none_on_an_empty_context(name, monkeypatch, tmp_path):
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)     # no trace of this run
    compute = harness.load_module("layer_metrics", name).compute
    for trace in (None, tr.Trace([op(0, 10), op(30, 10)])):
        ctx = harness.LayerContext(trace, {}, {}, 1.0, {}, {}, {}, {})
        assert compute(ctx) is None


def test_counter_readers_read_their_keys():
    stats = {"chunk_to_dispatch_ms": 3.9, "host_us/prefill_commit": 1500.0,
             "host_us/tick_launch": 600.0, "host_us/tick_commit": 800.0,
             "queue_wait_ms": 21.0, "emit_lag_ms": 0.4}
    ctx = harness.LayerContext(None, stats, {}, 1.0, {}, {}, {}, {})
    got = [harness.load_module("layer_metrics", n).compute(ctx) for n in NEW_METRICS[:6]]
    assert got == [3.9, 1500.0, 600.0, 800.0, 21.0, 0.4]


def test_the_manifest_entries_are_read_by_the_harness(tmp_path):
    """The real manifest's eight new entries, appended to a copy of the toy
    manifest's serving cell: ``load_cell`` takes them and every reader loads."""
    real = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    toy_path = harness.PACKAGE / "tests" / "toy" / "BENCHMARK.json"
    toy = json.loads(toy_path.read_text())
    added = [dict(m, workloads=["toy-serve"]) for m in real["per_layer"] if m["name"] in NEW_METRICS]
    assert [m["name"] for m in added] == NEW_METRICS
    assert [m["name"] for m in real["per_layer"][-8:]] == NEW_METRICS      # appended, at the end
    toy["per_layer"] += added
    toy["paths"] = [str((harness.PACKAGE / "tests" / "toy").relative_to(harness.ROOT))]
    manifest = tmp_path / "BENCHMARK.json"
    manifest.write_text(json.dumps(toy))
    cell = harness.load_cell(manifest, "toy-serve")
    assert set(NEW_METRICS) <= {m["name"] for m in cell.per_layer}
    ctx = harness.LayerContext(None, {"queue_wait_ms": 2.0}, {}, 1.0, {}, {}, {}, {})
    assert harness.layer_metrics(cell, ctx)["queue_wait_ms"] == {"value": 2.0, "unit": "ms"}


def test_a_cpu_trace_around_a_tiny_engine_has_the_phases_on_their_threads(tmp_path):
    """The whole path but the chip: the engine's spans, the emitter's and the
    gateway's land on three lines of the profiler's host plane, and ``load``
    tells the threads apart by what is on them."""
    import urllib.request

    import jax

    from accelerate_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from accelerate_tpu.serving import GatewayConfig, ServingEngine, ServingGateway

    model = LlamaForCausalLM(LlamaConfig.tiny(use_flash_attention=False))
    params = model.init_params(jax.random.PRNGKey(0), batch_size=2, seq_len=8)
    engine = ServingEngine(model, params, max_slots=2, max_len=64, prefill_chunk=8)
    gateway = ServingGateway(engine, config=GatewayConfig(port=0))
    gateway.start()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    try:
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            for base, length in ((1, 20), (40, 9)):
                body = {"prompt": list(range(base, base + length)), "max_new_tokens": 4,
                        "ignore_eos": True, "stream": True}
                request = urllib.request.Request(
                    gateway.url + "/v1/completions", data=json.dumps(body).encode(),
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(request, timeout=120) as response:
                    response.read()
            time.sleep(0.05)
        finally:
            jax.profiler.stop_trace()
    finally:
        gateway.shutdown(drain=False)

    events = hs.load(hs.newest_trace(tmp_path))
    engine_thread = hs.thread_of(events, hs.ENGINE_MARK)
    emitter_thread = hs.thread_of(events, hs.EMITTER_MARK)
    loop_thread = hs.thread_of(events, hs.GATEWAY_MARK)
    assert len({engine_thread, emitter_thread, loop_thread}) == 3
    on_engine = {e.name for e in hs.on_thread(events, engine_thread)}
    assert on_engine == {"sweep", "admit", "prefill_launch", "prefill_wait", "prefill_commit",
                         "tick_launch", "tick_wait", "tick_commit", "idle"}
    assert {e.name for e in hs.on_thread(events, emitter_thread)} == {"emit"}
    assert {e.name for e in hs.on_thread(events, loop_thread)} == {
        "gw.accept", "gw.route", "gw.sse_write", "gw.done"}
    chunks = [e for e in events if e.name == "prefill_launch"]
    assert len(chunks) == 3 + 2
    accept = [e for e in events if e.name == "gw.accept"]
    assert all(dict(e.stats).get("trace_id") for e in accept)
    assert 0 < hs.thread_busy_share(events, loop_thread, prefix="gw.") < 1
    # the segments of one thread never overlap
    segments = hs.innermost_segments(hs.on_thread(events, engine_thread))
    assert all(a[1] <= b[0] for a, b in zip(segments, segments[1:]))
    assert "launch lag" in hs.describe(tr.Trace([]), events)
