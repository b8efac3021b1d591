"""Async host runtime (one-tick-ahead dispatch + off-thread emission).

The acceptance-critical properties pinned here:

* AHEAD == OFFLINE — the engine dispatches tick N+1 before reconciling
  tick N against a speculative membership snapshot; the streams must
  stay BIT-IDENTICAL to offline ``generation.generate`` across the whole
  serving matrix: greedy, sampled, eos-latched, multi-tenant adapters,
  draft speculation, and draft-free prompt lookup. A stream that retires
  at tick N may waste one masked lane at N+1 — never emit a wrong or
  duplicate token.
* ZERO RECOMPILES — ahead dispatch reuses the same pinned executables:
  the warm chunk/decode programs serve a staggered prompt-length mix
  with the compile listener silent and the executable counts unchanged.
* PREEMPTION UNDER FLIGHT — pool exhaustion preempts a stream while a
  speculatively-dispatched tick is still in flight; the stale flight's
  commits for that stream are discarded by the epoch check and the
  resumed stream is bit-identical (exactly-once).
* OFF-THREAD EMISSION — a slow ``on_token`` consumer flow-controls its
  OWN stream (``emission_stalls``) without stalling the tick loop or
  corrupting any stream; a raising callback fails only its own request
  with the original error; the drain-on-retire barrier orders
  ``result()`` after the last buffered callback, including through
  ``shutdown(drain=True)``.
* HOST METRIC — ``host_us_per_tick`` (schedule+commit wall per tick,
  device waits excluded) flows through ServingStats into the summary
  and the flight recorder's periodic ``tick_profile`` events.
* GATEWAY SPANS — both front ends leave ``gw.accept`` (``gw.route``
  inside it), one ``gw.sse_write`` per token and ``gw.done`` in the
  gateway's own tracer, merged into ``/debug/trace`` beside the engine's.
"""

import json
import os
import sys
import threading
import time
import urllib.request

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from accelerate_tpu import generation  # noqa: E402
from accelerate_tpu.adapters import (  # noqa: E402
    AdapterBank,
    LoRAConfig,
    merge_adapter,
)
from accelerate_tpu.adapters.lora import (  # noqa: E402
    _get_path,
    adapter_module_paths,
    init_lora_params,
)
from accelerate_tpu.models.llama import LlamaConfig, LlamaForCausalLM  # noqa: E402
from accelerate_tpu.observability import validate_chrome_trace  # noqa: E402
from accelerate_tpu.serving import (  # noqa: E402
    GatewayConfig,
    RequestStatus,
    ServingEngine,
    ServingGateway,
)
from accelerate_tpu.utils.profiling import CompileWatcher  # noqa: E402

EOS = 7

PROMPTS = [
    np.array([[3, 5, 7, 11, 2]], np.int32),
    np.array([[1, 4, 9]], np.int32),
    np.array([[8, 6, 4, 2, 10, 12, 14]], np.int32),
    np.array([[42]], np.int32),
]


@pytest.fixture(scope="module")
def tiny():
    cfg = LlamaConfig.tiny(use_flash_attention=False)
    m = LlamaForCausalLM(cfg)
    params = m.init_params(jax.random.PRNGKey(0), batch_size=2, seq_len=8)
    return cfg, m, params


def _offline(m, params, prompt, n, seed=None, eos=EOS, **kw):
    rng = None if seed is None else jax.random.PRNGKey(seed)
    out = generation.generate(m, params, prompt, max_new_tokens=n,
                              eos_token_id=eos, rng=rng, **kw)
    return np.asarray(out)[0, prompt.shape[1]:]


def _assert_matches_offline(got, ref, n):
    got = np.asarray(got)
    assert np.array_equal(got, ref[: len(got)]), (got, ref)
    if len(got) < n:
        assert got[-1] == EOS and np.all(ref[len(got):] == EOS), (got, ref)


def _nonzero_adapter(params, rank, seed):
    ad = init_lora_params(jax.random.PRNGKey(seed), params,
                          LoRAConfig(rank=rank))
    for i, dotted in enumerate(adapter_module_paths(ad)):
        mod = _get_path(ad, dotted)
        k = jax.random.fold_in(jax.random.PRNGKey(seed + 997), i)
        mod["b"] = 0.05 * jax.random.normal(k, mod["b"].shape, mod["b"].dtype)
    return ad


def _run(eng, prompts=PROMPTS, n=24, **kw):
    """Staggered submission (exercises the slot mask mid-flight)."""
    reqs = []
    for p in prompts:
        reqs.append(eng.submit(p, max_new_tokens=n, **kw))
        time.sleep(0.01)
    return [np.asarray(r.result(timeout=180)) for r in reqs]


class TestAsyncVsSyncExactness:
    """Every cell: the streams of the one-tick-ahead loop == offline
    ``generate`` (the reference that stays), token for token, under
    staggered arrivals."""

    N = 24
    BASE = dict(max_slots=3, max_len=64, eos_token_id=EOS)

    def _pair(self, m, params, engine_kw=None, submit_kw=None,
              prompts=PROMPTS, n=N):
        eng = ServingEngine(m, params, **dict(self.BASE, **(engine_kw or {})))
        try:
            out = _run(eng, prompts=prompts, n=n, **(submit_kw or {}))
            stats = eng.stats.summary()
        finally:
            eng.shutdown(drain=False)
        return out, stats

    def test_greedy_dense(self, tiny):
        """The default constructor: 256-token chunks clamped to
        ``max_len``, the private prefix cache on."""
        _, m, params = tiny
        a, _ = self._pair(m, params)
        refs = [_offline(m, params, p, self.N) for p in PROMPTS]
        for got, ref in zip(a, refs):
            _assert_matches_offline(got, ref, self.N)

    def test_greedy_paged_chunked(self, tiny):
        _, m, params = tiny
        a, _ = self._pair(m, params,
                          engine_kw=dict(prefill_chunk=8, prefix_cache_mb=0.0))
        refs = [_offline(m, params, p, self.N) for p in PROMPTS]
        for got, ref in zip(a, refs):
            _assert_matches_offline(got, ref, self.N)

    def test_sampled_seeded(self, tiny):
        """Sampled streams consume one rng split per slot per tick; the
        ahead tick replays the same splits, so a fixed seed must stay
        bit-identical to offline."""
        _, m, params = tiny
        a, _ = self._pair(m, params,
                          engine_kw=dict(do_sample=True, temperature=0.9,
                                         top_k=50),
                          submit_kw=dict(seed=3))
        refs = [_offline(m, params, p, self.N, seed=3, do_sample=True,
                         temperature=0.9, top_k=50) for p in PROMPTS]
        for got, ref in zip(a, refs):
            _assert_matches_offline(got, ref, self.N)

    def test_eos_latch(self, tiny):
        """The stray ahead-tick a retiring stream leaves behind must be
        discarded host-side: no token may follow the eos latch."""
        _, m, params = tiny
        n = 48  # long enough for the tiny model to hit eos organically
        a, _ = self._pair(m, params, n=n)
        refs = [_offline(m, params, p, n) for p in PROMPTS]
        for got, ref in zip(a, refs):
            _assert_matches_offline(got, ref, n)

    def test_adapters(self, tiny):
        """Tenant and base traffic in one batch: each stream matches
        offline generate under its tenant's MERGED weights."""
        _, m, params = tiny
        ad = _nonzero_adapter(params, rank=4, seed=5)
        bank = AdapterBank(params, config=LoRAConfig(rank=4), max_adapters=3)
        bank.register("a", ad)
        eng = ServingEngine(m, params, adapters=bank,
                            **dict(self.BASE, prefill_chunk=8))
        try:
            a = _run(eng, adapter="a") + _run(eng)  # tenant + base traffic
        finally:
            eng.shutdown(drain=False)
        merged = merge_adapter(params, ad)
        refs = ([_offline(m, merged, p, self.N) for p in PROMPTS]
                + [_offline(m, params, p, self.N) for p in PROMPTS])
        for got, ref in zip(a, refs):
            _assert_matches_offline(got, ref, self.N)

    def test_spec_draft(self, tiny):
        """One-tick-ahead speculative dispatch passes a STALE per-slot
        ``remaining`` budget (safe: stale >= true, and the host commit
        loop enforces the true budget); streams must not notice."""
        _, m, params = tiny
        a, stats = self._pair(
            m, params,
            engine_kw=dict(prefill_chunk=8, prefix_cache_mb=0.0,
                           draft_model=m, draft_params=params, spec_tokens=4))
        assert stats["spec_ticks"] > 0
        refs = [_offline(m, params, p, self.N) for p in PROMPTS]
        for got, ref in zip(a, refs):
            _assert_matches_offline(got, ref, self.N)

    def test_spec_lookup(self, tiny):
        """Draft-free prompt-lookup proposals are built from the HOST
        token state — proposals steer acceptance, never the emitted law,
        so greedy streams stay exact."""
        _, m, params = tiny
        # Repetitive prompts so lookup actually proposes.
        prompts = [np.tile(p, (1, 3)) for p in PROMPTS[:3]]
        a, stats = self._pair(
            m, params, prompts=prompts,
            engine_kw=dict(prefill_chunk=8, prefix_cache_mb=0.0,
                           spec_lookup=3))
        assert stats["spec_ticks"] > 0
        refs = [_offline(m, params, p, self.N) for p in prompts]
        for got, ref in zip(a, refs):
            _assert_matches_offline(got, ref, self.N)


class TestAsyncZeroRecompile:
    def test_ahead_dispatch_keeps_executables_pinned(self, tiny):
        """The speculative membership mask and pre-covered page table of
        the ahead tick are DATA — after warmup a staggered prompt-length
        mix must run through the same warm executables with the compile
        listener silent."""
        _, m, params = tiny
        eng = ServingEngine(m, params, max_slots=3, max_len=64,
                            eos_token_id=EOS, prefill_chunk=8,
                            prefix_cache_mb=0.0)
        rng = np.random.default_rng(11)
        long = rng.integers(0, 256, size=(1, 29)).astype(np.int32)
        try:
            with CompileWatcher() as watcher:
                reqs = []
                for p in PROMPTS + [long]:
                    reqs.append(eng.submit(p, max_new_tokens=6, seed=3))
                    time.sleep(0.01)
                for r in reqs:
                    r.result(timeout=120)
        finally:
            eng.shutdown(drain=False)
        assert not watcher.events, (
            f"XLA recompiled after warmup: {watcher.events} — the ahead "
            "tick's mask/table must be data, never program shapes")
        assert eng._prefill_chunk._cache_size() == 1
        assert eng._decode._cache_size() == 1


    def test_next_tick_launches_before_the_last_commits(self, tiny):
        """One tick ahead, as an order of the loop's own phase records:
        in steady decode ``tick_launch`` of tick N+1 begins before
        ``tick_commit`` of tick N begins, so a tick's commit runs under
        the next tick's device time (the i-th commit has at least i + 1
        launches before it, where a dispatch-wait-commit loop has i)."""
        _, m, params = tiny
        eng = ServingEngine(m, params, max_slots=2, max_len=64,
                            eos_token_id=EOS)
        n = 16
        try:
            got = eng.submit(PROMPTS[0], max_new_tokens=n,
                             ignore_eos=True).result(timeout=120)
        finally:
            eng.shutdown(drain=True)  # the last commit's record is closed
        assert len(got) == n
        phase = {"tick_launch": [], "tick_commit": []}
        for _, t0, _, name, cat, _, _ in eng.trace_events():
            if cat == "phase" and name in phase:
                phase[name].append(t0)
        launches, commits = sorted(phase["tick_launch"]), sorted(
            phase["tick_commit"])
        assert len(commits) == n - 1, commits  # the prefill emits token 1
        for i, c in enumerate(commits, start=1):
            ahead = sum(t < c for t in launches)
            assert ahead >= i + 1, (
                f"commit {i} began after {ahead} launches: tick {i + 1} "
                "was not dispatched before tick {i} was settled")


    @pytest.mark.parametrize("mode", ["plain", "draft"])
    def test_a_two_token_request_is_not_held_back_by_the_tick_in_flight(
            self, tiny, mode):
        """The ahead dispatch leaves out a stream one token short of its
        budget only if that stream is IN the in-flight tick (it retires
        there). A request fresh from prefill with one token left is not:
        it must get its tick while the other streams keep decoding, not
        when the batch next drains."""
        _, m, params = tiny
        spec = (dict(draft_model=m, draft_params=params, spec_tokens=4)
                if mode == "draft" else {})
        eng = ServingEngine(m, params, max_slots=2, max_len=128,
                            eos_token_id=EOS, prefill_chunk=8,
                            prefix_cache_mb=0.0, **spec)
        stamps = []
        try:
            stream = eng.submit(
                PROMPTS[0], max_new_tokens=100, ignore_eos=True,
                on_token=lambda t: stamps.append(time.monotonic()))
            deadline = time.monotonic() + 60
            while len(stamps) < 3:
                assert time.monotonic() < deadline, "stream never decoded"
                time.sleep(0.001)
            short = eng.submit(PROMPTS[1], max_new_tokens=2, ignore_eos=True)
            assert short.wait(120) and stream.wait(120)
        finally:
            eng.shutdown(drain=False)
        assert len(short.tokens) == 2
        after = sum(t > short.finished_at for t in stamps)
        assert after >= 10, (
            f"the stream emitted only {after} of its 100 tokens after the "
            "two-token request finished: that request waited for the "
            "in-flight pipeline to drain")


class TestAsyncPreemption:
    def test_pool_exhaustion_under_flight_is_token_exact(self, tiny):
        """Preemption fires while a speculatively-dispatched tick is in
        flight; the flight's epoch check must discard the preempted
        stream's stale commit and the resumed stream stays bit-exact
        (exactly-once, no duplicate or missing token)."""
        _, m, params = tiny
        eng = ServingEngine(m, params, max_slots=2, max_len=64,
                            eos_token_id=EOS, prefill_chunk=8,
                            prefix_cache_mb=0.0, max_pages=10)
        n = 40
        try:
            refs = [_offline(m, params, p, n, eos=None)
                    for p in PROMPTS[:2]]
            reqs = [eng.submit(p, max_new_tokens=n, ignore_eos=True)
                    for p in PROMPTS[:2]]
            for r, ref in zip(reqs, refs):
                got = np.asarray(r.result(timeout=180))
                assert np.array_equal(got, ref), (got, ref)
            s = eng.stats.summary()
            assert s["preemptions"] >= 1, (
                "10 pages cannot hold two 6-page streams; the engine must "
                f"have preempted (stats: {s})")
        finally:
            eng.shutdown(drain=False)


class TestOffThreadEmission:
    def test_slow_consumer_stalls_only_its_own_stream(self, tiny):
        """A consumer sleeping far longer than a tick must backlog into
        the bounded emitter queue: the engine skips (flow-controls) that
        stream, counts ``emission_stalls``, and both the slow and the
        fast neighbor stream finish token-exact."""
        _, m, params = tiny
        eng = ServingEngine(m, params, max_slots=2, max_len=64,
                            eos_token_id=EOS, emission_queue=1)
        n = 10
        slow_seen = []

        def slow_cb(tok):
            time.sleep(0.05)
            slow_seen.append(tok)

        try:
            refs = [_offline(m, params, p, n) for p in PROMPTS[:2]]
            r_slow = eng.submit(PROMPTS[0], max_new_tokens=n,
                                on_token=slow_cb)
            r_fast = eng.submit(PROMPTS[1], max_new_tokens=n)
            got_slow = np.asarray(r_slow.result(timeout=180))
            got_fast = np.asarray(r_fast.result(timeout=180))
            _assert_matches_offline(got_slow, refs[0], n)
            _assert_matches_offline(got_fast, refs[1], n)
            # result() is ordered AFTER the last buffered callback.
            assert slow_seen == list(got_slow), (slow_seen, got_slow)
            assert eng.stats.summary()["emission_stalls"] > 0, (
                "a 50ms consumer against a ~ms tick must have hit the "
                "emission_queue=1 bound at least once")
        finally:
            eng.shutdown(drain=False)

    def test_raising_callback_fails_only_its_request(self, tiny):
        """An ``on_token`` raising on the EMITTER thread must retire its
        own request FAILED with the original error at the engine's next
        sweep — neighbors stream on untouched."""
        _, m, params = tiny
        eng = ServingEngine(m, params, max_slots=2, max_len=64,
                            eos_token_id=EOS)
        n = 16
        boom = RuntimeError("consumer exploded")
        calls = []

        def bad_cb(tok):
            calls.append(tok)
            if len(calls) >= 3:
                raise boom

        try:
            ref = _offline(m, params, PROMPTS[1], n)
            r_bad = eng.submit(PROMPTS[0], max_new_tokens=n,
                               on_token=bad_cb)
            r_ok = eng.submit(PROMPTS[1], max_new_tokens=n)
            _assert_matches_offline(r_ok.result(timeout=180), ref, n)
            assert r_bad.wait(timeout=60)
            assert r_bad.status is RequestStatus.FAILED
            assert r_bad.error is boom
            with pytest.raises(RuntimeError, match="failed"):
                r_bad.result()
            assert eng.error is None and eng.running  # engine unharmed
        finally:
            eng.shutdown(drain=False)

    def test_drain_on_retire_barrier_through_shutdown(self, tiny):
        """``shutdown(drain=True)`` must not drop buffered tokens: every
        committed token reaches the (slow) consumer before the engine
        joins its emitter, and ``done`` is observed only after the last
        callback ran."""
        _, m, params = tiny
        eng = ServingEngine(m, params, max_slots=1, max_len=64,
                            eos_token_id=EOS, emission_queue=2)
        n = 8
        seen = []
        order_ok = []

        def cb(tok):
            time.sleep(0.02)
            seen.append(tok)

        try:
            ref = _offline(m, params, PROMPTS[0], n)
            r = eng.submit(PROMPTS[0], max_new_tokens=n, on_token=cb)
            r._on_finish = lambda req: order_ok.append(len(seen))
        finally:
            eng.shutdown(drain=True)
        got = np.asarray(r.result(timeout=1))
        _assert_matches_offline(got, ref, n)
        assert seen == list(got), (seen, got)
        # the router hook fired after the full stream drained
        assert order_ok == [len(got)], (order_ok, got)


class TestHostTickMetric:
    def test_host_us_per_tick_flows_to_summary_and_flight(self, tiny):
        """``host_us_per_tick`` (tick interval minus device waits) must
        appear in the stats summary and in the periodic ``tick_profile``
        flight events; ``itl_ms`` keeps counting device-complete
        intervals."""
        _, m, params = tiny
        eng = ServingEngine(m, params, max_slots=2, max_len=64,
                            eos_token_id=EOS)
        try:
            _run(eng, prompts=PROMPTS[:2], n=16)
            s = eng.stats.summary()
            assert s["host_us_per_tick"] > 0.0, s
            assert s["host_us_per_tick_max"] >= s["host_us_per_tick"], s
            assert eng.stats.histograms()["itl_ms"]["count"] > 0, s
            profiles = [e for e in eng.flight_recorder.snapshot()
                        if e["kind"] == "tick_profile"]
            assert profiles, "no tick_profile event in the flight recorder"
            assert all("host_us" in e and "itl_ms" in e for e in profiles)
        finally:
            eng.shutdown(drain=False)


class TestGatewaySpans:
    @pytest.mark.parametrize("server", ["asyncio", "threading"])
    def test_gateway_spans_show_in_debug_trace(self, tiny, server):
        _, m, params = tiny
        eng = ServingEngine(m, params, max_slots=2, max_len=64,
                            eos_token_id=EOS)
        gw = ServingGateway(eng, config=GatewayConfig(port=0, server=server))
        gw.start()
        try:
            tid = f"gw-spans-{server}"
            req = urllib.request.Request(
                gw.url + "/v1/completions",
                data=json.dumps({"prompt": [3, 5, 7], "max_new_tokens": 6,
                                 "ignore_eos": True, "stream": True}).encode(),
                headers={"Content-Type": "application/json",
                         "X-Request-Id": tid})
            events = []
            with urllib.request.urlopen(req, timeout=120) as resp:
                for line in resp:
                    if line.startswith(b"data: "):
                        events.append(json.loads(line[6:]))
            assert events[-1]["done"] and events[-1]["status"] == "completed"
            assert [e["token"] for e in events[:-1]] == events[-1]["tokens"]

            with urllib.request.urlopen(
                    gw.url + f"/debug/trace?id={tid}", timeout=10) as resp:
                trace = json.loads(resp.read())
            assert validate_chrome_trace(trace) == []
            lanes = {e["pid"]: e["args"]["name"]
                     for e in trace["traceEvents"] if e["ph"] == "M"}
            assert "gateway" in lanes.values()
            spans = [e for e in trace["traceEvents"] if e["ph"] != "M"]
            assert all(e["args"]["trace_id"] == tid for e in spans)
            by_name = {}
            for e in spans:
                by_name.setdefault(e["name"], []).append(e)
            # the gateway's spans beside the engine's request-scoped ones
            assert {"gw.accept", "gw.route", "gw.sse_write", "gw.done",
                    "submit", "first_token", "retire"} <= set(by_name)
            assert all(lanes[e["pid"]] == "gateway"
                       for n, evs in by_name.items() if n.startswith("gw.")
                       for e in evs)
            assert len(by_name["gw.sse_write"]) == 6
            assert len(by_name["gw.done"]) == 1
            (accept,), (route,) = by_name["gw.accept"], by_name["gw.route"]
            assert accept["ts"] <= route["ts"]
            assert (route["ts"] + route["dur"]
                    <= accept["ts"] + accept["dur"] + 1e-3)
            # a refused request (no replica holds the adapter) leaves its span too
            bad = urllib.request.Request(
                gw.url + "/v1/completions",
                data=json.dumps({"prompt": [1], "max_new_tokens": 2,
                                 "adapter": "nobody"}).encode(),
                headers={"Content-Type": "application/json",
                         "X-Request-Id": tid + "-refused"})
            with pytest.raises(urllib.error.HTTPError) as err:
                urllib.request.urlopen(bad, timeout=30)
            assert err.value.code in (404, 503)
            refused = {e[3] for e in gw.tracer.events(tid + "-refused")}
            assert "gw.accept" in refused
        finally:
            gw.shutdown(drain=False)
