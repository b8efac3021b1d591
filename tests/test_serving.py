"""Continuous-batching serving engine (serving.ServingEngine).

The acceptance-critical properties pinned here:

* EXACTNESS — tokens streamed by the engine are bit-identical to offline
  ``generation.generate`` for the same (prompt, rng, sampling), including
  eos semantics, even when requests join mid-flight of other requests'
  decode loops (staggered arrivals exercise the slot mask, not the shape).
* ZERO RECOMPILES — after warmup, admitting and retiring requests of
  varying prompt lengths triggers no new XLA compilation (probed via
  jax.monitoring's event-duration listener, which fires per compile);
  the steady state is exactly ONE executable each for prefill_chunk and
  decode (and restore_prefix, with an external cache), whatever
  prompt-length mix arrives.
* CHUNKED PREFILL — chunk-size x prompt-length x sampling exactness
  against offline generate, decode ticks
  interleaving with a long prompt's chunk calls, and the prefix cache
  (unit LRU semantics + a repeat prompt admitting in one chunk).
* SCHEDULING SEMANTICS — bounded-queue backpressure, cancel (queued and
  running), per-request timeout (queued and running), error isolation
  (a raising stream callback fails only its own request), FCFS admission.
* LIFECYCLE — graceful drain on shutdown (plus async-checkpoint flush),
  preemption cooperation (finish in-flight, cancel queued, exit).

All engines share the module-scoped tiny Llama from test_generation.py's
convention; the slow-motion engine uses bench's deterministic-sleep model
so timing-sensitive tests don't depend on host speed.
"""

import os
import sys
import threading
import time
import types

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from accelerate_tpu import generation  # noqa: E402
from accelerate_tpu.models.llama import LlamaConfig, LlamaForCausalLM  # noqa: E402
from accelerate_tpu.utils.profiling import CompileWatcher  # noqa: E402
from accelerate_tpu.serving import (  # noqa: E402
    AdmissionQueue,
    PrefixCache,
    QueueClosed,
    QueueFull,
    Request,
    RequestStatus,
    ServingEngine,
    ServingStats,
    SlotScheduler,
)

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


@pytest.fixture(scope="module")
def engine(tiny):
    """Shared greedy engine (warmup paid once for the whole module)."""
    _, m, params = tiny
    eng = ServingEngine(m, params, max_slots=3, max_len=64, eos_token_id=EOS)
    yield eng
    if eng.running:
        eng.shutdown(drain=False)


@pytest.fixture(scope="module")
def sampled_engine(tiny):
    _, m, params = tiny
    eng = ServingEngine(m, params, max_slots=3, max_len=64, eos_token_id=EOS,
                        do_sample=True, temperature=0.9, top_k=50)
    yield eng
    if eng.running:
        eng.shutdown(drain=False)


@pytest.fixture(scope="module")
def slow_engine():
    """Engine over bench's deterministic-sleep model: ~10 ms per forward,
    so slot-occupancy windows are wide enough for race-free scheduling
    tests on any host."""
    import bench

    cfg = LlamaConfig.tiny(use_flash_attention=False)
    m = bench._sleepy_llama_cls(step_ms=10.0)(cfg)
    params = m.init_params(jax.random.PRNGKey(0), batch_size=1, seq_len=8)
    eng = ServingEngine(m, params, max_slots=1, max_len=32, max_queued=1)
    yield eng
    if eng.running:
        eng.shutdown(drain=False)


def _offline(m, params, prompt, n, seed=None, **kw):
    """Offline reference completion [n] (padded with eos past the latch)."""
    rng = None if seed is None else jax.random.PRNGKey(seed)
    out = generation.generate(m, params, prompt, max_new_tokens=n,
                              eos_token_id=EOS, rng=rng, **kw)
    return np.asarray(out)[0, prompt.shape[1]:]


def _assert_matches_offline(got, ref, n):
    """Engine stops AT eos; offline keeps the shape and pads with eos."""
    got = np.asarray(got)
    assert np.array_equal(got, ref[: len(got)]), (got, ref)
    if len(got) < n:
        assert got[-1] == EOS and np.all(ref[len(got):] == EOS), (got, ref)


class TestSchedulerUnits:
    def test_admission_queue_backpressure(self):
        q = AdmissionQueue(max_queued=2)
        a, b = Request([[1]]), Request([[2]])
        q.put(a, block=False)
        q.put(b, block=False)
        with pytest.raises(QueueFull):
            q.put(Request([[3]]), block=False)
        with pytest.raises(QueueFull):
            q.put(Request([[3]]), block=True, timeout=0.01)
        assert q.get_nowait() is a  # FCFS
        assert q.drain() == [b] and len(q) == 0

    def test_slot_scheduler_free_list(self):
        s = SlotScheduler(2)
        r0, r1 = Request([[1]]), Request([[2]])
        assert s.assign(r0) == 0 and s.assign(r1) == 1  # lowest-index-first
        assert not s.has_free() and s.active() == [(0, r0), (1, r1)]
        assert s.release(0) is r0 and r0.slot is None
        r2 = Request([[3]])
        assert s.assign(r2) == 0  # freed slot is reused
        assert s.occupant(0) is r2 and s.active_slots == 2

    def test_request_validation(self):
        with pytest.raises(ValueError, match="max_new_tokens"):
            Request([[1]], max_new_tokens=0)
        with pytest.raises(ValueError, match="prompt_ids"):
            Request(np.zeros((2, 3), np.int32))  # batched prompts: one per slot
        with pytest.raises(ValueError, match="prompt_ids"):
            Request(np.zeros((1, 1, 3), np.int32))
        r = Request([1, 2, 3])  # 1-D promotes to [1, S]
        assert r.prompt_ids.shape == (1, 3)

    def test_request_result_semantics(self):
        r = Request([[1]])
        with pytest.raises(TimeoutError):
            r.result(timeout=0.01)
        r._finish(RequestStatus.CANCELLED)
        with pytest.raises(RuntimeError, match="cancelled"):
            r.result()
        r2 = Request([[1]])
        r2.tokens.extend([4, 5])
        r2._finish(RequestStatus.COMPLETED)
        r2._finish(RequestStatus.FAILED, RuntimeError("late"))  # first wins
        assert r2.status is RequestStatus.COMPLETED
        np.testing.assert_array_equal(r2.result(), [4, 5])
        np.testing.assert_array_equal(r2.output_ids(), [[1, 4, 5]])

    def test_prefix_cache_lru_and_bounds(self):
        with pytest.raises(ValueError, match="capacity_bytes"):
            PrefixCache(0)
        pc = PrefixCache(capacity_bytes=100)
        pc.put(b"a", "A", 40)
        pc.put(b"b", "B", 40)
        assert pc.match([b"a", b"b"]) == ["A", "B"]
        # The chain stops at the first miss: a later chunk's KV is only
        # valid stacked on every earlier one.
        assert pc.match([b"a", b"x", b"b"]) == ["A"]
        pc.put(b"c", "C", 40)  # 120 > 100: evicts the LRU entry (b)
        assert pc.match([b"b"]) == []
        assert pc.match([b"a"]) == ["A"] and pc.match([b"c"]) == ["C"]
        assert len(pc) == 2 and pc.nbytes == 80
        assert pc.insertions == 3 and pc.evictions == 1
        pc.put(b"huge", "H", 1000)  # bigger than the whole budget: skipped
        assert pc.match([b"huge"]) == [] and pc.nbytes == 80
        pc.put(b"a", "A2", 40)  # re-put touches, never duplicates
        assert len(pc) == 2 and pc.match([b"a"]) == ["A"]
        pc.clear()
        assert len(pc) == 0 and pc.nbytes == 0 and pc.match([b"a"]) == []

    def test_stats_summary(self):
        st = ServingStats()
        st.record_submit(queue_depth=3)
        st.record_admit(queue_wait_ms=4.0, ttft_ms=10.0)
        st.record_tick(active_slots=2, committed_tokens=2, max_slots=4, seconds=0.01)
        st.record_finish(RequestStatus.COMPLETED)
        s = st.summary()
        assert s["requests_submitted"] == s["requests_completed"] == 1
        assert s["queue_wait_ms"] == 4.0 and s["ttft_ms_p50"] == 10.0
        assert s["slot_occupancy"] == 0.5 and s["batch_efficiency"] == 0.5
        assert s["tokens_emitted"] == 3  # 1 prefill + 2 decode
        assert s["decode_tokens_per_sec"] == pytest.approx(200.0)
        st.reset()
        assert st.summary()["requests_submitted"] == 0


class TestExactness:
    def test_greedy_staggered_matches_offline(self, engine, tiny):
        """Four requests (one more than there are slots) joining mid-flight:
        every stream must equal offline greedy generate token for token."""
        _, m, params = tiny
        n = 10
        reqs = []
        for p in PROMPTS:
            reqs.append(engine.submit(p, max_new_tokens=n))
            time.sleep(0.015)  # staggered: later prompts join a live batch
        for p, r in zip(PROMPTS, reqs):
            _assert_matches_offline(r.result(timeout=120),
                                    _offline(m, params, p, n), n)

    def test_sampled_staggered_matches_offline(self, sampled_engine, tiny):
        """Same but sampled: per-request seeds must reproduce the offline
        rng chain (split-for-prefill, then split-per-step) exactly."""
        _, m, params = tiny
        n = 10
        reqs = []
        for i, p in enumerate(PROMPTS):
            reqs.append(sampled_engine.submit(p, max_new_tokens=n, seed=100 + i))
            time.sleep(0.015)
        for i, (p, r) in enumerate(zip(PROMPTS, reqs)):
            ref = _offline(m, params, p, n, seed=100 + i,
                           do_sample=True, temperature=0.9, top_k=50)
            _assert_matches_offline(r.result(timeout=120), ref, n)

    def test_max_new_tokens_one_completes_at_prefill(self, engine, tiny):
        _, m, params = tiny
        p = PROMPTS[0]
        r = engine.submit(p, max_new_tokens=1)
        out = r.result(timeout=120)
        assert out.shape == (1,)
        assert out[0] == _offline(m, params, p, 1)[0]

    def test_streaming_callback_order(self, engine):
        streamed = []
        r = engine.submit(PROMPTS[1], max_new_tokens=6,
                          on_token=streamed.append)
        out = r.result(timeout=120)
        assert streamed == list(out)


class TestZeroRecompile:
    def test_no_compiles_after_warmup(self, engine):
        """The acceptance bar: once warmed, admitting/retiring requests of
        DIFFERENT prompt lengths into different slots runs only the two
        existing executables — jax.monitoring's per-compile events must
        stay silent across a full staggered round."""
        with CompileWatcher() as watcher:
            reqs = []
            for i, p in enumerate(PROMPTS):
                reqs.append(engine.submit(p, max_new_tokens=6, seed=7 + i))
                time.sleep(0.01)
            for r in reqs:
                r.result(timeout=120)
        assert not watcher.events, (
            f"XLA recompiled after warmup: {watcher.events} — continuous "
            "batching must change mask/state contents, never program shapes")


class TestChunkedExactness:
    """Chunked prefill changes WHEN prompt KV is written, never what is
    written: every (chunk size, prompt length, sampling) cell must be
    token-identical to offline generate (so to every other chunk size) —
    including non-multiple tails, single-chunk prompts, and S=1."""

    CHUNKS = (4, 16)
    LENS = (1, 5, 16, 23, 31)  # < C, non-multiples, == C, and multi-chunk

    @pytest.fixture(scope="class")
    def engines(self, tiny):
        _, m, params = tiny
        engs = {}
        for C in self.CHUNKS:
            engs[C] = ServingEngine(m, params, max_slots=2, max_len=64,
                                    eos_token_id=EOS, prefill_chunk=C,
                                    prefix_cache_mb=0.0, warmup=False)
        yield engs
        for e in engs.values():
            if e.running:
                e.shutdown(drain=False)

    def test_greedy_chunk_matrix(self, engines, tiny):
        _, m, params = tiny
        n = 8
        rng = np.random.default_rng(11)
        for C in self.CHUNKS:
            for S in self.LENS:
                p = rng.integers(0, 256, size=(1, S)).astype(np.int32)
                before = engines[C].serving_metrics()["prefill_chunks"]
                got_c = engines[C].submit(p, max_new_tokens=n).result(timeout=120)
                chunks = engines[C].serving_metrics()["prefill_chunks"] - before
                assert chunks == -(-S // C), (S, C, chunks)  # really chunked
                _assert_matches_offline(got_c, _offline(m, params, p, n), n)

    def test_sampled_chunk_matrix(self, tiny):
        """Sampled decoding pins the rng protocol: every chunk call splits
        the SAME per-request key the way offline generate splits it once,
        so the first sampled token (and the whole decode chain after it)
        cannot depend on the chunk count."""
        _, m, params = tiny
        kw = dict(max_slots=2, max_len=64, eos_token_id=EOS, do_sample=True,
                  temperature=0.9, top_k=50, warmup=False)
        engs = {C: ServingEngine(m, params, prefill_chunk=C,
                                 prefix_cache_mb=0.0, **kw)
                for C in self.CHUNKS}
        try:
            n = 10
            rng = np.random.default_rng(12)
            for S in (5, 13, 21):
                p = rng.integers(0, 256, size=(1, S)).astype(np.int32)
                seed = 200 + S
                ref = _offline(m, params, p, n, seed=seed, do_sample=True,
                               temperature=0.9, top_k=50)
                for C, eng in engs.items():
                    before = eng.serving_metrics()["prefill_chunks"]
                    got = eng.submit(p, max_new_tokens=n,
                                     seed=seed).result(timeout=120)
                    chunks = eng.serving_metrics()["prefill_chunks"] - before
                    assert chunks == -(-S // C), (S, C, chunks)
                    _assert_matches_offline(got, ref, n)
        finally:
            for e in engs.values():
                if e.running:
                    e.shutdown(drain=False)


class TestZeroRecompileChunked:
    def test_one_chunk_executable_for_any_length_mix(self):
        """Prompt lengths on both sides of the chunk width (3..300) run
        after warmup with zero compile/trace events and exactly ONE cached
        executable each for prefill_chunk and decode (and restore_prefix,
        where there is one)."""
        cfg = LlamaConfig.tiny(use_flash_attention=False,
                               max_position_embeddings=512)
        m = LlamaForCausalLM(cfg)
        params = m.init_params(jax.random.PRNGKey(0), batch_size=2, seq_len=8)
        eng = ServingEngine(m, params, max_slots=2, max_len=384,
                            eos_token_id=EOS, prefill_chunk=128,
                            prefix_cache_mb=4.0)
        rng = np.random.default_rng(3)
        try:
            with CompileWatcher() as watcher:
                reqs = []
                for i, S in enumerate((3, 9, 140, 260, 300)):
                    p = rng.integers(0, 256, size=(1, S)).astype(np.int32)
                    reqs.append(eng.submit(p, max_new_tokens=6, seed=i))
                    time.sleep(0.01)
                for r in reqs:
                    r.result(timeout=300)
        finally:
            eng.shutdown(drain=False)
        assert not watcher.events, (
            f"XLA recompiled after warmup: {watcher.events} — chunked "
            "prefill must serve every prompt length with the one "
            "fixed-shape executable")
        assert eng._prefill_chunk._cache_size() == 1
        # The private prefix cache restores by page-table aliasing on the
        # host — it compiles NO restore program (steady state is two warm
        # executables); only an external cache pins a third.
        assert eng._restore_prefix is None
        assert eng._decode._cache_size() == 1


class TestChunkedScheduling:
    def test_decode_ticks_between_prefill_chunks(self):
        """Acceptance: chunked admission must not stall active streams —
        while a 12-chunk prompt prefills (admission -> first token),
        an already-decoding stream keeps committing tokens. Uses the
        deterministic per-token sleep model so the prefill window is wide
        on any host."""
        import bench

        cfg = LlamaConfig.tiny(use_flash_attention=False)
        m = bench._sleepy_llama_cls(step_ms=1.0, per_token=True)(cfg)
        params = m.init_params(jax.random.PRNGKey(0), batch_size=1, seq_len=8)
        eng = ServingEngine(m, params, max_slots=2, max_len=128,
                            prefill_chunk=8, prefill_chunks_per_tick=1,
                            prefix_cache_mb=0.0)
        try:
            stamps = []
            stream = eng.submit([[5, 6, 7, 8]], max_new_tokens=120,
                                ignore_eos=True,
                                on_token=lambda t: stamps.append(time.monotonic()))
            t0 = time.monotonic()
            while len(stamps) < 3:
                assert time.monotonic() - t0 < 60, "stream never decoded"
                time.sleep(0.001)
            long_req = eng.submit(np.arange(96, dtype=np.int32)[None, :],
                                  max_new_tokens=1, ignore_eos=True)
            assert long_req.wait(60)
            mid = [s for s in stamps
                   if long_req.admitted_at < s < long_req.first_token_at]
            assert len(mid) >= 3, (
                f"only {len(mid)} stream tokens during the long prompt's "
                "12-chunk prefill: decode ticks are not interleaving")
            assert eng.serving_metrics()["prefill_chunks"] >= 12
            stream.cancel()
            stream.wait(60)
        finally:
            eng.shutdown(drain=False)


class TestPrefixCacheServing:
    def test_repeat_prompt_restores_and_matches(self, tiny):
        """A 30-token prompt (4 chunks of 8) runs cold as 4 chunk calls;
        the identical prompt again admits in exactly ONE (the final chunk
        — cached blocks hold KV, not the first token's logits) with its
        3 full chunks restored, and the tokens are identical."""
        _, m, params = tiny
        eng = ServingEngine(m, params, max_slots=2, max_len=64,
                            eos_token_id=EOS, prefill_chunk=8,
                            prefix_cache_mb=4.0, warmup=False)
        try:
            p = np.arange(1, 31, dtype=np.int32)[None, :]
            out1 = eng.submit(p, max_new_tokens=6).result(timeout=120)
            s1 = eng.serving_metrics()
            assert s1["prefill_chunks"] == 4
            assert s1["prefix_cache_hit_chunks"] == 0
            assert len(eng.prefix_cache) == 3  # full chunks 0..2 stored
            out2 = eng.submit(p, max_new_tokens=6).result(timeout=120)
            s2 = eng.serving_metrics()
            assert np.array_equal(out1, out2)
            _assert_matches_offline(out1, _offline(m, params, p, 6), 6)
            assert s2["prefill_chunks"] == 5  # the repeat cost ONE chunk
            assert s2["prefix_cache_hit_chunks"] == 3
            assert s2["prefix_cache_hit_rate"] == 0.5  # 3 hits / 6 lookups
            assert s2["prefix_cache_restored_bytes"] > 0
            assert s2["prefix_cache_entries"] == 3
            assert s2["prefix_cache_bytes"] == eng.prefix_cache.nbytes > 0
        finally:
            eng.shutdown(drain=False)


class TestAdmissionScreening:
    def test_idle_pop_screens_cancelled_and_expired(self, tiny):
        """Regression: the idle path used to admit its popped request
        without re-checking cancel/deadline. A request cancelled (or
        expired) while the engine idles must finish WITHOUT taking a slot
        — no tokens, no admit counters."""
        _, m, params = tiny
        eng = ServingEngine(m, params, max_slots=1, max_len=64,
                            eos_token_id=EOS, warmup=False)
        try:
            r = Request([[1, 2]], max_new_tokens=4)
            r.cancel()
            eng.submit(request=r)
            assert r.wait(30)
            assert r.status is RequestStatus.CANCELLED and r.tokens == []
            r2 = eng.submit([[3]], max_new_tokens=4, timeout=0.0)
            assert r2.wait(30)
            assert r2.status is RequestStatus.TIMED_OUT and r2.tokens == []
            s = eng.serving_metrics()
            assert s["requests_admitted"] == 0
            assert s["requests_cancelled"] == 1
            assert s["requests_timed_out"] == 1
        finally:
            eng.shutdown(drain=False)

    def test_request_handles_are_single_use(self, engine):
        r = engine.submit([[2, 4]], max_new_tokens=2)
        r.result(timeout=120)
        with pytest.raises(ValueError, match="single-use"):
            engine.submit(request=r)
        fresh = Request([[6]], max_new_tokens=2)
        engine.submit(request=fresh)
        with pytest.raises(ValueError, match="single-use"):
            engine.submit(request=fresh)  # in flight: equally stale
        fresh.wait(120)


class TestSchedulingSemantics:
    @staticmethod
    def _wait_status(req, status, timeout=60.0):
        t0 = time.monotonic()
        while req.status is not status:
            if time.monotonic() - t0 > timeout:
                raise AssertionError(f"{req} never reached {status}")
            time.sleep(0.002)

    def test_backpressure_and_cancel(self, slow_engine):
        """max_slots=1, max_queued=1: the third concurrent submit must
        bounce (QueueFull + rejected counter); cancelling then reaps both
        the running and the queued request."""
        rejected_before = slow_engine.serving_metrics()["requests_rejected"]
        r_run = slow_engine.submit([[1]], max_new_tokens=30)
        self._wait_status(r_run, RequestStatus.RUNNING)
        r_queued = slow_engine.submit([[2]], max_new_tokens=30)
        with pytest.raises(QueueFull):
            slow_engine.submit([[3]], max_new_tokens=5)
        assert slow_engine.serving_metrics()["requests_rejected"] == rejected_before + 1

        r_queued.cancel()
        r_run.cancel()
        assert r_run.wait(60) and r_queued.wait(60)
        assert r_run.status is RequestStatus.CANCELLED
        assert r_queued.status is RequestStatus.CANCELLED
        assert len(r_run.tokens) < 30  # actually stopped mid-decode
        with pytest.raises(RuntimeError, match="cancelled"):
            r_queued.result()

    def test_timeout_running_request(self, slow_engine):
        r = slow_engine.submit([[1]], max_new_tokens=30, timeout=0.08)
        assert r.wait(60)
        assert r.status is RequestStatus.TIMED_OUT
        assert 1 <= len(r.tokens) < 30  # partial progress, then the deadline

    def test_timeout_queued_request(self, slow_engine):
        r_run = slow_engine.submit([[1]], max_new_tokens=30)
        self._wait_status(r_run, RequestStatus.RUNNING)
        r = slow_engine.submit([[2]], max_new_tokens=5, timeout=0.05)
        time.sleep(0.06)
        r_run.cancel()  # frees the slot; the expired request must NOT run
        assert r.wait(60)
        assert r.status is RequestStatus.TIMED_OUT and r.tokens == []
        r_run.wait(60)

    def test_error_isolation(self, engine, tiny):
        """A raising on_token callback fails ITS request only: the slot
        frees and concurrently decoding requests still finish exact."""
        _, m, params = tiny
        boom = RuntimeError("consumer went away")

        def bad_cb(tok):
            if bad_cb.n >= 2:
                raise boom
            bad_cb.n += 1

        bad_cb.n = 0
        r_bad = engine.submit(PROMPTS[0], max_new_tokens=10, on_token=bad_cb)
        r_ok = engine.submit(PROMPTS[2], max_new_tokens=10)
        assert r_bad.wait(120) and r_ok.wait(120)
        assert r_bad.status is RequestStatus.FAILED and r_bad.error is boom
        with pytest.raises(RuntimeError, match="failed"):
            r_bad.result()
        n = 10
        _assert_matches_offline(r_ok.result(), _offline(m, params, PROMPTS[2], n), n)

    def test_submit_validation(self, engine):
        with pytest.raises(ValueError, match="empty prompt"):
            engine.submit(np.zeros((1, 0), np.int32))
        with pytest.raises(ValueError, match="max_len"):
            engine.submit([[1, 2, 3]], max_new_tokens=62)  # 3 + 62 > 64


class TestLifecycle:
    def test_shutdown_drains_and_flushes_saves(self, tiny, monkeypatch):
        """shutdown(drain=True) finishes every accepted request, then blocks
        on async checkpoint saves before returning — a serving process is
        usually the process that just trained the weights it serves."""
        from accelerate_tpu import checkpointing

        flushed = []
        monkeypatch.setattr(checkpointing, "wait_for_saves",
                            lambda: flushed.append(True))
        _, m, params = tiny
        eng = ServingEngine(m, params, max_slots=2, max_len=64,
                            eos_token_id=EOS, warmup=False)
        reqs = [eng.submit(p, max_new_tokens=5) for p in PROMPTS[:3]]
        eng.shutdown(drain=True)
        assert flushed == [True]
        assert not eng.running
        for r in reqs:
            assert r.status is RequestStatus.COMPLETED and 1 <= len(r.tokens) <= 5
        with pytest.raises(RuntimeError, match="not accepting"):
            eng.submit([[1]])

    def test_shutdown_without_drain_cancels(self, tiny):
        import bench

        cfg = LlamaConfig.tiny(use_flash_attention=False)
        m = bench._sleepy_llama_cls(step_ms=10.0)(cfg)
        params = m.init_params(jax.random.PRNGKey(0), batch_size=1, seq_len=8)
        eng = ServingEngine(m, params, max_slots=1, max_len=32, warmup=False)
        r1 = eng.submit([[1]], max_new_tokens=30)
        r2 = eng.submit([[2]], max_new_tokens=30)
        t0 = time.monotonic()
        while r1.status is not RequestStatus.RUNNING:
            assert time.monotonic() - t0 < 60
            time.sleep(0.002)
        eng.shutdown(drain=False)
        assert r1.status is RequestStatus.CANCELLED
        assert r2.status is RequestStatus.CANCELLED

    def test_preemption_drain(self, tiny):
        """With an accelerator reporting preemption, the engine finishes
        what is decoding, cancels what is queued, and exits — flushing
        work inside the notice window instead of taking more."""
        _, m, params = tiny
        acc = types.SimpleNamespace(policy=None, mesh=None,
                                    preemption_requested=False)
        eng = ServingEngine(m, params, max_slots=3, max_len=64,
                            eos_token_id=EOS, accelerator=acc, warmup=False)
        running = [eng.submit(p, max_new_tokens=45, ignore_eos=True)
                   for p in PROMPTS[:3]]
        queued = eng.submit(PROMPTS[3], max_new_tokens=45)
        t0 = time.monotonic()
        while eng._slots.active_slots < 3:  # all three lanes decoding
            assert time.monotonic() - t0 < 120
            time.sleep(0.001)
        acc.preemption_requested = True
        t0 = time.monotonic()
        while eng.running:
            assert time.monotonic() - t0 < 120, "engine did not exit on preemption"
            time.sleep(0.005)
        for r in running:
            assert r.status is RequestStatus.COMPLETED and len(r.tokens) == 45
        assert queued.status is RequestStatus.CANCELLED
        with pytest.raises(RuntimeError, match="not accepting"):
            eng.submit([[1]])

    def test_warmup_takes_the_collectors_full_pass(self, tiny):
        """Set-up leaves the collector's full pass due; warm-up takes it at
        its end, so it does not stop the threads in the first seconds of
        serving: a generation-2 collection runs inside ``start()`` after
        the last warm-up request has finished, and the counters of the
        older generations start serving at zero."""
        import gc

        _, m, params = tiny
        eng = ServingEngine(m, params, max_slots=1, max_len=32,
                            eos_token_id=EOS, autostart=False)
        seen = []            # (generation, requests completed so far)

        def watch(phase, info):
            if phase == "stop":
                seen.append((info["generation"],
                             eng.stats.summary()["requests_completed"]))

        gc.callbacks.append(watch)
        try:
            eng.start()
            older = gc.get_count()[1:]
        finally:
            gc.callbacks.remove(watch)
            eng.shutdown(drain=False)
        # warm-up resets its stats before the pass: it counts none finished
        assert (2, 0) in seen[-3:], seen[-5:]
        assert older[1] == 0 and older[0] <= 1, older

    def test_rejects_model_without_kv_cache(self):
        import flax.linen as nn

        dense = nn.Dense(4)
        params = dense.init(jax.random.PRNGKey(0), np.zeros((1, 4), np.float32))["params"]
        with pytest.raises(TypeError, match="KV cache"):
            ServingEngine(dense, params, autostart=False)


class TestMetrics:
    def test_serving_metrics_coherent(self, engine):
        """Run after the exactness/streaming tests on the shared engine:
        the cumulative counters must describe a working service."""
        s = engine.serving_metrics()
        assert s["requests_admitted"] >= 4
        assert s["requests_completed"] >= 4
        assert s["requests_submitted"] >= s["requests_admitted"]
        assert s["ttft_ms"] > 0 and s["ttft_ms_p95"] >= s["ttft_ms_p50"] > 0
        assert s["decode_tokens_per_sec"] > 0
        assert 0 < s["slot_occupancy"] <= 1.0
        assert 0 < s["batch_efficiency"] <= s["slot_occupancy"]
        assert s["tokens_emitted"] == s["decode_tokens"] + s["requests_admitted"]

    def test_accelerator_wiring(self, tiny):
        """An engine built with accelerator= shares the accelerator's
        ServingStats, so Accelerator.log(include_serving=True) and
        serving_metrics() see this engine without extra plumbing."""
        from accelerate_tpu import Accelerator
        from accelerate_tpu.tracking import with_serving_metrics

        acc = Accelerator()
        acc.serving_stats.record_submit(queue_depth=0)
        assert acc.serving_metrics()["requests_submitted"] == 1
        payload = with_serving_metrics({"loss": 1.0}, acc.serving_stats)
        assert payload["loss"] == 1.0
        assert payload["serving/requests_submitted"] == 1
        _, m, params = tiny
        eng = ServingEngine(m, params, max_slots=1, max_len=64,
                            accelerator=acc, autostart=False)
        assert eng.stats is acc.serving_stats


@pytest.mark.slow
class TestSoak:
    def test_sustained_mixed_load(self, engine, tiny):
        """Soak: 40 mixed-length requests with jittered arrivals; every
        stream completes, every stream is exact, and the counters balance."""
        _, m, params = tiny
        rng = np.random.default_rng(0)
        before = engine.serving_metrics()
        work = []
        for i in range(40):
            S = int(rng.integers(1, 24))
            n = int(rng.integers(1, 20))
            p = rng.integers(0, 256, size=(1, S)).astype(np.int32)
            work.append((p, n, engine.submit(p, max_new_tokens=n)))
            time.sleep(float(rng.random()) * 0.004)
        for p, n, r in work:
            _assert_matches_offline(r.result(timeout=300),
                                    _offline(m, params, p, n), n)
        after = engine.serving_metrics()
        assert after["requests_completed"] - before["requests_completed"] == 40
        assert after["requests_admitted"] - before["requests_admitted"] == 40

    def test_sustained_mixed_load_chunked_with_prefix_hits(self, tiny):
        """Chunked soak: 30 jittered requests drawn from a small prompt
        pool (so multi-chunk prompts repeat and the prefix cache actually
        fires mid-load); every stream exact, hits observed."""
        _, m, params = tiny
        eng = ServingEngine(m, params, max_slots=3, max_len=64,
                            eos_token_id=EOS, prefill_chunk=4,
                            prefix_cache_mb=2.0)
        try:
            rng = np.random.default_rng(1)
            pool = [rng.integers(0, 256, size=(1, S)).astype(np.int32)
                    for S in (1, 3, 6, 9, 14, 23)]
            work = []
            for _ in range(30):
                p = pool[int(rng.integers(len(pool)))]
                n = int(rng.integers(1, 16))
                work.append((p, n, eng.submit(p, max_new_tokens=n)))
                time.sleep(float(rng.random()) * 0.004)
            for p, n, r in work:
                _assert_matches_offline(r.result(timeout=300),
                                        _offline(m, params, p, n), n)
            s = eng.serving_metrics()
            assert s["requests_completed"] == 30
            assert s["prefix_cache_hit_chunks"] > 0
        finally:
            eng.shutdown(drain=False)


class TestLifecycleEdges:
    """Lifecycle races hardened for the gateway: submits outside the
    accepting window fail fast, and producers blocked on a full admission
    queue are woken (with an error) when the engine stops instead of
    hanging for their full block_timeout."""

    def test_submit_before_start_raises_immediately(self, tiny):
        _, m, params = tiny
        eng = ServingEngine(m, params, max_slots=1, max_len=32,
                            eos_token_id=EOS, autostart=False, warmup=False)
        try:
            with pytest.raises(RuntimeError, match="not accepting"):
                eng.submit([[1, 2]], max_new_tokens=2)
            eng.start()
            r = eng.submit([[1, 2]], max_new_tokens=2)
            assert r.wait(120)
        finally:
            eng.shutdown(drain=False)
        with pytest.raises(RuntimeError, match="not accepting"):
            eng.submit([[1, 2]], max_new_tokens=2)

    def test_submit_after_shutdown_raises_even_with_block(self, tiny):
        """block=True must not buy a stopped engine a grace period: the
        error is immediate, not a block_timeout-long hang."""
        _, m, params = tiny
        eng = ServingEngine(m, params, max_slots=1, max_len=32,
                            eos_token_id=EOS, warmup=False)
        eng.shutdown(drain=True)
        t0 = time.monotonic()
        with pytest.raises(RuntimeError, match="not accepting"):
            eng.submit([[1]], max_new_tokens=2, block=True, block_timeout=30)
        assert time.monotonic() - t0 < 5.0

    def test_queue_close_wakes_blocked_put(self):
        """Unit: a producer parked in put(block=True) on a FULL queue is
        woken by close() with QueueClosed — not left to ride out its
        timeout; items already accepted stay drainable."""
        q = AdmissionQueue(max_queued=1)
        q.put("held")
        woke = {}

        def producer():
            t0 = time.monotonic()
            try:
                q.put("late", block=True, timeout=30.0)
                woke["outcome"] = "accepted"
            except QueueClosed:
                woke["outcome"] = "closed"
            except QueueFull:
                woke["outcome"] = "full"
            woke["elapsed"] = time.monotonic() - t0

        t = threading.Thread(target=producer)
        t.start()
        time.sleep(0.05)  # parked in the condition wait
        q.close()
        t.join(timeout=10)
        assert not t.is_alive()
        assert woke["outcome"] == "closed"
        assert woke["elapsed"] < 5.0
        assert q.get_nowait() == "held"  # close() does not eat the backlog
        with pytest.raises(QueueClosed):
            q.put("post-close")

    @pytest.mark.slow
    def test_engine_stop_wakes_blocked_submit(self):
        """End-to-end: a submit(block=True) stuck behind a full admission
        queue errors out promptly when the engine shuts down underneath
        it."""
        import bench

        cfg = LlamaConfig.tiny(use_flash_attention=False)
        m = bench._sleepy_llama_cls(step_ms=10.0)(cfg)
        params = m.init_params(jax.random.PRNGKey(0), batch_size=1, seq_len=8)
        eng = ServingEngine(m, params, max_slots=1, max_len=32, max_queued=1)
        r_run = eng.submit([[1]], max_new_tokens=30)
        deadline = time.monotonic() + 60
        while r_run.status is not RequestStatus.RUNNING \
                and time.monotonic() < deadline:
            time.sleep(0.005)  # in its slot -> the 1-deep queue is free
        r_queued = eng.submit([[2]], max_new_tokens=30)
        outcome = {}

        def producer():
            t0 = time.monotonic()
            try:
                eng.submit([[3]], max_new_tokens=5, block=True,
                           block_timeout=60.0)
                outcome["kind"] = "accepted"
            except QueueFull:
                outcome["kind"] = "full"
            except RuntimeError:
                outcome["kind"] = "stopped"
            outcome["elapsed"] = time.monotonic() - t0

        t = threading.Thread(target=producer)
        t.start()
        time.sleep(0.1)  # parked in the queue's not_full wait
        eng.shutdown(drain=False)
        t.join(timeout=15)
        assert not t.is_alive(), "blocked submit hung past engine shutdown"
        assert outcome["kind"] == "stopped"
        assert outcome["elapsed"] < 10.0
        for r in (r_run, r_queued):
            assert r.wait(60)
            assert r.status in (RequestStatus.CANCELLED, RequestStatus.FAILED)

    def test_prefix_cache_oversize_put_rejected_without_eviction(self):
        """An oversize block must bounce at the door — never by evicting
        the whole (useful) cache first."""
        cache = PrefixCache(capacity_bytes=1024)
        cache.put(("a",), "blockA", 400)
        cache.put(("b",), "blockB", 400)
        assert cache.oversize_rejects == 0
        cache.put(("huge",), "big", 4096)  # > whole capacity
        assert cache.oversize_rejects == 1
        assert cache.match([("huge",)]) == []
        # The resident entries survived the oversize attempt untouched.
        assert len(cache) == 2 and cache.nbytes == 800
        assert cache.match([("a",)]) == ["blockA"]
        assert cache.match([("b",)]) == ["blockB"]
        assert cache.evictions == 0
        cache.clear()
        assert cache.oversize_rejects == 0


class TestConcurrentSubmit:
    @pytest.mark.slow
    def test_32_threads_no_lost_or_duplicated_requests(self, tiny):
        """32 producer threads x 4 submits each hammer one engine; queue
        bounce (QueueFull) is legal under the bounded queue, but every
        ACCEPTED request must complete exactly once with an exact stream,
        and the admission counters must balance to the thread-side tally."""
        _, m, params = tiny
        eng = ServingEngine(m, params, max_slots=3, max_len=64,
                            eos_token_id=EOS, max_queued=256)
        n_threads, per_thread, n_tok = 32, 4, 6
        refs = {i: _offline(m, params, p, n_tok)
                for i, p in enumerate(PROMPTS)}
        accepted = [[] for _ in range(n_threads)]
        bounced = [0] * n_threads
        start = threading.Barrier(n_threads)

        def worker(tid):
            start.wait()
            for j in range(per_thread):
                pi = (tid + j) % len(PROMPTS)
                try:
                    r = eng.submit(PROMPTS[pi], max_new_tokens=n_tok)
                except QueueFull:
                    bounced[tid] += 1
                    continue
                accepted[tid].append((pi, r))

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(n_threads)]
        before = eng.serving_metrics()
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            assert not any(t.is_alive() for t in threads)
            flat = [ar for per in accepted for ar in per]
            for pi, r in flat:
                assert r.wait(300)
                assert r.status is RequestStatus.COMPLETED
                _assert_matches_offline(r.tokens, refs[pi], n_tok)
            after = eng.serving_metrics()
            n_acc = len(flat)
            n_rej = sum(bounced)
            assert n_acc + n_rej == n_threads * per_thread
            assert after["requests_submitted"] - before["requests_submitted"] == n_acc
            assert after["requests_completed"] - before["requests_completed"] == n_acc
            assert after["requests_rejected"] - before["requests_rejected"] == n_rej
            # One terminal transition per handle: result() replays, and
            # output_ids() is exactly prompt + the streamed tokens.
            for pi, r in flat:
                full = r.output_ids()
                S = PROMPTS[pi].shape[1]
                assert full.shape == (1, S + len(r.tokens))
                assert list(full[0, S:]) == [int(t) for t in r.tokens]
        finally:
            eng.shutdown(drain=False)


@pytest.mark.parametrize("option", ["paged=False", "async_ticks=False",
                                    "prefill_chunk=None", "serve --no-paged"])
def test_removed_engine_options_are_refused(option, tiny):
    """The engine has one KV layout (the page pool), one prefill (chunked)
    and one tick loop (one tick ahead): the options that used to select
    the others are gone, not ignored."""
    _, m, params = tiny
    kw = dict(max_slots=1, max_len=16, autostart=False)
    if option == "serve --no-paged":
        from accelerate_tpu.commands.serve import serve_command_parser

        parser = serve_command_parser()
        parser.parse_args(["--model", "tiny"])  # the flag alone is refused
        with pytest.raises(SystemExit) as exc:
            parser.parse_args(["--model", "tiny", "--no-paged"])
        assert exc.value.code == 2
    elif option == "prefill_chunk=None":
        with pytest.raises(ValueError, match="only prefill"):
            ServingEngine(m, params, prefill_chunk=None, **kw)
    else:
        name = option.split("=")[0]
        with pytest.raises(TypeError, match=name):
            ServingEngine(m, params, **{name: False}, **kw)
