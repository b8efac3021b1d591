"""Paged KV-cache memory manager + speculative decoding (serving engine).

The acceptance-critical properties pinned here:

* PAGED == OFFLINE — paging decides WHERE KV rows live (a global page
  pool indexed through a per-slot page table), never what is read or
  written: every cell of the greedy/sampled/eos/adapter/failover matrix
  must be token-identical to offline ``generation.generate``.
* ZERO RECOMPILES — page allocation, frees, preemption and prefix
  aliasing are HOST work (the table is traced integer data), so a
  warmed paged engine serves a staggered prompt-length mix with the
  compile listener silent and exactly TWO warm executables (chunk +
  decode; its private alias cache restores by page-table writes and
  compiles NO restore program).  A speculative engine adds exactly one
  more (`_spec`) and stays silent too.
* POOL EXHAUSTION — when live streams outgrow the pool, the newest
  victim is preempted back to the queue and later resumes FROM SCRATCH
  as a longer prompt; its final stream is still bit-identical.
* ALIAS PREFIX CACHE — a repeat prompt admits by bumping page refcounts
  (``prefix_alias_chunks``), never by copying KV.
* SLIDING WINDOW — pages wholly behind the attention window are freed
  mid-stream (page-lifetime policy), with no effect on the tokens.
* VALIDATION — impossible requests and incoherent constructor combos
  fail fast with actionable errors, not deadlocks or silent fallbacks.
"""

import os
import sys
import time

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from accelerate_tpu import generation  # noqa: E402
from accelerate_tpu.adapters import (  # noqa: E402
    AdapterBank,
    LoRAConfig,
    init_lora_params,
    merge_adapter,
)
from accelerate_tpu.adapters.lora import (  # noqa: E402
    adapter_module_paths,
    _get_path,
)
from accelerate_tpu.models.llama import LlamaConfig, LlamaForCausalLM  # noqa: E402
from accelerate_tpu.serving import (  # noqa: E402
    PrefixCache,
    ReplicaSet,
    RequestStatus,
    ServingEngine,
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
    """Offline reference; ``eos=None`` mirrors the engine's ignore_eos."""
    rng = None if seed is None else jax.random.PRNGKey(seed)
    out = generation.generate(m, params, prompt, max_new_tokens=n,
                              eos_token_id=eos, rng=rng, **kw)
    return np.asarray(out)[0, prompt.shape[1]:]


def _assert_matches_offline(got, ref, n):
    """Engine stops AT eos; offline keeps the shape and pads with eos."""
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


class TestPagedVsDenseExactness:
    """Greedy and sampled streams served out of the page pool must be
    bit-identical to offline generate."""

    N = 24

    @pytest.fixture(scope="class")
    def engines(self, tiny):
        _, m, params = tiny
        kw = dict(max_slots=3, max_len=64, eos_token_id=EOS,
                  prefill_chunk=8, prefix_cache_mb=0.0)
        engs = {"paged": ServingEngine(m, params, **kw)}
        yield engs
        for e in engs.values():
            if e.running:
                e.shutdown(drain=False)

    @pytest.mark.parametrize("seed", [None, 11])
    def test_matrix_matches_dense_and_offline(self, tiny, engines, seed):
        _, m, params = tiny
        refs = [_offline(m, params, p, self.N, seed=seed) for p in PROMPTS]
        reqs = []
        for p in PROMPTS:  # staggered: joins exercise the page table
            reqs.append(engines["paged"].submit(p, max_new_tokens=self.N,
                                                seed=seed))
            time.sleep(0.01)
        for r, ref in zip(reqs, refs):
            _assert_matches_offline(r.result(timeout=120), ref, self.N)

    def test_eos_latch_paged(self, tiny, engines):
        """A stream that hits EOS mid-flight stops exactly where offline
        latches, with the request's pages released back to the pool."""
        _, m, params = tiny
        eng = engines["paged"]
        free0 = eng.free_pages
        prompt = np.array([[EOS, 3, EOS, 5]], np.int32)
        r = eng.submit(prompt, max_new_tokens=self.N)
        got = r.result(timeout=120)
        _assert_matches_offline(got, _offline(m, params, prompt, self.N),
                                self.N)
        deadline = time.monotonic() + 10
        while eng.free_pages < free0 and time.monotonic() < deadline:
            time.sleep(0.005)
        assert eng.free_pages == free0, "retired request leaked pages"

    def test_adapters_on_paged_engine(self, tiny):
        """Multi-tenant LoRA over the paged pool: each stream matches
        offline generate under its tenant's MERGED weights."""
        _, m, params = tiny
        ad = _nonzero_adapter(params, rank=4, seed=5)
        bank = AdapterBank(params, config=LoRAConfig(rank=4), max_adapters=3)
        bank.register("a", ad)
        eng = ServingEngine(m, params, max_slots=2, max_len=64,
                            eos_token_id=EOS, prefill_chunk=8, adapters=bank)
        try:
            n = 16
            refs = {"a": merge_adapter(params, ad), None: params}
            reqs = [(name, eng.submit(p, max_new_tokens=n, adapter=name))
                    for name, p in zip(["a", None, "a"], PROMPTS)]
            for (name, r), p in zip(reqs, PROMPTS):
                _assert_matches_offline(r.result(timeout=120),
                                        _offline(m, refs[name], p, n), n)
        finally:
            eng.shutdown(drain=False)

    def test_failover_streams_stay_token_exact(self, tiny):
        """Killing a replica mid-stream: survivors re-serve the moved
        requests from scratch on their own page pools, bit-identically."""
        _, m, params = tiny
        import bench

        sleepy = bench._sleepy_llama_cls(step_ms=15.0)(LlamaConfig.tiny(
            use_flash_attention=False))
        rs = ReplicaSet.from_factory(
            lambda: ServingEngine(sleepy, params, max_slots=4, max_len=64,
                                  eos_token_id=EOS, prefill_chunk=16), 2)
        n = 24
        refs = [_offline(sleepy, params, p, n) for p in PROMPTS]
        try:
            reqs = [rs.submit(p, max_new_tokens=n) for p in PROMPTS]
            deadline = time.monotonic() + 60
            while (min(len(r.tokens) for r in reqs) < 3
                   and time.monotonic() < deadline):
                time.sleep(0.005)
            assert min(len(r.tokens) for r in reqs) >= 3, "streams stalled"
            victim = reqs[0].replica_trail[0]
            rs.kill_replica(victim)
            for r in reqs:
                assert r.wait(timeout=120)
            for r, ref in zip(reqs, refs):
                assert r.status is RequestStatus.COMPLETED
                _assert_matches_offline(r.tokens, ref, n)
            assert any(r.replica_trail[0] == victim for r in reqs)
        finally:
            rs.shutdown()


class TestZeroRecompilePaged:
    def test_paged_steady_state_is_two_executables(self, tiny):
        """Admitting/retiring a staggered prompt-length mix — including a
        repeat prompt restored by page-table ALIASING — must run only
        the warm chunk + decode executables: page allocation is host
        work, and the private paged prefix cache compiles no restore
        program at all."""
        _, m, params = tiny
        eng = ServingEngine(m, params, max_slots=3, max_len=64,
                            eos_token_id=EOS, prefill_chunk=8,
                            prefix_cache_mb=4.0)
        rng = np.random.default_rng(9)
        long = rng.integers(0, 256, size=(1, 33)).astype(np.int32)
        try:
            with CompileWatcher() as watcher:
                reqs = []
                # tail repeat of the multi-chunk prompt -> alias restore
                for p in PROMPTS + [long, long]:
                    reqs.append(eng.submit(p, max_new_tokens=6, seed=3))
                    time.sleep(0.01)
                for r in reqs:
                    r.result(timeout=120)
        finally:
            eng.shutdown(drain=False)
        assert not watcher.events, (
            f"XLA recompiled after warmup: {watcher.events} — paging must "
            "move page-table CONTENTS, never program shapes")
        assert eng._prefill_chunk._cache_size() == 1
        assert eng._restore_prefix is None  # alias restores are host writes
        assert eng._decode._cache_size() == 1
        assert eng.stats.summary()["prefix_alias_chunks"] >= 1

    def test_speculative_adds_exactly_one_executable(self, tiny):
        _, m, params = tiny
        eng = ServingEngine(m, params, max_slots=2, max_len=64,
                            eos_token_id=EOS, prefill_chunk=8,
                            prefix_cache_mb=0.0,
                            draft_model=m, draft_params=params,
                            spec_tokens=4)
        try:
            with CompileWatcher() as watcher:
                reqs = []
                for p in PROMPTS:
                    reqs.append(eng.submit(p, max_new_tokens=8))
                    time.sleep(0.01)
                for r in reqs:
                    r.result(timeout=120)
        finally:
            eng.shutdown(drain=False)
        assert not watcher.events, (
            f"XLA recompiled after warmup: {watcher.events} — draft length "
            "and acceptance count are data, not shapes")
        assert eng._prefill_chunk._cache_size() == 1
        assert eng._spec._cache_size() == 1
        # a spec engine never runs the plain decode tick — every decode
        # goes through _spec, so _decode stays cold (<= 1 from warmup).
        assert eng._decode._cache_size() <= 1


class TestPoolExhaustionPreemption:
    def test_preempted_stream_resumes_token_exact(self, tiny):
        """Two streams whose worst-case footprints each fit the pool but
        together exceed it: the engine must preempt (not deadlock, not
        corrupt) and the loser's final stream — re-served from scratch
        as a longer prompt — must stay bit-identical to offline."""
        _, m, params = tiny
        eng = ServingEngine(m, params, max_slots=2, max_len=64,
                            eos_token_id=EOS, prefill_chunk=8,
                            prefix_cache_mb=0.0, max_pages=10)
        n = 40
        try:
            assert eng.total_pages == 10
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
            assert eng.page_pool_metrics()["preemptions"] >= 1
        finally:
            eng.shutdown(drain=False)


class TestAliasPrefixCache:
    def test_repeat_prompt_admits_by_refcount(self, tiny):
        """Paged prefix hits bump page refcounts instead of copying KV:
        the repeat admission reports alias chunks and the two streams
        are bit-identical."""
        _, m, params = tiny
        eng = ServingEngine(m, params, max_slots=2, max_len=96,
                            eos_token_id=EOS, prefill_chunk=8,
                            prefix_cache_mb=4.0)
        rng = np.random.default_rng(0)
        prompt = rng.integers(0, 256, size=(1, 33)).astype(np.int32)
        try:
            a = np.asarray(eng.submit(prompt, max_new_tokens=8,
                                      ignore_eos=True).result(timeout=120))
            b = np.asarray(eng.submit(prompt, max_new_tokens=8,
                                      ignore_eos=True).result(timeout=120))
            assert np.array_equal(a, b)
            s = eng.stats.summary()
            # 33 tokens = 4 full chunks of 8; all restorable by aliasing.
            assert s["prefix_alias_chunks"] >= 2, s
            assert s["prefix_cache_hit_chunks"] >= 2, s
        finally:
            eng.shutdown(drain=False)

    def test_external_cache_keeps_host_copy_path(self, tiny):
        """An EXTERNAL (fleet-shared) PrefixCache still stores host-copy
        blocks — slice-portable — and the paged engine compiles the
        restore executable for it."""
        _, m, params = tiny
        shared = PrefixCache(4 * 1024 * 1024)
        eng = ServingEngine(m, params, max_slots=2, max_len=96,
                            eos_token_id=EOS, prefill_chunk=8,
                            prefix_cache=shared)
        rng = np.random.default_rng(1)
        prompt = rng.integers(0, 256, size=(1, 24)).astype(np.int32)
        try:
            a = np.asarray(eng.submit(prompt, max_new_tokens=8,
                                      ignore_eos=True).result(timeout=120))
            b = np.asarray(eng.submit(prompt, max_new_tokens=8,
                                      ignore_eos=True).result(timeout=120))
            assert np.array_equal(a, b)
            assert eng._restore_prefix is not None
            assert eng.stats.summary()["prefix_cache_hit_chunks"] >= 2
        finally:
            eng.shutdown(drain=False)


class TestSlidingWindowPageLifetime:
    def test_windowed_model_frees_dead_pages(self, tiny):
        """With a uniform sliding window, a page whose last position falls
        wholly behind the window can never be attended again — the
        engine drops it mid-stream.  Tokens must still match offline
        (the window MASK, not page residency, defines the math)."""
        _, _, params = tiny
        cfg = LlamaConfig.tiny(use_flash_attention=False, sliding_window=16)
        m = LlamaForCausalLM(cfg)
        eng = ServingEngine(m, params, max_slots=2, max_len=64,
                            eos_token_id=EOS, prefill_chunk=8,
                            prefix_cache_mb=0.0)
        assert eng._page_window == 16
        n = 40
        prompt = np.array([[3, 5, 7, 11, 2, 8, 6, 4]], np.int32)
        peak = []
        try:
            r = eng.submit(prompt, max_new_tokens=n, ignore_eos=True,
                           on_token=lambda t: peak.append(
                               eng.page_pool_metrics()["pages_used"]))
            got = np.asarray(r.result(timeout=120))
            ref = _offline(m, params, prompt, n, eos=None)
            assert np.array_equal(got, ref), (got, ref)
            # 8 + 40 = 48 positions = 6 pages of 8 if nothing were freed;
            # a 16-token window keeps at most 3 live (+1 being written).
            assert max(peak) <= 4, peak
        finally:
            eng.shutdown(drain=False)


class TestSpeculativeDecoding:
    def test_spec_streams_are_token_identical(self, tiny):
        """Greedy speculative output must be bit-identical to the plain
        engine and offline — acceptance only SKIPS ticks, never changes
        tokens — including the eos latch, and must actually accept."""
        _, m, params = tiny
        eng = ServingEngine(m, params, max_slots=3, max_len=64,
                            eos_token_id=EOS, prefill_chunk=8,
                            prefix_cache_mb=0.0,
                            draft_model=m, draft_params=params,
                            spec_tokens=4)
        n = 24
        try:
            refs = [_offline(m, params, p, n) for p in PROMPTS]
            reqs = []
            for p in PROMPTS:
                reqs.append(eng.submit(p, max_new_tokens=n))
                time.sleep(0.01)
            for r, ref in zip(reqs, refs):
                _assert_matches_offline(r.result(timeout=120), ref, n)
            s = eng.stats.summary()
            assert s["spec_ticks"] > 0 and s["spec_accepted_tokens"] > 0, s
            assert s["spec_tokens_per_tick"] > 1.0, (
                "speculation must commit more than one token per verify "
                f"on average (stats: {s})")
        finally:
            eng.shutdown(drain=False)

    def test_spec_validation(self, tiny):
        """Only structural impossibilities reject now: the sampled /
        adapter / prefix-cache / mesh gates of PR 7 are gone (that lift
        is this PR's point) and must NOT raise."""
        _, m, params = tiny
        spec = dict(draft_model=m, draft_params=params)
        with pytest.raises(ValueError, match="spec_tokens"):
            ServingEngine(m, params, prefill_chunk=8, spec_tokens=0,
                          autostart=False, warmup=False, **spec)
        with pytest.raises(ValueError, match="mutually exclusive"):
            ServingEngine(m, params, prefill_chunk=8, spec_lookup=3,
                          autostart=False, warmup=False, **spec)
        with pytest.raises(ValueError, match="spec_lookup"):
            ServingEngine(m, params, prefill_chunk=8, spec_lookup=0,
                          autostart=False, warmup=False)
        # Previously-rejected configurations now construct cleanly.
        bank = AdapterBank(params, config=LoRAConfig(rank=4), max_adapters=2)
        for kw in (dict(do_sample=True, temperature=0.8),
                   dict(adapters=bank),
                   dict(prefix_cache=PrefixCache(1024 * 1024))):
            eng = ServingEngine(m, params, prefill_chunk=8, autostart=False,
                                warmup=False, **spec, **kw)
            assert eng._spec_mode == "draft"
        eng = ServingEngine(m, params, prefill_chunk=8, spec_lookup=3,
                            autostart=False, warmup=False)
        assert eng._spec_mode == "lookup"


class TestUniversalSpeculation:
    """The exactness matrix for the universal ``_spec`` executable: each
    previously-rejected mode (sampled, adapter tenant, prefix-cache,
    draft-free prompt lookup — tp=2 lives in test_serving_mesh.py) must
    emit exactly what its non-speculative twin emits, and the whole
    matrix must run through ONE warm ``_spec`` program with the compile
    listener silent."""

    N = 24
    BASE = dict(max_slots=3, max_len=64, eos_token_id=EOS, prefill_chunk=8,
                prefix_cache_mb=0.0)
    # Spans one-chunk and multi-chunk admission; avoids EOS.
    LONG = np.arange(1, 20, dtype=np.int32)[None] % 6 + 8

    def _run(self, eng, prompts=PROMPTS, **kw):
        reqs = []
        for p in prompts:
            reqs.append(eng.submit(p, max_new_tokens=self.N, **kw))
            time.sleep(0.01)
        return [np.asarray(r.result(timeout=120)) for r in reqs]

    def _pair(self, m, params, spec_kw, base_kw=None, **submit_kw):
        """(spec streams, non-spec streams) over the same traffic."""
        base_kw = dict(self.BASE, **(base_kw or {}))
        prompts = submit_kw.pop("prompts", PROMPTS)
        e1 = ServingEngine(m, params, **base_kw, **spec_kw)
        e0 = ServingEngine(m, params, **base_kw)
        try:
            a = self._run(e1, prompts=prompts, **submit_kw)
            b = self._run(e0, prompts=prompts, **submit_kw)
            assert e1.stats.summary()["spec_ticks"] > 0
        finally:
            e1.shutdown(drain=False)
            e0.shutdown(drain=False)
        return a, b

    def test_sampled_spec_is_exact_when_determinized(self, tiny):
        """do_sample + top_k=1 concentrates the warped law on one token,
        so the rejection-sampling accept path (the SAMPLED branch of
        speculative_emit, not the greedy one) must reproduce the dense
        sampled stream bit-exactly — any drift is an accept-rule or
        rng-discipline bug that randomness would have hidden."""
        _, m, params = tiny
        a, b = self._pair(m, params,
                          dict(draft_model=m, draft_params=params,
                               spec_tokens=4),
                          base_kw=dict(do_sample=True, top_k=1), seed=3)
        for x, y in zip(a, b):
            assert np.array_equal(x, y), (x, y)

    def test_sampled_spec_is_seed_deterministic(self, tiny):
        """With temperature the spec stream cannot be compared token-wise
        to the dense one (same law, different rng consumption), but a
        fixed per-request seed must still make it reproducible: the
        per-slot rng rows split exactly once per verify tick."""
        _, m, params = tiny
        kw = dict(self.BASE, do_sample=True, temperature=0.8,
                  draft_model=m, draft_params=params, spec_tokens=4)
        outs = []
        for _ in range(2):
            eng = ServingEngine(m, params, **kw)
            try:
                outs.append(self._run(eng, seed=5))
            finally:
                eng.shutdown(drain=False)
        for x, y in zip(*outs):
            assert np.array_equal(x, y), (x, y)

    def test_adapter_spec_matches_nonspec(self, tiny):
        """A tenant's speculative stream equals its non-speculative one:
        the per-slot adapter row gathers inside the verify while the
        draft stays base-weight (proposals steer acceptance, never the
        emitted law)."""
        _, m, params = tiny
        ad = _nonzero_adapter(params, rank=4, seed=1)
        banks = []
        for _ in range(2):
            bank = AdapterBank(params, config=LoRAConfig(rank=4),
                               max_adapters=2)
            bank.register("t1", ad)
            banks.append(bank)
        e1 = ServingEngine(m, params, adapters=banks[0], **self.BASE,
                           draft_model=m, draft_params=params, spec_tokens=4)
        e0 = ServingEngine(m, params, adapters=banks[1], **self.BASE)
        try:
            a = self._run(e1, adapter="t1") + self._run(e1)  # tenant + base
            b = self._run(e0, adapter="t1") + self._run(e0)
        finally:
            e1.shutdown(drain=False)
            e0.shutdown(drain=False)
        for x, y in zip(a, b):
            assert np.array_equal(x, y), (x, y)

    def test_prefix_hit_spec_matches_cold(self, tiny):
        """A prefix-cache engine speculates: the alias-restored slot's
        draft KV is rebuilt by the draft-only chunk program, and both the
        cold and the hit stream equal the non-speculative stream."""
        _, m, params = tiny
        kw = dict(max_slots=3, max_len=64, eos_token_id=EOS,
                  prefill_chunk=8)
        e1 = ServingEngine(m, params, prefix_cache_mb=4.0, **kw,
                           draft_model=m, draft_params=params, spec_tokens=4)
        e0 = ServingEngine(m, params, prefix_cache_mb=0.0, **kw)
        try:
            cold = self._run(e1, prompts=[self.LONG])
            hit = self._run(e1, prompts=[self.LONG])
            ref = self._run(e0, prompts=[self.LONG])
            s = e1.stats.summary()
            assert s["prefix_alias_chunks"] >= 1, s
        finally:
            e1.shutdown(drain=False)
            e0.shutdown(drain=False)
        assert np.array_equal(cold[0], ref[0]), (cold, ref)
        assert np.array_equal(hit[0], ref[0]), (hit, ref)

    def test_lookup_spec_matches_nonspec(self, tiny):
        """Draft-free prompt-lookup speculation: host n-gram proposals
        through the verify-only program, token-identical to plain greedy
        even when every proposal is a miss."""
        _, m, params = tiny
        rep = np.array([[4, 5, 6, 4, 5, 6, 4, 5, 6, 4, 5]], np.int32)
        a, b = self._pair(m, params, dict(spec_lookup=2, spec_tokens=4),
                          prompts=PROMPTS + [rep])
        for x, y in zip(a, b):
            assert np.array_equal(x, y), (x, y)

    def test_universal_spec_zero_recompiles(self, tiny):
        """One engine wearing EVERY lifted constraint at once — sampling
        (top_k=1), an adapter bank, an alias prefix cache, paged draft KV
        — serves mixed traffic (tenant + base, cold + prefix-hit) through
        ONE warm ``_spec`` and ONE warm draft-rebuild program, compile
        listener silent: adapter rows, page tables, proposals, and
        acceptance counts are all data, never shapes."""
        _, m, params = tiny
        bank = AdapterBank(params, config=LoRAConfig(rank=4),
                           max_adapters=2)
        bank.register("t1", _nonzero_adapter(params, rank=4, seed=1))
        eng = ServingEngine(m, params, max_slots=3, max_len=64,
                            eos_token_id=EOS, prefill_chunk=8,
                            prefix_cache_mb=4.0, adapters=bank,
                            do_sample=True, top_k=1,
                            draft_model=m, draft_params=params,
                            spec_tokens=4)
        try:
            with CompileWatcher() as watcher:
                self._run(eng, prompts=[self.LONG], seed=0)
                self._run(eng, prompts=[self.LONG], seed=0)  # prefix hit
                self._run(eng, adapter="t1", seed=1)
            assert eng._spec._cache_size() == 1
            assert eng._draft_chunk._cache_size() == 1
            assert eng._prefill_chunk._cache_size() == 1
            s = eng.stats.summary()
            assert s["spec_ticks"] > 0 and s["prefix_alias_chunks"] >= 1, s
        finally:
            eng.shutdown(drain=False)
        assert not watcher.events, (
            f"XLA recompiled after warmup: {watcher.events} — adapter "
            "rows, draft pages, and acceptance are data, not shapes")


class TestPagedValidation:
    def test_constructor_combos(self, tiny):
        _, m, params = tiny
        with pytest.raises(ValueError, match="chunked prefill"):
            ServingEngine(m, params, prefill_chunk=None,
                          autostart=False, warmup=False)
        with pytest.raises(ValueError, match="prefill_chunk"):
            ServingEngine(m, params, prefill_chunk=0,
                          autostart=False, warmup=False)
        with pytest.raises(ValueError, match="divide"):
            ServingEngine(m, params, prefill_chunk=8, page_size=3,
                          autostart=False, warmup=False)
        with pytest.raises(ValueError, match="divide"):
            ServingEngine(m, params, prefill_chunk=8, page_size=0,
                          autostart=False, warmup=False)
        with pytest.raises(ValueError, match="max_pages"):
            ServingEngine(m, params, prefill_chunk=8, max_pages=0,
                          autostart=False, warmup=False)

    def test_submit_rejects_unsatisfiable_footprint(self, tiny):
        """A lone request whose worst case exceeds the whole pool could
        never be scheduled — submit must refuse it synchronously."""
        _, m, params = tiny
        eng = ServingEngine(m, params, max_slots=2, max_len=64,
                            eos_token_id=EOS, prefill_chunk=8, max_pages=4,
                            warmup=False)
        try:
            with pytest.raises(ValueError, match="KV pages"):
                eng.submit(PROMPTS[0], max_new_tokens=40)
        finally:
            eng.shutdown(drain=False)


class TestPageAwareRouting:
    """The router folds KV-page headroom into the least-loaded score
    (``ReplicaSet._candidates`` via ``engine.page_deficit``): with slots
    and load equal, a replica whose pool cannot cover a request's worst-
    case footprint loses the tie-break — long prompts route around page
    pressure instead of forcing a preemption on arrival."""

    def _paged_fleet(self, tiny, n=2):
        _, m, params = tiny
        return ReplicaSet.from_factory(
            lambda: ServingEngine(m, params, max_slots=2, max_len=64,
                                  eos_token_id=EOS, prefill_chunk=8,
                                  prefix_cache_mb=0.0, max_pages=10), n)

    def test_page_starved_replica_loses_tie_break(self, tiny):
        rs = self._paged_fleet(tiny)
        taken = []
        try:
            e0 = rs.engine(0)
            # Both replicas idle: equal free slots, equal load. Starve
            # replica 0's pool down to one page (held from the test
            # thread; the idle engine allocates nothing meanwhile).
            while e0._pool.free_pages > 1:
                taken.append(e0._pool.alloc())

            total = int(PROMPTS[2].shape[1]) + 30  # 37 tokens -> 5 pages
            assert e0.page_deficit(total) > 0
            assert rs.engine(1).page_deficit(total) == 0
            order = [r.index for r in rs._candidates(total_tokens=total)]
            assert order == [1, 0], order

            # Un-starve: with page headroom equal again, the stable index
            # tie-break puts replica 0 back in front.
            while taken:
                e0._pool.decref(taken.pop())
            order = [r.index for r in rs._candidates(total_tokens=total)]
            assert order == [0, 1], order

            # End to end: re-starve and submit the long request — it must
            # land on (and stay on) the page-rich replica.
            while e0._pool.free_pages > 1:
                taken.append(e0._pool.alloc())
            req = rs.submit(PROMPTS[2], max_new_tokens=30, ignore_eos=True)
            req.wait(timeout=120)
            assert req.replica_trail == [1], req.replica_trail
        finally:
            while taken:
                rs.engine(0)._pool.decref(taken.pop())
            rs.shutdown(drain=False)

    def test_draft_spec_engine_reports_doubled_page_footprint(self, tiny):
        """A draft-speculating replica holds TWO pages per covered page
        span (target + draft columns of the same pool), so its
        ``page_deficit`` must report the doubled footprint — otherwise
        the router over-admits it and the admission gate preempts on
        arrival. Lookup engines carry no draft KV and report 1x."""
        _, m, params = tiny
        kw = dict(max_slots=2, max_len=64, eos_token_id=EOS,
                  prefill_chunk=8, prefix_cache_mb=0.0, max_pages=10,
                  autostart=False, warmup=False)
        plain = ServingEngine(m, params, **kw)
        spec = ServingEngine(m, params, draft_model=m, draft_params=params,
                             spec_tokens=4, **kw)
        lookup = ServingEngine(m, params, spec_lookup=2, spec_tokens=4,
                               **kw)
        try:
            total = 44  # -> 6 pages of 8; 12 with the draft factor
            assert plain._spec_page_factor == 1
            assert lookup._spec_page_factor == 1
            assert spec._spec_page_factor == 2
            assert plain.page_deficit(total) == 0
            assert lookup.page_deficit(total) == 0
            assert spec.page_deficit(total) == 2  # 12 needed, 10 free
        finally:
            for e in (plain, spec, lookup):
                e.shutdown(drain=False)


class TestChunkAttentionInBlocks:
    """A prefill chunk's attention reads the key blocks its queries can
    see (``models.llama._cached_attention``).  The shape rule gives one
    block at any toy size, so the test stands in for it: a multi-token
    call scores 16 key rows at a time against a 128-row view, a tick's
    single token the whole view, as on the chip.  Tokens must match
    offline ``generate`` (run under the real rule) on prompts shorter and
    longer than the window, through one warm chunk program."""

    BLOCK = 16

    @pytest.mark.parametrize("window", [None, 40], ids=["full", "windowed"])
    def test_mixtral_engine_matches_offline_across_blocks(self, window):
        from accelerate_tpu.models import llama
        from accelerate_tpu.models.mixtral import (MixtralConfig,
                                                   MixtralForCausalLM)

        cfg = MixtralConfig.tiny_moe(use_flash_attention=False,
                                     capacity_factor=4.0,
                                     sliding_window=window)
        m = MixtralForCausalLM(cfg)
        params = m.init_params(jax.random.PRNGKey(0))
        rng = np.random.default_rng(5)
        prompts = [rng.integers(1, 256, size=(1, n)).astype(np.int32)
                   for n in (20, 100, 57)]
        n = 12
        refs = [_offline(m, params, p, n, eos=None) for p in prompts]
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(
                llama, "cached_key_block",
                lambda rows, view: (min(self.BLOCK, view)
                                    if rows > cfg.num_attention_heads
                                    else view))
            eng = ServingEngine(m, params, max_slots=2, max_len=128,
                                prefill_chunk=16, prefix_cache_mb=0.0)
            try:
                with CompileWatcher() as watcher:
                    reqs = [eng.submit(p, max_new_tokens=n, ignore_eos=True)
                            for p in prompts]
                    got = [np.asarray(r.result(timeout=180)) for r in reqs]
                summary = eng.stats.summary()
                assert eng._prefill_chunk._cache_size() == 1
                assert eng._decode._cache_size() == 1
            finally:
                eng.shutdown(drain=False)
        for g, ref in zip(got, refs):
            assert np.array_equal(g, ref), (g, ref)
        assert not watcher.events, watcher.events
        # 2 + 7 + 4 chunks of 16 against 128-row views: a full layer scores
        # offset + 16 rows, all of them visible; a windowed one starts at
        # the block that holds offset - 39
        share, fill = (summary["prefill_attn_rows_share"],
                       summary["prefill_attn_rows_fill"])
        if window is None:
            scored = 16 * (sum(range(1, 3)) + sum(range(1, 8))
                           + sum(range(1, 5)))
            assert share == pytest.approx(scored / (13 * 128), abs=1e-6)
            assert fill == 1.0
        else:
            assert 0.0 < share < 656 / (13 * 128) and 0.5 < fill < 1.0
