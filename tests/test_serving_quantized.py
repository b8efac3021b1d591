"""Quantized serving: int8 KV pages (per-page scales) + int8 base weights.

The acceptance-critical properties pinned here:

* OFF MEANS OFF — ``kv_dtype=None`` / ``weights_dtype=None`` engines
  trace the quantization hooks into NOTHING: the fp paged engine stays
  bit-exact vs offline ``generation.generate``.
* ZERO RECOMPILES, SAME COUNTS — an int8 engine serves warm with the
  compile listener silent and the SAME warm-executable counts as its fp
  twin (quantize-at-write / dequantize-at-read live inside the existing
  programs; alloc/free/alias/preempt stay host work on the page table).
* PREFIX-CACHE ISOLATION — a shared (fleet-style) PrefixCache never
  restores an fp entry into an int8 pool or vice versa: chunk keys are
  seeded with the kv dtype, so each engine only ever hits its own kind.
* EXACT LoRA ON A QUANTIZED BASE — with ``weights_dtype="int8"`` the
  engine's math IS offline generate over the dequantized-quantized
  params: base requests match that reference token-exactly and adapter
  requests match the merged-adapter reference on the same quantized
  base (the low-rank path rides full precision on top).
* BYTE ACCOUNTING — int8 pages cost elems + one f32 scale per leaf,
  so the pool (and everything downstream of ``_page_bytes``) shrinks.
* VALIDATION — unsupported dtypes and dense+kv_dtype combos fail fast.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from accelerate_tpu import generation  # noqa: E402
from accelerate_tpu.adapters import (  # noqa: E402
    AdapterBank,
    LoRAConfig,
    init_lora_params,
    merge_adapter,
    quantize_base_weights,
)
from accelerate_tpu.adapters.quantize import dequantize_params  # noqa: E402
from accelerate_tpu.models.llama import LlamaConfig, LlamaForCausalLM  # noqa: E402
from accelerate_tpu.serving import PrefixCache, ServingEngine  # noqa: E402
from accelerate_tpu.serving.metrics import ServingStats  # noqa: E402
from accelerate_tpu.utils.profiling import CompileWatcher  # noqa: E402

EOS = 7

PROMPTS = [
    np.array([[3, 5, 7, 11, 2]], np.int32),
    np.array([[1, 4, 9]], np.int32),
    np.array([[2, 2, 6, 1, 8, 5, 3, 9, 4, 1, 7, 6]], np.int32),
]

BASE = dict(max_slots=2, max_len=64, eos_token_id=None, prefill_chunk=8,
            prefix_cache_mb=0.0)


@pytest.fixture(scope="module")
def tiny():
    cfg = LlamaConfig.tiny(use_flash_attention=False)
    m = LlamaForCausalLM(cfg)
    params = m.init_params(jax.random.PRNGKey(0), batch_size=2, seq_len=8)
    return cfg, m, params


def _offline(m, params, prompt, n, eos=None):
    out = generation.generate(m, params, prompt, max_new_tokens=n,
                              eos_token_id=eos)
    return np.asarray(out)[0, prompt.shape[1]:]


def _run(eng, prompts=PROMPTS, n=12, adapter=None):
    reqs = [eng.submit(p, max_new_tokens=n, ignore_eos=True, block=True,
                       adapter=adapter) for p in prompts]
    return [np.asarray(r.result(timeout=120)) for r in reqs]


class TestOffMeansOff:
    def test_fp_paged_engine_bit_exact_vs_offline(self, tiny):
        _, m, params = tiny
        eng = ServingEngine(m, params, **BASE)
        assert eng.kv_dtype is None and eng.weights_dtype is None
        try:
            for toks, p in zip(_run(eng), PROMPTS):
                assert np.array_equal(toks, _offline(m, params, p, 12)), (
                    "kv_dtype=None must stay BIT-exact vs offline generate")
        finally:
            eng.shutdown(drain=False)


class TestZeroRecompile:
    def test_int8_kv_same_executable_counts_as_fp(self, tiny):
        _, m, params = tiny
        counts = {}
        for kv in (None, "int8"):
            eng = ServingEngine(m, params, kv_dtype=kv, **BASE)
            try:
                _run(eng)
                with CompileWatcher() as watcher:
                    _run(eng)  # warm: staggered lengths, allocs, frees
                counts[kv] = (eng._prefill_chunk._cache_size(),
                              eng._decode._cache_size())
                if kv == "int8":
                    assert not watcher.events, (
                        f"int8 engine recompiled after warmup: "
                        f"{watcher.events} — quantization must live inside "
                        "the existing programs, not fork new shapes")
            finally:
                eng.shutdown(drain=False)
        assert counts["int8"] == counts[None] == (1, 1), counts

    def test_int8_kv_speculative_one_extra_executable(self, tiny):
        _, m, params = tiny
        eng = ServingEngine(m, params, kv_dtype="int8", draft_model=m,
                            draft_params=params, spec_tokens=4, **BASE)
        try:
            _run(eng, n=10)
            with CompileWatcher() as watcher:
                _run(eng, n=10)
            assert not watcher.events, watcher.events
            assert eng._prefill_chunk._cache_size() == 1
            assert eng._spec._cache_size() == 1
            assert eng.stats.summary()["spec_ticks"] > 0
        finally:
            eng.shutdown(drain=False)


class TestPrefixCacheIsolation:
    # 17 tokens = two full 8-token chunks worth of restorable prefix.
    PROMPT = np.arange(1, 18, dtype=np.int32)[None]

    def test_shared_cache_never_crosses_kv_dtypes(self, tiny):
        _, m, params = tiny
        shared = PrefixCache(8 * 2 ** 20)
        kw = dict(BASE)
        del kw["prefix_cache_mb"]
        fp = ServingEngine(m, params, prefix_cache=shared, **kw)
        q = ServingEngine(m, params, kv_dtype="int8", prefix_cache=shared,
                          **kw)
        try:
            ref = _offline(m, params, self.PROMPT, 8)
            # fp populates, then hits its own entry.
            a, b = (_run(fp, [self.PROMPT], n=8)[0] for _ in range(2))
            assert np.array_equal(a, ref) and np.array_equal(b, ref)
            assert fp.stats.summary()["prefix_cache_hit_chunks"] > 0
            # The int8 engine probes the SAME chunk content but must not
            # restore the fp blocks into its quantized pool...
            c = _run(q, [self.PROMPT], n=8)[0]
            assert q.stats.summary()["prefix_cache_hit_chunks"] == 0, (
                "an fp prefix entry restored into an int8 pool — chunk "
                "keys are no longer seeded with the kv dtype")
            # ...while its own (int8-keyed) entry hits on the repeat.
            d = _run(q, [self.PROMPT], n=8)[0]
            assert q.stats.summary()["prefix_cache_hit_chunks"] > 0
            assert np.array_equal(c, d)
            # And the int8 put did not clobber the fp entry either.
            before = fp.stats.summary()["prefix_cache_hit_chunks"]
            _run(fp, [self.PROMPT], n=8)
            assert fp.stats.summary()["prefix_cache_hit_chunks"] > before
        finally:
            fp.shutdown(drain=False)
            q.shutdown(drain=False)


class TestQuantizedWeights:
    def test_base_matches_offline_on_dequantized_params(self, tiny):
        _, m, params = tiny
        dq = dequantize_params(quantize_base_weights(params), jnp.float32)
        eng = ServingEngine(m, params, weights_dtype="int8", **BASE)
        try:
            for toks, p in zip(_run(eng), PROMPTS):
                assert np.array_equal(toks, _offline(m, dq, p, 12)), (
                    "weights_dtype='int8' must compute exactly offline "
                    "generate over the dequantized-quantized params")
        finally:
            eng.shutdown(drain=False)

    def test_lora_stays_exact_on_quantized_base(self, tiny):
        _, m, params = tiny
        cfg_l = LoRAConfig(rank=4)
        ad = init_lora_params(jax.random.PRNGKey(1), params, cfg_l)
        bank = AdapterBank(params, config=cfg_l, max_adapters=2)
        bank.register("a", ad)
        dq = dequantize_params(quantize_base_weights(params), jnp.float32)
        refs = {"a": merge_adapter(dq, ad), None: dq}
        eng = ServingEngine(m, params, weights_dtype="int8", adapters=bank,
                            **BASE)
        try:
            for name in ("a", None):
                for toks, p in zip(_run(eng, adapter=name), PROMPTS):
                    assert np.array_equal(
                        toks, _offline(m, refs[name], p, 12)), (
                        f"adapter={name!r} diverged on the quantized base "
                        "— the low-rank path must ride full precision "
                        "(AdapterBank row-0 identity included)")
        finally:
            eng.shutdown(drain=False)


class TestByteAccountingAndMetrics:
    def test_int8_pool_bytes_shrink_and_report_dtype(self, tiny):
        _, m, params = tiny
        fp = ServingEngine(m, params, **BASE)
        q = ServingEngine(m, params, kv_dtype="int8", **BASE)
        try:
            assert q.kv_cache_per_chip_bytes() < fp.kv_cache_per_chip_bytes()
            assert q._page_bytes < fp._page_bytes
            assert q.page_pool_metrics()["kv_dtype"] == "int8"
            assert fp.page_pool_metrics()["kv_dtype"] is None
        finally:
            fp.shutdown(drain=False)
            q.shutdown(drain=False)

    def test_logprob_drift_gauge_is_a_running_max_that_merges(self):
        a, b = ServingStats(), ServingStats()
        a.record_logprob_drift(0.01)
        a.record_logprob_drift(0.004)   # lower: must not regress the max
        b.record_logprob_drift(0.02)
        assert a.summary()["logprob_drift"] == 0.01
        a.merge(b)
        assert a.summary()["logprob_drift"] == 0.02
        assert ServingStats().summary()["logprob_drift"] == 0.0


class TestValidation:
    def test_unsupported_dtypes_fail_fast(self, tiny):
        _, m, params = tiny
        with pytest.raises(ValueError, match="kv_dtype"):
            ServingEngine(m, params, kv_dtype="int4", **BASE)
        with pytest.raises(ValueError, match="weights_dtype"):
            ServingEngine(m, params, weights_dtype="fp8", **BASE)
