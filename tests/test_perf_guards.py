"""Structural guards on the tier-1 fused train step that need no chip.

The headline TPU benchmark (bench.py) divides measured throughput by an
ANALYTIC FLOPs count to report MFU, and its compile time and memory depend
on structural properties of the lowered step (scan over layers, no host
traffic, donated state buffers, remat actually shrinking live memory).
These tests pin all of that on CPU via ``lower().compile()`` introspection,
so a regression is caught in CI instead of spending chip time.

Reference counterpart: the reference ships measured-hardware benchmarks
(`/root/reference/benchmarks/big_model_inference/README.md:26-37`) but has
no static FLOPs/memory guard; this lane is what makes the TPU-side MFU
denominator trustworthy without hardware in the loop.
"""

import dataclasses
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench  # noqa: E402
from accelerate_tpu import Accelerator, Model  # noqa: E402
from accelerate_tpu.data_loader import make_global_batch  # noqa: E402
from accelerate_tpu.models.llama import (  # noqa: E402
    LlamaConfig,
    LlamaForCausalLM,
    PipelinedLlamaForCausalLM,
    fused_causal_lm_loss,
)

BATCH, SEQ = 4, 256


def _tier1_like_config(remat=False, remat_policy="nothing"):
    """Scaled-down tier-1 shape (bench.py run_bench): same module classes,
    same loss, same step builder — only the dims shrink."""
    return LlamaConfig(
        vocab_size=512, hidden_size=128, intermediate_size=384,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=512, remat=remat, remat_policy=remat_policy,
        use_flash_attention=False,
    )


_compiled_cache = {}


def _compiled_step(remat=False, remat_policy="nothing"):
    """(compiled step, params, cfg) for the scaled tier-1 step; cached —
    each compile is several CPU-seconds."""
    key = (remat, remat_policy)
    if key in _compiled_cache:
        return _compiled_cache[key]
    cfg = _tier1_like_config(remat, remat_policy)
    model_def = PipelinedLlamaForCausalLM(cfg)
    params = model_def.init_params(jax.random.PRNGKey(0))
    acc = Accelerator(mixed_precision="bf16")
    model, opt = acc.prepare(Model(model_def, params), optax.adamw(1e-4))
    step = acc.compile_train_step(fused_causal_lm_loss(model_def), max_grad_norm=1.0)
    rng = np.random.default_rng(0)
    batch = make_global_batch(
        {"input_ids": rng.integers(0, cfg.vocab_size, size=(BATCH, SEQ)).astype(np.int32)},
        acc.mesh,
    )
    lowered = step._jitted.lower(
        model.params, opt.opt_state, opt.loss_scale, batch, jax.random.PRNGKey(0)
    )
    # Compile around the persistent cache (conftest warms one across runs):
    # a deserialized executable reports alias_size_in_bytes == 0, which
    # would fake a donation regression on any warm-cache run. jax latches
    # its cache-used decision at the first compile of the process, so the
    # config toggle only takes effect after reset_cache() drops the latch.
    from jax._src import compilation_cache as _cc

    cache_enabled = jax.config.jax_enable_compilation_cache
    try:
        jax.config.update("jax_enable_compilation_cache", False)
        _cc.reset_cache()
        compiled = lowered.compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_enabled)
        _cc.reset_cache()  # re-latch with the cache enabled for later tests
    _compiled_cache[key] = (compiled, model.params, cfg)
    return _compiled_cache[key]


def _flops(compiled) -> float:
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    return float(ca["flops"])


def _analytic_flops(cfg, params, layers=None) -> float:
    """bench.py's MFU denominator at (BATCH, SEQ) tokens; ``layers``
    overrides the layer count (for the scan-counted-once bound)."""
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params))
    n_matmul = n_params - cfg.vocab_size * cfg.hidden_size
    if layers is not None:
        per_layer = (
            2 * cfg.hidden_size * cfg.hidden_size                      # q, o proj
            + 2 * cfg.hidden_size * (cfg.num_key_value_heads
                                     * cfg.hidden_size // cfg.num_attention_heads)
            + 3 * cfg.hidden_size * cfg.intermediate_size              # mlp
        )
        n_matmul -= (cfg.num_hidden_layers - layers) * per_layer
        cfg_layers = layers
    else:
        cfg_layers = cfg.num_hidden_layers
    attn = 12.0 * cfg_layers * cfg.hidden_size * SEQ
    return (6.0 * n_matmul + attn) * BATCH * SEQ


class TestMFUDenominator:
    def test_analytic_formula_matches_xla_on_unrolled_model(self):
        """model_flops_per_token (6N + attention term) IS the MFU
        denominator; on the unrolled model XLA's own cost analysis must
        agree to a few percent — the analytic count a slight lower bound
        (XLA adds softmax/norm/rotary elementwise work)."""
        cfg = dataclasses.replace(_tier1_like_config(), num_hidden_layers=2)
        model = LlamaForCausalLM(cfg)
        params = model.init_params(jax.random.PRNGKey(0), batch_size=1, seq_len=8)
        ids = jnp.zeros((BATCH, SEQ), jnp.int32)

        def loss(p, ids):
            logits = model.apply({"params": p}, ids)
            tgt = jnp.roll(ids, -1, axis=1)
            lo = jax.nn.log_softmax(logits.astype(jnp.float32))
            return -jnp.take_along_axis(lo, tgt[..., None], -1).mean()

        compiled = jax.jit(jax.grad(loss)).lower(params, ids).compile()
        xla = _flops(compiled)
        n_params = sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params))
        n_matmul = n_params - cfg.vocab_size * cfg.hidden_size
        analytic = bench.model_flops_per_token(n_matmul, cfg, SEQ) * BATCH * SEQ
        ratio = xla / analytic
        assert 1.0 <= ratio <= 1.05, (
            f"XLA/analytic FLOPs ratio {ratio:.4f} out of band — the MFU "
            "denominator (bench.model_flops_per_token) no longer describes "
            "what the compiled step executes")

    def test_scanned_step_keeps_layer_scan(self):
        """XLA's cost model counts a lax.scan body ONCE; the fused tier-1
        step must therefore report far fewer FLOPs than the full analytic
        count (scan present) but at least the single-layer count (body not
        degenerate). An accidental unroll (or a cost-model change that
        starts multiplying by trip count) breaks the upper bound loudly."""
        compiled, params, cfg = _compiled_step()
        xla = _flops(compiled)
        full = _analytic_flops(cfg, params)
        single = _analytic_flops(cfg, params, layers=1)
        assert xla < 0.6 * full, (
            f"step reports {xla:.3e} FLOPs >= 60% of the analytic full count "
            f"{full:.3e}: either the layer scan unrolled (compile-time "
            "blowup) or XLA began counting scan trips — re-derive "
            "the MFU accounting either way")
        assert xla > 0.5 * single, (
            f"step reports {xla:.3e} FLOPs < half the single-layer analytic "
            f"count {single:.3e}: the loss/grad graph lost real work")


class TestInputPipelineOverlap:
    """CPU guards for the async host input pipeline (bench.overlap_microbench):
    a slow producer + a jitted step must OVERLAP — wall-clock near
    max(producer, step), not their sum — and a fast producer must leave the
    step loop essentially never waiting on data. 8 ms legs keep scheduler
    jitter small relative to the thresholds on loaded CI machines, and each
    guard retries once: the thresholds come from real sleeps, so a single
    burst of scheduler/GIL contention on an oversubscribed runner must not
    fail the suite — only a *reproducible* miss does."""

    PRODUCE_MS = 8.0
    STEP_MS = 8.0
    STEPS = 30

    @staticmethod
    def _retry_once(attempt):
        try:
            attempt()
        except AssertionError:
            attempt()

    def test_async_pipeline_overlaps_producer_and_step(self):
        def attempt():
            on = bench.overlap_microbench(
                steps=self.STEPS, produce_ms=self.PRODUCE_MS, step_ms=self.STEP_MS,
                async_prefetch=True)
            off = bench.overlap_microbench(
                steps=self.STEPS, produce_ms=self.PRODUCE_MS, step_ms=self.STEP_MS,
                async_prefetch=False)
            assert on["wall_s"] < 1.5 * on["ideal_s"], (
                f"async pipeline took {on['wall_s']:.3f}s >= 1.5x the ideal "
                f"max(producer, step) {on['ideal_s']:.3f}s: input work is not "
                "overlapping the step")
            speedup = off["wall_s"] / on["wall_s"]
            assert speedup >= 1.4, (
                f"async speedup vs async_prefetch=False only {speedup:.2f}x "
                f"(async {on['wall_s']:.3f}s, sync {off['wall_s']:.3f}s): the "
                "background worker is no longer hiding producer latency")
            # The sync loop must *measure* its serialized data wait — that
            # metric is how a production run discovers it needs the async path.
            assert off["data_wait_ms"] > 0.5 * self.PRODUCE_MS

        self._retry_once(attempt)

    def test_fast_producer_near_zero_data_wait(self):
        def attempt():
            out = bench.overlap_microbench(
                steps=self.STEPS, produce_ms=0.0, step_ms=5.0, async_prefetch=True)
            assert out["data_wait_ms"] < 2.0, (
                f"mean data_wait_ms {out['data_wait_ms']:.3f} with an instant "
                "producer: the prefetch queue is not staying ahead of the step")
            assert out["batches_waited"] == self.STEPS

        self._retry_once(attempt)


class TestFusedStepStructure:
    def test_no_host_memory_in_step(self):
        """The non-offload step must stay device-resident end to end: any
        host buffer in the executable means a hidden transfer inside the
        hot loop (HBM <-> host is the slowest edge)."""
        compiled, _, _ = _compiled_step()
        mem = compiled.memory_analysis()
        host = (mem.host_argument_size_in_bytes + mem.host_output_size_in_bytes
                + mem.host_temp_size_in_bytes)
        assert host == 0, f"step holds {host} host bytes"

    def test_donation_aliases_params_and_opt_state(self):
        """donate_argnums must alias params + optimizer state into the
        outputs — losing donation doubles the step's parameter footprint."""
        compiled, params, _ = _compiled_step()
        mem = compiled.memory_analysis()
        param_bytes = sum(
            int(np.prod(p.shape)) * p.dtype.itemsize
            for p in jax.tree_util.tree_leaves(params))
        opt_bytes = 2 * param_bytes  # adamw m + v, fp32 like the params
        assert mem.alias_size_in_bytes >= 0.95 * (param_bytes + opt_bytes), (
            f"aliased {mem.alias_size_in_bytes} < params+opt "
            f"{param_bytes + opt_bytes}: buffer donation regressed")

    def test_remat_shrinks_live_memory(self):
        """cfg.remat must visibly trade FLOPs for memory in the scanned
        model (guards the per-layer-checkpoint placement inside the scan
        body — checkpointing the whole scan saves nothing at peak), and
        the 'dots' policy must sit between 'nothing' and no-remat."""
        base, _, _ = _compiled_step(remat=False)
        full_remat, _, _ = _compiled_step(remat=True, remat_policy="nothing")
        dots, _, _ = _compiled_step(remat=True, remat_policy="dots")
        t_base = base.memory_analysis().temp_size_in_bytes
        t_full = full_remat.memory_analysis().temp_size_in_bytes
        t_dots = dots.memory_analysis().temp_size_in_bytes
        assert t_full < 0.5 * t_base, (
            f"remat temp {t_full} not < 50% of no-remat {t_base}: "
            "rematerialization is not reaching the scan body")
        assert t_full <= t_dots <= t_base, (t_full, t_dots, t_base)


class TestContinuousBatching:
    """CPU guard for the serving engine's scheduling win
    (bench.continuous_vs_static): with deterministic per-forward sleeps
    standing in for device step time, short staggered requests stuck
    behind one long request must finish ~Nx faster under continuous
    batching (slot joins mid-flight) than under static dynamic batching
    (head-of-line blocking until the whole batch drains). Sleep-driven
    like the overlap guards above, and retried once for the same reason:
    only a reproducible miss fails the suite."""

    @staticmethod
    def _retry_once(attempt):
        try:
            attempt()
        except AssertionError:
            attempt()

    def test_continuous_beats_static_on_staggered_arrivals(self):
        def attempt():
            out = bench.continuous_vs_static()
            assert out["speedup"] >= 1.5, (
                f"continuous batching speedup on short requests only "
                f"{out['speedup']:.2f}x (static {out['static_short_latency_s']:.3f} s "
                f"vs continuous {out['continuous_short_latency_s']:.3f} s): slot "
                "admission is no longer overlapping the long request's decode")
            # The win must come from scheduling, not from dropping work:
            st = out["continuous_stats"]
            assert st["requests_completed"] == out["n_short"] + 1

        self._retry_once(attempt)


class TestChunkedPrefill:
    """CPU guards for bounded-latency admission: a long prompt arriving
    over active decode streams must neither stall their next token for
    its whole prefill nor push late short arrivals behind it — admission
    interleaves chunk calls with decode ticks. Both guards are
    counter-exact (records and counts of one run, no timing), so they run
    once."""

    def test_chunked_admission_bounds_interference(self):
        """Three streams decode while a 96-token prompt and three short
        late arrivals prefill in 8-token chunks at
        ``prefill_chunks_per_tick=1``: the prefill costs exactly
        ``ceil(L / C) + n_late`` chunk calls, and in the order of the
        engine's own records no two chunk calls run without a decode tick
        between them while a stream is RUNNING."""
        from accelerate_tpu.serving import RequestStatus, ServingEngine

        L, C, n_streams, n_late = 96, 8, 3, 3
        cfg = LlamaConfig.tiny(use_flash_attention=False)
        model = bench._sleepy_llama_cls(step_ms=1.0, per_token=True)(cfg)
        params = model.init_params(jax.random.PRNGKey(0), batch_size=1,
                                   seq_len=8)
        eng = ServingEngine(model, params, max_slots=n_streams + 1 + n_late,
                            max_len=128, prefill_chunk=C,
                            prefill_chunks_per_tick=1, prefix_cache_mb=0.0)
        rng = np.random.default_rng(0)

        def short():
            return rng.integers(1, 200, size=(1, 4)).astype(np.int32)

        try:
            streams = [eng.submit(short(), max_new_tokens=120,
                                  ignore_eos=True) for _ in range(n_streams)]
            deadline = time.monotonic() + 60
            while any(len(r.tokens) < 3 for r in streams):
                assert time.monotonic() < deadline, "streams never decoded"
                time.sleep(0.001)
            before = eng.serving_metrics()["prefill_chunks"]
            arrivals = [eng.submit(
                rng.integers(1, 200, size=(1, L)).astype(np.int32),
                max_new_tokens=2, ignore_eos=True)]
            arrivals += [eng.submit(short(), max_new_tokens=2,
                                    ignore_eos=True) for _ in range(n_late)]
            for r in arrivals:
                assert r.wait(120)
            assert all(r.status is RequestStatus.RUNNING for r in streams), (
                "a stream finished inside the prefill window: the run shows "
                "nothing about interleaving (host too slow for 120 tokens?)")
            chunks = eng.serving_metrics()["prefill_chunks"] - before
            for r in streams:
                r.cancel()
        finally:
            eng.shutdown(drain=False)
        assert chunks == -(-L // C) + n_late, chunks
        ids = {r.trace_id for r in arrivals}
        order = sorted(
            (t0, name) for _, t0, _, name, _, tr, _ in eng.trace_events()
            if name == "decode_tick" or (name == "prefill_chunk" and tr in ids))
        names = [name for _, name in order]
        assert names.count("prefill_chunk") == chunks
        back_to_back = [i for i, (a, b) in enumerate(zip(names, names[1:]))
                        if a == b == "prefill_chunk"]
        assert not back_to_back, (
            f"chunk calls {back_to_back} ran with no decode tick between "
            "them: admission spent more than prefill_chunks_per_tick=1 "
            "between ticks")

    def test_cached_prefix_admits_in_one_chunk(self):
        out = bench.prefix_cache_hit_bench()
        assert out["warm_prefill_chunks"] == 1, (
            f"repeat of an identical {out['chunks_per_prompt']}-chunk prompt "
            f"cost {out['warm_prefill_chunks']} chunk calls — the prefix "
            "cache must reduce admission to the final chunk only")
        assert out["hit_chunks"] == out["chunks_per_prompt"] - 1
        assert out["cold_prefill_chunks"] == out["chunks_per_prompt"]
        assert out["tokens_equal"], (
            "restored-prefix decode diverged from the cold run")
        assert out["restored_bytes"] > 0 and out["cache_entries"] >= 1


class TestGatewayOverhead:
    """CPU guard for the HTTP serving layer (bench.gateway_overhead_bench):
    on the deterministic-sleep model, p95 TTFT through the full gateway
    stack (HTTP parse -> router -> engine -> SSE first event) must stay
    within 2x of direct ``engine.submit`` on the same warmed engine — the
    acceptance bound on what the network front door may cost. Sleep-driven
    and retried once, same as the other timing guards."""

    @staticmethod
    def _retry_once(attempt):
        try:
            attempt()
        except AssertionError:
            attempt()

    @pytest.mark.slow
    def test_gateway_ttft_within_2x_of_direct_submit(self):
        def attempt():
            out = bench.gateway_overhead_bench()
            assert out["overhead_ratio_p95"] is not None
            assert out["overhead_ratio_p95"] <= 2.0, (
                f"gateway p95 TTFT {out['http_ttft_ms_p95']:.1f} ms is "
                f"{out['overhead_ratio_p95']:.2f}x direct submit "
                f"({out['direct_ttft_ms_p95']:.1f} ms): the HTTP layer is "
                "adding more than routing + serialization")

        self._retry_once(attempt)


class TestAsyncioGateway:
    """Open-loop A/B guard for the asyncio front end
    (bench.open_loop_ab_bench): identical heavy-tailed open-loop load
    against both front ends, with the threading gateway capped at a
    small connection count so the burst pushes it past its knee. Past
    that knee the threading side refuses/queues at the front door (its
    p99 TTFT from scheduled arrival goes unbounded and is clamped at
    the wall deadline) while the asyncio side keeps every stream open —
    the acceptance bound is a >=2x p99-TTFT advantage. Timing-driven
    and retried once, same as the other guards."""

    @staticmethod
    def _retry_once(attempt):
        try:
            attempt()
        except AssertionError:
            attempt()

    @pytest.mark.slow
    def test_asyncio_p99_ttft_2x_better_past_threading_knee(self):
        def attempt():
            out = bench.open_loop_ab_bench()
            assert out["threading_conn_rejections"] > 0, (
                "the A/B load never hit the threading connection cap — "
                "the comparison stayed in the flat region and proves "
                "nothing")
            ratio = out["p99_ttft_ratio_threading_over_asyncio"]
            assert ratio is not None and ratio >= 2.0, (
                f"asyncio p99 TTFT advantage past the threading knee is "
                f"only {ratio}x (threading "
                f"{out['threading']['ttft_s']['p99_clamped']}s vs asyncio "
                f"{out['asyncio']['ttft_s']['p99_clamped']}s): the "
                "event-loop front end is no longer absorbing the burst "
                "the thread-per-connection front end refuses")

        self._retry_once(attempt)


class TestSLOControl:
    """Open-loop A/B guard for the SLO control plane
    (bench.slo_control_bench): the same seeded mixed interactive/batch
    load at ~2x saturation against an FCFS fleet
    (``priority_policy=None``) and the default priority-policy fleet.
    With a deep admission queue the control plane's priority admission
    must cut the interactive class's clamped p99 TTFT by >=2x versus
    FCFS — the headline SLO claim — without starving batch (every
    stream still completes; the policy reorders, it does not drop).
    Timing-driven and retried once, same as the other guards."""

    @staticmethod
    def _retry_once(attempt):
        try:
            attempt()
        except AssertionError:
            attempt()

    @pytest.mark.slow
    def test_interactive_p99_ttft_2x_better_than_fcfs(self):
        def attempt():
            out = bench.slo_control_bench()
            ratio = out["interactive_p99_ttft_ratio_fcfs_over_control"]
            assert ratio is not None and ratio >= 2.0, (
                f"interactive p99-TTFT advantage of the priority policy "
                f"over FCFS at 2x saturation is only {ratio}x "
                f"(FCFS {out['fcfs']['per_priority']['interactive']['ttft_s']['p99_clamped']}s "
                f"vs control "
                f"{out['control']['per_priority']['interactive']['ttft_s']['p99_clamped']}s): "
                "interactive arrivals are no longer jumping the batch "
                "backlog")
            assert (out["batch_completed_under_control"] or 0) > 0, (
                "priority scheduling starved the batch class outright")
            assert out["control"]["counters_balance"], (
                "control-plane run lost or duplicated stream outcomes")

        self._retry_once(attempt)


class TestObservabilityOverhead:
    """CPU guard for always-on tracing, on COUNTS (it used to time XLA:CPU
    around sleeps — ``bench.tracing_overhead_bench``'s >= 0.95 ratio — and
    failed on a busy host; what tracing costs on the chip is measured by
    the serving cell, ``PERF.md``). What lets tracing default ON is that
    its work per loop iteration is bounded and that off means off:

    * the records one loop iteration leaves are bounded by a constant plus
      a few per decode slot (``itl``, ``retire`` and the emitter's batch),
      never one per page, token of context or queued request;
    * the loop's phase spans carry no ``args`` dict and no trace id;
    * a disabled tracer emits nothing and allocates no span object."""

    #: sweep, admit, the three prefill phases + ``prefill_chunk`` and the
    #: admission's ``queue_wait`` / ``first_token`` / ``prefix_hit`` /
    #: flight mirror, the three tick phases + ``decode_tick``, ``idle``,
    #: a ``tick_profile`` mirror: 16 covers one iteration's fixed part.
    PER_ITERATION = 16
    PER_SLOT = 3

    @staticmethod
    def _serve(tracing: bool):
        engine, _, _, _ = bench._serving_test_engine(max_slots=4,
                                                     tracing=tracing)
        rng = np.random.default_rng(0)
        prompts = rng.integers(1, 200, size=(10, 4)).astype(np.int32)
        reqs = [engine.submit(prompts[i:i + 1], max_new_tokens=16, seed=i,
                              on_token=lambda tok: None, block=True)
                for i in range(len(prompts))]
        for r in reqs:
            assert r.wait(timeout=120)
        return engine

    def test_tracing_keeps_95_percent_decode_throughput(self):
        engine = self._serve(tracing=True)
        try:
            events = engine.trace_events()
            ticks = engine.stats.summary()["decode_ticks"]
        finally:
            engine.shutdown()
        phase = [e for e in events if e[4] == "phase"]
        assert ticks > 10 and phase
        assert all(e[5] is None and e[6] is None for e in phase), (
            "a loop phase span carries a trace id or an args dict: that is "
            "an allocation per phase on the decode hot path")
        engine_tid = next(e[0] for e in phase if e[3] == "tick_launch")
        sweeps = sorted(e[1] for e in phase if e[3] == "sweep")
        stamps = sorted(e[1] for e in events
                        if e[0] == engine_tid and e[1] >= sweeps[0])
        bound = self.PER_ITERATION + self.PER_SLOT * engine.max_slots
        lo = 0
        for nxt in sweeps[1:] + [float("inf")]:
            hi = lo
            while hi < len(stamps) and stamps[hi] < nxt:
                hi += 1
            assert hi - lo <= bound, (
                f"{hi - lo} trace records in one loop iteration (bound "
                f"{bound}): something now records per token, page or "
                "queued request")
            lo = hi
        emitted = sum(1 for e in phase if e[3] == "emit")
        assert emitted <= engine.stats.summary()["tokens_emitted"]

        off = self._serve(tracing=False)
        try:
            assert off.trace_events() == [] and len(off.tracer) == 0
            assert off.tracer.span("tick_launch") is off.tracer.span("emit"), (
                "a disabled tracer must hand back one shared no-op span")
            assert off.stats.summary()["host_us/tick_launch"] > 0  # counters stay on
        finally:
            off.shutdown()


class TestMultiTenantAdapters:
    """CPU guard for the adapter bank's serving win
    (bench.multi_tenant_adapter_bench): at 4 tenants, batching per-slot
    low-rank deltas through one shared compiled program must beat the
    sequential merge-swap-generate baseline by >= 2x, while remaining
    token-identical to generating on each tenant's merged weights.
    Sleep-driven like the guards above, retried once so only a
    reproducible miss fails the suite."""

    @staticmethod
    def _retry_once(attempt):
        try:
            attempt()
        except AssertionError:
            attempt()

    def test_bank_beats_sequential_merge_swap(self):
        def attempt():
            out = bench.multi_tenant_adapter_bench()
            assert out["tokens_equal"], (
                "batched adapter decode diverged from per-tenant merged "
                "weights — the bank gather is no longer exact")
            assert out["speedup"] >= 2.0, (
                f"multi-tenant speedup only {out['speedup']:.2f}x "
                f"(sequential swap {out['sequential_swap_s']:.3f} s vs "
                f"batched {out['batched_s']:.3f} s): adapter requests are "
                "no longer sharing decode ticks across tenants")
            assert out["adapter_requests"] == out["n_tenants"]

        self._retry_once(attempt)


class TestPagedCapacity:
    """CPU guard for the KV page pool's capacity
    (bench.paged_capacity_bench): a pool sized for 2 streams of
    ``max_len`` must sustain >= 2x that many short streams at once (the
    benchmark geometry gives 4x: a 16-token request covers 2 of the
    pool's 16 pages where a worst-case reservation is a whole 64-token
    row), with greedy output token-identical to offline generate and
    zero pool-exhaustion preemptions — the advertised concurrency really
    fits. Counts of one run, no timing."""

    def test_paged_serves_2x_slots_at_equal_hbm(self):
        out = bench.paged_capacity_bench()
        assert out["tokens_equal"], (
            "greedy output out of the page pool diverged from offline "
            "generate — the page gather/scatter is no longer an exact "
            "relayout")
        assert out["worst_case_slots"] == (
            out["pool_pages"] * out["page_size"] // out["max_len"])
        assert out["peak_concurrency"] >= 2 * out["worst_case_slots"], (
            f"peak concurrency {out['peak_concurrency']} from a pool of "
            f"{out['pool_pages']} pages ({out['kv_bytes']} bytes) that "
            f"reserves {out['worst_case_slots']} worst-case rows: the "
            "pool is no longer translating short requests into extra "
            "live slots")
        assert out["preemptions"] == 0, (
            f"{out['preemptions']} preemptions at the advertised "
            "concurrency — the pool does not actually fit it")


class TestQuantizedServing:
    """CPU guard for int8 KV serving (bench.quantized_serving_bench):
    at equal pool BYTES the int8 engine (quantized pages + per-page
    scales) must sustain >= 1.8x the fp engine's peak concurrency (the
    template geometry gives 2x: 1040-byte int8 pages vs 2048-byte fp
    pages buy 31 pages for the fp pool's 16), with zero preemptions,
    every int8-kv greedy token a near-tie with the fp argmax
    (``kv_logit_gap``), and ``logprob_drift`` (teacher-forced
    fp-vs-quantized-weights max |delta logprob| on served tokens) under
    the documented 0.25 tolerance. Speculation accept rate must not collapse under
    quantized pages. Sleep-driven, retried once so only a reproducible
    miss fails the suite."""

    @staticmethod
    def _retry_once(attempt):
        try:
            attempt()
        except AssertionError:
            attempt()

    def test_int8_kv_buys_concurrency_at_equal_hbm(self):
        def attempt():
            out = bench.quantized_serving_bench()
            assert out["kv_bytes"]["int8"] <= out["kv_bytes"]["fp"], (
                f"int8 pool is not within the fp byte budget "
                f"({out['kv_bytes']}): the A/B is no longer equal-HBM")
            ratio = out["concurrency_ratio"]
            assert ratio >= 1.8, (
                f"int8 peak concurrency only {ratio:.2f}x fp "
                f"({out['peak_concurrency']}) at equal pool bytes "
                f"({out['kv_bytes']}): quantized pages are no longer "
                "translating the byte savings into live slots")
            assert out["preemptions"] == 0, (
                f"{out['preemptions']} preemptions at the advertised "
                "int8 concurrency — the quantized pool does not fit it")
            # Logit drift, not token agreement: on random weights the
            # top two logits are often closer than any rounding resolves,
            # so WHERE greedy streams split (token_agreement, 0.854 on
            # jax 0.9.0) says nothing about the quantizer. int8 pages
            # round K/V to amax/127 per page, a relative error <= 1/254
            # per element, and logits of spread ~1.0 move by a few 1e-3
            # (0.003 at the one flip seen over four seeds). 0.05 is 5% of
            # the spread: ~15x that drift, and ~60x below a mangled view,
            # whose tokens score like random ones (~3 sigma under the
            # maximum of 256 logits).
            assert out["kv_logit_gap"] <= 0.05 * out["logit_std"], (
                f"an int8-kv greedy token sits {out['kv_logit_gap']} below "
                f"the fp argmax (logit std {out['logit_std']}; agreement "
                f"{out['token_agreement']}) — per-page scales are mangling "
                "the dequantized attention view, not just rounding it")
            assert out["logprob_drift"] <= 0.25, (
                f"logprob_drift {out['logprob_drift']} above the "
                "documented 0.25 tolerance — weight quantization is no "
                "longer bounded-divergence")
            assert (out["spec_accept_rate"]["int8"]
                    >= out["spec_accept_rate"]["fp"] - 0.1), (
                f"speculation accept rate collapsed under int8 pages "
                f"({out['spec_accept_rate']}): draft and target no "
                "longer see the same dequantized view")

        self._retry_once(attempt)


class TestSpeculativeDecoding:
    """CPU guard for universal speculative decoding
    (bench.speculative_bench): on the deterministic biased-logits
    fixture the verify step must accept > 1.3 committed tokens per tick
    (1.0 = speculation never helps) while staying token-identical to the
    non-speculative twin — in the greedy base case AND in every
    previously-rejected mode (sampled, adapter tenant, tp=2 slice,
    draft-free prompt lookup). A drop below the bar means the
    draft/verify chains stopped agreeing (cache corruption, position
    skew, rng drift), not a model change — the fixture has no ties to
    flake on. Retried once."""

    @staticmethod
    def _retry_once(attempt):
        try:
            attempt()
        except AssertionError:
            attempt()

    def test_accepted_tokens_per_step_all_modes(self):
        def attempt():
            out = bench.speculative_bench()
            cells = {"greedy": out}
            cells.update(out["modes"])
            for name, cell in cells.items():
                if "skipped" in cell:
                    continue
                assert cell["tokens_equal"], (
                    f"[{name}] speculative output diverged from its "
                    "non-speculative twin — the verify/commit chain "
                    "broke exactness")
                tps = cell["accepted_tokens_per_step"]
                assert tps > 1.3, (
                    f"[{name}] only {tps:.2f} committed tokens per "
                    f"speculative tick (ticks {cell['ticks']}): proposals "
                    "are no longer being accepted")
                assert (cell["ticks"]["speculative"]
                        < cell["ticks"]["baseline"]), name

        self._retry_once(attempt)


class TestZeROShardedOptimizer:
    """CPU guards for ZeRO-1/2 optimizer-state sharding (arXiv:2004.13336,
    bench.zero_sharding_bench): the compiled dp=2 step must carry only
    ~1/dp of the optimizer-state bytes per replica as arguments, and the
    sharded update (reduce-scatter grads -> shard-local Adam -> all-gather
    params) must cost <= 1.2x the replicated step's wall time while
    tracking its loss trajectory to fp32-reassociation noise."""

    DP = 2

    @staticmethod
    def _retry_once(attempt):
        try:
            attempt()
        except AssertionError:
            attempt()

    def _compiled_dp_step(self, zero):
        """(compiled executable, total opt-state bytes) for a dp=2 fused
        step over an MLP whose moments are dominated by shardable weights."""
        from accelerate_tpu import MeshConfig
        from accelerate_tpu.state import (AcceleratorState, GradientState,
                                          PartialState)

        for cls in (AcceleratorState, GradientState, PartialState):
            cls._reset_state()

        def apply(p, x):
            return jnp.tanh(x @ p["w1"] + p["b1"]) @ p["w2"] + p["b2"]

        def loss(p, batch):
            return jnp.mean((apply(p, batch["x"]) - batch["y"]) ** 2)

        k1, k2 = jax.random.split(jax.random.PRNGKey(0))
        params = {"w1": jax.random.normal(k1, (64, 512)) * 0.1,
                  "b1": jnp.zeros((512,)),
                  "w2": jax.random.normal(k2, (512, 64)) * 0.1,
                  "b2": jnp.zeros((64,))}
        acc = Accelerator(mesh_config=MeshConfig(
            dp=self.DP, devices=jax.devices()[:self.DP], zero_sharding=zero))
        model, opt = acc.prepare(Model(apply, params), optax.adamw(1e-3))
        step = acc.compile_train_step(loss, max_grad_norm=1.0)
        rng = np.random.default_rng(0)
        batch = make_global_batch(
            {"x": rng.normal(size=(16, 64)).astype(np.float32),
             "y": rng.normal(size=(16, 64)).astype(np.float32)}, acc.mesh)
        lowered = step._jitted.lower(model.params, opt.opt_state,
                                     opt.loss_scale, batch,
                                     jax.random.PRNGKey(0))
        from jax._src import compilation_cache as _cc

        cache_enabled = jax.config.jax_enable_compilation_cache
        try:
            jax.config.update("jax_enable_compilation_cache", False)
            _cc.reset_cache()
            compiled = lowered.compile()
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_enabled)
            _cc.reset_cache()
        opt_bytes = sum(leaf.nbytes
                        for leaf in jax.tree_util.tree_leaves(opt.opt_state))
        return compiled, opt_bytes

    def test_per_replica_opt_state_args_near_1_over_dp(self):
        """memory_analysis guard: argument_size_in_bytes is PER DEVICE, and
        params/batch/scale/rng are byte-identical across the two compiles —
        so the replicated-vs-zero argument delta is exactly the optimizer
        state each replica no longer holds. The residue (what the zero step
        still carries) must be <= (1/dp + eps) of the replicated state; eps
        covers the deliberately replicated scalars and small biases."""
        compiled_r, opt_total = self._compiled_dp_step(zero=False)
        compiled_z, opt_total_z = self._compiled_dp_step(zero=True)
        assert opt_total == opt_total_z  # same tree, different placement
        arg_r = compiled_r.memory_analysis().argument_size_in_bytes
        arg_z = compiled_z.memory_analysis().argument_size_in_bytes
        per_replica_opt = opt_total - (arg_r - arg_z)
        bound = (1.0 / self.DP + 0.02) * opt_total
        assert per_replica_opt <= bound, (
            f"zero step still holds {per_replica_opt} opt-state bytes per "
            f"replica (> {bound:.0f} = (1/{self.DP}+eps) of {opt_total}): "
            "the moment shardings are not reaching the compiled step")

    def test_step_time_and_trajectory_within_budget(self):
        def attempt():
            out = bench.zero_sharding_bench(steps=15, warmup=3)
            assert not out.get("skipped"), out
            assert out["memory_ratio"] <= 1.0 / self.DP + 0.05, out
            ratio = out["step_time_ratio"]
            assert ratio <= 1.2, (
                f"zero-sharded step is {ratio:.2f}x the replicated step "
                f"({out['step_ms_zero']:.2f}ms vs "
                f"{out['step_ms_replicated']:.2f}ms): the reduce-scatter/"
                "all-gather lowering has become more than communication")
            assert out["max_loss_diff"] <= 1e-4, (
                f"loss diverged {out['max_loss_diff']} from the replicated "
                "trajectory — more than fp32 reduce-scatter reassociation")

        self._retry_once(attempt)


class TestChaosRecovery:
    """CPU guard for the self-healing loop (bench.chaos_recovery_bench):
    a scripted chaos kill at a fixed decode tick under a running
    FleetSupervisor must (a) finish every in-flight stream token-exact on
    the survivor within the recovery budget and (b) rebuild + re-warm the
    dead replica back to HEALTHY without operator action. Sleep-driven
    and retried once, same as the other timing guards."""

    @staticmethod
    def _retry_once(attempt):
        try:
            attempt()
        except AssertionError:
            attempt()

    @pytest.mark.slow
    def test_kill_recovery_and_rejoin_within_budget(self):
        def attempt():
            out = bench.chaos_recovery_bench()
            assert out["chaos_fired"] == ["kill"], out
            assert out["all_completed"] and out["tokens_exact"], (
                f"streams did not survive the chaos kill exactly: {out}")
            assert out["recovery_s"] <= 5.0, (
                f"kill -> all-streams-done took {out['recovery_s']:.2f}s "
                "on the sleepy model: failover is stalling, not retrying")
            assert out["rejoined_healthy"] and out["restarts"] >= 1, (
                f"supervisor never healed the killed replica: {out}")
            assert out["rejoin_s"] <= 60.0, (
                f"kill -> replica HEALTHY took {out['rejoin_s']:.2f}s: "
                "rebuild + three-executable warmup should be seconds "
                "on the tiny model")

        self._retry_once(attempt)
