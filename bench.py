"""Benchmark: training throughput + MFU of the fused train step on real TPU.

Prints ONE JSON line: {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}.

The reference publishes no training-throughput numbers (SURVEY.md §6); the
tracked north-star is MFU (target >=45% for FSDP fine-tuning). vs_baseline
reports achieved_MFU / 0.45.

Runs in one process on the device jax gives it and names that device in the
result. Without a TPU it exits non-zero — unless the caller asked for the CPU
(``JAX_PLATFORMS=cpu``), which runs the tiny smoke config, labelled
``cpu_smoke`` and carrying no MFU.
"""

from __future__ import annotations

import json
import os
import sys
import time


# Peak bf16 TFLOP/s per chip by TPU generation.
PEAK_TFLOPS = {
    "v4": 275.0,
    "v5 lite": 197.0,  # v5e
    "v5e": 197.0,
    "v5p": 459.0,
    "v6": 918.0,
}

METRIC = "llama_train_tokens_per_sec_per_chip"

#: BASELINE.json's north-star: FSDP fine-tuning at >=45% MFU (the "≥45% MFU"
#: clause in its north_star field). vs_baseline = measured_mfu / TARGET_MFU on
#: a TPU backend and null otherwise — a CPU smoke has no meaningful MFU.
TARGET_MFU = 0.45


def detect_peak_tflops(device) -> float:
    kind = device.device_kind.lower()
    for key, val in PEAK_TFLOPS.items():
        if key in kind:
            return val
    raise ValueError(
        f"no published peak for device_kind {device.device_kind!r}; add it to "
        "PEAK_TFLOPS with its source rather than guessing")


def model_flops_per_token(n_params: int, cfg, seq: int) -> float:
    """Training FLOPs/token: 6N for matmul params + attention score/value
    term 12*L*h*seq (fwd 2 matmuls * 2 FLOPs * s*h per token, x3 for bwd)."""
    attn = 12.0 * cfg.num_hidden_layers * cfg.hidden_size * seq
    return 6.0 * n_params + attn


#: Tier-1 attempt ladder, best-MFU first (remat_policy, per-chip batch).
#: Lowered-step memory_analysis at the tier-1 config (einsum attention, CPU
#: estimate): no-remat needs ~39 GiB — over v5e's 16 GiB HBM — remat/"dots"
#: ~19 GiB (falls to ~9 with flash's O(S) residuals), remat/"nothing" b8
#: ~13.5 GiB, b4 ~11.7 GiB. Every rung taken is named in the result
#: (``oom_fallbacks``).
TIER1_LADDER = [("dots", 8), ("nothing", 8), ("nothing", 4)]


def tier1_llama_config(on_tpu: bool, remat_policy: str = "nothing"):
    """The ONE model config both benches measure — run_bench (single chip)
    and run_mesh_bench (explicit mesh) must stay cross-comparable, so the
    config lives here, not copy-pasted per bench. TPU: the tier-1 2B-class
    Llama on the flash kernel; CPU: the tiny smoke config exercising the
    same code path."""
    from accelerate_tpu.models.llama import LlamaConfig

    if not on_tpu:
        return LlamaConfig.tiny(use_flash_attention=False)
    return LlamaConfig(
        vocab_size=32000, hidden_size=2048, intermediate_size=5632,
        num_hidden_layers=10, num_attention_heads=16, num_key_value_heads=8,
        max_position_embeddings=2048, remat=True, remat_policy=remat_policy,
    )


def mfu_fields(tokens_per_sec_per_chip: float, cfg, seq: int, n_params: int) -> dict:
    """Shared MFU arithmetic: 6N (matmul params only — the input embedding
    is a gather) + attention FLOPs vs the chip's published peak. A CPU run
    has no peak and no MFU (both None)."""
    import jax

    n_matmul_params = n_params - cfg.vocab_size * cfg.hidden_size
    flops_per_tok = model_flops_per_token(n_matmul_params, cfg, seq)
    achieved_tflops = tokens_per_sec_per_chip * flops_per_tok / 1e12
    device = jax.devices()[0]
    if device.platform == "cpu":
        return {"mfu": None, "achieved_tflops": achieved_tflops, "peak_tflops": None}
    peak = detect_peak_tflops(device)
    return {"mfu": achieved_tflops / peak, "achieved_tflops": achieved_tflops,
            "peak_tflops": peak}


def overlap_microbench(steps: int = 30, produce_ms: float = 5.0, step_ms: float = 5.0,
                       async_prefetch: bool = True, prefetch_size: int = 4,
                       num_workers: int = 1) -> dict:
    """CPU-runnable proof that the async input pipeline overlaps host input
    work with the step: a synthetic producer burning ``produce_ms`` per batch
    feeds a jitted step whose device-side callback takes ``step_ms``. With
    overlap, wall-clock per step approaches max(produce, step); serialized it
    is their sum. Returns wall-clock plus the pipeline's own breakdown, so
    guards can assert both the speedup and near-zero ``data_wait_ms``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from accelerate_tpu.data_loader import DataLoaderShard

    class _SlowProducer:
        """len/iter source whose per-batch cost is a deterministic host sleep
        (fetch+collate stand-in; sleep releases the GIL like real IO)."""

        dataset = list(range(steps))
        batch_size = 4

        def __iter__(self):
            for i in range(steps):
                if produce_ms:
                    time.sleep(produce_ms / 1e3)
                yield {"x": np.full((4, 8), float(i), np.float32)}

        def __len__(self):
            return steps

    def _host_work(x):
        if step_ms:
            time.sleep(step_ms / 1e3)
        return np.float32(np.sum(x))

    @jax.jit
    def sleep_step(x):
        # The callback runs inside the compiled computation, so device_get
        # below blocks ~step_ms exactly like a real training step would.
        return jax.pure_callback(_host_work, jax.ShapeDtypeStruct((), jnp.float32), x)

    # Warm the compile outside the timed window.
    jax.device_get(sleep_step(np.zeros((4, 8), np.float32)))

    dl = DataLoaderShard(
        _SlowProducer(), mesh=None, stage_to_device=False,
        async_prefetch=async_prefetch, prefetch_size=prefetch_size,
        num_workers=num_workers,
    )
    t0 = time.perf_counter()
    out = None
    for batch in dl:
        out = sleep_step(batch["x"])
        jax.device_get(out)  # step loops block on metrics; model that here
    wall_s = time.perf_counter() - t0

    ideal_s = steps * max(produce_ms, step_ms) / 1e3
    serial_s = steps * (produce_ms + step_ms) / 1e3
    return {
        "steps": steps,
        "produce_ms": produce_ms,
        "step_ms": step_ms,
        "async_prefetch": async_prefetch,
        "prefetch_size": prefetch_size,
        "num_workers": num_workers,
        "wall_s": round(wall_s, 4),
        "ideal_s": round(ideal_s, 4),
        "serial_s": round(serial_s, 4),
        "vs_ideal": round(wall_s / ideal_s, 3) if ideal_s else None,
        **dl.pipeline_stats.summary(),
    }


def input_pipeline_extra(on_tpu: bool) -> dict:
    """The ``extra.input_pipeline`` payload: on CPU the full async-vs-sync
    overlap microbench (cheap, deterministic); on TPU only the stats of a
    short staged run are reported (no extra compiles)."""
    if on_tpu:
        return {}
    on = overlap_microbench(async_prefetch=True)
    off = overlap_microbench(async_prefetch=False)
    return {
        "async": on,
        "sync": off,
        "overlap_speedup": round(off["wall_s"] / on["wall_s"], 3) if on["wall_s"] else None,
    }


def _serving_test_engine(max_slots: int = 4, max_len: int = 64,
                         do_sample: bool = False, **kw):
    """(engine, model, params, cfg) on a tiny Llama — the serving
    microbenchmarks' shared fixture. Construction + warmup compile both
    engine programs, so callers time pure serving behavior."""
    import jax

    from accelerate_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from accelerate_tpu.serving import ServingEngine

    cfg = LlamaConfig.tiny()
    model = LlamaForCausalLM(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    engine = ServingEngine(model, params, max_slots=max_slots, max_len=max_len,
                           do_sample=do_sample, **kw)
    return engine, model, params, cfg


def serving_sweep(offered_loads=(20.0, 60.0, 200.0), n_requests: int = 12,
                  prompt_len: int = 4, max_new_tokens: int = 12,
                  max_slots: int = 4) -> dict:
    """Offered-load sweep over one warmed ServingEngine, paced
    OPEN-LOOP on a ``loadgen.ArrivalSchedule``: at each target load the
    schedule fixes every arrival time up front and submissions fire on
    that clock with ``block=False`` — a full admission queue sheds the
    request instead of stalling the sender — so the reported
    ``offered_rps`` is derived from the schedule and stays honest past
    saturation. The shape of the curve (TTFT flat while slots are free,
    rising once the queue forms, sheds appearing past the knee) is the
    payload, not absolute numbers.

    History note: through PR 16 this sweep reported ``offered_rps``
    while pacing CLOSED-loop (``submit(block=True)`` — the next send
    waited whenever the queue was full, silently sagging the realized
    rate to whatever the engine absorbed). The old measurement is kept
    under ``legacy_closed_loop`` with an explicit ``closed_loop: true``
    marker so trajectory diffs across the methodology switch read as a
    measurement change, not a perf change."""
    import numpy as np

    from accelerate_tpu.loadgen import ArrivalSchedule
    from accelerate_tpu.serving import QueueFull

    engine, _, _, _ = _serving_test_engine(max_slots=max_slots)
    rng = np.random.default_rng(0)
    prompts = rng.integers(1, 200, size=(n_requests, prompt_len)).astype(np.int32)

    def _one_load(load: float, closed_loop: bool) -> dict:
        engine.stats.reset()
        sched = ArrivalSchedule(n_requests, 1.0 / load, dist="uniform",
                                seed=0)
        offsets = sched.offsets()
        t0 = time.perf_counter()
        reqs, shed = [], 0
        for i in range(n_requests):
            target = t0 + (i / load if closed_loop else offsets[i])
            delay = target - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            try:
                reqs.append(engine.submit(prompts[i:i + 1],
                                          max_new_tokens=max_new_tokens,
                                          seed=i, block=closed_loop))
            except QueueFull:
                shed += 1
        for r in reqs:
            r.wait(timeout=120)
        wall_s = time.perf_counter() - t0
        s = engine.serving_metrics()
        point = {
            "offered_rps": (load if closed_loop
                            else round(sched.offered_rps, 3)),
            "target_rps": load,
            "shed": shed,
            "completed": s["requests_completed"],
            "wall_s": round(wall_s, 4),
            "throughput_tokens_per_sec": round(
                s["tokens_emitted"] / wall_s, 3) if wall_s else None,
            "decode_tokens_per_sec": s["decode_tokens_per_sec"],
            "ttft_ms_p50": s["ttft_ms_p50"],
            "ttft_ms_p95": s["ttft_ms_p95"],
            "queue_wait_ms": s["queue_wait_ms"],
            "slot_occupancy": s["slot_occupancy"],
            "batch_efficiency": s["batch_efficiency"],
        }
        return point

    try:
        points = [_one_load(load, closed_loop=False)
                  for load in offered_loads]
        legacy = [_one_load(load, closed_loop=True)
                  for load in offered_loads]
    finally:
        engine.shutdown()
    return {
        "n_requests": n_requests,
        "prompt_len": prompt_len,
        "max_new_tokens": max_new_tokens,
        "max_slots": max_slots,
        "closed_loop": False,
        "loads": points,
        "legacy_closed_loop": {"closed_loop": True, "loads": legacy},
    }


def _sleepy_llama_cls(step_ms: float, per_token: bool = False):
    """A tiny-Llama subclass whose forward ALSO burns a deterministic
    ``step_ms`` host sleep (pure_callback, data-dependent so XLA cannot
    elide it; ``broadcast_all`` so the engine's vmapped tick sleeps ONCE,
    not once per slot). Same trick as :func:`overlap_microbench`'s
    sleep-step: on CPU the tiny model decodes a token in ~50µs inside a
    compiled scan, so scheduling effects drown in host overhead — pinning
    the per-step cost to a real-model magnitude makes the continuous-vs-
    static comparison measure SCHEDULING, deterministically.

    ``per_token=True`` scales the sleep by the call's STATIC sequence
    width (``step_ms`` per input position), modeling the real cost shape
    of prefill: a width-C chunk burns ``C * step_ms`` where a decode tick
    burns ``step_ms`` — what the admission tests need for a long
    prompt's prefill window to be wide on any host."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from accelerate_tpu.models.llama import LlamaForCausalLM

    class _SleepyLlama(LlamaForCausalLM):
        def apply(self, variables, *args, **kwargs):
            out = super().apply(variables, *args, **kwargs)
            width = int(np.shape(args[0])[-1]) if per_token and args else 1

            def _sleep(x):
                time.sleep(width * step_ms / 1e3)
                return np.zeros(np.shape(x), np.float32)

            if isinstance(out, tuple):
                logits, cache = out
                # The callback input must VARY per decode step (an element
                # of the logits), or XLA hoists the loop-invariant callback
                # out of the offline decode scan and the static path stops
                # paying the per-step cost.
                z = jax.pure_callback(
                    _sleep, jax.ShapeDtypeStruct((), jnp.float32),
                    logits[(0,) * logits.ndim].astype(jnp.float32),
                    vmap_method="broadcast_all")
                return logits + z.astype(logits.dtype), cache
            return out

    return _SleepyLlama


def _biased_llama_cls(bias: float = 50.0, period: int = 6, lo: int = 9):
    """A tiny-Llama subclass whose logits get a DETERMINISTIC next-token
    bias: position ``i``'s logits are dominated by a ``bias``-sized
    one-hot on ``(ids[i] + 1) % period + lo`` — a fixed permutation walk
    over ``[lo, lo + period)``. The speculation accept-rate guards run on
    this, not on a random tiny model, because a random model's near-tied
    bf16 logits make draft-vs-target argmax agreement a coin flip (the
    PR 7 flake): here the target chain is a closed token cycle, any
    draft sharing the class proposes it exactly, a prompt-lookup matcher
    re-finds it after one period, and temperature sampling concentrates
    ~all mass on it (``exp(bias)`` dominance) so the rejection rule
    accepts too. The real transformer still runs — its logits survive,
    quantized to a coarse grid and scaled to 0.01 so they can never flip
    the argmax (or the sampled law) yet keep XLA from eliding the
    forward — and the walk avoids the test EOS id (7) by construction."""
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.models.llama import LlamaForCausalLM

    class _BiasedLlama(LlamaForCausalLM):
        def apply(self, variables, *args, **kwargs):
            out = super().apply(variables, *args, **kwargs)
            ids = args[0] if args else kwargs["input_ids"]
            if isinstance(out, tuple):
                logits, cache = out
            else:
                logits, cache = out, None
            nxt = (ids + 1) % period + lo
            hot = jax.nn.one_hot(nxt, logits.shape[-1], dtype=logits.dtype)
            logits = (jnp.round(logits * 8.0) / 8.0 * 0.01
                      + jnp.asarray(bias, logits.dtype) * hot)
            return logits if cache is None else (logits, cache)

    return _BiasedLlama


def continuous_vs_static(n_short: int = 3, short_new_tokens: int = 8,
                         long_new_tokens: int = 48, arrival_ms: float = 5.0,
                         prompt_len: int = 4, max_slots: int = 4,
                         max_len: int = 64, step_ms: float = 2.0) -> dict:
    """Staggered-arrival latency comparison on the traffic continuous
    batching exists for (Orca): ONE long request followed by short ones.

    * static baseline — dynamic-batch-on-idle over offline ``generate``:
      when idle, take every arrived request as one fixed batch; the batch
      decodes to its LONGEST member, and later arrivals wait for the whole
      batch. The shorts queue behind the long request — head-of-line
      blocking.
    * continuous — the ServingEngine: shorts join the batch mid-flight in
      free slots while the long request keeps its own slot.

    Both paths run the SAME sleepy model (every forward costs a
    deterministic ``step_ms``; see :func:`_sleepy_llama_cls`) and are fully
    precompiled before timing, so the gap is scheduling — not compilation,
    not host-overhead asymmetry. ``speedup`` is static/continuous on the
    SHORT requests' mean latency — the number head-of-line blocking
    actually moves."""
    import jax
    import numpy as np

    from accelerate_tpu import generation
    from accelerate_tpu.models.llama import LlamaConfig
    from accelerate_tpu.serving import ServingEngine

    model = _sleepy_llama_cls(step_ms)(LlamaConfig.tiny())
    params = model.init_params(jax.random.PRNGKey(0))
    engine = ServingEngine(model, params, max_slots=max_slots, max_len=max_len)
    n_requests = 1 + n_short
    rng = np.random.default_rng(0)
    prompts = rng.integers(1, 200, size=(n_requests, prompt_len)).astype(np.int32)
    new_tokens = [long_new_tokens] + [short_new_tokens] * n_short
    arrivals = [i * arrival_ms / 1e3 for i in range(n_requests)]

    def run_static():
        # Precompile every (batch, max_new) the loop can produce: the long
        # request always rides alone (it arrives first and decodes far past
        # the last arrival), shorts batch in any split.
        np.asarray(generation.generate(model, params, prompts[:1],
                                       max_new_tokens=long_new_tokens))
        for b in range(1, min(n_short, max_slots) + 1):
            np.asarray(generation.generate(model, params, prompts[1:1 + b],
                                           max_new_tokens=short_new_tokens))
        latency = [0.0] * n_requests
        next_idx, t0 = 0, time.perf_counter()
        while next_idx < n_requests:
            now = time.perf_counter() - t0
            n_arrived = next_idx
            while n_arrived < n_requests and arrivals[n_arrived] <= now:
                n_arrived += 1
            if n_arrived == next_idx:
                time.sleep(0.0005)
                continue
            batch = list(range(next_idx, min(n_arrived, next_idx + max_slots)))
            np.asarray(generation.generate(
                model, params, prompts[batch],
                max_new_tokens=max(new_tokens[i] for i in batch)))
            done = time.perf_counter() - t0
            for i in batch:
                latency[i] = done - arrivals[i]
            next_idx = batch[-1] + 1
        return latency

    def run_continuous():
        engine.stats.reset()
        t0 = time.perf_counter()
        reqs = []
        for i in range(n_requests):
            delay = t0 + arrivals[i] - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            reqs.append(engine.submit(prompts[i:i + 1],
                                      max_new_tokens=new_tokens[i],
                                      block=True))
        for r in reqs:
            r.wait(timeout=120)
        return [r.finished_at - r.submitted_at for r in reqs]

    try:
        static_lat = run_static()
        cont_lat = run_continuous()
        stats = engine.serving_metrics()
    finally:
        engine.shutdown()
    static_short = sum(static_lat[1:]) / n_short
    cont_short = sum(cont_lat[1:]) / n_short
    return {
        "n_short": n_short,
        "short_new_tokens": short_new_tokens,
        "long_new_tokens": long_new_tokens,
        "arrival_ms": arrival_ms,
        "max_slots": max_slots,
        "static_mean_latency_s": round(sum(static_lat) / n_requests, 4),
        "continuous_mean_latency_s": round(sum(cont_lat) / n_requests, 4),
        "static_short_latency_s": round(static_short, 4),
        "continuous_short_latency_s": round(cont_short, 4),
        "speedup": round(static_short / cont_short, 3) if cont_short else None,
        "continuous_stats": stats,
    }


def prefix_cache_hit_bench(prompt_len: int = 33, prefill_chunk: int = 8,
                           max_new_tokens: int = 4) -> dict:
    """Prefix-cache payoff, counter-exact: submit one multi-chunk prompt
    cold, then the IDENTICAL prompt again. The repeat must admit in
    exactly ONE chunk call (the final chunk always re-runs for its
    logits; every full chunk before it restores from cache), emit the
    same tokens, and the hit counters must balance — all read from
    ``serving_metrics()``, so the result is deterministic on any host."""
    engine, _, _, _ = _serving_test_engine(
        max_slots=2, prefill_chunk=prefill_chunk, prefix_cache_mb=4.0)
    import numpy as np

    rng = np.random.default_rng(0)
    prompt = rng.integers(1, 200, size=(1, prompt_len)).astype(np.int32)
    chunks_total = -(-prompt_len // prefill_chunk)
    try:
        r1 = engine.submit(prompt, max_new_tokens=max_new_tokens, seed=3)
        r1.wait(timeout=120)
        cold = engine.serving_metrics()
        cold_ttft = (r1.first_token_at - r1.submitted_at) * 1e3
        r2 = engine.submit(prompt, max_new_tokens=max_new_tokens, seed=3)
        r2.wait(timeout=120)
        warm = engine.serving_metrics()
        warm_ttft = (r2.first_token_at - r2.submitted_at) * 1e3
        tokens_equal = bool(np.array_equal(r1.result(), r2.result()))
    finally:
        engine.shutdown()
    return {
        "prompt_len": prompt_len,
        "prefill_chunk": prefill_chunk,
        "chunks_per_prompt": chunks_total,
        "cold_prefill_chunks": cold["prefill_chunks"],
        "warm_prefill_chunks": warm["prefill_chunks"] - cold["prefill_chunks"],
        "hit_chunks": warm["prefix_cache_hit_chunks"],
        "hit_rate": warm["prefix_cache_hit_rate"],
        "restored_bytes": warm["prefix_cache_restored_bytes"],
        "cache_entries": warm["prefix_cache_entries"],
        "cache_bytes": warm["prefix_cache_bytes"],
        "cold_ttft_ms": round(cold_ttft, 3),
        "warm_ttft_ms": round(warm_ttft, 3),
        "tokens_equal": tokens_equal,
    }


def _post_stream_ttft(url: str, payload: dict, timeout: float = 60.0):
    """POST a streaming completion and return (ttft_s, tokens, final_event):
    time from request send to the first SSE token event, the streamed
    token list, and the final summary event."""
    import json
    import urllib.request

    req = urllib.request.Request(
        url + "/v1/completions",
        data=json.dumps(dict(payload, stream=True)).encode(),
        headers={"Content-Type": "application/json"})
    tokens, final, ttft = [], None, None
    t0 = time.perf_counter()
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        for line in resp:
            line = line.strip()
            if not line.startswith(b"data: "):
                continue
            ev = json.loads(line[6:])
            if ev.get("done"):
                final = ev
                break
            if ttft is None:
                ttft = time.perf_counter() - t0
            tokens.append(ev["token"])
    return ttft, tokens, final


def gateway_overhead_bench(n_requests: int = 8, prompt_len: int = 4,
                           max_new_tokens: int = 8,
                           step_ms: float = 5.0) -> dict:
    """Closed-loop HTTP load against the gateway vs direct
    ``engine.submit`` on the SAME warmed engine: sequential requests, p95
    TTFT each way. The sleepy model pins per-token cost to a real-model
    magnitude so the ratio measures the HTTP+routing layer against real
    work, not against a ~50µs tiny-model forward where any socket
    round-trip would look catastrophic. The perf guard pins the ratio."""
    import jax
    import numpy as np

    from accelerate_tpu.models.llama import LlamaConfig
    from accelerate_tpu.serving import (
        GatewayConfig,
        ReplicaSet,
        ServingEngine,
        ServingGateway,
    )

    model = _sleepy_llama_cls(step_ms)(LlamaConfig.tiny())
    params = model.init_params(jax.random.PRNGKey(0))
    engine = ServingEngine(model, params, max_slots=4, max_len=64,
                           prefill_chunk=16, prefix_cache_mb=0.0)
    rng = np.random.default_rng(0)
    prompts = rng.integers(1, 200, size=(n_requests, prompt_len)).astype(np.int32)
    gw = ServingGateway(ReplicaSet([engine]),
                        config=GatewayConfig(port=0))
    gw.start()
    try:
        direct_ttft, http_ttft = [], []
        # One untimed exchange per path: first HTTP hit pays connection /
        # handler-thread setup that steady-state traffic never sees again.
        engine.submit(prompts[0:1], max_new_tokens=2, seed=0,
                      block=True).wait(timeout=60)
        _post_stream_ttft(gw.url, {"prompt": prompts[0].tolist(),
                                   "max_new_tokens": 2, "seed": 0})
        for i in range(n_requests):
            r = engine.submit(prompts[i:i + 1],
                              max_new_tokens=max_new_tokens, seed=i,
                              block=True)
            r.wait(timeout=60)
            direct_ttft.append(r.first_token_at - r.submitted_at)
        for i in range(n_requests):
            ttft, toks, final = _post_stream_ttft(
                gw.url, {"prompt": prompts[i].tolist(),
                         "max_new_tokens": max_new_tokens, "seed": i})
            http_ttft.append(ttft)
    finally:
        gw.shutdown()

    def p95(xs):
        return sorted(xs)[min(len(xs) - 1, int(round(0.95 * (len(xs) - 1))))]

    d95, h95 = p95(direct_ttft) * 1e3, p95(http_ttft) * 1e3
    return {
        "n_requests": n_requests,
        "step_ms": step_ms,
        "direct_ttft_ms_p95": round(d95, 3),
        "http_ttft_ms_p95": round(h95, 3),
        "overhead_ratio_p95": round(h95 / d95, 3) if d95 else None,
    }


def open_loop_ab_bench(n_streams: int = 48,
                       mean_interarrival_s: float = 0.005,
                       step_ms: float = 2.0,
                       threading_connections: int = 8,
                       slo_ttft_s: float = 2.0,
                       wall_deadline_s: float = 60.0) -> dict:
    """Threading-vs-asyncio gateway front ends under IDENTICAL open-loop
    offered load, deliberately past the threading front end's saturation
    knee (its connection cap is pinned low so the knee is cheap to
    reach): the same seeded ``loadgen`` schedule and traffic profile
    drive both, so every difference in the two reports is the front end.
    Past the knee the threading server refuses the excess at its
    connection cap — those streams never start, so measured from their
    *scheduled* arrival their TTFT is unbounded and the offered-load p99
    (clamped at the wall deadline for a finite number) collapses, while
    the asyncio front end keeps accepting: its event loop holds every
    stream open for a few KB each and the engine's admission queue does
    the real flow control. The perf guard pins the p99-TTFT ratio and
    that the threading side actually hit its cap (otherwise the A/B
    never left the flat region and proves nothing)."""
    import jax

    from accelerate_tpu.loadgen import (
        ArrivalSchedule,
        TrafficProfile,
        build_report,
        fetch_gateway_metrics,
        run_open_loop,
    )
    from accelerate_tpu.models.llama import LlamaConfig
    from accelerate_tpu.serving import (
        GatewayConfig,
        ReplicaSet,
        ServingEngine,
        ServingGateway,
    )

    cfg = LlamaConfig.tiny()
    model = _sleepy_llama_cls(step_ms)(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    out = {"n_streams": n_streams, "step_ms": step_ms,
           "threading_connections": threading_connections}
    for server in ("threading", "asyncio"):
        rs = ReplicaSet.from_factory(
            lambda: ServingEngine(model, params, max_slots=4, max_len=64,
                                  prefill_chunk=16, prefix_cache_mb=0.0,
                                  max_queued=2 * n_streams), 1)
        gw_cfg = GatewayConfig(
            server=server, port=0,
            max_connections=(threading_connections
                             if server == "threading" else None))
        # Same seeds both sides: identical arrival times, identical
        # request shapes — the offered load really is the control.
        sched = ArrivalSchedule(n_streams, mean_interarrival_s,
                                dist="lognormal", sigma=0.8, seed=0)
        prof = TrafficProfile(
            prompt_len_median=4, prompt_len_max=8, out_tokens_median=6,
            out_tokens_max=10, sampled_fraction=0.5, seed=1)
        with ServingGateway(rs, config=gw_cfg) as gw:
            run = run_open_loop(gw.url, sched, prof,
                                vocab_size=cfg.vocab_size,
                                wall_deadline_s=wall_deadline_s)
            metrics = fetch_gateway_metrics(gw.url)
        out[server] = build_report(run, sched, prof, slo_ttft_s=slo_ttft_s,
                                   clamp_s=wall_deadline_s,
                                   server_metrics=metrics)
    thr = out["threading"]["ttft_s"]["p99_clamped"]
    aio = out["asyncio"]["ttft_s"]["p99_clamped"]
    out["p99_ttft_ratio_threading_over_asyncio"] = (
        round(thr / aio, 3) if thr and aio else None)
    out["threading_conn_rejections"] = (
        out["threading"].get("server_metrics", {}).get("conn_rejections"))
    return out


def slo_control_bench(n_streams: int = 96,
                      mean_interarrival_s: float = 0.01,
                      step_ms: float = 5.0,
                      interactive_fraction: float = 0.25,
                      slo_ttft_s: float = 0.5,
                      wall_deadline_s: float = 60.0) -> dict:
    """SLO control plane A/B at ~2x saturation: the same seeded open-loop
    schedule and mixed interactive/batch traffic profile drive two
    single-replica fleets that differ ONLY in the engine's priority
    policy — ``priority_policy=None`` (the historical FCFS baseline:
    priority declared but not acted on) vs the default
    :class:`~accelerate_tpu.serving.PriorityPolicy` (priority admission
    queue + lowest-class-first preemption). Offered load is ~2x the
    fleet's decode throughput, so a deep admission queue builds; under
    FCFS an interactive arrival waits behind every batch stream already
    queued and its TTFT tail tracks the full backlog, while under the
    control plane it jumps to the interactive bucket and the tail tracks
    only same-class work. The perf guard pins the interactive-class
    clamped-p99-TTFT ratio (FCFS over control) at >= 2x — the headline
    SLO claim — and that batch still completes (work-conserving, not
    starvation)."""
    import jax

    from accelerate_tpu.loadgen import (
        ArrivalSchedule,
        TrafficProfile,
        build_report,
        fetch_gateway_metrics,
        run_open_loop,
    )
    from accelerate_tpu.models.llama import LlamaConfig
    from accelerate_tpu.serving import (
        GatewayConfig,
        ReplicaSet,
        ServingEngine,
        ServingGateway,
    )

    cfg = LlamaConfig.tiny()
    model = _sleepy_llama_cls(step_ms)(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    out = {"n_streams": n_streams, "step_ms": step_ms,
           "mean_interarrival_s": mean_interarrival_s,
           "interactive_fraction": interactive_fraction}
    for side, policy in (("fcfs", None), ("control", "default")):
        rs = ReplicaSet.from_factory(
            lambda p=policy: ServingEngine(
                model, params, max_slots=2, max_len=64, prefill_chunk=16,
                prefix_cache_mb=0.0, max_queued=2 * n_streams,
                priority_policy=p), 1)
        # Same seeds both sides: identical arrivals, shapes, and class
        # assignments — the only variable is the scheduling policy.
        sched = ArrivalSchedule(n_streams, mean_interarrival_s,
                                dist="lognormal", sigma=0.8, seed=0)
        prof = TrafficProfile(
            prompt_len_median=4, prompt_len_max=8, out_tokens_median=6,
            out_tokens_max=10, sampled_fraction=0.0,
            priorities=(("interactive", interactive_fraction),
                        ("batch", 1.0 - interactive_fraction)),
            seed=1)
        with ServingGateway(rs, config=GatewayConfig(server="asyncio",
                                                     port=0)) as gw:
            run = run_open_loop(gw.url, sched, prof,
                                vocab_size=cfg.vocab_size,
                                wall_deadline_s=wall_deadline_s)
            metrics = fetch_gateway_metrics(gw.url)
        out[side] = build_report(run, sched, prof, slo_ttft_s=slo_ttft_s,
                                 clamp_s=wall_deadline_s,
                                 server_metrics=metrics)
    fcfs = (out["fcfs"]["per_priority"].get("interactive", {})
            .get("ttft_s", {}).get("p99_clamped"))
    ctrl = (out["control"]["per_priority"].get("interactive", {})
            .get("ttft_s", {}).get("p99_clamped"))
    out["interactive_p99_ttft_ratio_fcfs_over_control"] = (
        round(fcfs / ctrl, 3) if fcfs and ctrl else None)
    out["batch_completed_under_control"] = (
        out["control"]["per_priority"].get("batch", {}).get("completed"))
    return out


def replica_failover_bench(n_inflight: int = 4, step_ms: float = 20.0,
                           prompt_len: int = 6,
                           max_new_tokens: int = 24) -> dict:
    """Kill 1 of 2 replicas with ``n_inflight`` streams in flight and
    measure failover: recovery time (kill -> every stream finished on the
    survivor), whether every resumed stream is token-identical to the
    uninterrupted offline reference (greedy: it must be), and the
    router's fence/failover counters."""
    import jax
    import numpy as np

    from accelerate_tpu import generation
    from accelerate_tpu.models.llama import LlamaConfig
    from accelerate_tpu.serving import ReplicaSet, ServingEngine

    model = _sleepy_llama_cls(step_ms)(LlamaConfig.tiny())
    params = model.init_params(jax.random.PRNGKey(0))

    def factory():
        return ServingEngine(model, params, max_slots=max(4, n_inflight),
                             max_len=64, prefill_chunk=16,
                             prefix_cache_mb=4.0)

    rs = ReplicaSet.from_factory(factory, 2)
    rng = np.random.default_rng(0)
    prompts = rng.integers(1, 200,
                           size=(n_inflight, prompt_len)).astype(np.int32)
    refs = [np.asarray(generation.generate(
        model, params, prompts[i:i + 1], max_new_tokens=max_new_tokens)
        )[0, prompt_len:] for i in range(n_inflight)]
    try:
        reqs = [rs.submit(prompts[i:i + 1], max_new_tokens=max_new_tokens,
                          seed=i) for i in range(n_inflight)]
        # Let every stream emit a few tokens, then kill the replica that
        # holds the FIRST request (some requests ride along, some don't —
        # both paths are exercised).
        deadline = time.perf_counter() + 60
        while (min(len(r.tokens) for r in reqs) < 3
               and time.perf_counter() < deadline):
            time.sleep(0.01)
        victim = reqs[0].replica_trail[0]
        t_kill = time.perf_counter()
        rs.kill_replica(victim)
        for r in reqs:
            r.wait(timeout=120)
        recovery_s = time.perf_counter() - t_kill
        exact = all(
            np.array_equal(np.asarray(r.tokens), refs[i][:len(r.tokens)])
            for i, r in enumerate(reqs))
        completed = all(r.status.value == "completed" for r in reqs)
        fleet = rs.fleet_metrics()
    finally:
        rs.shutdown()
    return {
        "n_inflight": n_inflight,
        "step_ms": step_ms,
        "recovery_s": round(recovery_s, 4),
        "all_completed": completed,
        "tokens_exact": bool(exact),
        "failovers": fleet["fleet_failovers"],
        "fences": fleet["fleet_fences"],
        "replicas_failed": fleet["replicas_failed"],
    }


def chaos_recovery_bench(n_inflight: int = 4, step_ms: float = 20.0,
                         prompt_len: int = 6, max_new_tokens: int = 24,
                         kill_tick: int = 6) -> dict:
    """The self-healing drill: a scripted chaos kill (deterministic, at
    decode tick ``kill_tick``) under a running FleetSupervisor. Measures
    the two recovery clocks — kill -> every stream finished on the
    survivor (``recovery_s``) and kill -> dead replica rebuilt, re-warmed
    and back HEALTHY (``rejoin_s``) — plus stream exactness across the
    failover and the supervisor's restart accounting."""
    import jax
    import numpy as np

    from accelerate_tpu import generation
    from accelerate_tpu.models.llama import LlamaConfig
    from accelerate_tpu.serving import (
        ChaosSchedule,
        FleetSupervisor,
        ReplicaSet,
        ReplicaState,
        ServingEngine,
    )

    model = _sleepy_llama_cls(step_ms)(LlamaConfig.tiny())
    params = model.init_params(jax.random.PRNGKey(0))

    def factory():
        return ServingEngine(model, params, max_slots=max(4, n_inflight),
                             max_len=64, prefill_chunk=16,
                             prefix_cache_mb=4.0)

    chaos = ChaosSchedule().kill(at_tick=kill_tick)
    chaos_engine = ServingEngine(model, params,
                                 max_slots=max(4, n_inflight), max_len=64,
                                 prefill_chunk=16, prefix_cache_mb=4.0,
                                 chaos=chaos)
    rs = ReplicaSet([chaos_engine, factory()], factories=[factory, factory])
    rng = np.random.default_rng(0)
    prompts = rng.integers(1, 200,
                           size=(n_inflight, prompt_len)).astype(np.int32)
    refs = [np.asarray(generation.generate(
        model, params, prompts[i:i + 1], max_new_tokens=max_new_tokens)
        )[0, prompt_len:] for i in range(n_inflight)]
    sup = FleetSupervisor(rs, hang_timeout_s=5.0, poll_interval_s=0.02,
                          restart_backoff_s=0.05)
    try:
        sup.start()
        reqs = [rs.submit(prompts[i:i + 1], max_new_tokens=max_new_tokens,
                          seed=i) for i in range(n_inflight)]
        # t_kill = the moment the scripted fault actually fires (the
        # chaos engine's error goes non-None); both clocks start there.
        t_kill = None
        deadline = time.perf_counter() + 120
        while time.perf_counter() < deadline:
            if t_kill is None and chaos_engine.error is not None:
                t_kill = time.perf_counter()
            if all(r.done for r in reqs):
                break
            time.sleep(0.005)
        if t_kill is None:  # kill raced the final waits; pin it now
            t_kill = time.perf_counter()
        recovery_s = time.perf_counter() - t_kill
        exact = all(
            np.array_equal(np.asarray(r.tokens), refs[i][:len(r.tokens)])
            for i, r in enumerate(reqs))
        completed = all(r.status.value == "completed" for r in reqs)
        deadline = time.perf_counter() + 120
        while (rs.replicas[0].state is not ReplicaState.HEALTHY
               and time.perf_counter() < deadline):
            time.sleep(0.02)
        rejoin_s = time.perf_counter() - t_kill
        rejoined = rs.replicas[0].state is ReplicaState.HEALTHY
        fleet = rs.fleet_metrics()
    finally:
        sup.stop()
        rs.shutdown()
    return {
        "n_inflight": n_inflight,
        "step_ms": step_ms,
        "kill_tick": kill_tick,
        "recovery_s": round(recovery_s, 4),
        "rejoin_s": round(rejoin_s, 4),
        "rejoined_healthy": bool(rejoined),
        "all_completed": completed,
        "tokens_exact": bool(exact),
        "failovers": fleet["fleet_failovers"],
        "restarts": fleet["fleet_restarts"],
        "chaos_fired": chaos.fired(),
    }


def _test_lora_adapters(params, n_tenants: int, rank: int):
    """``n_tenants`` distinct rank-``rank`` adapters with nonzero B factors
    (a fresh ``init_lora_params`` is a zero delta — useless for telling
    tenants apart)."""
    import jax

    from accelerate_tpu.adapters import LoRAConfig, init_lora_params

    cfg = LoRAConfig(rank=rank)
    out = []
    for t in range(n_tenants):
        ad = init_lora_params(jax.random.PRNGKey(t), params, cfg)
        flat, _ = jax.tree_util.tree_flatten_with_path(ad)
        leaves = []
        for i, (path, leaf) in enumerate(flat):
            if getattr(path[-1], "key", None) == "b":
                k = jax.random.fold_in(jax.random.PRNGKey(1000 + t), i)
                leaf = 0.05 * jax.random.normal(k, leaf.shape, leaf.dtype)
            leaves.append(leaf)
        out.append(jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(ad), leaves))
    return out


def multi_tenant_adapter_bench(n_tenants: int = 4, prompt_len: int = 4,
                               max_new_tokens: int = 24, rank: int = 4,
                               step_ms: float = 10.0) -> dict:
    """Batched multi-tenant LoRA serving vs sequential merged-weight
    swapping, ``n_tenants`` tenants with one request each:

    * batched — ONE engine with an :class:`AdapterBank`: every tenant's
      request decodes in its own slot of the SAME vmapped tick, each slot
      gathering its own bank row; the per-tick sleepy cost is paid once
      for all tenants.
    * sequential — the no-bank alternative: per tenant, merge the adapter
      into the base weights (the swap cost) and run offline ``generate``;
      tenants serialize, so every tenant pays the full per-token cost.

    Both paths run the SAME sleepy model and are precompiled before
    timing (merged params are jit ARGUMENTS, so swapping tenants never
    recompiles the sequential path either — the measured gap is
    batching, not compilation). ``tokens_equal`` asserts each tenant's
    served stream is token-identical to offline generate on its merged
    weights — the correctness half of the A/B."""
    import jax
    import numpy as np

    from accelerate_tpu import generation
    from accelerate_tpu.adapters import AdapterBank, LoRAConfig, merge_adapter
    from accelerate_tpu.models.llama import LlamaConfig
    from accelerate_tpu.serving import ServingEngine

    model = _sleepy_llama_cls(step_ms)(LlamaConfig.tiny())
    params = model.init_params(jax.random.PRNGKey(0))
    adapters = _test_lora_adapters(params, n_tenants, rank)
    names = [f"tenant{t}" for t in range(n_tenants)]
    rng = np.random.default_rng(0)
    prompts = rng.integers(1, 200,
                           size=(n_tenants, prompt_len)).astype(np.int32)

    merged = [merge_adapter(params, ad) for ad in adapters]

    # Sequential baseline, precompiled: one untimed generate so the timed
    # loop pays merge + execution only, never compilation.
    np.asarray(generation.generate(model, merged[0], prompts[:1],
                                   max_new_tokens=max_new_tokens))
    t0 = time.perf_counter()
    seq_out = []
    for t in range(n_tenants):
        w = merge_adapter(params, adapters[t])  # the per-tenant swap cost
        jax.block_until_ready(w)
        seq_out.append(np.asarray(generation.generate(
            model, w, prompts[t:t + 1],
            max_new_tokens=max_new_tokens))[0, prompt_len:])
    sequential_s = time.perf_counter() - t0

    bank = AdapterBank(params, config=LoRAConfig(rank=rank),
                       max_adapters=n_tenants + 1)
    engine = ServingEngine(model, params, max_slots=n_tenants, max_len=64,
                           prefix_cache_mb=0.0, adapters=bank)
    try:
        for name, ad in zip(names, adapters):
            engine.register_adapter(name, ad)
        t0 = time.perf_counter()
        reqs = [engine.submit(prompts[t:t + 1],
                              max_new_tokens=max_new_tokens,
                              adapter=names[t], block=True)
                for t in range(n_tenants)]
        for r in reqs:
            r.wait(timeout=120)
        batched_s = time.perf_counter() - t0
        tokens_equal = all(
            np.array_equal(np.asarray(reqs[t].tokens), seq_out[t])
            for t in range(n_tenants))
        stats = engine.serving_metrics()
    finally:
        engine.shutdown()
    return {
        "n_tenants": n_tenants,
        "rank": rank,
        "step_ms": step_ms,
        "max_new_tokens": max_new_tokens,
        "sequential_swap_s": round(sequential_s, 4),
        "batched_s": round(batched_s, 4),
        "speedup": round(sequential_s / batched_s, 3) if batched_s else None,
        "tokens_equal": bool(tokens_equal),
        "adapter_requests": stats.get("adapter_requests"),
        "adapter_loads": stats.get("adapter_loads"),
    }


def adapters_extra(on_tpu: bool) -> dict:
    """The ``extra.adapters`` payload: the batched-vs-sequential-swap
    multi-tenant A/B on the sleepy tiny model (CPU only, same reasoning
    as :func:`serving_extra`)."""
    if on_tpu:
        return {}
    return {"multi_tenant": multi_tenant_adapter_bench()}


def serving_tp_bench(n_requests: int = 3, prompt_len: int = 6,
                     max_new_tokens: int = 16) -> dict:
    """Mesh-sliced serving A/B: the SAME requests through a single-chip
    engine and a tp=2 slice. The payload is correctness + footprint, not
    wall-clock (CPU collectives prove nothing about a real interconnect):

    * ``tokens_equal`` — tp=2 must be token-identical to tp=1 (GSPMD
      shards the math, never changes it);
    * ``warm_executables`` — both engines hold exactly the warm
      programs (chunk / decode tick; paged engines alias prefix
      restores and compile no restore program), sharded or not;
    * ``kv_per_chip_ratio`` — live KV state bytes per chip ≈ 1/tp;
    * ``compiled_arg_bytes`` — ``memory_analysis()`` of a fresh decode
      compile, showing XLA itself plans ~1/tp the argument bytes.
    """
    import jax
    import numpy as np

    if jax.device_count() < 2:
        return {"skipped": f"needs >= 2 devices (have {jax.device_count()})"}

    from accelerate_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from accelerate_tpu.serving import ServingEngine

    model = LlamaForCausalLM(LlamaConfig.tiny())
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = rng.integers(1, 200,
                           size=(n_requests, prompt_len)).astype(np.int32)
    kw = dict(max_slots=2, max_len=64, prefill_chunk=16,
              do_sample=True, temperature=0.8, top_k=40)

    def serve(tp):
        engine = ServingEngine(model, params,
                               **(dict(kw, tp=tp) if tp > 1 else kw))
        try:
            toks = []
            for i in range(n_requests):
                r = engine.submit(prompts[i:i + 1],
                                  max_new_tokens=max_new_tokens,
                                  seed=i, block=True)
                toks.append(np.asarray(r.result(timeout=120)))
            # Paged engines alias prefix restores through the page table
            # and have no compiled restore program (_restore_prefix None).
            warm = [f._cache_size() for f in
                    (engine._prefill_chunk, engine._decode,
                     engine._restore_prefix) if f is not None]
            kv_pc = engine.kv_cache_per_chip_bytes()
            mem = engine.decode_memory_analysis()
            arg_bytes = getattr(mem, "argument_size_in_bytes", None)
        finally:
            engine.shutdown()
        return toks, warm, kv_pc, arg_bytes

    toks1, warm1, kv1, arg1 = serve(1)
    toks2, warm2, kv2, arg2 = serve(2)
    tokens_equal = all(np.array_equal(a, b) for a, b in zip(toks1, toks2))
    return {
        "tp": 2,
        "n_requests": n_requests,
        "max_new_tokens": max_new_tokens,
        "tokens_equal": bool(tokens_equal),
        "warm_executables": {"tp1": warm1, "tp2": warm2},
        "kv_per_chip_bytes": {"tp1": kv1, "tp2": kv2},
        "kv_per_chip_ratio": round(kv2 / kv1, 4) if kv1 else None,
        "compiled_arg_bytes": {"tp1": arg1, "tp2": arg2},
    }


def paged_capacity_bench(worst_case_slots: int = 2, max_len: int = 64,
                         page_size: int = 8, prompt_len: int = 4,
                         new_tokens: int = 12, step_ms: float = 2.0) -> dict:
    """Slots a KV pool sustains on short traffic — the page pool's
    capacity claim, as counts.

    A pool of ``worst_case_slots * max_len`` tokens is what
    ``worst_case_slots`` streams need if each may grow to ``max_len``.
    The engine gets exactly that pool (``worst_case_slots * max_len /
    page_size`` pages) and as many slots as it can cover at the
    benchmark's actual sequence length (``prompt + new`` tokens = a
    couple of pages), then serves one burst of that many requests on the
    deterministic-sleep model. ``peak_concurrency`` is the maximum
    number of overlapping admitted->finished intervals;
    ``slots_ratio`` divides it by ``worst_case_slots``. Greedy tokens
    must equal offline ``generate`` (paging is a memory layout, not a
    semantic change) and the run must not preempt (the pool really fits
    the advertised concurrency)."""
    import jax
    import numpy as np

    from accelerate_tpu import generation
    from accelerate_tpu.models.llama import LlamaConfig
    from accelerate_tpu.serving import ServingEngine

    pool_pages = worst_case_slots * max_len // page_size
    pages_per_req = -(-(prompt_len + new_tokens) // page_size)
    slots = pool_pages // pages_per_req

    model = _sleepy_llama_cls(step_ms)(LlamaConfig.tiny())
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = rng.integers(1, 200, size=(slots, prompt_len)).astype(np.int32)

    engine = ServingEngine(model, params, max_len=max_len, max_slots=slots,
                           max_pages=pool_pages, prefill_chunk=page_size,
                           eos_token_id=None)
    try:
        kv_bytes = engine.kv_cache_per_chip_bytes()
        reqs = [engine.submit(prompts[i:i + 1], max_new_tokens=new_tokens,
                              ignore_eos=True, block=True)
                for i in range(slots)]
        toks = [np.asarray(r.result(timeout=300)) for r in reqs]
        # Peak concurrency = max overlap of slot-residency intervals.
        events = sorted([(r.admitted_at, 1) for r in reqs]
                        + [(r.finished_at, -1) for r in reqs])
        peak = cur = 0
        for _, d in events:
            cur += d
            peak = max(peak, cur)
        stats = engine.serving_metrics()
    finally:
        engine.shutdown()
    offline = np.asarray(generation.generate(
        model, params, prompts, max_new_tokens=new_tokens))[:, prompt_len:]
    return {
        "worst_case_slots": worst_case_slots,
        "slots": slots,
        "max_len": max_len,
        "page_size": page_size,
        "pool_pages": pool_pages,
        "request_tokens": prompt_len + new_tokens,
        "kv_bytes": kv_bytes,
        "peak_concurrency": peak,
        "slots_ratio": round(peak / worst_case_slots, 3),
        "tokens_equal": all(np.array_equal(a, b)
                            for a, b in zip(toks, offline)),
        "preemptions": stats["preemptions"],
        "page_utilization": stats["page_utilization"],
    }


def speculative_bench(prompt_len: int = 5, new_tokens: int = 24,
                      spec_tokens: int = 4, n_requests: int = 3) -> dict:
    """Speculative-decoding A/B matrix on the deterministic biased-logits
    fixture (:func:`_biased_llama_cls` — draft and target share the model
    class, so every divergence is a verify/commit bug, never draft
    quality or bf16 tie noise). The greedy base case keeps the legacy
    top-level keys; ``modes`` adds the four configurations PR 7 rejected
    and this engine now serves: temperature sampling (rejection-sampling
    accept), an AdapterBank tenant, a tp=2 mesh slice (self-skips below
    2 devices), and draft-free prompt-lookup. Each entry reports
    ``accepted_tokens_per_step`` (committed tokens per verify tick — 1.0
    means speculation never helps) and exactness vs its non-speculative
    twin on the SAME traffic; wall-clock is not reported (on CPU the
    K-step draft scan costs more host time than it saves — the win is
    device steps, which is what ticks count)."""
    import jax
    import numpy as np

    from accelerate_tpu.adapters import (AdapterBank, LoRAConfig,
                                         init_lora_params)
    from accelerate_tpu.models.llama import LlamaConfig
    from accelerate_tpu.serving import ServingEngine

    model = _biased_llama_cls()(LlamaConfig.tiny())
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    prompts = rng.integers(9, 15,
                           size=(n_requests, prompt_len)).astype(np.int32)

    def serve(adapter=None, seed=None, with_bank=False, **kw):
        if with_bank:
            bank = AdapterBank(params, config=LoRAConfig(rank=4),
                               max_adapters=2)
            bank.register("tenant", init_lora_params(
                jax.random.PRNGKey(1), params, LoRAConfig(rank=4)))
            kw["adapters"] = bank
        engine = ServingEngine(model, params, max_slots=2, max_len=64,
                               prefill_chunk=8, eos_token_id=None, **kw)
        try:
            toks = [np.asarray(
                engine.submit(prompts[i:i + 1], max_new_tokens=new_tokens,
                              ignore_eos=True, block=True, adapter=adapter,
                              seed=None if seed is None else seed + i)
                .result(timeout=300))
                for i in range(n_requests)]
            stats = engine.serving_metrics()
        finally:
            engine.shutdown()
        return toks, stats

    def ab(spec_kw, base_kw=None, **traffic):
        base_kw = base_kw or {}
        b_toks, b_stats = serve(**base_kw, **traffic)
        s_toks, s_stats = serve(**base_kw, **spec_kw, **traffic)
        out = {
            "tokens_equal": bool(all(np.array_equal(a, b)
                                     for a, b in zip(b_toks, s_toks))),
            "ticks": {"baseline": b_stats["decode_ticks"],
                      "speculative": s_stats["decode_ticks"]},
            "tick_ratio": round(b_stats["decode_ticks"]
                                / max(s_stats["decode_ticks"], 1), 3),
            "accepted_tokens_per_step": s_stats["spec_tokens_per_tick"],
            "accept_rate": s_stats["spec_accept_rate"],
        }
        if "spec_lookup" in spec_kw:
            out["lookup_hit_rate"] = s_stats["spec_lookup_hit_rate"]
        return out

    draft = dict(draft_model=model, draft_params=params,
                 spec_tokens=spec_tokens)
    out = ab(draft)
    out.update(spec_tokens=spec_tokens, n_requests=n_requests,
               new_tokens=new_tokens)
    modes = {
        "sampled": ab(draft, base_kw=dict(do_sample=True, temperature=0.8),
                      seed=0),
        "adapter": ab(draft, adapter="tenant", with_bank=True),
        "lookup": ab(dict(spec_lookup=2, spec_tokens=spec_tokens)),
    }
    if jax.device_count() >= 2:
        modes["tp2"] = ab(draft, base_kw=dict(tp=2))
    else:
        modes["tp2"] = {"skipped": "needs >= 2 devices "
                                   f"(have {jax.device_count()})"}
    out["modes"] = modes
    return out


def quantized_serving_bench(worst_case_slots: int = 2, max_len: int = 64,
                            page_size: int = 8, prompt_len: int = 4,
                            new_tokens: int = 16, step_ms: float = 2.0,
                            spec_tokens: int = 4) -> dict:
    """Equal-HBM quantized-KV A/B — the int8 serving tentpole's claim.

    Capacity: the fp paged engine gets the 16-page template pool
    (``worst_case_slots * max_len / page_size`` pages, same as
    :func:`paged_capacity_bench`); the ``kv_dtype="int8"`` engine gets
    the SAME pool BYTES, which buy it ``itemsize``-ish times more pages
    (per-page f32 scales included — the engine's own ``_page_bytes``
    accounting) and proportionally more slots. ``concurrency_ratio`` is
    peak overlapping admitted->finished intervals, int8/fp, at equal
    HBM — the perf guard pins >= 1.8. Decode throughput rides along.

    Divergence: on the real (non-sleepy) tiny model, int8-kv and
    int8-kv+weights engines report per-stream prefix token agreement vs
    the fp engine, and ``logprob_drift`` — max |delta logprob| of the
    quantized engine's emitted tokens between the full-precision and
    quantized-weights forwards, teacher-forced — fed through
    ``ServingStats.record_logprob_drift`` so it surfaces exactly where
    /metrics reports it (``kv_dtype=None`` engines pin 0.0 drift and
    bit-exactness in the test suite, not here).

    Speculation: the draft-model A/B from :func:`speculative_bench`
    re-runs with int8 kv pages — draft and target both read the
    dequantized view, so the accept rate must not collapse."""
    import time as _time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from accelerate_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from accelerate_tpu.serving import ServingEngine

    pool_pages = worst_case_slots * max_len // page_size
    pages_per_req = -(-(prompt_len + new_tokens) // page_size)
    fp_slots = pool_pages // pages_per_req

    model = _sleepy_llama_cls(step_ms)(LlamaConfig.tiny())
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)

    def serve(n_req, prompts, **kw):
        engine = ServingEngine(model, params, max_len=max_len,
                               prefill_chunk=page_size, eos_token_id=None,
                               **kw)
        try:
            kv_bytes = engine.kv_cache_per_chip_bytes()
            page_bytes = engine._page_bytes
            pool = [np.asarray(l)
                    for l in jax.tree_util.tree_leaves(engine._state["pool"])]
            t0 = _time.perf_counter()
            reqs = [engine.submit(prompts[i:i + 1],
                                  max_new_tokens=new_tokens,
                                  ignore_eos=True, block=True)
                    for i in range(n_req)]
            toks = [np.asarray(r.result(timeout=300)) for r in reqs]
            wall = _time.perf_counter() - t0
            events = sorted([(r.admitted_at, 1) for r in reqs]
                            + [(r.finished_at, -1) for r in reqs])
            peak = cur = 0
            for _, d in events:
                cur += d
                peak = max(peak, cur)
            stats = engine.serving_metrics()
        finally:
            engine.shutdown()
        return dict(toks=toks, peak=peak, kv_bytes=kv_bytes,
                    page_bytes=page_bytes, pool=pool, wall=wall,
                    stats=stats)

    fp_prompts = rng.integers(1, 200,
                              size=(fp_slots, prompt_len)).astype(np.int32)
    fp = serve(fp_slots, fp_prompts, max_slots=fp_slots,
               max_pages=pool_pages)
    # Equal pool bytes: derive the int8 per-page cost from the fp pool's
    # own geometry (elements/page + one f32 scale per leaf per page —
    # the formula ServingEngine._page_bytes uses), then buy as many int8
    # pages as the fp pool's bytes cover.
    n_leaves = len(fp["pool"])
    elems = fp["page_bytes"] // fp["pool"][0].dtype.itemsize
    int8_page_bytes = elems + 4 * n_leaves
    int8_pages = (pool_pages * fp["page_bytes"]) // int8_page_bytes
    int8_slots = int8_pages // pages_per_req
    q_prompts = rng.integers(1, 200,
                             size=(int8_slots, prompt_len)).astype(np.int32)
    q = serve(int8_slots, q_prompts, max_slots=int8_slots,
              max_pages=int8_pages, kv_dtype="int8")
    assert q["page_bytes"] == int8_page_bytes, \
        f"page-byte accounting drifted: {q['page_bytes']} != {int8_page_bytes}"

    # --- divergence on the real tiny model (no sleeps) ---------------
    dmodel = LlamaForCausalLM(LlamaConfig.tiny())
    dparams = dmodel.init_params(jax.random.PRNGKey(0))
    div_prompts = rng.integers(1, 200, size=(3, prompt_len)).astype(np.int32)

    def run_engine(**kw):
        engine = ServingEngine(dmodel, dparams, max_slots=3, max_len=max_len,
                               prefill_chunk=page_size, eos_token_id=None,
                               max_pages=pool_pages, **kw)
        try:
            toks = [np.asarray(
                engine.submit(div_prompts[i:i + 1], max_new_tokens=new_tokens,
                              ignore_eos=True, block=True).result(timeout=300))
                for i in range(3)]
        finally:
            engine.shutdown()
        return toks, engine.stats

    def agreement(a, b):
        # Mean fraction of positions that agree before the first split
        # (after a split greedy trajectories are incomparable).
        fracs = []
        for x, y in zip(a, b):
            n = min(len(x), len(y))
            eq = int(np.argmin(np.equal(x[:n], y[:n]))) \
                if not np.array_equal(x[:n], y[:n]) else n
            fracs.append(eq / max(n, 1))
        return round(float(np.mean(fracs)), 4)

    base_toks, _ = run_engine()
    kv_toks, _ = run_engine(kv_dtype="int8")
    both_toks, both_stats = run_engine(kv_dtype="int8", weights_dtype="int8")

    # logprob drift: teacher-forced fp vs quantized-weights forwards on
    # the quantized engine's own emitted sequences.
    from accelerate_tpu.adapters.quantize import (dequantize_params,
                                                  quantize_base_weights)
    dq = dequantize_params(quantize_base_weights(dparams), jnp.float32)

    def token_logprobs(p, seq):
        logits = dmodel.apply({"params": p}, jnp.asarray(seq[None, :-1]))
        lp = jax.nn.log_softmax(logits[0].astype(jnp.float32), axis=-1)
        picked = jnp.take_along_axis(
            lp, jnp.asarray(seq[1:, None], jnp.int32), axis=-1)
        return np.asarray(picked[:, 0])

    drift = 0.0
    for i, toks in enumerate(both_toks):
        seq = np.concatenate([div_prompts[i], np.asarray(toks, np.int32)])
        d = np.abs(token_logprobs(dparams, seq) - token_logprobs(dq, seq))
        drift = max(drift, float(d[len(div_prompts[i]) - 1:].max()))
    both_stats.record_logprob_drift(drift)

    # int8-KV logit gap: the fp model, teacher-forced on the int8-kv
    # engine's own greedy tokens — how far below the fp maximum each chosen
    # token's fp logit sits. Bounded rounding keeps every choice a near-tie
    # with the fp argmax (0 when no token flips); a mangled dequantized view
    # scores like a random token. Unlike prefix agreement it does not depend
    # on where random weights happen to have a near-tie.
    kv_gap, spreads = 0.0, []
    for i, toks in enumerate(kv_toks):
        seq = np.concatenate([div_prompts[i], np.asarray(toks, np.int32)])
        logits = np.asarray(dmodel.apply(
            {"params": dparams}, jnp.asarray(seq[None, :-1]))[0], np.float32)
        pred = logits[len(div_prompts[i]) - 1:]
        chosen = pred[np.arange(len(toks)), np.asarray(toks)]
        kv_gap = max(kv_gap, float((pred.max(-1) - chosen).max()))
        spreads.append(float(pred.std()))

    # --- speculation accept rate with int8 kv pages ------------------
    bmodel = _biased_llama_cls()(LlamaConfig.tiny())
    bparams = bmodel.init_params(jax.random.PRNGKey(0))
    b_prompts = rng.integers(9, 15, size=(3, 5)).astype(np.int32)

    def spec_run(**kw):
        engine = ServingEngine(bmodel, bparams, max_slots=2, max_len=max_len,
                               prefill_chunk=8, eos_token_id=None,
                               draft_model=bmodel, draft_params=bparams,
                               spec_tokens=spec_tokens, **kw)
        try:
            for i in range(3):
                engine.submit(b_prompts[i:i + 1], max_new_tokens=16,
                              ignore_eos=True,
                              block=True).result(timeout=300)
            stats = engine.serving_metrics()
        finally:
            engine.shutdown()
        return stats

    s_fp = spec_run()
    s_q = spec_run(kv_dtype="int8")

    return {
        "pool_pages": {"fp": pool_pages, "int8": int8_pages},
        "page_bytes": {"fp": fp["page_bytes"], "int8": q["page_bytes"]},
        "kv_bytes": {"fp": fp["kv_bytes"], "int8": q["kv_bytes"]},
        "slots": {"fp": fp_slots, "int8": int8_slots},
        "peak_concurrency": {"fp": fp["peak"], "int8": q["peak"]},
        "concurrency_ratio": round(q["peak"] / max(fp["peak"], 1), 3),
        "decode_tok_s": {
            "fp": round(fp_slots * new_tokens / max(fp["wall"], 1e-9), 1),
            "int8": round(int8_slots * new_tokens / max(q["wall"], 1e-9), 1),
        },
        "preemptions": q["stats"]["preemptions"],
        "token_agreement": {"kv": agreement(base_toks, kv_toks),
                            "kv+weights": agreement(base_toks, both_toks)},
        "logprob_drift": both_stats.summary()["logprob_drift"],
        "kv_logit_gap": round(kv_gap, 5),
        "logit_std": round(float(np.mean(spreads)), 4),
        "spec_accept_rate": {"fp": s_fp["spec_accept_rate"],
                             "int8": s_q["spec_accept_rate"]},
    }


def tracing_overhead_bench(n_requests: int = 10, prompt_len: int = 4,
                           max_new_tokens: int = 16, repeats: int = 3) -> dict:
    """Tracing on/off A/B: identical traffic through two warmed tiny-model
    engines, one with the span tracer enabled (the default) and one with
    ``tracing=False``. Reports each arm's best decode tokens/sec over
    ``repeats`` windows (best-of damps host scheduler noise) and their
    ratio — the acceptance budget for always-on tracing is ratio >= 0.95
    (tracing must cost host-side tuple appends, never device work)."""
    import numpy as np

    def run(tracing: bool) -> dict:
        engine, _, _, _ = _serving_test_engine(max_slots=4, tracing=tracing)
        rng = np.random.default_rng(0)
        prompts = rng.integers(1, 200,
                               size=(n_requests, prompt_len)).astype(np.int32)
        try:
            best = 0.0
            for _ in range(repeats):
                engine.stats.reset()
                reqs = [engine.submit(prompts[i:i + 1],
                                      max_new_tokens=max_new_tokens,
                                      seed=i, block=True)
                        for i in range(n_requests)]
                for r in reqs:
                    r.wait(timeout=120)
                best = max(best,
                           engine.serving_metrics()["decode_tokens_per_sec"])
            spans = len(engine.tracer)
        finally:
            engine.shutdown()
        return {"decode_tokens_per_sec": best, "spans_buffered": spans}

    off = run(tracing=False)
    on = run(tracing=True)
    return {
        "n_requests": n_requests,
        "max_new_tokens": max_new_tokens,
        "repeats": repeats,
        "tracing_off": off,
        "tracing_on": on,
        "overhead_ratio": round(
            on["decode_tokens_per_sec"]
            / max(off["decode_tokens_per_sec"], 1e-9), 4),
    }


def observability_extra(on_tpu: bool) -> dict:
    """The ``extra.observability`` payload: the tracing on/off decode-
    throughput A/B on the tiny model (CPU only; on TPU tracing rides the
    tier-1 serving story, not an extra compile)."""
    if on_tpu:
        return {}
    return {"tracing_overhead": tracing_overhead_bench()}


def zero_sharding_bench(steps: int = 30, warmup: int = 5, dp: int = 2,
                        hidden: int = 512, ffn: int = 2048,
                        batch: int = 32) -> dict:
    """ZeRO-sharded vs replicated optimizer-state A/B on a dp-way mesh.

    Same model, same seed, same batches; the only difference is
    ``MeshConfig(zero_sharding=True)``. Records (a) per-replica optimizer-
    state bytes measured from the actual array placement (device-0 shard
    bytes), (b) median fused-step wall time for both, and (c) the max loss
    divergence over the run (expected ~1e-6: the reduce-scattered update
    reassociates fp32 sums). test_perf_guards.py guards the compiled-step
    memory_analysis and the <=1.2x step-time ratio; this records the same
    pair in the committed artifact.
    """
    import time as _time

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from accelerate_tpu import Accelerator, Model
    from accelerate_tpu.data_loader import make_global_batch
    from accelerate_tpu.parallel.mesh import MeshConfig
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

    if len(jax.devices()) < dp:
        return {"skipped": f"needs >= {dp} devices (have {len(jax.devices())})"}

    class _MLP:
        def apply(self, params, x):
            h = jnp.tanh(x @ params["w1"] + params["b1"])
            return h @ params["w2"]

    def init_params():
        k1, k2 = jax.random.split(jax.random.PRNGKey(0))
        return {"w1": (jax.random.normal(k1, (hidden, ffn)) * 0.05).astype(jnp.float32),
                "b1": jnp.zeros((ffn,), jnp.float32),
                "w2": (jax.random.normal(k2, (ffn, hidden)) * 0.05).astype(jnp.float32)}

    def loss_fn(params, b):
        x, y = b
        h = jnp.tanh(x @ params["w1"] + params["b1"])
        return jnp.mean((h @ params["w2"] - y) ** 2)

    x = np.asarray(jax.random.normal(jax.random.PRNGKey(1), (batch, hidden)))
    y = np.asarray(jax.random.normal(jax.random.PRNGKey(2), (batch, hidden)))

    def per_replica_opt_bytes(opt_state) -> int:
        dev0 = jax.devices()[0]
        total = 0
        for leaf in jax.tree_util.tree_leaves(opt_state):
            shards = getattr(leaf, "addressable_shards", None)
            if shards is None:
                total += getattr(leaf, "nbytes", 0)
                continue
            total += sum(s.data.nbytes for s in shards if s.device == dev0)
        return total

    def run(zero: bool) -> dict:
        for cls in (AcceleratorState, GradientState, PartialState):
            cls._reset_state()
        acc = Accelerator(mesh_config=MeshConfig(
            dp=dp, devices=jax.devices()[:dp], zero_sharding=zero))
        model, opt = acc.prepare(Model(_MLP(), init_params()), optax.adamw(1e-3))
        step = acc.compile_train_step(loss_fn, model, opt, max_grad_norm=1.0)
        gbatch = (make_global_batch(x, acc.mesh), make_global_batch(y, acc.mesh))
        losses, times = [], []
        for i in range(steps):
            t0 = _time.perf_counter()
            m = step(gbatch)
            jax.block_until_ready(m["loss"])
            if i >= warmup:
                times.append(_time.perf_counter() - t0)
            losses.append(float(m["loss"]))
        return {
            "losses": losses,
            "step_ms": round(1000 * float(np.median(times)), 4),
            "opt_bytes_per_replica": per_replica_opt_bytes(opt.opt_state),
        }

    repl = run(False)
    zero = run(True)
    mem_ratio = zero["opt_bytes_per_replica"] / max(repl["opt_bytes_per_replica"], 1)
    return {
        "dp": dp,
        "steps": steps,
        "opt_bytes_per_replica_replicated": repl["opt_bytes_per_replica"],
        "opt_bytes_per_replica_zero": zero["opt_bytes_per_replica"],
        "memory_ratio": round(mem_ratio, 4),
        "step_ms_replicated": repl["step_ms"],
        "step_ms_zero": zero["step_ms"],
        "step_time_ratio": round(zero["step_ms"] / max(repl["step_ms"], 1e-9), 4),
        "max_loss_diff": max(abs(a - b) for a, b in zip(repl["losses"], zero["losses"])),
        "final_loss": zero["losses"][-1],
    }


def serving_extra(on_tpu: bool) -> dict:
    """The ``extra.serving`` payload: on CPU the offered-load sweep, the
    continuous-vs-static staggered-arrival comparison, the prefix-cache
    hit check, the gateway pair — HTTP-overhead-vs-direct-submit plus
    the replica-kill failover drill — and the paged pair — slots a pool
    sustains on short traffic plus the speculative-decoding
    accepted-tokens/step A/B (cheap, tiny model); on TPU skipped —
    serving the tier-1 model is its own benchmark, not a rider on the
    training run."""
    if on_tpu:
        return {}
    return {
        "sweep": serving_sweep(),
        "continuous_vs_static": continuous_vs_static(),
        "chunked_prefill": {
            "prefix_cache": prefix_cache_hit_bench(),
        },
        "gateway": {
            "overhead": gateway_overhead_bench(),
            "failover": replica_failover_bench(),
        },
        "open_loop": open_loop_ab_bench(),
        "slo": slo_control_bench(),
        "chaos": chaos_recovery_bench(),
        "tp": serving_tp_bench(),
        "paged": paged_capacity_bench(),
        "quantized": quantized_serving_bench(),
        "speculative": speculative_bench(),
    }


def _flash_blocks(cfg, seq: int) -> dict:
    """The tiles the flash kernels choose at this shape (bf16 compute)."""
    from accelerate_tpu.ops.flash_pallas import KERNELS, tile_plan

    head_dim = cfg.hidden_size // cfg.num_attention_heads
    plans = {k: tile_plan(seq, seq, head_dim, "bfloat16", kernel=k) for k in KERNELS}
    return {k: [p.block_q, p.block_k] for k, p in plans.items()}


def run_bench(on_tpu: bool) -> dict:
    import jax
    import numpy as np
    import optax

    from accelerate_tpu.utils.platforms import enable_compilation_cache
    from accelerate_tpu.utils.platforms import device_kind as _device_kind

    enable_compilation_cache()

    from accelerate_tpu import Accelerator, Model
    from accelerate_tpu.data_loader import make_global_batch
    from accelerate_tpu.models.llama import PipelinedLlamaForCausalLM, fused_causal_lm_loss

    if on_tpu:
        seq, iters, warmup = 1024, 20, 3
        ladder = TIER1_LADDER
    else:  # the CPU smoke the caller asked for
        seq, iters, warmup = 32, 3, 1
        ladder = [("nothing", 4)]

    def attempt(remat_policy, batch):
        cfg = tier1_llama_config(on_tpu, remat_policy)
        # Scan-over-layers layout for BOTH tiers: the decoder block is traced
        # and compiled ONCE and lax.scan'd over the stacked [L, ...] params,
        # instead of inlining N copies. Using the same model class + loss on
        # CPU means every smoke run exercises the exact tier-1 code path.
        model_def = PipelinedLlamaForCausalLM(cfg)
        params = model_def.init_params(jax.random.PRNGKey(0))

        acc = Accelerator(mixed_precision="bf16")
        model, opt = acc.prepare(Model(model_def, params), optax.adamw(1e-4))
        # Chunked LM-head loss: never materializes the [tokens, vocab]
        # logits — at vocab 32k that's the train step's largest activation
        # (~1 GB at this config) and pure HBM traffic saved.
        step = acc.compile_train_step(fused_causal_lm_loss(model_def),
                                      max_grad_norm=1.0)

        rng = np.random.default_rng(0)
        batches = [
            make_global_batch(
                {"input_ids": rng.integers(0, cfg.vocab_size, size=(batch, seq)).astype(np.int32)},
                acc.mesh,
            )
            for _ in range(4)
        ]

        for i in range(warmup):
            metrics = step(batches[i % 4])
        jax.device_get(metrics["loss"])

        t0 = time.perf_counter()
        for i in range(iters):
            metrics = step(batches[i % 4])
        jax.device_get(metrics["loss"])
        dt = time.perf_counter() - t0

        tokens = batch * seq * iters
        tokens_per_sec = tokens / dt
        n_chips = len(jax.devices())
        tokens_per_sec_per_chip = tokens_per_sec / n_chips

        n_params = sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(model.params))
        flops = mfu_fields(tokens_per_sec_per_chip, cfg, seq, n_params)
        mfu = flops["mfu"]

        result = {
            "metric": METRIC,
            "value": round(tokens_per_sec_per_chip, 1),
            "unit": "tokens/s/chip",
            "vs_baseline": round(mfu / TARGET_MFU, 4) if on_tpu else None,
            "extra": {
                "baseline_target_mfu": TARGET_MFU,
                "mfu": round(mfu, 4) if on_tpu else None,
                "achieved_tflops": round(flops["achieved_tflops"], 2),
                "peak_tflops": flops["peak_tflops"],
                "step_ms": round(1000 * dt / iters, 2),
                "config": {
                    "hidden": cfg.hidden_size, "layers": cfg.num_hidden_layers,
                    "batch": batch, "seq": seq, "backend": jax.default_backend(),
                    "flash_attention": cfg.use_flash_attention,
                    "flash_blocks": _flash_blocks(cfg, seq),
                    "remat_policy": remat_policy if cfg.remat else None,
                },
                "device_kind": _device_kind(),
                "loss": float(metrics["loss"]),
            },
        }
        trace_dir = os.environ.get("ACCELERATE_TPU_BENCH_TRACE")
        if trace_dir and on_tpu:
            # A profiler trace is the MFU gap-analysis artifact; a failed
            # capture is recorded in the result, not fatal to it.
            try:
                with jax.profiler.trace(trace_dir):
                    for i in range(2):
                        step(batches[i % 4])
                    jax.device_get(metrics["loss"])
                result["extra"]["profile_trace"] = trace_dir
            except Exception as e:  # noqa: BLE001
                result["extra"]["profile_trace_error"] = f"{type(e).__name__}: {e}"
        # Input-pipeline breakdown: stage a few tier-1-shaped host batches
        # through the async loader (no new compiles) so data_wait_ms/stage_ms
        # land in the committed artifact next to MFU.
        try:
            from accelerate_tpu.data_loader import DataLoaderShard

            raw = [{"input_ids": rng.integers(0, cfg.vocab_size, size=(batch, seq)).astype(np.int32)}
                   for _ in range(3)]

            class _L:
                dataset = list(range(3 * batch))
                batch_size = batch

                def __iter__(self):
                    return iter(raw)

                def __len__(self):
                    return len(raw)

            pdl = DataLoaderShard(_L(), mesh=acc.mesh, prefetch_size=2)
            for _ in pdl:
                pass
            pipeline = pdl.pipeline_stats.summary()
            if not on_tpu:
                pipeline["overlap"] = input_pipeline_extra(on_tpu)
            result["extra"]["input_pipeline"] = pipeline
        except Exception as e:  # noqa: BLE001 - observability must not kill the result
            result["extra"]["input_pipeline_error"] = f"{type(e).__name__}: {e}"
        # Serving payload: offered-load sweep + continuous-vs-static on the
        # tiny model (CPU only; see serving_extra) — lands the serving
        # layer's TTFT/throughput/occupancy story next to MFU.
        try:
            serving = serving_extra(on_tpu)
            if serving:
                result["extra"]["serving"] = serving
        except Exception as e:  # noqa: BLE001 - observability must not kill the result
            result["extra"]["serving_error"] = f"{type(e).__name__}: {e}"
        # Multi-tenant LoRA payload: batched-bank vs sequential merged-
        # weight swapping on the tiny model (CPU only; see adapters_extra).
        try:
            adapters = adapters_extra(on_tpu)
            if adapters:
                result["extra"]["adapters"] = adapters
        except Exception as e:  # noqa: BLE001 - observability must not kill the result
            result["extra"]["adapters_error"] = f"{type(e).__name__}: {e}"
        # Observability rider: tracing on/off decode-throughput A/B on the
        # tiny serving model (CPU only; see observability_extra) — pins the
        # <=5% budget for always-on request tracing next to the MFU story.
        try:
            obs = observability_extra(on_tpu)
            if obs:
                result["extra"]["observability"] = obs
        except Exception as e:  # noqa: BLE001 - observability must not kill the result
            result["extra"]["observability_error"] = f"{type(e).__name__}: {e}"
        # ZeRO optimizer-state sharding A/B: per-replica moment bytes and
        # step-time ratio, replicated vs dp-sharded (CPU only — the
        # multi-device A/B compiles four extra programs; on TPU that story
        # belongs to a dedicated mesh bench, not a tier-1 rider).
        if not on_tpu:
            try:
                result["extra"]["training"] = {"zero": zero_sharding_bench()}
            except Exception as e:  # noqa: BLE001 - observability must not kill the result
                result["extra"]["training_error"] = f"{type(e).__name__}: {e}"
        return result

    last_oom = None
    for remat_policy, batch in ladder:
        try:
            result = attempt(remat_policy, batch)
            if last_oom:
                result["extra"]["oom_fallbacks"] = last_oom
            return result
        except Exception as e:  # noqa: BLE001 - only OOM falls down the ladder
            msg = str(e)
            if not ("RESOURCE_EXHAUSTED" in msg or "out of memory" in msg.lower()):
                raise
            last_oom = f"{remat_policy}/b{batch} OOM"
            jax.clear_caches()
    raise RuntimeError(f"all tier-1 ladder attempts OOMed (last: {last_oom})")


#: Axes the mesh perf harness accepts (pp/ep have their own schedules and are
#: dry-run-validated in __graft_entry__; the perf story is dp/fsdp/tp/cp).
PERF_MESH_AXES = ("dp", "fsdp", "tp", "cp")


def parse_mesh_spec(spec: str) -> dict:
    """'dp=4,fsdp=2' -> {'dp': 4, 'fsdp': 2} (axes validated, sizes >= 1)."""
    out = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        ax, _, val = part.partition("=")
        if ax not in PERF_MESH_AXES:
            raise ValueError(
                f"unknown mesh axis {ax!r} (choose from {', '.join(PERF_MESH_AXES)})")
        if not val.isdigit() or int(val) < 1:
            raise ValueError(f"mesh axis {ax} needs a positive size, got {val!r}")
        out[ax] = int(val)
    if not out:
        raise ValueError("empty --mesh spec; expected e.g. dp=8 or fsdp=4,tp=2")
    return out


def run_mesh_bench(mesh_spec: dict, on_tpu: bool, quick: bool = False) -> dict:
    """Multi-chip perf: per-chip tokens/s (+ MFU on TPU) and scaling
    efficiency of the SAME fused train step run_bench times, over an
    explicit dp/fsdp/tp/cp mesh (BASELINE.md's 8->256-chip scaling axis;
    reference equivalent: its multi-GPU benchmark configs,
    /root/reference/benchmarks/fp8/{ddp,fsdp,distrib_deepspeed}.py).

    Scaling efficiency = per-chip tokens/s on the N-device mesh divided by
    per-chip tokens/s of an identical 1-device run measured in the same
    process — the number that tells you what the mesh costs you, not just
    what it gives you. On an emulated CPU mesh the absolute numbers are
    meaningless but every sharding/collective in the step is real; the
    harness is pod-ready by construction (``quick`` trims iters for the
    dryrun stage).
    """
    import math

    import jax
    import numpy as np
    import optax

    from accelerate_tpu import Accelerator, MeshConfig, Model
    from accelerate_tpu.data_loader import make_global_batch
    from accelerate_tpu.models.llama import (
        LlamaConfig,
        PipelinedLlamaForCausalLM,
        fused_causal_lm_loss,
    )
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState
    from accelerate_tpu.utils import (
        ContextParallelPlugin,
        FullyShardedDataParallelPlugin,
        TensorParallelPlugin,
    )
    from accelerate_tpu.utils.platforms import device_kind as _device_kind
    from accelerate_tpu.utils.platforms import enable_compilation_cache

    enable_compilation_cache()
    n_chips = math.prod(mesh_spec.values())
    if len(jax.devices()) < n_chips:
        raise RuntimeError(
            f"mesh {mesh_spec} needs {n_chips} devices, have {len(jax.devices())}")

    if on_tpu:
        seq, per_chip_batch, iters, warmup = 1024, 4, 10, 2
        ladder = TIER1_LADDER
    else:
        seq, per_chip_batch = 32, 2
        iters, warmup = (2, 1) if quick else (3, 1)
        ladder = [("nothing", per_chip_batch)]

    def timed(spec: dict, cfg, pcb: int) -> dict:
        for cls in (AcceleratorState, GradientState, PartialState):
            cls._reset_state()
        n = math.prod(spec.values())
        full = {ax: spec.get(ax, 1) for ax in PERF_MESH_AXES}
        acc = Accelerator(
            mixed_precision="bf16",
            mesh_config=MeshConfig(**full, devices=jax.devices()[:n]),
            fsdp_plugin=(FullyShardedDataParallelPlugin(min_weight_size_to_shard=1)
                         if full["fsdp"] > 1 else None),
            tp_plugin=(TensorParallelPlugin(tp_size=full["tp"])
                       if full["tp"] > 1 else None),
            cp_plugin=(ContextParallelPlugin(cp_size=full["cp"])
                       if full["cp"] > 1 else None),
        )
        model_def = PipelinedLlamaForCausalLM(cfg)
        # Batch rides the data axes (dp x fsdp); cp shards seq instead. The
        # init dummy must already respect the data axes: a cp plugin's
        # attention shard_map is traced during init too.
        data_ways = full["dp"] * full["fsdp"]
        batch_rows = pcb * data_ways
        params = model_def.init_params(jax.random.PRNGKey(0), batch_size=data_ways)
        model, opt = acc.prepare(Model(model_def, params), optax.adamw(1e-4))
        step = acc.compile_train_step(fused_causal_lm_loss(model_def),
                                      max_grad_norm=1.0)
        rng = np.random.default_rng(0)
        batches = [
            make_global_batch(
                {"input_ids": rng.integers(
                    0, cfg.vocab_size, size=(batch_rows, seq)).astype(np.int32)},
                acc.mesh,
            )
            for _ in range(2)
        ]
        for i in range(warmup):
            metrics = step(batches[i % 2])
        jax.device_get(metrics["loss"])
        t0 = time.perf_counter()
        for i in range(iters):
            metrics = step(batches[i % 2])
        loss = float(jax.device_get(metrics["loss"]))
        dt = time.perf_counter() - t0
        assert np.isfinite(loss), f"non-finite loss {loss} on mesh {spec}"
        tokens_per_sec = batch_rows * seq * iters / dt
        n_params = sum(int(np.prod(p.shape))
                       for p in jax.tree_util.tree_leaves(model.params))
        return {
            "mesh": {ax: sz for ax, sz in full.items() if sz > 1} or {"dp": 1},
            "n_chips": n,
            "tokens_per_sec": tokens_per_sec,
            "tokens_per_sec_per_chip": tokens_per_sec / n,
            "step_ms": 1000 * dt / iters,
            "loss": loss,
            "n_params": n_params,
        }

    def attempt_ladder(spec: dict) -> tuple[dict, object, int, str | None]:
        """Same OOM ladder as run_bench: fall to cheaper remat/batch on
        RESOURCE_EXHAUSTED, naming every rung taken in the result."""
        last_oom = None
        for remat_policy, pcb in ladder:
            cfg = tier1_llama_config(on_tpu, remat_policy)
            try:
                return timed(spec, cfg, pcb), cfg, pcb, last_oom
            except Exception as e:  # noqa: BLE001 - only OOM descends
                msg = str(e)
                if not ("RESOURCE_EXHAUSTED" in msg or "out of memory" in msg.lower()):
                    raise
                last_oom = f"{remat_policy}/b{pcb} OOM"
                jax.clear_caches()
        raise RuntimeError(f"all mesh ladder attempts OOMed (last: {last_oom})")

    mesh_run, cfg, per_chip_batch, oom = attempt_ladder(mesh_spec)
    # The 1-chip reference must run the exact surviving config/batch or the
    # efficiency ratio compares different programs.
    single = timed({"dp": 1}, cfg, per_chip_batch)
    eff = (mesh_run["tokens_per_sec_per_chip"] / single["tokens_per_sec_per_chip"]
           if single["tokens_per_sec_per_chip"] else 0.0)

    flops = mfu_fields(mesh_run["tokens_per_sec_per_chip"], cfg, seq,
                       mesh_run["n_params"])
    mfu = flops["mfu"]
    achieved_tflops, peak = flops["achieved_tflops"], flops["peak_tflops"]

    return {
        "metric": METRIC,
        "value": round(mesh_run["tokens_per_sec_per_chip"], 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / TARGET_MFU, 4) if on_tpu else None,
        "extra": {
            "baseline_target_mfu": TARGET_MFU,
            "mesh": mesh_run["mesh"],
            "n_chips": mesh_run["n_chips"],
            "scaling_efficiency": round(eff, 4),
            "single_chip_tokens_per_sec": round(single["tokens_per_sec_per_chip"], 1),
            "step_ms": round(mesh_run["step_ms"], 2),
            "single_chip_step_ms": round(single["step_ms"], 2),
            "mfu": round(mfu, 4) if on_tpu else None,
            "achieved_tflops": round(achieved_tflops, 2),
            "peak_tflops": peak,
            "config": {
                "hidden": cfg.hidden_size, "layers": cfg.num_hidden_layers,
                "per_chip_batch": per_chip_batch, "seq": seq,
                "backend": jax.default_backend(),
            },
            "device_kind": _device_kind(),
            "loss": round(mesh_run["loss"], 4),
            **({"oom_fallbacks": oom} if oom else {}),
        },
    }


def _asked_for_cpu() -> bool:
    """Whether the caller pinned the CPU (``JAX_PLATFORMS=cpu``): the only
    way to get a run that is not on a TPU."""
    return os.environ.get("JAX_PLATFORMS", "").split(",")[0].strip().lower() == "cpu"


def _require_tpu(n_chips: int = 1) -> None:
    """Exit non-zero unless jax gives this process ``n_chips`` TPU devices."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < n_chips:
        raise SystemExit(
            f"bench.py: needs {n_chips} TPU device(s) but jax gives "
            f"{len(devices)} x {devices[0].platform!r} ({devices[0].device_kind}); "
            "nothing was measured. JAX_PLATFORMS=cpu asks for the CPU smoke.")


def main_mesh(spec: str) -> int:
    """--mesh: the mesh bench on this host's TPU chips, or — only when the
    caller asked for the CPU — on that many virtual CPU devices, labelled
    ``emulated``. Too few chips is an error, never an emulation."""
    import math

    mesh_spec = parse_mesh_spec(spec)
    n_chips = math.prod(mesh_spec.values())
    on_tpu = not _asked_for_cpu()
    if on_tpu:
        _require_tpu(n_chips)
    else:
        from accelerate_tpu.utils.platforms import request_virtual_cpu_devices

        request_virtual_cpu_devices(n_chips)
    result = run_mesh_bench(mesh_spec, on_tpu=on_tpu)
    if not on_tpu:
        result["extra"]["emulated"] = True
    print(json.dumps(result))
    return 0


def main() -> int:
    """One process, one device: the tier-1 run on the TPU, or — only when the
    caller asked for the CPU — the tiny smoke, labelled ``cpu_smoke``."""
    on_tpu = not _asked_for_cpu()
    if on_tpu:
        _require_tpu()
    result = run_bench(on_tpu=on_tpu)
    if not on_tpu:
        result["extra"]["cpu_smoke"] = True
    print(json.dumps(result))
    return 0


def _arg_value(flag: str) -> str | None:
    idx = sys.argv.index(flag)
    return sys.argv[idx + 1] if idx + 1 < len(sys.argv) else None


def _cli() -> int:
    if "--mesh" in sys.argv:
        spec = _arg_value("--mesh")
        if spec is None:
            raise SystemExit("--mesh needs a spec, e.g. --mesh dp=8")
        return main_mesh(spec)
    return main()


if __name__ == "__main__":
    sys.exit(_cli())
