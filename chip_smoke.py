"""chip_smoke.py — the quickest proof that the trainer and the server still
start on the chip, through the entry points a user calls.

    python chip_smoke.py                 # one TPU chip: trainer, kernels, server, served_form
    python chip_smoke.py --four-chips    # one four-chip host: the sharded paths only
    python chip_smoke.py --rehearse-cpu  # the same control flow at toy size on the CPU

One process does everything: a chip belongs to one process at a time. Every
phase prints one JSON object; an exception in any phase ends the run with a
non-zero exit — nothing is caught and skipped. Without ``--rehearse-cpu`` the
first thing checked is that jax's first device is a TPU (jax itself falls
back to the CPU with only a warning when libtpu cannot start). The last line
of a successful run is ``{"ok": true, "device": {"platform": ..., "kind":
..., "count": ...}}`` with the device as jax reports it.

Wall times printed here are labelled ``smoke, not a measurement``: one cold
run, compiles included, no warm-up discipline. They are not benchmark numbers.

Configurations (depth is the only cut; weights are random, made from --seed):

* Trainer — the dense block of the Mistral family at Mistral-7B-v0.1's
  published widths through ``LlamaConfig`` (hidden 4096, 32 query / 8 KV heads
  of 128, MLP 14336, vocab 32000, ``sliding_window=4096``), flash attention,
  remat, sequence 4096 (window = sequence, so the full-causal kernel runs),
  two layers: 218 M per layer + 262 M embeddings/head in fp32 with Adam. The
  compiled step holds 7.8 GiB of arguments (params and both moments, donated)
  + 3.7 GiB of temporaries, and peaks at 8.4 GB on the chip; a third layer's
  16 B per parameter would not leave room for the next phases.
* Server — ``MixtralConfig.mixtral_8x7b`` with ``num_hidden_layers=3``
  (1.45 B parameters per sparse layer; 3 layers + embeddings/head is 9.2 GB in
  bf16 on a 16 GB chip, the rest is KV pages and workspace), built the way
  ``accelerate-tpu serve`` builds it and driven over HTTP (JSON and SSE).
* Served form — the ``cohere2_moe`` family at Command A+'s published widths
  (hidden 4096, 128 query / 8 KV heads of 128, 16 of 128 experts held, four
  layers) at the benchmark cell's engine shapes, weights all zero: nothing
  runs, the tick and the chunk are COMPILED and their text is read. A TPU
  layout is nothing a CPU test can see.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import http.client
import json
import re
import sys
import threading
import time
from urllib.parse import urlparse

import numpy as np

NOT_A_MEASUREMENT = "smoke, not a measurement"

# Flash vs einsum, as max|got - want| / max|want| (the metric the flash tests
# use). bf16 keeps 8 significant bits (eps = 2**-8 ~ 3.9e-3). The kernel rounds
# q.k products' softmax weights and its output to bf16 and accumulates in fp32,
# so a few eps of the largest element is the honest floor; the backward stacks
# two such roundings (recomputed weights, then dO products). 2e-2 ~ 5 eps and
# 4e-2 ~ 10 eps leave that room, while a masking, indexing or GQA-grouping
# fault shows up as O(1).
KERNEL_TOL = {"fwd": 2e-2, "bwd": 4e-2}

# Greedy tokens from the engine, scored by a monolithic cached forward of the
# same weights: how far below that position's maximum each chosen token's
# reference logit sits. With random weights the final RMSNorm feeds a
# fan-in-scaled head, so logits are ~N(0, 1) over 32000 entries and the top two
# are often closer than bf16 reduction order resolves (eps 3.9e-3 on logits of
# magnitude ~4, through three layers and a 4096-wide head): a flip between
# such NEAR_TIEs is not a fault. A sparse model adds a second, discrete source:
# the same rounding can flip a token's second expert where two router logits
# nearly tie, and then that token's MLP output — and its logits — move by a
# good fraction of a sigma (0.88 seen on the chip: my chip run, PR 22) while
# its neighbours stay put. A wrong page, position or slot is neither: it
# scores like a random token, ~4 below the maximum, for every token after it.
# So: each request's first token (prefill alone, three routing decisions)
# within NEAR_TIE; at least MIN_SHARE_WITHIN_TOL of all served tokens within
# LOGIT_TOL; none beyond MAX_GAP.
NEAR_TIE = 0.05
LOGIT_TOL = 0.25
MIN_SHARE_WITHIN_TOL = 0.9
MAX_GAP = 2.0

# One device vs fsdp=2 x tp=2 on the same seeded steps: the first loss comes
# from identical weights and differs only by bf16 reduction order across the
# tp all-reduce (observed ~1e-3 on a loss of ~10.4); later steps compound it
# through Adam. 0.05 absolute (0.5 %) bounds that without hiding a sharding
# fault, which moves the loss by O(1) or makes it non-finite.
MESH_LOSS_TOL = 0.05

# AdamW moves every weight by about the learning rate per step whatever the
# gradient's size. At 4096-wide layers 1e-3 overshoots by the fourth step on
# the chip (losses 10.88, 9.08, 8.88, 14.14: my chip run, PR 22); 1e-4 is the
# usual fine-tuning order for this family and falls steadily.
LEARNING_RATE = 1e-4

SIZES = {
    "real": {
        "trainer": dict(vocab_size=32000, hidden_size=4096, intermediate_size=14336,
                        num_hidden_layers=2, num_attention_heads=32,
                        num_key_value_heads=8, max_position_embeddings=32768,
                        sliding_window=4096, seq=4096, batch=2, steps=4),
        "server": dict(num_hidden_layers=3, max_slots=8, max_len=1024,
                       prefill_chunk=256, max_pages=512,
                       prompt_lens=(300, 520, 700, 330), max_new_tokens=64),
        # serve-phi4flash-closed48-reason's tick: 48 lanes, 40 query heads over 10 key pairs
        # of 128, 449 pages of 256 flat rows, 16 pages a lane, the layers' window
        "paged_attention": dict(slots=48, q_heads=40, n_rep=4, head_dim=128, page=256,
                                pages_per_slot=16, pages=449, window=512),
        "served_form": dict(vocab_size=32768, hidden_size=4096, intermediate_size=4096,
                            num_hidden_layers=4, num_attention_heads=128,
                            num_key_value_heads=8, head_dim=128, sliding_window=4096,
                            num_experts=128, num_experts_per_tok=8, num_shared_experts=4,
                            held_experts=(0, 16), max_slots=16, max_len=8192,
                            prefill_chunk=256, max_pages=513),
    },
    # The rehearsal: the same control flow at sizes the CPU finishes in
    # seconds. Widths here mean nothing.
    "tiny": {
        "trainer": dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                        num_hidden_layers=1, num_attention_heads=4,
                        num_key_value_heads=2, max_position_embeddings=512,
                        sliding_window=128, seq=128, batch=2, steps=4),
        "server": dict(num_hidden_layers=2, vocab_size=256, hidden_size=64,
                       intermediate_size=128, num_attention_heads=4,
                       num_key_value_heads=2, max_position_embeddings=512,
                       num_experts=4, max_slots=4, max_len=128,
                       prefill_chunk=16, max_pages=None,
                       prompt_lens=(20, 37, 50, 24), max_new_tokens=8),
        "paged_attention": dict(slots=4, q_heads=8, n_rep=4, head_dim=128, page=16,
                                pages_per_slot=4, pages=17, window=24),
        "served_form": dict(vocab_size=256, hidden_size=64, intermediate_size=32,
                            num_hidden_layers=4, num_attention_heads=8,
                            num_key_value_heads=2, head_dim=16, sliding_window=8,
                            num_experts=8, num_experts_per_tok=2, num_shared_experts=2,
                            held_experts=(0, 4), max_slots=4, max_len=64,
                            prefill_chunk=8, max_pages=None),
    },
}


def require(ok, message) -> None:
    """A check that survives ``python -O`` (assert does not)."""
    if not ok:
        raise AssertionError(message)


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def compile_report(watcher) -> dict:
    """XLA backend compiles a CompileWatcher saw (jaxpr traces of small eager
    ops are not compiles), their seconds, and persistent-cache hits."""
    secs = [d for name, d in watcher.durations if name == BACKEND_COMPILE]
    return {"compiles": len(secs), "compile_s": round(sum(secs), 2),
            "cache_hits": watcher.cache_hits}


def peak_bytes(device) -> int | None:
    """The device's peak since the process started (not since the phase)."""
    stats = device.memory_stats()  # None on backends that do not report it
    return None if stats is None else stats.get("peak_bytes_in_use")


def release() -> None:
    """Drop what the previous phase left behind before the next one sizes
    itself against 16 GB: device buffers, compiled programs, and the
    Accelerator's process-wide state (a trainer's mesh would otherwise stay
    the ambient mesh of the server's MoE layers)."""
    import jax

    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

    for cls in (AcceleratorState, GradientState, PartialState):
        cls._reset_state()
    gc.collect()
    jax.clear_caches()
    gc.collect()


# ---------------------------------------------------------------------------
# Trainer
# ---------------------------------------------------------------------------

def trainer_config(size: dict):
    from accelerate_tpu.models.llama import LlamaConfig

    keys = ("vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "max_position_embeddings",
            "sliding_window")
    return LlamaConfig(**{k: size[k] for k in keys}, remat=True)


def run_trainer(size: dict, seed: int, mesh: dict | None = None, devices=None):
    """README quickstart, verbatim in shape: Accelerator -> prepare(Model,
    optax.adamw, NumpyDataLoader) -> compile_train_step -> a few steps on one
    repeated seeded batch. ``mesh`` (e.g. ``{"fsdp": 2, "tp": 2}``) shards it;
    ``devices`` restricts it (the one-device twin of a four-chip run).
    Returns ``(report, prepared model)``."""
    import jax
    import optax

    from accelerate_tpu import Accelerator, MeshConfig, Model, NumpyDataLoader
    from accelerate_tpu.models.llama import LlamaForCausalLM, causal_lm_loss
    from accelerate_tpu.utils import FullyShardedDataParallelPlugin, TensorParallelPlugin
    from accelerate_tpu.utils.profiling import CompileWatcher

    mesh = mesh or {}
    accelerator = Accelerator(
        mixed_precision="bf16",
        mesh_config=MeshConfig(**mesh, devices=devices),
        fsdp_plugin=FullyShardedDataParallelPlugin() if mesh.get("fsdp", 1) > 1 else None,
        tp_plugin=TensorParallelPlugin(tp_size=mesh["tp"]) if mesh.get("tp", 1) > 1 else None,
    )
    cfg = trainer_config(size)
    module = LlamaForCausalLM(cfg)
    t0 = time.perf_counter()
    params = jax.jit(module.init_params)(jax.random.PRNGKey(seed))
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))

    # One seeded batch, repeated: the loss must fall as the model memorises it.
    B, S, steps = size["batch"], size["seq"], size["steps"]
    rows = np.random.default_rng(seed).integers(0, cfg.vocab_size, (B, S)).astype(np.int32)
    dataset = [{"input_ids": rows[i % B]} for i in range(B * steps)]
    model, optimizer, loader = accelerator.prepare(
        Model(module, params), optax.adamw(LEARNING_RATE), NumpyDataLoader(dataset, batch_size=B))
    step = accelerator.compile_train_step(causal_lm_loss(module.apply), max_grad_norm=1.0)
    setup_s = time.perf_counter() - t0

    losses, step_s, first = [], [], None
    with CompileWatcher() as watcher:
        for i, batch in enumerate(loader):
            t0 = time.perf_counter()
            metrics = step(batch)
            losses.append(float(jax.device_get(metrics["loss"])))
            step_s.append(round(time.perf_counter() - t0, 3))
            if i == 0:
                first = compile_report(watcher)
                watcher.reset()
        recompiles = watcher.events
    require(len(losses) == steps, f"took {len(losses)} steps, wanted {steps}")
    require(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    require(losses[-1] < losses[0], f"loss did not fall on a repeated batch: {losses}")
    require(not recompiles, f"recompiled after step 1: {recompiles}")

    # The compiled step itself: is the Pallas kernel in it, and what does it
    # hold? (Compiled again from the persistent cache; nothing runs.)
    compiled = step._jitted.lower(model.params, optimizer.opt_state, optimizer.loss_scale,
                                  batch, accelerator.next_rng_key()).compile()
    mem = compiled.memory_analysis()
    pallas = "tpu_custom_call" in compiled.as_text()
    out = {
        "mesh": {ax: n for ax, n in accelerator.mesh.shape.items() if n > 1} or {"dp": 1},
        "devices": sorted(d.id for d in accelerator.mesh.devices.flat),
        "config": {**{k: getattr(cfg, k) for k in (
            "hidden_size", "intermediate_size", "num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "vocab_size", "sliding_window")},
            "seq": S, "batch": B, "remat": cfg.remat, "flash": cfg.use_flash_attention},
        "n_params": n_params,
        "losses": [round(l, 5) for l in losses],
        "recompiles_after_step_1": len(recompiles),
        "first_step": first,
        "setup_s": round(setup_s, 2),
        "step_wall_s": step_s,
        "times": NOT_A_MEASUREMENT,
        "pallas_call_in_step_hlo": pallas,
        "step_memory_bytes": {
            "arguments": mem.argument_size_in_bytes, "outputs": mem.output_size_in_bytes,
            "aliased": mem.alias_size_in_bytes, "temporaries": mem.temp_size_in_bytes},
        "peak_bytes_in_use_so_far": peak_bytes(accelerator.mesh.devices.flat[0]),
    }
    return out, model


def param_spread(model) -> dict:
    """Where a sharded trainer's parameters live: devices holding a shard of
    each leaf, and each device's share of all parameter bytes."""
    import jax

    per_device: dict = {}
    total = 0
    narrow = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(model.params)[0]:
        total += leaf.nbytes
        shards = leaf.addressable_shards
        for s in shards:
            per_device[s.device.id] = per_device.get(s.device.id, 0) + s.data.nbytes
        # A leaf is "quartered" when four devices each hold a quarter of it.
        if not (len({s.device.id for s in shards}) == 4
                and all(s.data.nbytes * 4 == leaf.nbytes for s in shards)):
            narrow.append((jax.tree_util.keystr(path), int(leaf.size)))
    return {
        "devices": sorted(per_device),
        "share_of_param_bytes": {d: round(b / total, 4) for d, b in sorted(per_device.items())},
        "leaves_not_quartered": narrow,
    }


# ---------------------------------------------------------------------------
# Kernel agreement
# ---------------------------------------------------------------------------

def run_kernels(size: dict, seed: int) -> dict:
    """Flash fwd and bwd at the trainer's attention shapes against
    ``_einsum_attention`` in fp32 at the highest matmul precision."""
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.ops import flash_pallas
    from accelerate_tpu.ops.attention import _einsum_attention
    from accelerate_tpu.ops.flash_pallas import pallas_flash_attention

    t = size["trainer"]
    S, H, G = t["seq"], t["num_attention_heads"], t["num_key_value_heads"]
    D = t["hidden_size"] // H
    kq, kk, kv, kd = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(kq, (1, S, H, D), jnp.bfloat16)
    k = jax.random.normal(kk, (1, S, G, D), jnp.bfloat16)
    v = jax.random.normal(kv, (1, S, G, D), jnp.bfloat16)
    do = jax.random.normal(kd, (1, S, H, D), jnp.bfloat16)

    def flash(q, k, v):
        return pallas_flash_attention(q, k, v, causal=True)  # tiles from the shape

    plans = {kern: flash_pallas.tile_plan(S, S, D, "bfloat16", kernel=kern)
             for kern in flash_pallas.KERNELS}

    def reference(q, k, v):
        with jax.default_matmul_precision("highest"):
            return _einsum_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                                     v.astype(jnp.float32), causal=True)

    def with_grads(fn):
        def run(q, k, v, do):
            out, vjp = jax.vjp(fn, q, k, v)
            return (out, *vjp(do.astype(out.dtype)))
        return jax.jit(run)

    t0 = time.perf_counter()
    got = jax.block_until_ready(with_grads(flash)(q, k, v, do))
    flash_s = time.perf_counter() - t0
    # The reference materialises fp32 [heads, S, S] logits and their
    # cotangents: one KV group at a time (heads are independent) keeps that
    # at ~1 GiB instead of ~10.
    ref, rep = with_grads(reference), H // G
    groups = [ref(q[:, :, g * rep:(g + 1) * rep], k[:, :, g:g + 1], v[:, :, g:g + 1],
                  do[:, :, g * rep:(g + 1) * rep]) for g in range(G)]
    want = jax.block_until_ready(
        tuple(jnp.concatenate(parts, axis=2) for parts in zip(*groups)))

    def rel_err(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))

    errs = {name: rel_err(g, w) for name, g, w in zip(("out", "dq", "dk", "dv"), got, want)}
    out = {
        "shape": {"batch": 1, "seq": S, "q_heads": H, "kv_heads": G, "head_dim": D,
                  "dtype": "bfloat16", "causal": True},
        "interpreted": flash_pallas._interpret(),
        "tiles": {kern: {"block_q": p.block_q, "block_k": p.block_k, "steps_per_head": p.steps,
                         "compute_steps": p.compute_steps, "masked_steps": p.masked_steps}
                  for kern, p in plans.items()},
        "max_rel_err": {k: round(e, 6) for k, e in errs.items()},
        "tolerance": KERNEL_TOL,
        "flash_fwd_bwd_first_call_s": round(flash_s, 2),
        "times": NOT_A_MEASUREMENT,
    }
    require(errs["out"] <= KERNEL_TOL["fwd"], f"flash forward disagrees with einsum: {errs}")
    require(max(errs["dq"], errs["dk"], errs["dv"]) <= KERNEL_TOL["bwd"], (
        f"flash backward disagrees with einsum: {errs}"))
    out["paged_attention"] = run_paged_attention(size["paged_attention"], seed)
    return out


def run_paged_attention(p: dict, seed: int) -> dict:
    """The decode tick's paged-attention kernel (ops/paged_attention.py) once
    at the cell's shapes, with and without the window, against the XLA work
    list it takes the place of — both through ``update_kv_cache_and_attend``
    under the tick's vmap, the pool an argument. Lanes at ragged positions,
    one of them idle; pages handed out in order as the engine's pool would."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from accelerate_tpu.models import llama
    from accelerate_tpu.ops import paged_attention

    S, H, rep, hd, P, Np = (p[k] for k in ("slots", "q_heads", "n_rep", "head_dim", "page",
                                           "pages_per_slot"))
    G = H // rep
    rng = np.random.default_rng(seed)
    pos = rng.integers(P, Np * P - 1, size=S)
    pos[:3] = (1, P, Np * P - 1)
    live = np.ones((S,), bool)
    live[S // 2] = False
    handed = iter(rng.permutation(np.arange(1, p["pages"])))
    table = np.zeros((S, Np), np.int32)
    for s in range(S):
        for j in range(pos[s] // P + 1):
            table[s, j] = next(handed, 0) or 1 + (s * Np + j) % (p["pages"] - 1)
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    pool = {n: jax.random.normal(k, (p["pages"], 1, P, G * hd), jnp.bfloat16)
            for n, k in zip(("k", "v"), keys)}
    q = jax.random.normal(keys[2], (S, 1, 1, H, hd), jnp.bfloat16)
    own = jax.random.normal(keys[3], (S, 1, 1, G, hd), jnp.bfloat16)

    def attend(window, on_kernel):
        def tick(pool, q, own, table, pos, live):
            def one_lane(q, own, pages, at, alive):
                cache = llama.PagedCache(pool=pool, scales=None, pages=pages, live=alive)
                return llama.update_kv_cache_and_attend(cache, q, own, own, at, rep,
                                                        sliding_window=window)[0]
            return jax.vmap(one_lane)(q, own, table, pos, live)

        probe = paged_attention.tpu_backend
        paged_attention.tpu_backend = lambda: on_kernel      # which branch this trace takes
        try:
            return jax.block_until_ready(jax.jit(tick)(
                pool, q, own, jnp.asarray(table), jnp.asarray(pos, jnp.int32), jnp.asarray(live)))
        finally:
            paged_attention.tpu_backend = probe

    errs = {}
    for name, window in (("full", None), ("window", p["window"])):
        got, want = (attend(window, on).astype(jnp.float32) for on in (True, False))
        errs[name] = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
        require(bool(jnp.isfinite(got).all()) and errs[name] <= KERNEL_TOL["fwd"], (
            f"the paged-attention kernel disagrees with the XLA work list: {errs}"))
    _, count = paged_attention.live_pages(pos, live, Np, P, None, lib=np)
    return {"shape": {**p, "dtype": "bfloat16"}, "interpreted": paged_attention._interpret(),
            "live_pages_full": int(count.sum()),
            "max_rel_err_to_work_list": {k: round(e, 6) for k, e in errs.items()},
            "tolerance": KERNEL_TOL["fwd"]}


# ---------------------------------------------------------------------------
# Server
# ---------------------------------------------------------------------------

def server_model(size: dict, seed: int, to_host: bool = False):
    """``(module, params)`` for the server: bf16 weights made leaf by leaf on
    the device (an fp32 ``model.init`` of three sparse layers is 17 GB).
    ``to_host`` returns numpy weights instead, for fleets that place a copy
    on each of several devices."""
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.models.mixtral import MixtralConfig, MixtralForCausalLM

    overrides = {k: v for k, v in size.items()
                 if k in MixtralConfig.__dataclass_fields__}
    cfg = MixtralConfig.mixtral_8x7b(**overrides)
    module = MixtralForCausalLM(cfg)
    shapes = jax.eval_shape(
        lambda: module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def make(key, shape, scale):
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(jnp.bfloat16)

    make = jax.jit(make, static_argnums=(1,))
    out = []
    for i, (path, leaf) in enumerate(leaves):
        name = jax.tree_util.keystr(path)
        if "norm" in name:  # RMSNorm scales start at one
            arr = jnp.ones(leaf.shape, jnp.bfloat16)
        else:
            # fan-in scaling (the second-to-last dim is the contraction for
            # both [D, F] kernels and expert-major [E, D, F] stacks).
            fan_in = leaf.shape[-2] if len(leaf.shape) >= 2 else leaf.shape[-1]
            arr = make(jax.random.fold_in(jax.random.PRNGKey(seed), i), leaf.shape,
                       float(fan_in) ** -0.5)
        out.append(np.asarray(arr) if to_host else arr)
    params = jax.tree_util.tree_unflatten(jax.tree.structure(shapes), out)
    return module, params


def serve_args(size: dict, extra=()):
    """The ``accelerate-tpu serve`` argument namespace for this size — the
    CLI's own parser and defaults, so the smoke builds what the CLI builds."""
    from accelerate_tpu.commands.serve import serve_command_parser

    argv = ["--port", "0", "--max-slots", str(size["max_slots"]),
            "--max-len", str(size["max_len"]),
            "--prefill-chunk", str(size["prefill_chunk"])]
    if size["max_pages"] is not None:
        argv += ["--max-pages", str(size["max_pages"])]
    return serve_command_parser().parse_args(argv + list(extra))


def build_fleet(args, module, params):
    """``commands.serve.build_fleet`` with its progress lines sent to stderr:
    stdout carries one JSON object per phase and nothing else."""
    from accelerate_tpu.commands import serve

    with contextlib.redirect_stdout(sys.stderr):
        return serve.build_fleet(args, module, params)


def prompts_for(size: dict, vocab: int, seed: int):

    rng = np.random.default_rng(seed + 1)
    return [rng.integers(1, vocab, n).astype(np.int32).tolist() for n in size["prompt_lens"]]


def post_completion(url: str, prompt, max_new_tokens: int, stream: bool) -> dict:
    """One real ``POST /v1/completions``; returns the final summary payload
    plus, for SSE, the tokens as they were streamed."""
    u = urlparse(url)
    conn = http.client.HTTPConnection(u.hostname, u.port, timeout=600)
    body = json.dumps({"prompt": prompt, "max_new_tokens": max_new_tokens, "stream": stream})
    t0 = time.perf_counter()
    conn.request("POST", "/v1/completions", body, {"Content-Type": "application/json"})
    resp = conn.getresponse()
    if resp.status != 200:
        raise AssertionError(f"HTTP {resp.status}: {resp.read()[:500]!r}")
    if not stream:
        out = json.loads(resp.read())
    else:
        streamed, out = [], None
        for raw in resp:
            line = raw.decode().strip()
            if not line.startswith("data:"):
                continue
            event = json.loads(line[len("data:"):])
            if event.get("done"):
                out = event
                break
            streamed.append(event["token"])
        require(out is not None, "SSE stream ended without its done event")
        require(streamed == out["tokens"], "streamed tokens differ from the summary")
    conn.close()
    out["wall_s"] = round(time.perf_counter() - t0, 3)
    out["mode"] = "sse" if stream else "json"
    return out


def metrics_value(url: str, name: str) -> float:
    u = urlparse(url)
    conn = http.client.HTTPConnection(u.hostname, u.port, timeout=60)
    conn.request("GET", "/metrics")
    text = conn.getresponse().read().decode()
    conn.close()
    for line in text.splitlines():
        parts = line.split()
        if len(parts) == 2 and parts[0] == name:
            return float(parts[1])
    raise AssertionError(f"/metrics has no {name}")


def serve_requests(replica_set, args, prompts, max_new_tokens: int) -> dict:
    """Put the CLI's gateway in front of ``replica_set``, send every prompt
    as a real HTTP request (JSON and SSE alternating, all in flight at once),
    check /metrics for compiles after warm-up, and shut down cleanly."""
    from accelerate_tpu.commands.serve import gateway_config
    from accelerate_tpu.serving import ServingGateway

    thread_errors = []
    prev_hook = threading.excepthook
    threading.excepthook = lambda a: thread_errors.append(
        f"{a.thread.name}: {a.exc_type.__name__}: {a.exc_value}")
    gateway = ServingGateway(replica_set, config=gateway_config(args))
    gateway.start()
    try:
        results = [None] * len(prompts)

        def one(i):
            results[i] = post_completion(gateway.url, prompts[i], max_new_tokens,
                                         stream=bool(i % 2))

        threads = [threading.Thread(target=one, args=(i,), name=f"client-{i}")
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        compiles = metrics_value(gateway.url, "accelerate_tpu_xla_compile_events_total")
    finally:
        gateway.shutdown(drain=True)
        threading.excepthook = prev_hook
    require(not thread_errors, f"thread exceptions: {thread_errors}")
    for i, r in enumerate(results):
        require(r is not None, f"request {i} never returned")
        require(r["status"] == "completed", f"request {i}: {r}")
        require(len(r["tokens"]) == max_new_tokens, (
            f"request {i} returned {len(r['tokens'])} tokens, asked {max_new_tokens}"))
        require(r["prompt_len"] == len(prompts[i]), f"request {i}: wrong prompt_len {r}")
    require(compiles == 0, f"{compiles} compile events after warm-up")
    return {
        "requests": [{"mode": r["mode"], "prompt_len": r["prompt_len"],
                      "new_tokens": len(r["tokens"]), "replica_trail": r["replica_trail"],
                      "wall_s": r["wall_s"]} for r in results],
        "compile_events_after_warmup": int(compiles),
        "clean_shutdown": True,
        "tokens": [r["tokens"] for r in results],
    }


def score_against_reference(module, params, prompts, tokens) -> dict:
    """The engine-free check. For every request: one monolithic cached
    forward (the path offline ``generate`` prefills through) over prompt +
    generated tokens, giving for each generated token how far its logit sits
    below that position's maximum (0 = the reference would have chosen it
    too). Offline ``generate`` on the first prompt is scored the same way: a
    second engine-free path, showing what bf16 and expert flips alone do."""
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.big_modeling import cache_factory_for
    from accelerate_tpu.generation import generate

    factory = cache_factory_for(module)

    @jax.jit
    def gaps(params, ids):  # params as an argument: a closure would bake
        # 9 GB of weights into the program as constants
        cache = factory(1, ids.shape[1], jnp.bfloat16)
        logits, _ = module.apply({"params": params}, ids, cache=cache, cache_pos=0)
        pred = logits[0, :-1].astype(jnp.float32)        # position t scores token t+1
        chosen = jnp.take_along_axis(pred, ids[0, 1:, None], axis=-1)[:, 0]
        return pred.max(-1) - chosen, pred.std()

    def score(prompt, toks):
        gap, std = gaps(params, jnp.asarray([prompt + toks], jnp.int32))
        return np.asarray(gap)[len(prompt) - 1:], float(std)

    def summary(gap):
        return {"max": round(float(gap.max()), 4),
                "share_exact": round(float((gap == 0).mean()), 4),
                "share_within_near_tie": round(float((gap <= NEAR_TIE).mean()), 4),
                "share_within_tolerance": round(float((gap <= LOGIT_TOL).mean()), 4)}

    scored = [score(p, t) for p, t in zip(prompts, tokens)]
    engine = np.concatenate([g for g, _ in scored])
    first = np.asarray([g[0] for g, _ in scored])      # decided by prefill alone
    offline = np.asarray(generate(module, params, np.asarray([prompts[0]], np.int32),
                                  max_new_tokens=len(tokens[0])))[0, len(prompts[0]):]
    same = int(np.cumprod(offline == np.asarray(tokens[0])).sum())
    out = {"logit_gap_of_served_tokens": summary(engine),
           "logit_gap_of_first_tokens": [round(float(g), 4) for g in first],
           "logit_gap_of_offline_generate_tokens":
               summary(score(prompts[0], [int(t) for t in offline])[0]),
           "near_tie": NEAR_TIE, "tolerance": LOGIT_TOL,
           "reference_logit_std": round(scored[0][1], 3),
           "offline_generate_leading_tokens_equal": f"{same}/{len(tokens[0])}"}
    require(first.max() <= NEAR_TIE,
            f"a prefill's first token is not a near-tie with the reference argmax: {out}")
    require((engine <= LOGIT_TOL).mean() >= MIN_SHARE_WITHIN_TOL and engine.max() <= MAX_GAP,
            f"engine tokens are not near-argmax under the reference: {out}")
    return out


# ---------------------------------------------------------------------------
# The served form of the q and k kernels (compiled, not run)
# ---------------------------------------------------------------------------

def kernel_relayouts(text: str, elements: int) -> list:
    """The instructions of a compiled program that only move a bfloat16 array
    of ``elements`` values (the weights' type here; float32 arrays are
    activations): a ``copy``, ``reshape`` or ``transpose`` whose result is
    that large (a product that reads the array is a fusion or a convolution,
    a free reinterpretation a ``bitcast``)."""
    found = []
    for line in text.splitlines():
        m = re.search(r"= bf16\[([\d,]+)\]\S* (copy|reshape|transpose)\(", line)
        if m and int(np.prod([int(d) for d in m.group(1).split(",")])) == elements:
            found.append(line.strip()[:200])
    return found


def run_served_form(size: dict) -> dict:
    """Compile the ``cohere2_moe`` tick and chunk at the benchmark cell's
    shapes and read their text: in the published form both laid every 134 MB
    q kernel out anew in every call (a ``copy`` for its orientation, on
    sliding layers a ``reshape`` for the interleaved pairs: 3.2 ms of a
    17.3 ms tick). The engine holds the kernels in the family's served form;
    no instruction of either program may only move a whole q kernel."""
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.models.cohere2_moe import Cohere2MoeConfig, Cohere2MoeForCausalLM
    from accelerate_tpu.serving import ServingEngine

    engine_keys = ("max_slots", "max_len", "prefill_chunk", "max_pages")
    cfg = Cohere2MoeConfig(**{k: v for k, v in size.items() if k not in engine_keys})
    module = Cohere2MoeForCausalLM(cfg)
    shapes = jax.eval_shape(lambda: module.init_params(jax.random.PRNGKey(0)))
    params = jax.tree.map(lambda s: jnp.zeros(s.shape, jnp.bfloat16), shapes)
    eng = ServingEngine(module, params, **{k: size[k] for k in engine_keys},
                        autostart=False, warmup=False)
    del params
    try:
        S, C = eng.max_slots, size["prefill_chunk"]
        table = np.zeros((S, eng._pages_per_slot), np.int32)
        t0 = time.perf_counter()
        texts = {
            "tick": eng._decode.lower(eng.params, eng._state, np.ones((S,), bool), table),
            "chunk": eng._prefill_chunk.lower(
                eng.params, eng._state, np.zeros((1, C), np.int32), np.int32(0), table[0],
                np.int32(0), np.int32(C), jax.random.PRNGKey(0)),
        }
        texts = {name: low.compile().as_text() for name, low in texts.items()}
        q_kernel = cfg.num_attention_heads * cfg.head_dim * cfg.hidden_size
        out = {"weights_served_form_leaves": eng._served_form_leaves,
               "weights_served_form_bytes": eng._served_form_bytes,
               "compile_s": round(time.perf_counter() - t0, 2), "compile_s_is": NOT_A_MEASUREMENT,
               "q_kernel_relayouts": {name: kernel_relayouts(text, q_kernel)
                                      for name, text in texts.items()},
               "peak_bytes": peak_bytes(jax.devices()[0])}
    finally:
        eng.shutdown(drain=False)
    sliding = sum(cfg.window_for(i) is not None for i in range(cfg.num_hidden_layers))
    require(out["weights_served_form_leaves"] == cfg.num_hidden_layers + sliding,
            f"the engine did not put every q kernel and the sliding layers' k kernels "
            f"into the served form: {out}")
    require(not any(out["q_kernel_relayouts"].values()),
            f"a compiled serving program lays a whole q kernel out anew in every call: {out}")
    return out


def run_server(size: dict, seed: int) -> dict:
    import jax

    from accelerate_tpu.utils.profiling import CompileWatcher

    t0 = time.perf_counter()
    module, params = server_model(size, seed)
    weights_s = time.perf_counter() - t0
    args = serve_args(size)
    with CompileWatcher() as watcher:
        t0 = time.perf_counter()
        replica_set = build_fleet(args, module, params)   # builds and warms up
        warmup_s = time.perf_counter() - t0
        warm = compile_report(watcher)
    cfg = module.config
    prompts = prompts_for(size, cfg.vocab_size, seed)
    served = serve_requests(replica_set, args, prompts, size["max_new_tokens"])
    tokens = served.pop("tokens")
    engine = replica_set.engine(0)
    pool_bytes = sum(l.nbytes for l in jax.tree.leaves(engine._state["pool"]))
    del engine, replica_set
    release()
    check = score_against_reference(module, params, prompts, tokens)
    return {
        "config": {**{k: getattr(cfg, k) for k in (
            "hidden_size", "intermediate_size", "num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "vocab_size", "num_experts", "top_k")},
            **{k: size[k] for k in ("max_slots", "max_len", "prefill_chunk", "max_pages")},
            "weights_dtype": "bfloat16"},
        "n_params": sum(int(p.size) for p in jax.tree.leaves(params)),
        "kv_pool_bytes": pool_bytes,
        "weights_s": round(weights_s, 2),
        "warmup_s": round(warmup_s, 2),
        "warmup": warm,
        "times": NOT_A_MEASUREMENT,
        **served,
        **check,
        "peak_bytes_in_use_so_far": peak_bytes(jax.devices()[0]),
    }


# ---------------------------------------------------------------------------
# Four chips: only what exists only across chips, and what it is compared with
# ---------------------------------------------------------------------------

def device_ids(tree) -> list:
    import jax

    return sorted({d.id for leaf in jax.tree.leaves(tree) for d in leaf.devices()})


def run_four_chip_trainer(size: dict, seed: int, real_widths: bool) -> dict:
    import jax

    single, model = run_trainer(size["trainer"], seed, devices=jax.devices()[:1])
    del model
    release()
    sharded, model = run_trainer(size["trainer"], seed, mesh={"fsdp": 2, "tp": 2})
    spread = param_spread(model)
    diffs = [abs(a - b) for a, b in zip(single["losses"], sharded["losses"])]
    out = {"one_device": single, "fsdp2_tp2": sharded, "param_spread": spread,
           "max_loss_diff": round(max(diffs), 5), "tolerance": MESH_LOSS_TOL}
    require(max(diffs) <= MESH_LOSS_TOL, f"sharded losses drifted from one device: {out}")
    require(spread["devices"] == sorted(d.id for d in jax.devices()[:4]),
            f"parameters are not spread over the four devices: {spread}")
    if real_widths:  # the toy rehearsal's leaves are below fsdp's size floor
        require(all(0.24 <= s <= 0.26 for s in spread["share_of_param_bytes"].values()), spread)
        # Only the norm scales (4096 wide) may be held whole on every chip.
        require(all(n <= 4096 for _, n in spread["leaves_not_quartered"]), spread)
    return out


def run_four_chip_serving(size: dict, seed: int) -> dict:
    """(c) ``serve --replicas 4``: four plain replicas, each on its own chip —
    replica 0 is the one-chip engine everything is compared with. (b) ``serve
    --tp 2 --replicas 2``: two tp=2 slices, each on its own two chips."""
    import jax

    module, params = server_model(size, seed, to_host=True)
    prompts = prompts_for(size, module.config.vocab_size, seed)
    n = size["max_new_tokens"]
    out = {}

    def engine_devices(rs):
        return [{"params": device_ids(rs.engine(i).params),
                 "kv": device_ids(rs.engine(i)._state["pool"])} for i in range(len(rs))]

    def tokens_from_every_replica(rs):
        """Greedy tokens for prompt 0 from each replica, submitted directly
        (the router would send them all to the least-loaded one)."""

        ids = np.asarray([prompts[0]], np.int32)
        reqs = [rs.engine(i).submit(ids, max_new_tokens=n) for i in range(len(rs))]
        for r in reqs:
            require(r.wait(600), "replica did not finish")
        return [list(map(int, r.tokens)) for r in reqs]

    # (c) plain replicas
    args = serve_args(size, ["--replicas", "4"])
    replicas = build_fleet(args, module, params)
    placed = engine_devices(replicas)
    per_replica = tokens_from_every_replica(replicas)
    served = serve_requests(replicas, args, prompts, n)
    reference_tokens = served.pop("tokens")
    out["replicas_4"] = {"devices": placed, **served,
                         "prompt0_tokens_equal_across_replicas":
                             all(t == per_replica[0] for t in per_replica)}
    want = [[d.id] for d in jax.devices()[:4]]
    require([p["params"] for p in placed] == want and [p["kv"] for p in placed] == want, (
        f"replicas are not one per chip: {placed}"))
    require(out["replicas_4"]["prompt0_tokens_equal_across_replicas"], per_replica)
    del replicas
    release()

    # (b) tp=2 slices
    args = serve_args(size, ["--replicas", "2", "--tp", "2"])
    slices = build_fleet(args, module, params)
    placed = engine_devices(slices)
    per_slice = tokens_from_every_replica(slices)
    served = serve_requests(slices, args, prompts, n)
    slice_tokens = served.pop("tokens")
    del slices
    release()
    ids = [d.id for d in jax.devices()[:4]]
    want = [ids[:2], ids[2:]]
    require([p["params"] for p in placed] == want and [p["kv"] for p in placed] == want, (
        f"slices are not on their own two chips: {placed}"))
    # The slices' tokens against the one-chip engine's, through the same
    # engine-free reference: both must be near-argmax; exact equality is
    # reported, not required (bf16 reduction order differs across tp).
    params_dev = jax.device_put(params, jax.devices()[0])
    check_one = score_against_reference(module, params_dev, prompts, reference_tokens)
    check_tp = score_against_reference(module, params_dev, prompts, slice_tokens)
    equal = sum(a == b for a, b in zip(reference_tokens, slice_tokens))
    out["tp2_slices_2"] = {
        "devices": placed, **served,
        "prompt0_tokens_equal_across_slices": per_slice[0] == per_slice[1],
        "requests_token_equal_to_one_chip": f"{equal}/{len(prompts)}",
        "one_chip_vs_reference": check_one, "slices_vs_reference": check_tp}
    return out


# ---------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the paths that span four chips (and what they "
                         "are compared with) on a four-chip host")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="run the same control flow at toy size on (virtual) CPU "
                         "devices; proves the script, says nothing about the chip")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--phases", default=None,
                    help="comma-separated subset of the mode's phases "
                         "(one chip: trainer,kernels,server,served_form; four: "
                         "trainer,serving)")
    opts = ap.parse_args()
    need = 4 if opts.four_chips else 1

    if opts.rehearse_cpu:
        from accelerate_tpu.utils.platforms import force_cpu_platform

        force_cpu_platform(num_virtual_devices=need)
    import jax
    import jaxlib

    import accelerate_tpu.native as native
    from accelerate_tpu.utils.platforms import enable_compilation_cache

    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    if not opts.rehearse_cpu and device["platform"] != "tpu":
        print(f"chip_smoke.py: jax's first device is {device}, not a TPU; nothing ran. "
              "(--rehearse-cpu rehearses the control flow on the CPU.)", file=sys.stderr)
        return 1
    if len(devices) < need:
        print(f"chip_smoke.py: needs {need} device(s), jax gives {len(devices)}",
              file=sys.stderr)
        return 1
    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None
    emit("start", device=device, jax=jax.__version__, jaxlib=jaxlib.__version__,
         libtpu=libtpu_version, compilation_cache=enable_compilation_cache(),
         native_host_library_loaded=native.available(), seed=opts.seed,
         mode="four-chips" if opts.four_chips else "one-chip",
         rehearsal=opts.rehearse_cpu)

    size = SIZES["tiny" if opts.rehearse_cpu else "real"]
    if opts.four_chips:
        phases = {"trainer": lambda: run_four_chip_trainer(size, opts.seed,
                                                           not opts.rehearse_cpu),
                  "serving": lambda: run_four_chip_serving(size["server"], opts.seed)}
    else:
        def trainer():
            out, _ = run_trainer(size["trainer"], opts.seed)
            # The kernel must be in the step on the chip (the CPU rehearsal
            # takes the einsum path by design and says so).
            require(out["pallas_call_in_step_hlo"] or opts.rehearse_cpu, (
                "no tpu_custom_call in the compiled train step"))
            return out

        phases = {"trainer": trainer,
                  "kernels": lambda: run_kernels(size, opts.seed),
                  "server": lambda: run_server(size["server"], opts.seed),
                  "served_form": lambda: run_served_form(size["served_form"])}
    wanted = opts.phases.split(",") if opts.phases else list(phases)
    unknown = [p for p in wanted if p not in phases]
    if unknown:
        ap.error(f"unknown phase(s) {unknown}; this mode has {list(phases)}")
    for name in wanted:
        t0 = time.perf_counter()
        result = phases[name]()
        emit(name, **result, phase_wall_s=round(time.perf_counter() - t0, 2))
        release()
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
