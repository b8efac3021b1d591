"""Llama-family decoder-only transformer, TPU-first.

The reference framework wraps externally-defined torch models (HF
transformers); this framework ships native flax model families so the full
training path (sharding rules, pallas attention, remat) is exercised
end-to-end. Design notes:

* Parameter names match the TP sharding rules in parallel/sharding.py
  (q_proj/k_proj/v_proj/o_proj, gate_proj/up_proj/down_proj, embed/lm_head)
  so Megatron-style column/row layouts apply automatically.
* All matmuls keep a trailing dim that is a multiple of 128 for MXU tiling
  at real model sizes; compute dtype comes from the caller's policy (params
  are cast before apply — see precision.py).
* Attention dispatches to the Pallas flash kernel on TPU (ops/attention.py)
  and falls back to an einsum implementation elsewhere; with a cp>1 mesh the
  ring variant shards the sequence axis.
* ``remat`` wraps each block in jax.checkpoint to trade FLOPs for HBM.
"""

from __future__ import annotations

import dataclasses
import math
import functools
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import flax.linen as nn
import numpy as np

from ..observability.program_parts import program_part


@dataclasses.dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    # HF-style rope scaling dict, e.g. {"rope_type": "llama3", "factor": 8.0,
    # "low_freq_factor": 1.0, "high_freq_factor": 4.0,
    # "original_max_position_embeddings": 8192} or {"rope_type": "linear",
    # "factor": 2.0}. None = vanilla RoPE.
    rope_scaling: Optional[dict] = None
    # Mistral-style local attention: each token sees only the last N keys.
    sliding_window: Optional[int] = None
    tie_word_embeddings: bool = False
    # Family knobs that turn this skeleton into Qwen2 / Gemma:
    # Qwen2 puts biases on the q/k/v projections (never on o_proj).
    attention_qkv_bias: bool = False
    attention_out_bias: bool = False
    # Gemma: GeGLU MLP ("gelu_tanh"), zero-centered RMSNorm scales (the
    # checkpoint stores w with the norm computing 1 + w), sqrt(hidden)
    # embedding scaling, and a head_dim decoupled from hidden/heads
    # (gemma-7b: 16 heads x 256 = 4096 != hidden 3072).
    mlp_activation: str = "silu"  # "silu" (SwiGLU) | "gelu_tanh"/"gelu_exact" (GeGLU)
    rms_norm_unit_offset: bool = False
    scale_embeddings: bool = False
    head_dim_override: Optional[int] = None
    # Gemma2: per-layer attention patterns and sandwich norms.
    # layer_windows[i] is layer i's sliding window (None = full attention) —
    # built from the HF config's layer_types; overrides the uniform
    # sliding_window when set. post_norms adds the 4-norm block (attn/mlp
    # outputs normed before their residual adds). Softcaps bound logits via
    # cap * tanh(x / cap); query_pre_attn_scalar replaces head_dim in the
    # attention scale.
    layer_windows: Optional[tuple] = None
    post_norms: bool = False
    attn_logit_softcapping: Optional[float] = None
    final_logit_softcapping: Optional[float] = None
    query_pre_attn_scalar: Optional[float] = None
    remat: bool = False
    # Intermediates saved through a remat'd block: "dots" | "nothing" |
    # "everything" (parallel/sharding.resolve_remat_policy).
    remat_policy: str = "dots"
    use_flash_attention: bool = True
    # 'auto' uses ring/Ulysses context parallelism when the ambient mesh has
    # cp > 1 (ops/ring_attention.py), flash/einsum otherwise.
    attention_backend: str = "auto"
    # fp8 projections (ops/quant.py Fp8Dense, delayed scaling): the TE-swap
    # equivalent (reference: utils/transformer_engine.py:40-49). Pair with
    # Accelerator(mixed_precision="fp8") — the fp8 statistics params are
    # partitioned out of the optimizer automatically.
    use_fp8: bool = False
    fp8_margin: int = 0
    fp8_amax_history_len: int = 16
    fp8_amax_compute_algo: str = "max"
    fp8_format: str = "HYBRID"  # HYBRID: e4m3 fwd / e5m2 bwd

    @classmethod
    def llama3_8b(cls, **overrides):
        cfg = cls(
            vocab_size=128256, hidden_size=4096, intermediate_size=14336,
            num_hidden_layers=32, num_attention_heads=32, num_key_value_heads=8,
            max_position_embeddings=8192, rope_theta=500000.0,
        )
        return dataclasses.replace(cfg, **overrides)

    @classmethod
    def qwen2_7b(cls, **overrides):
        cfg = cls(
            vocab_size=152064, hidden_size=3584, intermediate_size=18944,
            num_hidden_layers=28, num_attention_heads=28, num_key_value_heads=4,
            max_position_embeddings=32768, rope_theta=1e6, rms_norm_eps=1e-6,
            attention_qkv_bias=True,
        )
        return dataclasses.replace(cfg, **overrides)

    @classmethod
    def gemma2_9b(cls, **overrides):
        cfg = cls(
            vocab_size=256000, hidden_size=3584, intermediate_size=14336,
            num_hidden_layers=42, num_attention_heads=16, num_key_value_heads=8,
            head_dim_override=256, max_position_embeddings=8192, rms_norm_eps=1e-6,
            tie_word_embeddings=True, mlp_activation="gelu_tanh",
            rms_norm_unit_offset=True, scale_embeddings=True, post_norms=True,
            attn_logit_softcapping=50.0, final_logit_softcapping=30.0,
            query_pre_attn_scalar=256.0,
            layer_windows=tuple(4096 if i % 2 == 0 else None for i in range(42)),
        )
        return dataclasses.replace(cfg, **overrides)

    @classmethod
    def tiny(cls, **overrides):
        """Test-size config (used by unit tests and dryrun_multichip)."""
        cfg = cls(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=128,
        )
        return dataclasses.replace(cfg, **overrides)

    @property
    def head_dim(self):
        """Per-head width: hidden_size // num_attention_heads, unless the
        family decouples it (``head_dim_override``, e.g. Gemma)."""
        if self.head_dim_override is not None:
            return self.head_dim_override
        return self.hidden_size // self.num_attention_heads

    @property
    def sm_scale(self):
        """Attention logit scale: 1/sqrt(query_pre_attn_scalar or head_dim)."""
        base = self.query_pre_attn_scalar
        return (base if base is not None else self.head_dim) ** -0.5

    def window_for(self, layer_idx: int):
        """Layer ``layer_idx``'s sliding window (None = full attention)."""
        if self.layer_windows is not None:
            return self.layer_windows[layer_idx]
        return self.sliding_window


def _dense_factory(cfg: "LlamaConfig", compute_dtype):
    """Projection-layer constructor honoring ``cfg.use_fp8``."""
    if not cfg.use_fp8:
        return lambda feats, name, use_bias=False: nn.Dense(
            feats, use_bias=use_bias, name=name, dtype=compute_dtype, param_dtype=jnp.float32
        )
    from ..ops.quant import E4M3, E5M2, Fp8Dense

    fwd, bwd = {
        "HYBRID": (E4M3, E5M2),
        "E4M3": (E4M3, E4M3),
        "E5M2": (E5M2, E5M2),
    }[cfg.fp8_format]
    return lambda feats, name, use_bias=False: Fp8Dense(
        feats, use_bias=use_bias, name=name, dtype=compute_dtype,
        margin=cfg.fp8_margin, amax_history_len=cfg.fp8_amax_history_len,
        amax_compute_algo=cfg.fp8_amax_compute_algo, fwd_dtype=fwd, bwd_dtype=bwd,
    )


class RMSNorm(nn.Module):
    eps: float = 1e-5
    # Gemma convention: the checkpoint stores zero-centered scales and the
    # norm computes (1 + w) * x̂; init is zeros so a fresh model is identity.
    unit_offset: bool = False

    @nn.compact
    def __call__(self, x):
        dtype = x.dtype
        x32 = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        norm = x32 * jax.lax.rsqrt(var + self.eps)
        init = nn.initializers.zeros if self.unit_offset else nn.initializers.ones
        scale = self.param("scale", init, (x.shape[-1],), jnp.float32)
        if self.unit_offset:
            scale = 1.0 + scale
        return (norm * scale).astype(dtype)


def scale_rope_frequencies(inv_freq: jnp.ndarray, rope_scaling: dict) -> jnp.ndarray:
    """Apply HF-style RoPE scaling to the base inverse frequencies.

    "linear" divides every frequency by ``factor`` (position interpolation);
    "llama3" (Llama 3.1+) keeps high frequencies, scales low frequencies by
    ``factor``, and smoothly interpolates the band in between — the published
    long-context recipe, vectorized with jnp.where so it stays jittable.
    """
    rope_type = rope_scaling.get("rope_type", rope_scaling.get("type", "default"))
    if rope_type in ("default", None):
        return inv_freq
    factor = float(rope_scaling.get("factor", 1.0))
    if rope_type == "linear":
        return inv_freq / factor
    if rope_type == "llama3":
        low = float(rope_scaling.get("low_freq_factor", 1.0))
        high = float(rope_scaling.get("high_freq_factor", 4.0))
        original = float(rope_scaling.get("original_max_position_embeddings", 8192))
        wavelen = 2.0 * jnp.pi / inv_freq
        low_wavelen = original / low
        high_wavelen = original / high
        smooth = (original / wavelen - low) / (high - low)
        interpolated = (1.0 - smooth) * inv_freq / factor + smooth * inv_freq
        scaled = jnp.where(wavelen > low_wavelen, inv_freq / factor, interpolated)
        return jnp.where(wavelen < high_wavelen, inv_freq, scaled)
    raise NotImplementedError(f"rope_scaling type {rope_type!r} (supported: linear, llama3)")


def rotary_embedding(positions: jnp.ndarray, head_dim: int, theta: float,
                     dtype=jnp.float32, rope_scaling: Optional[dict] = None):
    """RoPE tables: returns (cos, sin) of shape [..., seq, head_dim//2]."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    if rope_scaling:
        inv_freq = scale_rope_frequencies(inv_freq, rope_scaling)
    angles = positions[..., None].astype(jnp.float32) * inv_freq
    return jnp.cos(angles).astype(dtype), jnp.sin(angles).astype(dtype)


def apply_rotary(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray):
    """x: [batch, seq, heads, head_dim]; rotate pairs (even, odd halves)."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    cos = cos[:, :, None, :]
    sin = sin[:, :, None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def multi_head_attention(
    q, k, v, causal: bool = True, use_flash: bool = True, segment_ids=None,
    backend: str = "auto", sliding_window: Optional[int] = None,
    sm_scale: Optional[float] = None, logit_softcap: Optional[float] = None,
):
    """Dispatch between the attention implementations in ops/.

    ``logit_softcap`` (Gemma2) bounds logits via cap * tanh(s / cap) and
    routes to the einsum path (the flash kernel has no softcap; the CP
    strategies reject it). ``sm_scale`` overrides the 1/sqrt(head_dim)
    logit scale (Gemma2's query_pre_attn_scalar).

    ``sliding_window`` (Mistral) narrower than the sequence routes to the
    *windowed* flash kernel (banded grid — O(S*w) compute and HBM traffic)
    or the windowed einsum mask; the CP strategies compute full causal
    attention and are rejected, since they would silently widen the
    receptive field.

    backend semantics:
      * 'auto'    — context-parallel (ring/Ulysses) when the ambient mesh has
                    cp > 1 and the sequence is evenly cp-shardable (a growing
                    generate() sequence quietly falls back); else flash when
                    available, else einsum.
      * 'ring' / 'ulysses' — always route through the CP entry point, which
                    raises on non-shardable shapes instead of silently
                    changing memory asymptotics; a *trivial* cp axis (mesh
                    property, not a shape accident) still means single-device
                    attention. Incompatible with segment_ids.
      * 'flash'   — Pallas kernel when the platform/shape supports it, einsum
                    otherwise (availability is a hardware property).
      * 'einsum'  — always the XLA einsum path.
    """
    from ..ops.attention import _einsum_attention, flash_attention, flash_attention_available

    if backend not in ("auto", "ring", "ulysses", "flash", "einsum"):
        raise ValueError(
            f"unknown attention_backend {backend!r}; expected auto/ring/ulysses/flash/einsum"
        )
    if logit_softcap is not None:
        # Softcap lives inside the flash kernel and the einsum path; the CP
        # strategies must reject rather than silently drop the cap.
        if backend in ("ring", "ulysses"):
            raise ValueError(f"attention_backend={backend!r} does not support logit_softcap")
        window = (sliding_window if sliding_window is not None
                  and sliding_window < q.shape[1] else None)
        if (backend != "einsum" and use_flash and causal
                and flash_attention_available(q)):
            return flash_attention(
                q, k, v, causal=True, sliding_window=window,
                segment_ids=segment_ids,
                sm_scale=sm_scale, logit_softcap=logit_softcap)
        return _einsum_attention(q, k, v, causal=causal, segment_ids=segment_ids,
                                 sliding_window=sliding_window, sm_scale=sm_scale,
                                 logit_softcap=logit_softcap)
    # GQA: every path is narrow-KV-native — the flash kernel indexes the
    # shared kv head in its BlockSpecs, the einsum path contracts grouped,
    # and the CP paths rotate G-wide KV over the interconnect. The expanded
    # copy survives only for a tp axis that cannot shard G heads
    # (ring_attention._expand_kv, below).
    if sliding_window is not None and sliding_window < q.shape[1]:
        # Only a window narrower than the sequence masks anything; when
        # window >= seq, full causal attention is exact and every fast path
        # below stays available (Mistral-7B sets window=4096, so typical
        # prefills never branch here). A narrower window uses the windowed
        # flash kernel — O(S * w) with whole K blocks skipped — or the
        # windowed einsum mask as fallback.
        if backend in ("ring", "ulysses"):
            raise ValueError(
                f"attention_backend={backend!r} does not support sliding_window")
        if backend != "einsum" and use_flash and causal:
            # Window + segments compose inside the kernel (packed long-doc
            # training keeps the banded O(S*w) asymptotics).
            return flash_attention(q, k, v, causal=True,
                                   sliding_window=sliding_window,
                                   segment_ids=segment_ids, sm_scale=sm_scale)
        return _einsum_attention(q, k, v, causal=causal,
                                 segment_ids=segment_ids,
                                 sliding_window=sliding_window,
                                 sm_scale=sm_scale)
    if backend in ("auto", "ring", "ulysses"):
        from ..ops.ring_attention import (
            _axis_size,
            _expand_kv,
            _resolve_mesh,
            context_parallel_attention,
        )

        if segment_ids is not None and backend != "auto":
            raise ValueError(f"attention_backend={backend!r} does not support segment_ids")
        if sm_scale is not None and backend != "auto":
            raise ValueError(f"attention_backend={backend!r} does not support sm_scale")
        mesh = _resolve_mesh(None)
        cp = _axis_size(mesh, "cp")
        if backend != "auto" or (cp > 1 and segment_ids is None and sm_scale is None
                                 and q.shape[1] % cp == 0):
            if cp > 1:
                # GQA KV stays unrepeated here: the ring rotates (and
                # Ulysses all_to_alls) G-wide KV over the interconnect,
                # expanding only at the local contraction. Exception: a tp
                # axis that cannot shard G heads needs the expanded copy.
                tp = _axis_size(mesh, "tp")
                kc, vc = (k, v) if (tp <= 1 or k.shape[2] % tp == 0) else _expand_kv(q, k, v)
                return context_parallel_attention(
                    q, kc, vc, mesh=mesh, causal=causal, strategy=backend, use_flash=use_flash
                )
    if backend != "einsum" and use_flash and flash_attention_available(q):
        # segment_ids are masked inside the Pallas kernel, so packed-sequence
        # training keeps flash's memory asymptotics.
        return flash_attention(q, k, v, causal=causal,
                               segment_ids=segment_ids, sm_scale=sm_scale)
    return _einsum_attention(q, k, v, causal=causal, segment_ids=segment_ids,
                             sm_scale=sm_scale)


def _layer_window(config, layer_idx: int):
    """Duck-typed per-layer window: LlamaConfig.window_for when present,
    else a uniform ``sliding_window`` attribute (mixtral et al.)."""
    if hasattr(config, "window_for"):
        return config.window_for(layer_idx)
    return getattr(config, "sliding_window", None)


def init_kv_cache(config: "LlamaConfig", batch_size: int, max_len: int, dtype=jnp.bfloat16,
                  ring_slack: int = 0):
    """Per-layer KV cache: tuple of ``{"k", "v"}`` with [B, max_len, n_kv, hd]
    buffers (KV heads stored *unrepeated* — GQA expansion happens at attention
    time, so the cache is ``n_q/n_kv``× smaller than the score matrices).

    Sliding-window layers (Mistral; Gemma2's local layers) get a RING buffer
    of ``window`` slots instead — a query only ever sees the last ``window``
    keys, so decode-cache memory is O(window), not O(max_len) (32k-context
    Mistral-7B: 8x smaller). Ring caches carry a ``pos`` buffer [B, window]
    recording each slot's global position (-1 = never written); the batch
    dim exists so beam search's batch-axis cache reordering maps over it
    like any other leaf.

    ``ring_slack`` adds capacity beyond the window (speculative decoding:
    a rejected overshoot write must not EVICT still-in-window committed
    keys — the attention window itself stays ``w`` via the position mask)."""
    caches = []
    n_kv, hd = config.num_key_value_heads, config.head_dim
    for i in range(config.num_hidden_layers):
        w = _layer_window(config, i)
        if w is not None and w < max_len:
            size = min(w + ring_slack, max_len)
            caches.append({
                "k": jnp.zeros((batch_size, size, n_kv, hd), dtype),
                "v": jnp.zeros((batch_size, size, n_kv, hd), dtype),
                "pos": jnp.full((batch_size, size), -1, jnp.int32),
            })
        else:
            shape = (batch_size, max_len, n_kv, hd)
            caches.append({"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)})
    return tuple(caches)


#: What one key block's float32 scores may take: half of a v5e core's 128 MiB
#: of VMEM, the largest size at which the compiler was seen to keep them there.
_SCORE_BLOCK_BYTES = 64 * 2**20


def cached_key_block(score_rows: int, L: int) -> int:
    """Key rows :func:`_cached_attention` scores at a time, from the call's
    static shape alone: ``score_rows = B * H * S`` float32 scores a key row,
    against a cache of ``L`` rows. ``L`` means one block — the whole view in
    one pass, which is what every single-token decode, every speculative
    verify and every chunk against a short cache gets.

    The rule: the widest power of two (from 128, the lane width) whose
    block of scores fits ``_SCORE_BLOCK_BYTES``. Scores that fit stay in VMEM
    between the product that writes them and the two passes that read them;
    wider, they go through HBM three times, which costs four times as much a
    key row. v5e readings for 128 heads x 256 queries, ms a block (PERF.md
    section 6, PR 32): 256 rows 0.049, **512 rows (64 MiB) 0.077**, 1024 rows
    0.57, 2048 rows 1.27; a block also costs ~0.02 ms whatever its width
    (rescaling the accumulator), so the widest that fits wins."""
    block = 128
    while 2 * block * score_rows * 4 <= _SCORE_BLOCK_BYTES:
        block *= 2
    return min(block, L)


def cached_key_extent(cache_pos, S: int, L: int, block: int, sliding_window=None,
                      lib=jnp):
    """Key blocks ``[first, last)`` of width ``block`` that a query block of
    ``S`` positions at ``cache_pos`` can see in a cache of ``L`` rows: keys
    up to ``cache_pos + S - 1``, from ``cache_pos - sliding_window + 1`` on a
    windowed layer. ``cache_pos`` may be traced (``lib=jnp``); the serving
    engine counts the same blocks on the host (``lib=np``)."""
    last = lib.minimum((cache_pos + S + block - 1) // block, -(-L // block))
    if sliding_window is None:
        return 0, last
    return lib.maximum(cache_pos - sliding_window + 1, 0) // block, last


def cached_attention_rows(cache_pos: int, S: int, L: int, score_rows: int,
                          sliding_window=None):
    """Host arithmetic for the serving counters: ``(scored, visible)`` key
    rows of one layer's :func:`_cached_attention` call — the rows the program
    scores under :func:`cached_key_block`'s rule, and the rows some query of
    the block can see."""
    block = cached_key_block(score_rows, L)
    first, last = cached_key_extent(cache_pos, S, L, block, sliding_window, lib=np)
    lo = 0 if sliding_window is None else max(cache_pos - sliding_window + 1, 0)
    return int(last - first) * block, min(cache_pos + S, L) - lo


def _cached_attention(q, k_all, v_all, cache_pos, n_rep: int, sliding_window=None,
                      sm_scale=None, logit_softcap=None, alibi_slopes=None):
    """Attention of q [B, S, H, hd] against the linear cache [B, L, n_kv, hd].

    Valid keys are those at global index <= cache_pos + (local query index):
    one mask expression covers both prefill (S = prompt, cache_pos = 0, the
    ordinary causal triangle) and decode (S = 1, cache_pos = t, attend to
    everything written so far).

    Where :func:`cached_key_block` gives one block (decode, speculative
    verify, short caches) every row of the cache is scored and the mask
    discards what no query sees (future slots hold zeros or a previous
    occupant's rows). Else — a prefill chunk against a long view — only the
    key blocks :func:`cached_key_extent` names are read at all
    (:func:`_bounded_cached_attention`): the cost follows the rows the
    queries can see, not L. Same mathematics: a row outside the extent is one
    whose softmax weight the mask makes exactly 0.

    GQA is a *grouped* einsum — queries reshape to [B, S, n_kv, rep, hd] and
    contract directly against the unrepeated cache, so per-token HBM traffic
    scales with n_kv, never with a materialized n_q-wide K/V copy.
    """
    B, S, H, _ = q.shape
    L = k_all.shape[1]
    block = cached_key_block(B * H * S, L)
    if block < L:
        return _bounded_cached_attention(
            q, k_all, v_all, cache_pos, n_rep, block, sliding_window=sliding_window,
            sm_scale=sm_scale, logit_softcap=logit_softcap, alibi_slopes=alibi_slopes)
    q_pos = cache_pos + jnp.arange(S, dtype=jnp.int32)
    k_pos = jnp.arange(L, dtype=jnp.int32)[None, :]
    mask = k_pos <= q_pos[:, None]
    if sliding_window is not None:
        mask &= k_pos > q_pos[:, None] - sliding_window
    return _grouped_cached_attention(q, k_all, v_all, mask[None], n_rep,
                                     sm_scale=sm_scale, logit_softcap=logit_softcap,
                                     alibi_slopes=alibi_slopes, k_positions=k_pos[0])


def _bounded_cached_attention(q, k_all, v_all, cache_pos, n_rep: int, block: int,
                              sliding_window=None, sm_scale=None, logit_softcap=None,
                              alibi_slopes=None):
    """:func:`_cached_attention` over the key blocks the queries can see: a
    loop whose trip count follows ``cache_pos`` (traced: one program for every
    offset; forward only), each step scoring ``block`` key rows in float32 as
    :func:`_grouped_cached_attention` scores all of them, under a running
    maximum and a running sum. A block that overhangs ``L`` is pulled back
    inside and the rows the step before it scored are masked."""
    from ..ops.attention import softcap_logits

    B, S, H, hd = q.shape
    L = k_all.shape[1]
    G = H // n_rep
    scale = hd**-0.5 if sm_scale is None else sm_scale
    qg = (q * scale).astype(jnp.float32).reshape(B, S, G, n_rep, hd)
    q_pos = (cache_pos + jnp.arange(S, dtype=jnp.int32))[:, None]
    first, last = cached_key_extent(cache_pos, S, L, block, sliding_window)
    if alibi_slopes is not None:
        slopes = alibi_slopes.astype(jnp.float32).reshape(G, n_rep)[None, :, :, None, None]

    def one_block(j, carry):
        m, l, acc = carry
        start = jnp.minimum(j * block, L - block)
        k_blk = jax.lax.dynamic_slice_in_dim(k_all, start, block, axis=1)
        v_blk = jax.lax.dynamic_slice_in_dim(v_all, start, block, axis=1)
        k_pos = (start + jnp.arange(block, dtype=jnp.int32))[None, :]
        logits = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k_blk.astype(jnp.float32))
        logits = softcap_logits(logits, logit_softcap)
        if alibi_slopes is not None:
            logits = logits + slopes * k_pos.astype(jnp.float32)
        mask = (k_pos >= j * block) & (k_pos <= q_pos)
        if sliding_window is not None:
            mask &= k_pos > q_pos - sliding_window
        logits = jnp.where(mask, logits, -1e30)
        # A row wholly masked so far carries weight exp(0) per masked key;
        # the first visible key (its own, at the latest) rescales that by
        # exp(-1e30 - m) = 0 exactly.
        m_new = jnp.maximum(m, logits.max(-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(logits - m_new)
        l = l * alpha + p.sum(-1, keepdims=True)
        acc = acc * alpha + jnp.einsum("bgrqk,bkgd->bgrqd", p, v_blk.astype(jnp.float32))
        return m_new, l, acc

    stat = jnp.zeros((B, G, n_rep, S, 1), jnp.float32)
    _, l, acc = jax.lax.fori_loop(
        first, last, one_block,
        (stat - 1e30, stat, jnp.zeros((B, G, n_rep, S, hd), jnp.float32)))
    out = jnp.moveaxis(acc / l, 3, 1)                            # [B, S, G, rep, hd]
    return out.reshape(B, S, H, hd).astype(q.dtype)


def _ring_cached_attention(q, cache, cache_pos, n_rep: int, window: int,
                           sm_scale=None, logit_softcap=None, alibi_slopes=None):
    """Ring-cache decode: validity comes from the per-slot ``pos`` buffer —
    a slot is visible iff it has been written (pos >= 0), is not in the
    query's future, and lies inside the window."""
    S = q.shape[1]
    q_pos = cache_pos + jnp.arange(S, dtype=jnp.int32)          # [S]
    slot_pos = cache["pos"]                                     # [B, W]
    mask = (
        (slot_pos[:, None, :] >= 0)
        & (slot_pos[:, None, :] <= q_pos[None, :, None])
        & (slot_pos[:, None, :] > q_pos[None, :, None] - window)
    )  # [B, S, W]
    return _grouped_cached_attention(q, cache["k"], cache["v"], mask, n_rep,
                                     sm_scale=sm_scale, logit_softcap=logit_softcap,
                                     alibi_slopes=alibi_slopes, k_positions=slot_pos)


def _grouped_cached_attention(q, k_all, v_all, mask, n_rep: int,
                              sm_scale=None, logit_softcap=None,
                              alibi_slopes=None, k_positions=None):
    """Shared cached-attention core: q [B, S, H, hd] against [B, L, n_kv, hd]
    with a caller-built validity mask [B or 1, S, L]. GQA is a *grouped*
    einsum — queries reshape to [B, S, n_kv, rep, hd] and contract directly
    against the unrepeated cache, so per-token HBM traffic scales with n_kv,
    never with a materialized n_q-wide K/V copy.

    ``alibi_slopes`` [H] adds BLOOM-style position bias slope_h * key_pos
    (``k_positions`` [L] or [B, L] — absolute stored positions; softmax is
    per-row shift-invariant, so this equals the relative slope*(j-i) form).
    """
    from ..ops.attention import softcap_logits

    B, S, H, hd = q.shape
    scale = hd**-0.5 if sm_scale is None else sm_scale
    qg = (q * scale).astype(jnp.float32).reshape(B, S, H // n_rep, n_rep, hd)
    logits = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k_all.astype(jnp.float32))
    logits = softcap_logits(logits, logit_softcap)
    if alibi_slopes is not None:
        sl = alibi_slopes.astype(jnp.float32).reshape(H // n_rep, n_rep)
        kp = k_positions.astype(jnp.float32)
        kp = kp[None, None, None, None, :] if kp.ndim == 1 else kp[:, None, None, None, :]
        logits = logits + sl[None, :, :, None, None] * kp
    # logits: [B, G, rep, S, L] <- mask broadcast over the two head dims.
    logits = jnp.where(mask[:, None, None, :, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bgrqk,bkgd->bqgrd", probs, v_all.astype(jnp.float32))
    return out.reshape(B, S, H, hd).astype(q.dtype)


def update_kv_cache_and_attend(cache, q, k, v, cache_pos, n_rep: int, sliding_window=None,
                               sm_scale=None, logit_softcap=None, alibi_slopes=None):
    """Write this call's K/V into the cache at ``cache_pos`` and attend q
    against what it can see of the buffer: on a linear cache the rows up to
    the last query's own, from the window's start on a windowed layer
    (:func:`_cached_attention` — every row under the mask where the call is
    one key block, only the visible key blocks where it is several). Shared
    by every cached attention (Llama, GPT-2, ...).
    Returns (out [B,S,H,hd], new_cache).

    Ring caches (``"pos"`` present — sliding-window layers) write slot
    ``pos % capacity``. Multi-token writes at ANY position (initial prefill,
    chunked prefill, speculative verification) attend the pre-write ring
    contents concatenated with the chunk, masked by per-slot positions;
    single-token decode writes one slot and attends the ring alone.

    A :class:`PagedCache` (the serving engine's plain decode tick) is not
    written here at all: the token's row is scored beside the pool's rows
    and returned as the cache, for the engine to write
    (:func:`_paged_kv_attend`)."""
    if isinstance(cache, PagedCache):
        return _paged_kv_attend(cache, q, k, v, cache_pos, n_rep, sliding_window,
                                sm_scale, logit_softcap, alibi_slopes)
    if "pos" not in cache:
        start = (0, cache_pos, 0, 0)
        with program_part("kv_write"):
            new_cache = {
                "k": jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype), start),
                "v": jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype), start),
            }
        with program_part("kv_attn"):
            out = _cached_attention(q, new_cache["k"], new_cache["v"], cache_pos, n_rep,
                                    sliding_window=sliding_window, sm_scale=sm_scale,
                                    logit_softcap=logit_softcap, alibi_slopes=alibi_slopes)
        return out, new_cache

    window = cache["k"].shape[1]
    B, S = q.shape[0], q.shape[1]
    if S > 1:
        # Multi-token write (prefill OR chunked prefill / speculative
        # verification at any cache_pos): attend against the PRE-WRITE ring
        # contents concatenated with the chunk itself. Ring slots hold
        # positions < cache_pos and the chunk holds [cache_pos, cache_pos+S),
        # so there are no duplicates; the per-position mask handles
        # never-written (-1) and out-of-window slots uniformly. On the
        # empty-ring initial prefill every ring slot is masked and this
        # degenerates to windowed causal attention over the chunk.
        eff_window = min(sliding_window or window, window)
        k_comb = jnp.concatenate([cache["k"], k.astype(cache["k"].dtype)], axis=1)
        v_comb = jnp.concatenate([cache["v"], v.astype(cache["v"].dtype)], axis=1)
        chunk_pos = cache_pos + jnp.arange(S, dtype=jnp.int32)       # [S]
        pos_comb = jnp.concatenate(
            [cache["pos"], jnp.broadcast_to(chunk_pos, (B, S))], axis=1)  # [B, W+S]
        q_pos = chunk_pos
        # Ring slots are valid only for positions strictly BEFORE the chunk:
        # a previous multi-token write may have left stale entries at
        # positions this chunk covers (speculative overshoot) — the chunk
        # segment supersedes them, and without this bound the same position
        # would be attended twice (once stale, once fresh).
        seg_valid = jnp.concatenate(
            [cache["pos"] < cache_pos, jnp.ones((B, S), bool)], axis=1)
        mask = (
            seg_valid[:, None, :]
            & (pos_comb[:, None, :] >= 0)
            & (pos_comb[:, None, :] <= q_pos[None, :, None])
            & (pos_comb[:, None, :] > q_pos[None, :, None] - eff_window)
        )  # [B, S, W+S]
        with program_part("kv_attn"):
            out = _grouped_cached_attention(q, k_comb, v_comb, mask, n_rep,
                                            sm_scale=sm_scale, logit_softcap=logit_softcap,
                                            alibi_slopes=alibi_slopes, k_positions=pos_comb)
        # Scatter the last `window` entries (unique slots) into the ring.
        take = min(S, window)
        idx = cache_pos + jnp.arange(S - take, S, dtype=jnp.int32)   # global positions
        slots = idx % window
        with program_part("kv_write"):
            new_cache = {
                "k": cache["k"].at[:, slots].set(k[:, S - take:].astype(cache["k"].dtype)),
                "v": cache["v"].at[:, slots].set(v[:, S - take:].astype(cache["v"].dtype)),
                "pos": cache["pos"].at[:, slots].set(jnp.broadcast_to(idx, (B, take))),
            }
        return out, new_cache

    slot = jax.lax.rem(cache_pos, window)
    with program_part("kv_write"):
        new_cache = {
            "k": jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                              (0, slot, 0, 0)),
            "v": jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                              (0, slot, 0, 0)),
            "pos": jax.lax.dynamic_update_slice(
                cache["pos"], jnp.broadcast_to(cache_pos, (B, 1)).astype(jnp.int32), (0, slot)),
        }
    with program_part("kv_attn"):
        out = _ring_cached_attention(q, new_cache, cache_pos, n_rep,
                                     window=min(sliding_window or window, window),
                                     sm_scale=sm_scale, logit_softcap=logit_softcap,
                                     alibi_slopes=alibi_slopes)
    return out, new_cache


def init_latent_cache(num_layers: int, batch_size: int, max_len: int, rank: int, rope_dim: int,
                      dtype=jnp.bfloat16):
    """Per-layer LATENT cache: a tuple of ``{"latent": [B, max_len, rank],
    "rope": [B, max_len, rope_dim]}`` — a token's normed joint latent of keys
    and values and its rotated decoupled key, shared by every head: no head
    axis, always linear. Two leaves, not one row of ``rank + rope_dim``: a
    product contracts over ``rank`` (a whole number of 128-lane tiles) and
    the values ARE the latent leaf, where one 576-wide row cost a decode
    tick a transposed copy and a sliced copy of every lane's view a layer
    (2.3 ms of 7 on a v5e at 32 x 8192 rows; PERF.md section 6, PR 33)."""
    return tuple({"latent": jnp.zeros((batch_size, max_len, rank), dtype),
                  "rope": jnp.zeros((batch_size, max_len, rope_dim), dtype)}
                 for _ in range(num_layers))


def latent_attention_form(S: int, rank: int, nope_dim: int, v_dim: int) -> str:
    """``"absorbed"`` or ``"expanded"``: the cheaper way to attend ``S``
    queries a head against a latent cache, from the call's static shape alone
    (as :func:`cached_key_block` is: no option, no flag).

    A key row costs, for each head, ``S * (2 (rank + rope) + 2 rank)``
    multiply-adds absorbed (scores and the weighted sum live in the latent
    space) and ``2 rank (nope + v) + S * (2 (nope + rope) + 2 v)`` expanded
    (the row's per-head key and value are made first, once for all ``S``
    queries). Expanding wins once the queries that share a row outnumber
    ``2 rank (nope + v) / (4 rank - 2 nope - 2 v)``: 171 at rank 512 and
    heads of 128 + 128, so one token a slot (a tick) and a speculative
    verify are absorbed and a 256-token chunk is expanded. The v5e sweep
    that checked the rule is in PERF.md section 6 (PR 33)."""
    saved = 4 * rank - 2 * nope_dim - 2 * v_dim        # a query's saving on an expanded row
    if saved <= 0:
        return "absorbed"
    return "expanded" if S * saved > 2 * rank * (nope_dim + v_dim) else "absorbed"


def _latent_cached_attention(q_nope, q_rope, latent, k_rope, w_uk, w_uv, cache_pos, sm_scale):
    """Attention of ``S`` queries a head against the latent cache (``latent
    [B, L, rank]``, ``k_rope [B, L, rope]``): ``q_nope [B, S, H, nope]``,
    ``q_rope [B, S, H, rope]`` (rotated), ``w_uk [rank, H, nope]`` / ``w_uv
    [rank, H, v]`` the key and value halves of the up-projection. Returns
    ``[B, S, H, v]``.

    ``score(t, s) = (q_nope . (c_s W_uk) + q_rope . k_r(s)) * sm_scale`` and
    ``o = sum_s p(t, s) (c_s W_uv)`` in either form; absorbed folds ``W_uk``
    into the query and applies ``W_uv`` after the weighted sum of latents
    (:func:`latent_attention_form`). Key rows are read as
    :func:`_cached_attention` reads them: one block under the mask where
    :func:`cached_key_block` gives one, else only the blocks
    :func:`cached_key_extent` names, under a running maximum and sum.
    Products take their operands in the wider of the queries' and the
    cache's types and accumulate in float32."""
    B, S, H, _ = q_nope.shape
    L, rank = latent.shape[1], w_uk.shape[0]
    out_dtype = q_nope.dtype
    cdt = jnp.promote_types(out_dtype, latent.dtype)
    w_uk, w_uv = w_uk.astype(cdt), w_uv.astype(cdt)
    f32 = dict(preferred_element_type=jnp.float32)
    absorbed = latent_attention_form(S, rank, w_uk.shape[-1], w_uv.shape[-1]) == "absorbed"
    q_rope = (q_rope * sm_scale).astype(cdt)
    if absorbed:
        q_nope = jnp.einsum("bshd,rhd->bshr", q_nope.astype(cdt), w_uk, **f32) * sm_scale
    else:
        q_nope = q_nope * sm_scale
    q_nope = q_nope.astype(cdt)                        # absorbed: a query in the latent space

    def scores_and_values(c, k_r):
        """c [B, K, rank], k_r [B, K, rope] -> (logits [B, H, S, K] f32, values)."""
        c, k_r = c.astype(cdt), k_r.astype(cdt)
        rope_part = jnp.einsum("bshd,bkd->bhsk", q_rope, k_r, **f32)
        if absorbed:
            return jnp.einsum("bshr,bkr->bhsk", q_nope, c, **f32) + rope_part, c
        k_nope = jnp.einsum("bkr,rhd->bkhd", c, w_uk, **f32).astype(cdt)
        logits = jnp.einsum("bshd,bkhd->bhsk", q_nope, k_nope, **f32) + rope_part
        return logits, jnp.einsum("bkr,rhd->bkhd", c, w_uv, **f32).astype(cdt)

    def weigh(p, values):
        """p [B, H, S, K] f32 -> [B, H, S, rank (absorbed) or v]."""
        spec = "bhsk,bkr->bhsr" if absorbed else "bhsk,bkhd->bhsd"
        return jnp.einsum(spec, p.astype(cdt), values, **f32)

    q_pos = (cache_pos + jnp.arange(S, dtype=jnp.int32))[:, None]
    block = cached_key_block(B * H * S, L)
    if block >= L:
        logits, values = scores_and_values(latent, k_rope)
        mask = jnp.arange(L, dtype=jnp.int32)[None, :] <= q_pos
        out = weigh(jax.nn.softmax(jnp.where(mask, logits, -1e30), axis=-1), values)
    else:
        first, last = cached_key_extent(cache_pos, S, L, block)

        def one_block(j, carry):
            m, l, acc = carry
            start = jnp.minimum(j * block, L - block)
            logits, values = scores_and_values(
                jax.lax.dynamic_slice_in_dim(latent, start, block, axis=1),
                jax.lax.dynamic_slice_in_dim(k_rope, start, block, axis=1))
            k_pos = (start + jnp.arange(block, dtype=jnp.int32))[None, :]
            logits = jnp.where((k_pos >= j * block) & (k_pos <= q_pos), logits, -1e30)
            m_new = jnp.maximum(m, logits.max(-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(logits - m_new)
            return m_new, l * alpha + p.sum(-1, keepdims=True), acc * alpha + weigh(p, values)

        stat = jnp.zeros((B, H, S, 1), jnp.float32)
        width = rank if absorbed else w_uv.shape[-1]
        _, l, acc = jax.lax.fori_loop(
            first, last, one_block,
            (stat - 1e30, stat, jnp.zeros((B, H, S, width), jnp.float32)))
        out = acc / l
    if absorbed:
        out = jnp.einsum("bhsr,rhd->bhsd", out.astype(cdt), w_uv, **f32)
    return jnp.moveaxis(out, 1, 2).astype(out_dtype)                      # [B, S, H, v]


def update_latent_cache_and_attend(cache, q_nope, q_rope, c_kv, k_rope, w_uk, w_uv, cache_pos,
                                   sm_scale: float):
    """Write this call's latents ``[B, S, rank]`` and rotated keys ``[B, S,
    rope]`` into the cache at ``cache_pos`` and attend the queries against
    what they can see of it (:func:`_latent_cached_attention`). Returns
    ``(out [B, S, H, v], new_cache)``: the latent twin of
    :func:`update_kv_cache_and_attend`, a :class:`PagedCache` included
    (:func:`_paged_latent_attend`)."""
    if isinstance(cache, PagedCache):
        return _paged_latent_attend(cache, q_nope, q_rope, c_kv, k_rope, w_uk, w_uv,
                                    cache_pos, sm_scale)
    with program_part("kv_write"):
        new_cache = {
            "latent": jax.lax.dynamic_update_slice(
                cache["latent"], c_kv.astype(cache["latent"].dtype), (0, cache_pos, 0)),
            "rope": jax.lax.dynamic_update_slice(
                cache["rope"], k_rope.astype(cache["rope"].dtype), (0, cache_pos, 0)),
        }
    with program_part("kv_attn"):
        out = _latent_cached_attention(q_nope, q_rope, new_cache["latent"], new_cache["rope"],
                                       w_uk, w_uv, cache_pos, sm_scale)
    return out, new_cache


class ServedForm(NamedTuple):
    """What a family's ``served_form()`` hands the serving engine: the form in
    which its weights are held on the device while they are served, where that
    is not the published one (the layout checkpoints, ``generate`` and the
    trainer read). ``to_served`` and ``to_published`` are pure functions
    ``params -> params``, each the other's inverse bit for bit; a leaf they do
    not move comes back as the same object, and every array of a leaf that is
    itself a pytree (an int8 kernel with its scales) moves alike. ``module``
    reads the served tree and computes what the family's own module computes
    on the published one. The engine runs ``to_served`` once, at load
    (serving/engine.py ``_hold_in_served_form``); a family without the hook
    is served as published."""
    module: Any
    to_served: Callable
    to_published: Callable


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["pool", "scales", "pages", "live"],
                   meta_fields=["dtype", "sharded"])
@dataclasses.dataclass(frozen=True)
class PagedCache:
    """One layer's cache as the serving engine's plain decode tick hands it
    to a lane of its vmapped batch-1 forward. Shared by all lanes: the page
    pool itself (``pool``: leaf name -> ``[pages, 1, P, ...]``, page 0 the
    scratch page) and, for an int8 pool, its pages' scales (``scales``: leaf
    name -> ``[pages]`` float32; else None). The lane's own: its row of the
    page table (``pages [Np]`` pool ids, 0 where nothing is allocated) and
    whether a stream runs in it (``live``). ``dtype`` (static) is what an
    int8 pool's rows widen to; None reads each leaf in its own type.
    ``sharded`` (static) says that a mesh axis splits the pool's leaves, which
    a trace cannot see and a Mosaic kernel cannot be partitioned over.

    The two ``update_*_cache_and_attend`` functions read such a cache in
    place (:func:`_attend_work_list`) and return the token's new row, not
    a cache: nothing here is written."""
    pool: dict
    scales: Optional[dict]
    pages: Any
    live: Any
    dtype: Any = None
    sharded: bool = False

    def row_dtype(self, name: str):
        return self.pool[name].dtype if self.dtype is None else self.dtype


#: What one step of the tick's work list may hold in float32 scores (heads x
#: items x rows). A step has a fixed cost, so fewer, larger steps win until
#: the step's gathered pages, scores and weighted values no longer fit a v5e
#: core's VMEM together: 128 heads at 256 x 64, 512 x 32 and 1024 x 16 (8 MiB)
#: read within 7 % of each other, 1024 x 64 (32 MiB) costs four times as much
#: (the sweep is in PERF.md section 6, PR 34).
_TICK_SCORE_BYTES = 8 * 2**20


#: The largest slice the TPU's gather takes whole. Asked for more (a page of
#: 256 rows x 1280 values is 640 KiB), the compiler halves the OPERAND: the
#: whole pool leaf copied in two parts in every step that gathers from it
#: (0.9 ms a copy of a 294 MB leaf, 71 ms of a 161 ms tick and 16 ms of a 42 ms
#: chunk: PERF.md section 6, PR 35). A page of 256 rows x 1024 values, 512 KiB,
#: is gathered whole.
_GATHER_SLICE_BYTES = 512 * 1024


def gather_pages(leaf, ids):
    """``leaf[ids]`` for a pool leaf ``[pages, 1, P, ...]`` and page ids of
    any shape -> ``[*ids.shape, 1, P, ...]``, gathered in row-parts of at
    most ``_GATHER_SLICE_BYTES`` each where a page is larger: the leaf read
    as ``[pages * parts, 1, P / parts, ...]`` (the same bytes in place) and
    each id as its ``parts`` part ids. A page that fits is one plain gather."""
    P = leaf.shape[2]
    page_bytes = math.prod(leaf.shape[1:]) * leaf.dtype.itemsize
    parts = 1
    while page_bytes > parts * _GATHER_SLICE_BYTES and P % (2 * parts) == 0:
        parts *= 2
    if parts == 1:
        return leaf[ids]
    split = leaf.reshape((leaf.shape[0] * parts, 1, P // parts) + leaf.shape[3:])
    rows = split[ids[..., None] * parts + jnp.arange(parts, dtype=ids.dtype)]
    return rows.reshape(ids.shape + leaf.shape[1:])


def tick_key_tiles(score_heads: int, lanes: int, L: int, page: int) -> tuple:
    """``(block, group)`` of the decode tick's work list, from the static
    shape alone (as :func:`cached_key_block`: no option, no flag): key rows
    an item covers — a whole number of pages, 512 rows where the page allows
    it — and items a step scores together: the largest power of two whose
    ``score_heads x group x block`` float32 scores fit ``_TICK_SCORE_BYTES``,
    at most the list's capacity ``lanes x ceil(L / block)``."""
    block = min(page * max(1, 512 // page), L)
    capacity = lanes * -(-L // block)
    group = 1
    while 2 * group * block * score_heads * 4 <= _TICK_SCORE_BYTES and 2 * group <= capacity:
        group *= 2
    return block, group


def tick_key_extent(pos, live, L: int, block: int, sliding_window=None, lib=jnp):
    """``(first, count)`` per lane: the key blocks of width ``block`` that
    hold pool rows one query at ``pos`` can see — the rows before its own,
    which is in no page yet (:func:`cached_key_extent` at no query rows) —
    and none for a lane no stream runs in, whatever its stale ``pos``.
    Traced in the tick (``lib=jnp``), counted on the host by the serving
    engine (``lib=np``)."""
    first, last = cached_key_extent(pos, 0, L, block, sliding_window, lib=lib)
    return lib.broadcast_to(first, lib.shape(last)), lib.where(live, last - first, 0)


def _attend_work_list(score, weigh, m, acc, pool, scales, table, pos, live, *,
                      sliding_window=None, dtype=None):
    """Softmax attention of one query a lane over the pool rows its stream
    holds, for all ``S`` lanes of a decode tick at once. The work is laid out
    across lanes: every running lane's key blocks (:func:`tick_key_extent`)
    are counted, summed cumulatively and so numbered into one list of live
    ``(slot, block)`` items — capacity ``S x ceil(L / block)``, the live count
    ``T`` traced. A loop of ``ceil(T / group)`` steps (traced: one program
    for every mix of positions) gathers a step's items' pages from the pool
    (an int8 pool's widen by their scales in the same step), scores them and
    merges each item's (max, sum, weighted values) into its slot's running
    triple; several items of a step may belong to one slot, so the merge is
    a segment reduction over ``[S, group]``, in float32. No lane's view is
    built; a row outside the list is one the mask gives weight 0 exactly.

    ``score(slot [G], blocks, k_pos [G, K]) -> [G, *heads, K]`` float32
    logits of the rows ``blocks`` (leaf name -> ``[G, K, ...]``) against the
    queries of lanes ``slot``; ``weigh(p [G, *heads, K], blocks) -> [G,
    *heads, width]``. ``m [S, *heads]`` / ``acc [S, *heads, width]`` start
    the running triple: the token's own row, scored by the caller (its sum
    is 1), so every lane, idle ones too, ends with a finite value. ``table
    [S, Np]``, ``pos [S]``, ``live [S]``. Returns ``acc / sum``."""
    S, Np = table.shape
    names = sorted(pool)
    P = pool[names[0]].shape[2]
    L = Np * P
    block, G = tick_key_tiles(m[0].size, S, L, P)
    bp = block // P
    first, count = tick_key_extent(pos, live, L, block, sliding_window)
    ends = jnp.cumsum(count)
    T = ends[-1]
    heads = (1,) * (m.ndim - 1)
    lanes = jnp.arange(S, dtype=jnp.int32)

    def one_step(t, carry):
        m, l, acc = carry
        idx = t * G + jnp.arange(G, dtype=jnp.int32)
        valid = idx < T
        item = jnp.minimum(idx, T - 1)          # past the list: its last item again, masked
        slot = (item[:, None] >= ends[None, :]).sum(-1).astype(jnp.int32)
        blk = first[slot] + item - (ends[slot] - count[slot])
        page = blk[:, None] * bp + jnp.arange(bp, dtype=jnp.int32)
        ids = jnp.where(page < Np, table[slot[:, None], jnp.minimum(page, Np - 1)], 0)
        blocks = {}
        for name in names:
            rows = gather_pages(pool[name], ids)                     # [G, bp, 1, P, ...]
            if scales is not None:
                s = scales[name][ids].reshape(ids.shape + (1,) * (rows.ndim - 2))
                rows = (rows.astype(jnp.float32) * s).astype(dtype)
            blocks[name] = rows.reshape((G, block) + rows.shape[4:])
        k_pos = blk[:, None] * block + jnp.arange(block, dtype=jnp.int32)
        q_pos = pos[slot][:, None]
        mask = valid[:, None] & (k_pos < q_pos)
        if sliding_window is not None:
            mask &= k_pos > q_pos - sliding_window
        logits = score(slot, blocks, k_pos)
        logits = jnp.where(mask.reshape((G,) + heads + (block,)), logits, -1e30)
        m_i = logits.max(-1)
        p = jnp.exp(logits - m_i[..., None])
        # An item wholly masked (past the list) carries weight exp(0) a row
        # under its own maximum; against its slot's, which is finite (the
        # token's own row), that is exp(-1e30 - m) = 0 exactly.
        mine = ((slot[None, :] == lanes[:, None]) & valid[None, :])  # [S, G]
        m_new = jnp.maximum(m, jnp.where(mine.reshape((S, G) + heads), m_i[None], -1e30).max(1))
        w = jnp.exp(m_i - m_new[slot])
        alpha = jnp.exp(m - m_new)
        seg = dict(precision=jax.lax.Precision.HIGHEST)
        mine = mine.astype(jnp.float32)
        l = l * alpha + jnp.tensordot(mine, w * p.sum(-1), 1, **seg)
        acc = acc * alpha[..., None] + jnp.tensordot(mine, w[..., None] * weigh(p, blocks), 1,
                                                      **seg)
        return m_new, l, acc

    _, l, acc = jax.lax.fori_loop(0, (T + G - 1) // G, one_step, (m, jnp.ones_like(m), acc))
    return acc / l[..., None]


def _paged_attention(kind, lane, shared, cache: PagedCache, cache_pos, sliding_window=None):
    """:func:`_attend_work_list` over all lanes of the tick, from a batch-1
    forward (:func:`_over_the_ticks_lanes`). ``lane`` holds what is a lane's
    own (queries, the token's row), ``shared`` what all lanes share besides
    the pool; ``kind(lane, shared, pos)`` -> ``(score, weigh, m, acc)`` over
    a leading lane axis."""

    def over_lanes(lane, shared, pool, scales, table, pos, live):
        score, weigh, m, acc = kind(lane, shared, pos)
        return _attend_work_list(score, weigh, m, acc, pool, scales, table, pos, live,
                                 sliding_window=sliding_window, dtype=cache.dtype)

    return _over_the_ticks_lanes(over_lanes, lane, shared, cache, cache_pos)


def _over_the_ticks_lanes(over_lanes, lane, shared, cache: PagedCache, cache_pos):
    """The bridge between the tick's ``jax.vmap`` over batch-1 forwards and
    an attention over all lanes at once: one function whose batching rule IS
    ``over_lanes(lane, shared, pool, scales, table, pos, live)`` over a
    leading lane axis (``jax.custom_batching.custom_vmap``), so the model
    code stays the one batch-1 path. Unbatched, it runs the same code with
    one lane."""

    @jax.custom_batching.custom_vmap
    def attend(lane, shared, pool, scales, pages, pos, live):
        one = jax.tree.map(lambda x: x[None], (lane, pages, pos, live))
        return over_lanes(one[0], shared, pool, scales, *one[1:])[0]

    @attend.def_vmap
    def batched(axis_size, in_batched, lane, shared, pool, scales, pages, pos, live):
        if any(jax.tree.leaves(in_batched[1:4])):
            raise NotImplementedError("a paged cache's pool is shared by every lane of a vmap")
        lane, pages, pos, live = jax.tree.map(
            lambda x, b: x if b else jnp.broadcast_to(x, (axis_size,) + jnp.shape(x)),
            (lane, pages, pos, live), (in_batched[0],) + tuple(in_batched[4:]))
        return over_lanes(lane, shared, pool, scales, pages, pos, live), True

    with program_part("kv_attn"):
        return attend(lane, shared, cache.pool, cache.scales, cache.pages,
                      jnp.asarray(cache_pos, jnp.int32), cache.live)


def _paged_kv_attend(cache: PagedCache, q, k, v, cache_pos, n_rep: int, sliding_window=None,
                     sm_scale=None, logit_softcap=None, alibi_slopes=None):
    """:func:`update_kv_cache_and_attend` for one token of one lane against a
    :class:`PagedCache`: scores as :func:`_grouped_cached_attention` scores a
    view (grouped einsum in float32, softcap, ALiBi, the window's mask), over
    the pool's rows in place. Returns ``(out [1, 1, H, hd], {"k", "v"}: the
    token's row [1, 1, n_kv, hd] in the cache's type)``."""
    from ..ops.attention import softcap_logits

    B, S, H, hd = q.shape
    if (B, S) != (1, 1):
        raise NotImplementedError(f"a paged cache takes one token of one stream a call, got {q.shape}")
    scale = hd**-0.5 if sm_scale is None else sm_scale
    if cache.pool["k"].ndim == 4:
        return _paged_flat_kv_attend(cache, q, k, v, cache_pos, n_rep, scale, sliding_window)
    row = {"k": k.astype(cache.row_dtype("k")), "v": v.astype(cache.row_dtype("v"))}
    slopes = None if alibi_slopes is None else alibi_slopes.astype(jnp.float32).reshape(
        H // n_rep, n_rep)

    def kind(lane, slopes, pos):
        qg, k_own, v_own = lane                                      # [S, G, rep, hd], [S, G, hd] x 2

        def score(slot, blocks, k_pos):
            logits = jnp.einsum("igrd,ikgd->igrk", qg[slot], blocks["k"].astype(jnp.float32))
            logits = softcap_logits(logits, logit_softcap)
            if slopes is not None:
                logits = logits + slopes[None, :, :, None] * k_pos.astype(jnp.float32)[:, None, None]
            return logits

        def weigh(p, blocks):
            return jnp.einsum("igrk,ikgd->igrd", p, blocks["v"].astype(jnp.float32))

        lanes = jnp.arange(qg.shape[0], dtype=jnp.int32)
        m = score(lanes, {"k": k_own[:, None]}, pos[:, None])[..., 0]
        acc = jnp.broadcast_to(v_own.astype(jnp.float32)[:, :, None], qg.shape)
        return score, weigh, m, acc

    qg = (q * scale).astype(jnp.float32).reshape(H // n_rep, n_rep, hd)
    out = _paged_attention(kind, (qg, row["k"][0, 0], row["v"][0, 0]), slopes, cache, cache_pos,
                           sliding_window)
    return out.reshape(1, 1, H, hd).astype(q.dtype), row


def _paged_flat_kv_attend(cache: PagedCache, q, k, v, cache_pos, n_rep: int, scale: float,
                          sliding_window=None):
    """:func:`_paged_kv_attend` over a pool whose leaves keep a token's heads
    side by side in ONE row (``[pages, 1, P, G * hd]``: rows of whole lanes
    however many heads there are; with a head axis of 10 or 20 the TPU's
    compiler re-laid the whole pool out in every attention of a tick). A
    step's gathered rows are multiplied AS THEY LIE: each query is placed in
    its key head's columns of a ``G * hd``-wide row of zeros (a zero adds
    nothing to a score), the weighted sum is taken over the whole row and a
    head keeps its own columns of it. That is ``G`` times the arithmetic of
    the per-head products, which a tick has to spare, and no copy of the
    rows into a head-major layout, which it has not. Products in the wider
    of the queries' and the rows' types, accumulated in float32 (as the
    latent form). Returns ``(out [1, 1, H, hd], {"k", "v"}: the token's row
    [1, 1, G * hd])``.

    On the TPU, over a pool without scales that no mesh axis shards
    (``ops.paged_attention.paged_attention_available``: what the code can
    see, no option), the pool's rows are read by ONE Mosaic kernel call
    instead of that list's loop: each live page fetched once for both
    products, multiplied as it lies in VMEM (:func:`_paged_flat_kernel`).
    The list is that kernel's reference, and what every other backend runs."""
    from ..ops.paged_attention import paged_attention_available

    _, _, H, hd = q.shape
    G = H // n_rep
    row = {"k": k.astype(cache.row_dtype("k")).reshape(1, 1, G * hd),
           "v": v.astype(cache.row_dtype("v")).reshape(1, 1, G * hd)}
    cdt = jnp.promote_types(q.dtype, row["k"].dtype)
    f32 = dict(preferred_element_type=jnp.float32)
    if paged_attention_available(cache.pool, cache.scales, cache.sharded):
        lane = ((q[0, 0] * scale).astype(cdt), row["k"][0, 0], row["v"][0, 0])
        out = _over_the_ticks_lanes(
            functools.partial(_paged_flat_kernel, n_rep=n_rep, sliding_window=sliding_window),
            lane, None, cache, cache_pos)
        return out.reshape(1, 1, H, hd).astype(q.dtype), row
    own = jnp.eye(G, dtype=bool)[:, None, :, None]                   # head g keeps columns g

    def kind(lane, shared, pos):
        q_rows, k_own, v_own = lane                                  # [S, G, rep, G * hd], [S, G * hd] x 2

        def score(slot, blocks, k_pos):
            return jnp.einsum("igrc,ikc->igrk", q_rows[slot], blocks["k"].astype(cdt), **f32)

        def weigh(p, blocks):
            rows = jnp.einsum("igrk,ikc->igrc", p.astype(cdt), blocks["v"].astype(cdt), **f32)
            rows = rows.reshape(rows.shape[:3] + (G, hd))
            return jnp.where(own, rows, 0.0).sum(3)

        lanes = jnp.arange(q_rows.shape[0], dtype=jnp.int32)
        m = score(lanes, {"k": k_own[:, None]}, pos[:, None])[..., 0]
        acc = jnp.broadcast_to(v_own.astype(jnp.float32).reshape(-1, G, 1, hd),
                               q_rows.shape[:3] + (hd,))
        return score, weigh, m, acc

    qg = (q[0, 0] * scale).reshape(G, n_rep, 1, hd)
    q_rows = jnp.where(own, qg, jnp.zeros((), qg.dtype)).reshape(G, n_rep, G * hd).astype(cdt)
    out = _paged_attention(kind, (q_rows, row["k"][0, 0], row["v"][0, 0]), None, cache, cache_pos,
                           sliding_window)
    return out.reshape(1, 1, H, hd).astype(q.dtype), row


def _paged_flat_kernel(lane, shared, pool, scales, table, pos, live, *, n_rep: int,
                       sliding_window=None):
    """All lanes' attention over a flat-row pool through the Mosaic kernel
    (``ops/paged_attention.py``): the kernel's unnormalised (max, sum,
    weighted values) over the rows the pool holds, merged with the token's
    own row — in no page yet, scored here as the work list scores it — and
    normalised. ``lane`` = ``(q [S, H, hd] scaled, k_own, v_own [S, G *
    hd])``. Returns ``[S, H, hd]`` float32; a lane that holds no row (idle
    ones too) ends with its own value row."""
    from ..ops.paged_attention import paged_flat_attention

    del shared, scales
    q, k_own, v_own = lane
    S, H, hd = q.shape
    G = H // n_rep
    m_pool, l_pool, acc_pool = paged_flat_attention(
        q, pool["k"], pool["v"], table, pos, live, n_rep=n_rep, sliding_window=sliding_window)
    m_own = jnp.einsum("sgrd,sgd->sgr", q.reshape(S, G, n_rep, hd),
                       k_own.reshape(S, G, hd).astype(q.dtype),
                       preferred_element_type=jnp.float32).reshape(S, H)
    v_own = jnp.repeat(v_own.astype(jnp.float32).reshape(S, G, hd), n_rep, axis=1)
    m = jnp.maximum(m_own, m_pool)
    w_own, w_pool = jnp.exp(m_own - m), jnp.exp(m_pool - m)
    acc = w_own[..., None] * v_own + w_pool[..., None] * acc_pool
    return acc / (w_own + w_pool * l_pool)[..., None]


def attend_shared_kv_cache(cache, q, cache_pos, n_rep: int, row=None, sm_scale=None):
    """Attention of a layer that reads a cache entry ANOTHER layer owns, and
    writes nothing (a cross-decoder over one layer's K/V, models/phi4flash.py).
    ``cache`` is that entry as its owner left it in this call: a linear
    ``{"k", "v"}`` ``[B, L, n_kv, hd]`` that already holds the call's rows (a
    prefill chunk's view: the bounded blocks of :func:`_cached_attention`),
    or the serving tick's :class:`PagedCache` with ``row``, the owner's
    ``{"k", "v"}`` of this token ``[1, 1, n_kv, hd]`` — in no page yet, so the
    reader scores it beside the pool's rows exactly as the owner did
    (:func:`_paged_kv_attend`, the work list over the pool in place). Causal,
    no window. Returns ``out [B, S, H, hd]``."""
    if isinstance(cache, PagedCache):
        return _paged_kv_attend(cache, q, row["k"], row["v"], cache_pos, n_rep,
                                sm_scale=sm_scale)[0]
    with program_part("kv_attn"):
        return _cached_attention(q, cache["k"], cache["v"], cache_pos, n_rep, sm_scale=sm_scale)


def _paged_latent_attend(cache: PagedCache, q_nope, q_rope, c_kv, k_rope, w_uk, w_uv, cache_pos,
                         sm_scale: float):
    """:func:`update_latent_cache_and_attend` for one token of one lane
    against a :class:`PagedCache`, in the absorbed form (what
    :func:`latent_attention_form` gives one query a head): ``W_uk`` folded
    into the query and ``W_uv`` applied after the weighted sum of latents,
    as :func:`_latent_cached_attention` does, over the pool's rows in place.
    Returns ``(out [1, 1, H, v], {"latent", "rope"}: the token's row)``."""
    B, S, H, _ = q_nope.shape
    if (B, S) != (1, 1):
        raise NotImplementedError(
            f"a paged cache takes one token of one stream a call, got {q_nope.shape}")
    row = {"latent": c_kv.astype(cache.row_dtype("latent")),
           "rope": k_rope.astype(cache.row_dtype("rope"))}
    out_dtype = q_nope.dtype
    cdt = jnp.promote_types(out_dtype, row["latent"].dtype)
    w_uk, w_uv = w_uk.astype(cdt), w_uv.astype(cdt)
    f32 = dict(preferred_element_type=jnp.float32)

    def kind(lane, shared, pos):
        q_c, q_r, c_own, r_own = lane                                # [S, H, rank], [S, H, rope], [S, rank], [S, rope]

        def score(slot, blocks, k_pos):
            return (jnp.einsum("ihr,ikr->ihk", q_c[slot], blocks["latent"].astype(cdt), **f32)
                    + jnp.einsum("ihd,ikd->ihk", q_r[slot], blocks["rope"].astype(cdt), **f32))

        def weigh(p, blocks):
            return jnp.einsum("ihk,ikr->ihr", p.astype(cdt), blocks["latent"].astype(cdt), **f32)

        lanes = jnp.arange(q_c.shape[0], dtype=jnp.int32)
        m = score(lanes, {"latent": c_own[:, None], "rope": r_own[:, None]}, pos[:, None])[..., 0]
        acc = jnp.broadcast_to(c_own.astype(jnp.float32)[:, None], q_c.shape)
        return score, weigh, m, acc

    q_c = (jnp.einsum("hd,rhd->hr", q_nope[0, 0].astype(cdt), w_uk, **f32) * sm_scale).astype(cdt)
    q_r = (q_rope[0, 0] * sm_scale).astype(cdt)
    out = _paged_attention(kind, (q_c, q_r, row["latent"][0, 0], row["rope"][0, 0]), None,
                           cache, cache_pos)
    out = jnp.einsum("hr,rhd->hd", out.astype(cdt), w_uv, **f32)
    return out[None, None].astype(out_dtype), row


def _lora_delta(y, x, lora, name):
    """Add a gathered low-rank LoRA delta to a projection output.

    ``lora`` is a per-module dict of ``{"a","b","scale"}`` trees (or None).
    Membership is a *static* Python-dict lookup, so a given adapter target
    set traces one fixed program; the delta is computed as
    ``((x @ a) @ b) * scale`` — ``W + a@b`` is never materialized.
    """
    if not lora or name not in lora:
        return y
    mod = lora[name]
    a = mod["a"].astype(x.dtype)
    b = mod["b"].astype(x.dtype)
    return y + ((x @ a) @ b) * mod["scale"].astype(x.dtype)


def _lora_sub(lora, name):
    return None if lora is None else lora.get(name)


class LlamaAttention(nn.Module):
    config: LlamaConfig
    # Per-layer sliding window: the sentinel "config" reads the uniform
    # cfg.sliding_window (every pre-layer_windows caller, incl. mixtral);
    # LlamaBlock passes cfg.window_for(layer_idx) for Gemma2-style mixtures.
    window: Any = "config"

    @nn.compact
    def __call__(self, x, positions, causal=True, cache=None, cache_pos=None,
                 segment_ids=None, lora=None):
        cfg = self.config
        window = cfg.sliding_window if self.window == "config" else self.window
        with program_part("attn_local" if window is not None else "attn_global"):
            B, S, _ = x.shape
            n_q, n_kv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
            dense = _dense_factory(cfg, x.dtype)
            qkv_bias = cfg.attention_qkv_bias
            q = dense(n_q * hd, "q_proj", use_bias=qkv_bias)(x)
            k = dense(n_kv * hd, "k_proj", use_bias=qkv_bias)(x)
            v = dense(n_kv * hd, "v_proj", use_bias=qkv_bias)(x)
            q = _lora_delta(q, x, lora, "q_proj").reshape(B, S, n_q, hd)
            k = _lora_delta(k, x, lora, "k_proj").reshape(B, S, n_kv, hd)
            v = _lora_delta(v, x, lora, "v_proj").reshape(B, S, n_kv, hd)

            cos, sin = rotary_embedding(positions, hd, cfg.rope_theta, dtype=x.dtype,
                                        rope_scaling=cfg.rope_scaling)
            q = apply_rotary(q, cos, sin)
            k = apply_rotary(k, cos, sin)

            # query_pre_attn_scalar / softcap default to the vanilla scale / no
            # cap, so non-Gemma2 configs hit the identical fast paths as before.
            sm_scale = None if cfg.query_pre_attn_scalar is None else cfg.sm_scale
            softcap = cfg.attn_logit_softcapping

            if cache is not None:
                # KV-cached path (generate).
                out, new_cache = update_kv_cache_and_attend(
                    cache, q, k, v, cache_pos, n_q // n_kv,
                    sliding_window=window, sm_scale=sm_scale, logit_softcap=softcap)
                out = out.reshape(B, S, n_q * hd)
                proj = dense(cfg.hidden_size, "o_proj", use_bias=cfg.attention_out_bias)(out)
                return _lora_delta(proj, out, lora, "o_proj"), new_cache

            # GQA KV goes in unrepeated: every dense path is narrow-KV-native,
            # and CP strategies move G-wide KV over ICI.
            out = multi_head_attention(
                q, k, v, causal=causal, use_flash=cfg.use_flash_attention,
                segment_ids=segment_ids,
                backend=cfg.attention_backend, sliding_window=window,
                sm_scale=sm_scale, logit_softcap=softcap,
            )
            out = out.reshape(B, S, n_q * hd)
            proj = dense(cfg.hidden_size, "o_proj", use_bias=cfg.attention_out_bias)(out)
            return _lora_delta(proj, out, lora, "o_proj")


class LlamaMLP(nn.Module):
    config: LlamaConfig

    @nn.compact
    @program_part("mlp_dense")
    def __call__(self, x, lora=None):
        cfg = self.config
        dense = _dense_factory(cfg, x.dtype)
        gate = _lora_delta(dense(cfg.intermediate_size, "gate_proj")(x), x, lora, "gate_proj")
        up = _lora_delta(dense(cfg.intermediate_size, "up_proj")(x), x, lora, "up_proj")
        if cfg.mlp_activation == "gelu_tanh":    # GeGLU, tanh approx (Gemma)
            act = jax.nn.gelu(gate, approximate=True)
        elif cfg.mlp_activation == "gelu_exact":  # GeGLU, exact erf
            act = jax.nn.gelu(gate, approximate=False)
        elif cfg.mlp_activation == "silu":       # SwiGLU (Llama et al.)
            act = jax.nn.silu(gate)
        else:
            raise NotImplementedError(f"mlp_activation {cfg.mlp_activation!r}")
        h = act * up
        return _lora_delta(dense(cfg.hidden_size, "down_proj")(h), h, lora, "down_proj")


class LlamaBlock(nn.Module):
    config: LlamaConfig
    layer_idx: int = 0

    @nn.compact
    def __call__(self, x, positions, cache=None, cache_pos=None, segment_ids=None,
                 lora=None):
        cfg = self.config
        norm = functools.partial(RMSNorm, cfg.rms_norm_eps, unit_offset=cfg.rms_norm_unit_offset)
        attn_in = norm(name="input_norm")(x)
        attn = LlamaAttention(cfg, window=cfg.window_for(self.layer_idx),
                              name="self_attn")(attn_in, positions, cache=cache,
                                                cache_pos=cache_pos,
                                                segment_ids=segment_ids,
                                                lora=_lora_sub(lora, "self_attn"))
        new_cache = None
        if cache is not None:
            attn, new_cache = attn
        mlp_lora = _lora_sub(lora, "mlp")
        if cfg.post_norms:
            # Gemma2 sandwich block: sublayer OUTPUTS are normed before their
            # residual adds, and the MLP gets its own pre-norm.
            h = x + norm(name="post_attn_norm")(attn)
            mlp_in = norm(name="pre_ffn_norm")(h)
            h = h + norm(name="post_ffn_norm")(LlamaMLP(cfg, name="mlp")(mlp_in, lora=mlp_lora))
        else:
            h = x + attn
            h = h + LlamaMLP(cfg, name="mlp")(norm(name="post_attn_norm")(h), lora=mlp_lora)
        return h if cache is None else (h, new_cache)


class LlamaModel(nn.Module):
    """Decoder stack without head."""

    config: LlamaConfig

    @nn.compact
    def __call__(self, input_ids, positions=None, cache=None, cache_pos=None,
                 segment_ids=None, lora=None):
        cfg = self.config
        if positions is None:
            start = 0 if cache_pos is None else cache_pos
            positions = start + jnp.arange(input_ids.shape[1], dtype=jnp.int32)[None, :]
            positions = jnp.broadcast_to(positions, input_ids.shape)
        if segment_ids is not None and cache is not None:
            raise ValueError(
                "segment_ids (packed sequences) is a training feature; the "
                "KV-cache decode path does not apply segment masking")
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, name="embed_tokens", param_dtype=jnp.float32)
        with program_part("embed"):
            x = embed(input_ids)
            if cfg.scale_embeddings:
                # Gemma: activations enter the stack scaled by sqrt(hidden). HF
                # rounds the scalar to the activations' dtype (bf16 under
                # torch_dtype=bfloat16, fp32 here where embeddings run fp32), so
                # casting to x.dtype reproduces HF exactly at matching dtypes.
                x = x * jnp.asarray(cfg.hidden_size ** 0.5, x.dtype)
        block_cls = LlamaBlock
        if cfg.remat:
            from ..parallel.sharding import resolve_remat_policy

            block_cls = nn.remat(LlamaBlock, policy=resolve_remat_policy(cfg.remat_policy))
        new_caches = []
        for i in range(cfg.num_hidden_layers):
            layer_lora = _lora_sub(lora, f"layers_{i}")
            if cache is None:
                x = block_cls(cfg, layer_idx=i, name=f"layers_{i}")(
                    x, positions, segment_ids=segment_ids, lora=layer_lora)
            else:
                x, layer_cache = block_cls(cfg, layer_idx=i, name=f"layers_{i}")(
                    x, positions, cache=cache[i], cache_pos=cache_pos,
                    lora=layer_lora,
                )
                new_caches.append(layer_cache)
        with program_part("lm_head"):             # the final norm goes with the head
            x = RMSNorm(cfg.rms_norm_eps, unit_offset=cfg.rms_norm_unit_offset, name="norm")(x)
        return x if cache is None else (x, tuple(new_caches))


class LlamaForCausalLM(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, input_ids, positions=None, cache=None, cache_pos=None,
                 return_hidden=False, segment_ids=None, lora=None):
        cfg = self.config
        x = LlamaModel(cfg, name="model")(input_ids, positions, cache=cache,
                                          cache_pos=cache_pos, segment_ids=segment_ids,
                                          lora=_lora_sub(lora, "model"))
        new_cache = None
        if cache is not None:
            x, new_cache = x
        if return_hidden:
            # Pre-head normed hidden states (fused LM-head losses compute
            # logits chunk-by-chunk themselves; ops/fused_loss.py).
            return x if cache is None else (x, new_cache)
        from ..ops.attention import softcap_logits

        with program_part("lm_head"):
            if cfg.tie_word_embeddings:
                embed = self.variables["params"]["model"]["embed_tokens"]["embedding"]
                logits = x @ embed.T.astype(x.dtype)
            else:
                # The lm_head stays high-precision even under fp8 — its output
                # feeds the softmax directly (standard TE practice).
                logits = nn.Dense(cfg.vocab_size, use_bias=False, name="lm_head", dtype=x.dtype,
                                  param_dtype=jnp.float32)(x)
            logits = softcap_logits(logits, cfg.final_logit_softcapping)
        return logits if cache is None else (logits, new_cache)

    def init_params(self, rng, batch_size=1, seq_len=8):
        """Initialize a parameter pytree from a PRNG key (shape-driving args are traced-free)."""
        dummy = jnp.zeros((batch_size, seq_len), jnp.int32)
        return self.init(rng, dummy)["params"]


class PipelinedLlamaForCausalLM:
    """Pipeline-parallel Llama: the decoder blocks are *stacked* — every
    block-param leaf carries a leading ``[num_layers, ...]`` dim sharded over
    the ``pp`` mesh axis — and applied via the GPipe microbatch schedule in
    :func:`accelerate_tpu.parallel.pipeline.pipeline_apply`.

    Replaces the reference's Megatron pipeline engine delegation (reference:
    utils/megatron_lm.py:1035-1056) with one differentiable jitted
    expression; with ``pp=1`` in the mesh it degrades to a scan over layers
    (same params layout, no schedule).

    Not an ``nn.Module``: the apply is a pure function so the pipeline scan
    controls layer application directly. Interchange with the sequential
    `LlamaForCausalLM` layout via ``from_sequential_params`` /
    ``to_sequential_params``.
    """

    def __init__(self, config: LlamaConfig, num_microbatches: Optional[int] = None):
        if config.layer_windows is not None and len(set(config.layer_windows)) > 1:
            raise NotImplementedError(
                "PipelinedLlamaForCausalLM scans one block over stacked params; "
                "heterogeneous per-layer windows (layer_windows) need the "
                "sequential LlamaForCausalLM")
        self.config = config
        self.num_microbatches = num_microbatches

    # -- parameter init / layout ------------------------------------------

    def init_params(self, rng, seq_len: int = 8, batch_size: int = 1):
        """Initialize a parameter pytree from a PRNG key (shape-driving args
        are traced-free). ``batch_size`` only matters when a context-parallel
        plugin is active: the cp attention shard_map traced during init needs
        the dummy batch divisible by the data mesh axes (dp x fsdp)."""
        cfg = self.config
        r_embed, r_blocks, r_head = jax.random.split(rng, 3)
        dummy_x = jnp.zeros((batch_size, seq_len, cfg.hidden_size), jnp.float32)
        dummy_pos = jnp.zeros((batch_size, seq_len), jnp.int32)
        block = LlamaBlock(cfg)
        layer_rngs = jax.random.split(r_blocks, cfg.num_hidden_layers)
        blocks = jax.vmap(lambda r: block.init(r, dummy_x, dummy_pos)["params"])(layer_rngs)
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, param_dtype=jnp.float32).init(
            r_embed, jnp.zeros((1, 1), jnp.int32)
        )["params"]
        norm_scale = (jnp.zeros if cfg.rms_norm_unit_offset else jnp.ones)(
            (cfg.hidden_size,), jnp.float32)
        params = {
            "model": {
                "embed_tokens": embed,
                "blocks": blocks,
                "norm": {"scale": norm_scale},
            }
        }
        if not cfg.tie_word_embeddings:
            params["lm_head"] = nn.Dense(cfg.vocab_size, use_bias=False, param_dtype=jnp.float32).init(
                r_head, jnp.zeros((1, cfg.hidden_size))
            )["params"]
        return params

    @staticmethod
    def from_sequential_params(params):
        """`LlamaForCausalLM` params (layers_0..layers_{n-1}) -> pipelined layout."""
        from ..parallel.pipeline import stack_layer_params

        blocks, rest = stack_layer_params(params["model"], prefix="layers_")
        out = {"model": {**rest, "blocks": blocks}}
        if "lm_head" in params:
            out["lm_head"] = params["lm_head"]
        return out

    @staticmethod
    def to_sequential_params(params):
        from ..parallel.pipeline import unstack_layer_params

        model = {k: v for k, v in params["model"].items() if k != "blocks"}
        model.update(unstack_layer_params(params["model"]["blocks"], prefix="layers_"))
        out = {"model": model}
        if "lm_head" in params:
            out["lm_head"] = params["lm_head"]
        return out

    # -- forward -----------------------------------------------------------

    def apply(self, variables, input_ids, positions=None, segment_ids=None,
              return_hidden=False):
        """Flax apply over stacked per-stage params (pipeline schedule inside).

        ``return_hidden=True`` yields the pre-head normed hidden states so
        :func:`fused_causal_lm_loss` can run its chunked LM head — the same
        contract as ``LlamaForCausalLM(..., return_hidden=True)``. Packed
        batches ride along as ``segment_ids`` (they join ``positions`` in the
        pipeline's per-example extras). Besides pipelining, this layout is
        the fast-compile path for deep stacks: the block is traced/compiled
        once and scanned, not inlined per layer.
        """
        from ..parallel.pipeline import pipeline_apply

        cfg = self.config
        p = variables["params"] if isinstance(variables, dict) and "params" in variables else variables
        if positions is None:
            positions = jnp.arange(input_ids.shape[1])[None, :].astype(jnp.int32)
            positions = jnp.broadcast_to(positions, input_ids.shape)
        emb = p["model"]["embed_tokens"]["embedding"]
        x = jnp.take(emb, input_ids, axis=0)
        if cfg.scale_embeddings:
            x = x * jnp.asarray(cfg.hidden_size ** 0.5, x.dtype)

        block = LlamaBlock(cfg)

        if segment_ids is None:
            extras = positions

            def block_fn(p_layer, h, pos):
                return block.apply({"params": p_layer}, h, pos)
        else:
            extras = (positions, segment_ids)

            def block_fn(p_layer, h, exs):
                pos, seg = exs
                return block.apply({"params": p_layer}, h, pos, segment_ids=seg)

        from ..parallel.sharding import resolve_remat_policy

        x = pipeline_apply(
            block_fn,
            p["model"]["blocks"],
            x,
            extras=extras,
            num_microbatches=self.num_microbatches,
            remat=cfg.remat,
            remat_policy=resolve_remat_policy(cfg.remat_policy) if cfg.remat else None,
        )
        x = RMSNorm(cfg.rms_norm_eps, unit_offset=cfg.rms_norm_unit_offset).apply(
            {"params": p["model"]["norm"]}, x)
        if return_hidden:
            return x
        from ..ops.attention import softcap_logits

        if cfg.tie_word_embeddings:
            logits = x @ emb.T.astype(x.dtype)
        else:
            logits = x @ p["lm_head"]["kernel"].astype(x.dtype)
        return softcap_logits(logits, cfg.final_logit_softcapping)

    __call__ = apply


def _targets_and_mask(batch):
    """Shared label semantics for every causal-LM loss: next-token shift when
    no explicit labels, -100 = ignored (HF convention). Returns
    (safe_targets, float mask) with -100 slots zeroed out."""
    targets = batch.get("labels", None)
    if targets is None:
        targets = jnp.pad(batch["input_ids"][:, 1:], ((0, 0), (0, 1)), constant_values=-100)
    mask = (targets != -100).astype(jnp.float32)
    safe_targets = jnp.where(targets == -100, 0, targets)
    return safe_targets, mask


def masked_next_token_ce(logits, batch):
    """Next-token cross-entropy over a batch with optional ``labels`` (-100 =
    ignored, HF convention). Shared by every causal-LM loss builder."""
    safe_targets, mask = _targets_and_mask(batch)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, safe_targets[..., None], axis=-1)[..., 0]
    return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def causal_lm_loss(apply_fn):
    """Build a loss_fn(params, batch[, rng]) for Accelerator.backward /
    compile_train_step: next-token cross-entropy with optional loss mask."""

    def loss_fn(params, batch, rng=None):
        kwargs = {}
        # Packed-sequence batches (data_loader.pack_sequences) carry
        # per-token positions + segment ids; plain batches don't.
        if "positions" in batch:
            kwargs["positions"] = batch["positions"]
        if "segment_ids" in batch:
            kwargs["segment_ids"] = batch["segment_ids"]
        logits = apply_fn({"params": params}, batch["input_ids"], **kwargs)
        return masked_next_token_ce(logits, batch)

    return loss_fn


def fused_causal_lm_loss(module, num_chunks: int = 8):
    """Memory-efficient loss: the [tokens, vocab] logits are never
    materialized — the LM head runs chunked over the vocabulary with an
    online softmax (ops/fused_loss.py). Numerics match `causal_lm_loss`
    to fp32-accumulation tolerance; peak activation memory drops by
    ~vocab/num_chunks at the head.

    ``module`` is any model exposing ``.config`` and an
    ``apply(variables, input_ids, ..., return_hidden=True)`` that yields
    pre-head hidden states: both `LlamaForCausalLM` and the scan-based
    `PipelinedLlamaForCausalLM` qualify, including packed-sequence batches
    (``positions`` + ``segment_ids``)."""
    from ..ops.fused_loss import chunked_softmax_xent

    cfg = module.config

    def loss_fn(params, batch, rng=None):
        p = params["params"] if isinstance(params, dict) and "params" in params else params
        kwargs = {}
        # Packed batches (data_loader.pack_sequences) — same forwarding as
        # causal_lm_loss, or documents would silently attend across each
        # other under the memory-efficient head.
        if "positions" in batch:
            kwargs["positions"] = batch["positions"]
        if "segment_ids" in batch:
            kwargs["segment_ids"] = batch["segment_ids"]
        h = module.apply({"params": p}, batch["input_ids"], return_hidden=True,
                         **kwargs)  # [B,S,H]
        if cfg.tie_word_embeddings:
            kernel = p["model"]["embed_tokens"]["embedding"].T
        else:
            kernel = p["lm_head"]["kernel"]
        safe, mask = _targets_and_mask(batch)
        B, S, H = h.shape
        return chunked_softmax_xent(
            h.reshape(B * S, H), kernel.astype(h.dtype),
            safe.reshape(-1), mask.reshape(-1), num_chunks,
            cfg.final_logit_softcapping,
        )

    return loss_fn
