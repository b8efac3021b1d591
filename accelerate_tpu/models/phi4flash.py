"""Phi-4-mini-flash-reasoning decoder family (``model_type`` ``phi4flash``;
the decoder-hybrid-decoder of arXiv:2507.06607, "SambaY"), serving-first.

Layer ``i`` of ``n`` (``Phi4FlashConfig.mixer``):

* ``i < n/2``, even: a Mamba-1 layer (selective scan; its state is an SSM
  state ``[d_state, d_inner]`` in float32 and the last ``d_conv - 1`` inputs
  of its causal convolution);
* ``i < n/2``, odd: differential attention over a window of
  ``sliding_window`` keys, with its own K/V;
* ``i == n/2``: a Mamba layer that also hands its scan output ``m`` (before
  the gate, ``D * x'`` included) to every gated memory unit above it;
* ``i == n/2 + 1``: differential attention, full, with its own K/V: the one
  cache the cross-decoder reads;
* above, even: a gated memory unit ``W_2 (silu(W_1 u) * m)``, stateless;
* above, odd: differential CROSS-attention: its own query against the K/V
  layer ``n/2 + 1`` wrote, causal; it has no key/value projection and writes
  no cache row.

Every layer is ``a = x + mixer(LN1(x))``, ``y = a + MLP(LN2(a))`` with
LayerNorms (scale and bias) and a SwiGLU MLP of one fused ``gate_up``
projection; a final LayerNorm, logits against the embedding (tied). There is
no positional encoding: the Mamba layers carry order.

Differential attention pairs heads ``(2n, 2n+1)``: query pair ``n`` scores
key pair ``p = n // 2`` twice (first heads, second heads), each softmax
weighs the 128-wide ``V_p = [v_2p | v_2p+1]``, and the second is subtracted
``lambda`` times before an RMSNorm of 128 and the factor ``1 - lambda_init``.
HOW THIS PROGRAM COMPUTES IT, in the published head order throughout: the
key pair is kept as ONE 128-wide key ``K_p = [k_2p | k_2p+1]`` beside ``V_p``,
and query head ``2n + which`` is widened to 128 with zeros in the half of the
other key, so that its score against ``K_p`` is its score against its own
key. The two softmaxes of all pairs are then ONE plain grouped attention —
40 query heads over 10 key/value heads of 128, scale ``1/8`` — through
models/llama.py's two cached-attention entry points as they are (a chunk:
the bounded key blocks; a tick: the work list over the pool in place);
subtraction, RMSNorm and the factor are plain ``jnp`` after the call. A
cached token's row is kept with its heads side by side (``[.., 1280]``: rows
of whole lanes; with a head axis of 10 or 20 the compiler re-laid the whole
pool out in every attention of a tick, PERF.md section 6, PR 35).

The cache this family declares (``init_cache``) has one entry for each of
the first ``n/2 + 2`` layers: ``{"k", "v"}`` ``[B, L, kv_heads * head_dim]`` with a
length axis for an attention layer, ``{"conv", "ssm"}`` WITHOUT one for a Mamba layer. The
serving engine pages the former and holds the latter per slot
(serving/engine.py, "recurrent entries"); ``attention_layout`` tells it which
cache entry each attention reads and through which window. A chunk padded
past its prompt passes ``valid_len``: padded steps leave the state as it is.

Readings the published config does not carry (its ``configuration_phi4flash``
defaults, which reproduce the published 3.85 G parameters): ``mamba_d_state``
16, ``mamba_d_conv`` 4, ``mamba_expand`` 2, ``mamba_dt_rank`` ceil(hidden/16);
the convolution has a bias, the Mamba projections none; the attention
projections have biases; ``lambda_init(i) = 0.8 - 0.6 exp(-0.3 i)`` with ``i``
the layer's index; a query's own position counts among the window's keys.

The program's parts (observability/program_parts.py): ``embed``, ``ssm_in``,
``ssm_conv``, ``ssm_scan``, ``ssm_out``, ``gmu``, ``attn_diff_local``,
``attn_diff_global``, ``attn_cross_shared`` (models/llama.py's ``kv_attn`` /
``kv_write`` inside them), ``mlp_dense``, ``lm_head``: on every operation's
``op_name`` and so in the metadata of a device trace's events, where
``chipbench/op_scopes.py`` reads them. Generation contract:
``(input_ids, positions, cache, cache_pos) -> logits, cache``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..observability.program_parts import program_part
from ..ops.selective_scan import selective_scan as selective_scan_kernel
from .cohere2_moe import _Kernel
from .llama import (PagedCache, _cached_attention, attend_shared_kv_cache,
                    update_kv_cache_and_attend)


@dataclasses.dataclass
class Phi4FlashConfig:
    vocab_size: int = 200064
    hidden_size: int = 2560
    intermediate_size: int = 10240
    num_hidden_layers: int = 32
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    sliding_window: int = 512
    mb_per_layer: int = 2
    layer_norm_eps: float = 1e-5
    max_position_embeddings: int = 262144
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: Optional[int] = None

    def __post_init__(self):
        n = self.num_hidden_layers
        if self.mb_per_layer != 2 or n % 2 or n < 4:
            raise ValueError("phi4flash alternates Mamba and attention (mb_per_layer 2) over an "
                             f"even number of layers >= 4 (got {self.mb_per_layer}, {n})")
        if self.num_attention_heads % 4 or self.num_attention_heads != 2 * self.num_key_value_heads:
            raise ValueError("differential attention pairs query heads and key heads: "
                             "num_attention_heads = 2 * num_key_value_heads, a multiple of 4")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def dt_rank(self) -> int:
        return self.mamba_dt_rank or math.ceil(self.hidden_size / 16)

    @property
    def memory_layer(self) -> int:
        """The Mamba layer whose scan output the gated memory units read."""
        return self.num_hidden_layers // 2

    @property
    def shared_kv_layer(self) -> int:
        """The full-attention layer whose K/V the cross-attentions read."""
        return self.num_hidden_layers // 2 + 1

    def mixer(self, i: int) -> str:
        """``"mamba"``, ``"attn"`` (own K/V), ``"gmu"`` or ``"cross"``."""
        if i <= self.shared_kv_layer:
            return "attn" if i % 2 else "mamba"
        return "cross" if i % 2 else "gmu"

    def window_for(self, i: int) -> Optional[int]:
        return self.sliding_window if self.mixer(i) == "attn" and i < self.memory_layer else None

    def lambda_init(self, i: int) -> float:
        return 0.8 - 0.6 * math.exp(-0.3 * i)

    @classmethod
    def tiny(cls, **overrides):
        """Test size: 8 layers (2 Mamba + 2 windowed, the memory layer, the
        shared-K/V layer, one GMU and one cross-attention ... twice)."""
        cfg = dict(vocab_size=256, hidden_size=64, intermediate_size=96, num_hidden_layers=8,
                   num_attention_heads=8, num_key_value_heads=4, sliding_window=8,
                   max_position_embeddings=512, mamba_d_state=4)
        cfg.update(overrides)
        return cls(**cfg)


class LayerNorm(nn.Module):
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        x32 = x.astype(jnp.float32)
        mean = jnp.mean(x32, -1, keepdims=True)
        var = jnp.mean(jnp.square(x32 - mean), -1, keepdims=True)
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (x.shape[-1],), jnp.float32)
        return ((x32 - mean) * jax.lax.rsqrt(var + self.eps) * scale + bias).astype(x.dtype)


class _BiasKernel(nn.Module):
    """``<name>/kernel`` + ``<name>/bias``, computed in the input's type."""
    features: int

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (x.shape[-1], self.features), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (self.features,), jnp.float32)
        return x @ kernel.astype(x.dtype) + bias.astype(x.dtype)


# ---------------------------------------------------------------------------
# The selective scan
# ---------------------------------------------------------------------------

def selective_scan(x, dt, A, B, C, h0):
    """Mamba-1 recurrence over ``S`` steps from a carried state, float32:
    ``h_t = exp(dt_t * A) * h_{t-1} + (dt_t * x_t) * B_t``, ``y_t = h_t . C_t``.
    By the shape: one step (a decode tick) is the plain update in XLA;
    several (a prefill chunk, a prompt) are one call of the Pallas kernel
    ``ops.selective_scan.selective_scan``, which holds the state on the chip
    for all of them (the interpreter on the CPU).

    ``x``, ``dt`` ``[b, S, d]``; ``A`` ``[N, d]`` (negative); ``B``, ``C``
    ``[b, S, N]``; ``h0`` ``[b, N, d]``. Returns ``(y [b, S, d], h_S)``. A
    step with ``dt == 0`` leaves the state as it is. Forward only: the
    kernel has no derivative."""
    if x.shape[1] > 1:
        return selective_scan_kernel(x, dt, A, B, C, h0)
    a, b = jnp.exp(dt[:, :, None, :] * A), (dt * x)[:, :, None, :] * B[..., None]
    h = a[:, 0] * h0 + b[:, 0]
    return jnp.einsum("bnd,bn->bd", h, C[:, 0])[:, None], h


class Mamba(nn.Module):
    """The Mamba-1 mixer. Returns ``(out, scan output y, new state)``; the
    state is ``{"conv": [B, d_conv - 1, d_inner], "ssm": [B, d_state,
    d_inner] float32}`` after the last of the first ``valid_len`` steps."""
    config: Phi4FlashConfig

    @nn.compact
    def __call__(self, u, state=None, valid_len=None):
        cfg = self.config
        B, S, _ = u.shape
        d, N, R, K = cfg.d_inner, cfg.mamba_d_state, cfg.dt_rank, cfg.mamba_d_conv
        f32 = jnp.float32
        with program_part("ssm_in"):
            xz = _Kernel(2 * d, name="in_proj")(u)
            x, z = xz[..., :d], xz[..., d:]
        with program_part("ssm_conv"):
            w = self.param("conv_kernel", nn.initializers.lecun_normal(), (K, d), f32)
            b = self.param("conv_bias", nn.initializers.zeros, (d,), f32)
            before = (jnp.zeros((B, K - 1, d), x.dtype) if state is None
                      else state["conv"].astype(x.dtype))
            xp = jnp.concatenate([before, x], axis=1)                       # [B, S + K - 1, d]
            xc = sum(xp[:, j:j + S].astype(f32) * w[j] for j in range(K)) + b
            xc = jax.nn.silu(xc)                                            # float32
            if valid_len is None:
                new_conv = xp[:, S:]
            else:                                    # the last K - 1 inputs that are real
                new_conv = jax.lax.dynamic_slice_in_dim(xp, valid_len, K - 1, axis=1)
        with program_part("ssm_scan"):
            dbc = _Kernel(R + 2 * N, name="x_proj")(xc.astype(u.dtype))
            dt_w = self.param("dt_proj", nn.initializers.lecun_normal(), (R, d), f32)
            dt_b = self.param("dt_bias", nn.initializers.zeros, (d,), f32)
            dt = jax.nn.softplus(
                jnp.einsum("bsr,rd->bsd", dbc[..., :R], dt_w.astype(u.dtype),
                           preferred_element_type=f32) + dt_b.astype(f32))
            if valid_len is not None:                # a padded step leaves the state alone
                dt = jnp.where(jnp.arange(S)[None, :, None] < valid_len, dt, 0.0)
            # stored [d_state, d_inner]: the published A_log, transposed
            A = -jnp.exp(self.param("A_log", nn.initializers.zeros, (N, d), f32).astype(f32))
            D = self.param("D", nn.initializers.ones, (d,), f32).astype(f32)
            h0 = jnp.zeros((B, N, d), f32) if state is None else state["ssm"]
            y, h = selective_scan(xc, dt, A, dbc[..., R:R + N].astype(f32),
                                  dbc[..., R + N:].astype(f32), h0)
            y = y + D * xc
        with program_part("ssm_out"):
            out = _Kernel(cfg.hidden_size, name="out_proj")(
                (y * jax.nn.silu(z.astype(f32))).astype(u.dtype))
        conv_dtype = x.dtype if state is None else state["conv"].dtype
        return out, y, {"conv": new_conv.astype(conv_dtype), "ssm": h}


class GatedMemoryUnit(nn.Module):
    config: Phi4FlashConfig

    @nn.compact
    def __call__(self, u, memory):
        with program_part("gmu"):
            gate = jax.nn.silu(_Kernel(self.config.d_inner, name="in_proj")(u).astype(jnp.float32))
            return _Kernel(self.config.hidden_size, name="out_proj")(
                (gate * memory).astype(u.dtype))


class DiffAttention(nn.Module):
    """Differential attention. ``kind == "attn"`` projects and caches its own
    K/V; ``"cross"`` projects a query alone and reads ``shared``: the cache
    entry of ``shared_kv_layer`` as that layer left it in this call (a view
    that holds the call's rows; in a decode tick the pool's entry and the
    token's row beside it)."""
    config: Phi4FlashConfig
    layer_idx: int

    @nn.compact
    def __call__(self, u, cache=None, cache_pos=None, shared=None):
        cfg = self.config
        B, S, _ = u.shape
        H, G, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        pairs, wide = G // 2, 2 * hd
        kind, window = cfg.mixer(self.layer_idx), cfg.window_for(self.layer_idx)
        scope = ("attn_cross_shared" if kind == "cross"
                 else "attn_diff_local" if window is not None else "attn_diff_global")
        # query head 2n + which, widened: its 64 values in the half of ITS key of the pair
        half = (jnp.arange(H)[:, None] % 2 == jnp.arange(2)[None, :])[..., None]   # [H, 2, 1]
        widen = lambda q: (q.reshape(B, S, H, 1, hd) * half.astype(q.dtype)).reshape(B, S, H, wide)  # noqa: E731
        attend = dict(n_rep=H // pairs, sm_scale=hd ** -0.5)
        new_cache = None
        with program_part(scope):
            if kind == "cross":
                q = widen(_BiasKernel(H * hd, name="q_proj")(u))
                by_head = lambda t: jax.tree.map(                                  # noqa: E731
                    lambda x: x.reshape(x.shape[:2] + (pairs, wide)), t)
                entry, row = shared
                out = attend_shared_kv_cache(
                    entry if isinstance(entry, PagedCache) else by_head(entry), q,
                    0 if cache_pos is None else cache_pos, row=by_head(row), **attend)
            else:
                qkv = _BiasKernel((H + 2 * G) * hd, name="qkv_proj")(u)
                q = widen(qkv[..., :H * hd])
                k = qkv[..., H * hd:(H + G) * hd].reshape(B, S, pairs, wide)      # K_p = [k_2p | k_2p+1]
                v = qkv[..., (H + G) * hd:].reshape(B, S, pairs, wide)            # V_p = [v_2p | v_2p+1]
                if cache is None:                # no cache: the call's own rows are all there is
                    new_cache = {"k": k, "v": v}
                    out = _cached_attention(q, k, v, 0, sliding_window=window, **attend)
                elif isinstance(cache, PagedCache):
                    out, new_cache = update_kv_cache_and_attend(
                        cache, q, k, v, cache_pos, sliding_window=window, **attend)
                else:                            # a linear view: rows as heads for the call
                    by_head = {n: x.reshape(x.shape[:2] + (pairs, wide)) for n, x in cache.items()}
                    out, by_head = update_kv_cache_and_attend(
                        by_head, q, k, v, cache_pos, sliding_window=window, **attend)
                    new_cache = {n: x.reshape(cache[n].shape) for n, x in by_head.items()}
            # out [B, S, H, 128]: head 2n is pair n's first softmax over V, 2n + 1 its second
            lam = [self.param(f"lambda_{n}", nn.initializers.normal(0.1), (hd,), jnp.float32)
                   for n in ("q1", "k1", "q2", "k2")]
            lam_init = cfg.lambda_init(self.layer_idx)
            lam_full = (jnp.exp(jnp.sum(lam[0].astype(jnp.float32) * lam[1].astype(jnp.float32)))
                        - jnp.exp(jnp.sum(lam[2].astype(jnp.float32) * lam[3].astype(jnp.float32)))
                        + lam_init)
            o = out.astype(jnp.float32).reshape(B, S, H // 2, 2, wide)
            o = o[..., 0, :] - lam_full * o[..., 1, :]
            scale = self.param("subln", nn.initializers.ones, (wide,), jnp.float32)
            o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True) + cfg.layer_norm_eps)
            o = (o * scale.astype(jnp.float32) * (1.0 - lam_init)).astype(u.dtype)
            out = _BiasKernel(cfg.hidden_size, name="o_proj")(o.reshape(B, S, H * hd))
        return out, new_cache


class Phi4FlashMLP(nn.Module):
    config: Phi4FlashConfig

    @nn.compact
    def __call__(self, x):
        f = self.config.intermediate_size
        with program_part("mlp_dense"):
            gu = _Kernel(2 * f, name="gate_up_proj")(x)
            return _Kernel(x.shape[-1], name="down_proj")(jax.nn.silu(gu[..., :f]) * gu[..., f:])


class Phi4FlashBlock(nn.Module):
    config: Phi4FlashConfig
    layer_idx: int

    @nn.compact
    def __call__(self, x, cache=None, cache_pos=None, valid_len=None, memory=None, shared=None):
        """Returns ``(y, this layer's new cache entry or None, memory)``."""
        cfg = self.config
        kind = cfg.mixer(self.layer_idx)
        u = LayerNorm(cfg.layer_norm_eps, name="input_norm")(x)
        entry = None
        if kind == "mamba":
            mixed, y, entry = Mamba(cfg, name="mixer")(u, state=cache, valid_len=valid_len)
            if self.layer_idx == cfg.memory_layer:
                memory = y
        elif kind == "gmu":
            mixed = GatedMemoryUnit(cfg, name="mixer")(u, memory)
        else:
            mixed, entry = DiffAttention(cfg, self.layer_idx, name="mixer")(
                u, cache=cache, cache_pos=cache_pos, shared=shared)
        x = x + mixed
        x = x + Phi4FlashMLP(cfg, name="mlp")(LayerNorm(cfg.layer_norm_eps, name="post_norm")(x))
        return x, entry, memory


class Phi4FlashForCausalLM(nn.Module):
    config: Phi4FlashConfig

    def init_cache(self, batch_size: int, max_len: int, dtype=jnp.bfloat16, ring_slack: int = 0):
        """The cache this family declares (``big_modeling.cache_factory_for``
        asks the module first): one entry for each layer up to
        ``shared_kv_layer``. An attention layer's has a length axis (all
        linear: the serving engine reads a windowed layer's pages through its
        window; nothing rings, ``ring_slack`` is unused); a Mamba layer's has
        none — fixed size, whatever ``max_len``."""
        cfg = self.config
        G, hd = cfg.num_key_value_heads, cfg.head_dim
        entries = []
        for i in range(cfg.shared_kv_layer + 1):
            if cfg.mixer(i) == "mamba":
                entries.append({
                    "conv": jnp.zeros((batch_size, cfg.mamba_d_conv - 1, cfg.d_inner), dtype),
                    "ssm": jnp.zeros((batch_size, cfg.mamba_d_state, cfg.d_inner), jnp.float32)})
            else:
                entries.append({"k": jnp.zeros((batch_size, max_len, G * hd), dtype),
                                "v": jnp.zeros((batch_size, max_len, G * hd), dtype)})
        return tuple(entries)

    def attention_layout(self) -> list:
        """``(window or None, cache entry)`` for each attention of a forward
        pass, in layer order: what the serving engine's row counters are sums
        over. The cross-attentions read ``shared_kv_layer``'s entry."""
        cfg = self.config
        return [(cfg.window_for(i), i if cfg.mixer(i) == "attn" else cfg.shared_kv_layer)
                for i in range(cfg.num_hidden_layers) if cfg.mixer(i) in ("attn", "cross")]

    @nn.compact
    def __call__(self, input_ids, positions=None, cache=None, cache_pos=None, valid_len=None):
        cfg = self.config
        del positions                                 # no positional encoding
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, name="embed_tokens",
                         param_dtype=jnp.float32)
        with program_part("embed"):
            x = embed(input_ids)
        new_cache, memory, shared = [], None, None
        for i in range(cfg.num_hidden_layers):
            entry = None if cache is None or i > cfg.shared_kv_layer else cache[i]
            x, new_entry, memory = Phi4FlashBlock(cfg, i, name=f"layers_{i}")(
                x, cache=entry, cache_pos=cache_pos, valid_len=valid_len, memory=memory,
                shared=shared)
            if i <= cfg.shared_kv_layer:
                new_cache.append(new_entry)
            if i == cfg.shared_kv_layer:
                # what the cross-attentions read: in a decode tick the pool's
                # entry with the token's row (in no page yet) beside it, else
                # the view this layer has just written
                shared = (entry, new_entry) if isinstance(entry, PagedCache) else (new_entry, None)
        with program_part("lm_head"):
            x = LayerNorm(cfg.layer_norm_eps, name="norm")(x)
            logits = jnp.einsum("bsh,vh->bsv", x, embed.embedding.astype(x.dtype),
                                preferred_element_type=jnp.float32)
        if cache is not None:
            return logits, tuple(new_cache)
        return logits

    def init_params(self, rng, batch_size=1, seq_len=8):
        dummy = jnp.zeros((batch_size, seq_len), jnp.int32)
        return self.init(rng, dummy)["params"]
