"""openPangu-Ultra-MoE decoder family (``model_type`` ``pangu_ultra_moe``),
serving-first.

One layer, from the published config keys:

* sandwich norms (``sandwich_norm``): four RMSNorms a layer,
  ``a = x + post_attn_norm(attn(input_norm(x)))``,
  ``y = a + post_mlp_norm(mlp(pre_mlp_norm(a)))``;
* latent attention (MLA): a low-rank query (``q_a_proj`` -> RMSNorm ->
  ``q_b_proj``: heads of ``qk_nope_head_dim`` + ``qk_rope_head_dim``), and
  ONE joint latent for keys and values (``kv_a_proj``: ``kv_lora_rank``
  values, RMSNormed, and a decoupled rotary key of ``qk_rope_head_dim``
  shared by all heads). ``kv_b_proj`` expands the latent to each head's
  no-position key and value. The cache holds the normed latent and the
  rotated key — ``kv_lora_rank + qk_rope_head_dim`` values a token a layer,
  nothing per head (``init_cache``; models/llama.py
  ``update_latent_cache_and_attend`` attends in the absorbed or the expanded
  form, whichever the call's shape makes cheaper);
* the first ``first_k_dense_replace`` layers carry a dense SwiGLU MLP; later
  layers a router of the model's full width, sigmoid-scored, top-k,
  renormalised (``norm_topk_prob``) and scaled by ``routed_scaling_factor``,
  over the routed experts HELD here (``held_experts``, ops/moe.py
  ``moe_held_apply``: what the absent experts would add is left out), plus
  ``n_shared_experts`` always-on experts (one SwiGLU of their joint width);
* final RMSNorm, logits against the head's own matrix (untied).

Readings the config does not settle: the router has no groups and no
correction bias; rotary pairs are ``(i, i + rope/2)`` (models/llama.py
``apply_rotary``); no rope scaling, so the softmax scale is
``(nope + rope) ** -0.5``. The multi-token-prediction module
(``num_nextn_predict_layers``) is not part of this model.

The program's parts (observability/program_parts.py): ``embed``, ``attn_mla``
(inside it ``mla_q`` / ``mla_latent`` / ``mla_out``, and models/llama.py's
``kv_attn`` / ``kv_write`` around the cache), ``mlp_dense``, ``moe_router`` /
``moe_experts`` (ops/moe.py), ``moe_shared`` and ``lm_head``. They are on
every operation's ``op_name``; a profiler trace keeps them in each event's
metadata (``tf_op``), which ``chipbench/op_scopes.py`` reads into device time
by part. The model follows the generation contract of ``MixtralForCausalLM``:
``(input_ids, positions, cache, cache_pos) -> logits, cache``.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..observability.program_parts import program_part
from .cohere2_moe import _ExpertStacks, _Kernel
from .llama import (RMSNorm, _latent_cached_attention, apply_rotary, init_latent_cache,
                    rotary_embedding, update_latent_cache_and_attend)


@dataclasses.dataclass
class PanguUltraMoeConfig:
    vocab_size: int = 153600
    hidden_size: int = 7680
    intermediate_size: int = 18432         # the leading dense layers' MLP
    moe_intermediate_size: int = 2048      # one routed expert; a shared one likewise
    num_hidden_layers: int = 61
    first_k_dense_replace: int = 3
    num_attention_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-5
    rope_theta: float = 25600000.0
    #: the router's width: the routed experts of the whole model.
    num_experts: int = 256
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    #: ``(first, count)`` of the routed experts whose weights live here
    #: (one rank of an expert-parallel deployment); None = all of them.
    held_experts: Optional[tuple] = None

    @property
    def held(self) -> tuple:
        return (0, self.num_experts) if self.held_experts is None else tuple(self.held_experts)

    @property
    def cache_row_width(self) -> int:
        """Values the cache holds a token a layer: the latent and the rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def sm_scale(self) -> float:
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5

    @classmethod
    def tiny(cls, **overrides):
        """Test size: one dense layer and three expert layers, 8 experts top-2."""
        cfg = cls(vocab_size=256, hidden_size=64, intermediate_size=96,
                  moe_intermediate_size=32, num_hidden_layers=4, first_k_dense_replace=1,
                  num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8,
                  qk_rope_head_dim=4, v_head_dim=8, max_position_embeddings=512,
                  rope_theta=10000.0, num_experts=8, num_experts_per_tok=2)
        return dataclasses.replace(cfg, **overrides)


class PanguMLAttention(nn.Module):
    """Latent attention. Reads ``num_attention_heads``, ``q_lora_rank``,
    ``kv_lora_rank``, the three head widths, ``rope_theta``, ``rms_norm_eps``,
    ``hidden_size`` and ``sm_scale`` of its config (models/longcat_flash.py
    hands it its own). ``latent_scale`` multiplies the normed latent before
    it is cached and expanded (LongCat-Flash's ``mla_scale_kv_lora``), so
    the cache holds the scaled row.

    Learned sparse attention (models/glm_moe_dsa.py): ``indexer`` is the
    layer's indexer module (``indexer(config, name="indexer")(x, c_q,
    positions, cache, cache_pos) -> (selection, index_k)``, handed the normed
    low-rank query); ``selection`` (models/llama.py
    ``update_index_cache_and_select``: a row mask, or a paged lane's row ids)
    restricts the attention to the rows it keeps — the indexer's own, or
    another layer's where this one has none. With either the call returns
    ``(out, new_cache, selection)``, the cache with the indexer's ``index_k``
    in it."""
    config: PanguUltraMoeConfig
    latent_scale: float = 1.0
    indexer: Optional[type] = None

    @nn.compact
    def __call__(self, x, positions, cache=None, cache_pos=None, selection=None):
        from ..ops.attention import _einsum_attention

        cfg = self.config
        B, S, _ = x.shape
        H, rank = cfg.num_attention_heads, cfg.kv_lora_rank
        dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        with program_part("attn_mla"):
            cos, sin = rotary_embedding(positions, dr, cfg.rope_theta)         # float32
            with program_part("mla_q"):
                c_q = RMSNorm(cfg.rms_norm_eps, name="q_a_norm")(
                    _Kernel(cfg.q_lora_rank, name="q_a_proj")(x))
                q = _Kernel(H * (dn + dr), name="q_b_proj")(c_q).reshape(B, S, H, dn + dr)
                q_nope = q[..., :dn]
                q_rope = apply_rotary(q[..., dn:].astype(jnp.float32), cos, sin).astype(x.dtype)
            with program_part("mla_latent"):
                kv = _Kernel(rank + dr, name="kv_a_proj")(x)
                c_kv = RMSNorm(cfg.rms_norm_eps, name="kv_a_norm")(kv[..., :rank])
                if self.latent_scale != 1.0:
                    c_kv = (c_kv.astype(jnp.float32) * self.latent_scale).astype(x.dtype)
                k_r = apply_rotary(kv[..., None, rank:].astype(jnp.float32), cos, sin)[:, :, 0]
                k_r = k_r.astype(x.dtype)                 # c_kv and k_r are what is cached
            w_ukv = _RawKernel(rank, H * (dn + dv), name="kv_b_proj")().reshape(rank, H, dn + dv)
            w_uk, w_uv = w_ukv[..., :dn], w_ukv[..., dn:]
            new_cache = index_k = None
            if self.indexer is not None:             # dsa_index and dsa_select: models/llama.py
                selection, index_k = self.indexer(cfg, name="indexer")(
                    x, c_q, positions, cache, cache_pos)
            if cache is not None:                    # kv_attn and kv_write: models/llama.py
                out, new_cache = update_latent_cache_and_attend(
                    cache, q_nope, q_rope, c_kv, k_r, w_uk, w_uv, cache_pos, cfg.sm_scale,
                    select=selection)
                if index_k is not None:
                    new_cache = dict(new_cache, index_k=index_k)
            elif selection is not None:              # the call's own rows, under the selection
                with program_part("kv_attn"):
                    out = _latent_cached_attention(q_nope, q_rope, c_kv, k_r, w_uk, w_uv, 0,
                                                   cfg.sm_scale, selection)
            else:                                    # the plain form: every head's key and value
                k_nope = jnp.einsum("bsr,rhd->bshd", c_kv, w_uk.astype(x.dtype))
                k = jnp.concatenate(
                    [k_nope, jnp.broadcast_to(k_r[:, :, None], (B, S, H, dr))], -1)
                v = jnp.einsum("bsr,rhd->bshd", c_kv, w_uv.astype(x.dtype))
                out = _einsum_attention(jnp.concatenate([q_nope, q_rope], -1), k, v,
                                        causal=True, sm_scale=cfg.sm_scale)
            with program_part("mla_out"):
                out = _Kernel(cfg.hidden_size, name="o_proj")(out.reshape(B, S, H * dv))
        if self.indexer is not None or selection is not None:
            return out, new_cache, selection
        return out, new_cache


class _RawKernel(nn.Module):
    """``<name>/kernel`` handed out as it is: for a projection the caller
    applies in parts (``kv_b_proj``) or in float32 (the head)."""
    in_features: int
    features: int

    @nn.compact
    def __call__(self):
        return self.param("kernel", nn.initializers.lecun_normal(),
                          (self.in_features, self.features), jnp.float32)


class _SwiGLU(nn.Module):
    """gate/up/down projections stored as ``<name>/kernel`` (the dense
    layers' MLP and the shared experts)."""
    width: int

    @nn.compact
    def __call__(self, x):
        gate = _Kernel(self.width, name="gate_proj")(x)
        up = _Kernel(self.width, name="up_proj")(x)
        return _Kernel(x.shape[-1], name="down_proj")(jax.nn.silu(gate) * up)


class PanguMoeMLP(nn.Module):
    """Router over all experts + the held routed experts (scaled) + the
    shared experts. Sows the layer's pick counts into ``moe_stats``."""
    config: PanguUltraMoeConfig

    @nn.compact
    def __call__(self, x):
        from ..ops.moe import moe_held_apply

        cfg = self.config
        D, F = cfg.hidden_size, cfg.moe_intermediate_size
        first, count = cfg.held
        router = self.param("router", nn.initializers.lecun_normal(),
                            (D, cfg.num_experts), jnp.float32)
        experts = _ExpertStacks(count, D, F, name="experts")()
        # moe_held_apply names its two parts moe_router and moe_experts
        routed, stats = moe_held_apply(
            experts, router, x, top_k=cfg.num_experts_per_tok, scores="sigmoid",
            normalize_gates=cfg.norm_topk_prob, held=(first, count))
        self.sow("moe_stats", "picks", stats["picks"])
        with program_part("moe_shared"):
            shared = _SwiGLU(cfg.n_shared_experts * F, name="shared_experts")(x)
        return routed * cfg.routed_scaling_factor + shared


class PanguBlock(nn.Module):
    config: PanguUltraMoeConfig
    layer_idx: int = 0

    @nn.compact
    def __call__(self, x, positions, cache=None, cache_pos=None):
        cfg = self.config
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, name=name)          # noqa: E731
        attn, new_cache = PanguMLAttention(cfg, name="self_attn")(
            norm("input_norm")(x), positions, cache=cache, cache_pos=cache_pos)
        x = x + norm("post_attn_norm")(attn)
        n = norm("pre_mlp_norm")(x)
        if self.layer_idx < cfg.first_k_dense_replace:
            with program_part("mlp_dense"):
                mlp = _SwiGLU(cfg.intermediate_size, name="mlp")(n)
        else:
            mlp = PanguMoeMLP(cfg, name="mlp")(n)
        return x + norm("post_mlp_norm")(mlp), new_cache


class PanguUltraMoeForCausalLM(nn.Module):
    config: PanguUltraMoeConfig

    #: the variable collection the MoE layers sow their pick counts into; the
    #: serving engine asks for it and folds it into its counters.
    serving_stats_collection = "moe_stats"

    def init_cache(self, batch_size: int, max_len: int, dtype=jnp.bfloat16, ring_slack: int = 0):
        """The cache this family declares (``big_modeling.cache_factory_for``
        asks the module first): a token's latent and its rotary key a layer,
        no head axis. No layer has a window, so nothing rings and
        ``ring_slack`` is unused."""
        cfg = self.config
        return init_latent_cache(cfg.num_hidden_layers, batch_size, max_len,
                                 cfg.kv_lora_rank, cfg.qk_rope_head_dim, dtype)

    @nn.compact
    def __call__(self, input_ids, positions=None, cache=None, cache_pos=None):
        cfg = self.config
        if positions is None:
            start = 0 if cache_pos is None else cache_pos
            positions = start + jnp.arange(input_ids.shape[1], dtype=jnp.int32)[None, :]
            positions = jnp.broadcast_to(positions, input_ids.shape)
        with program_part("embed"):
            x = nn.Embed(cfg.vocab_size, cfg.hidden_size, name="embed_tokens",
                         param_dtype=jnp.float32)(input_ids)
        new_caches = []
        for i in range(cfg.num_hidden_layers):
            x, layer_cache = PanguBlock(cfg, layer_idx=i, name=f"layers_{i}")(
                x, positions, cache=None if cache is None else cache[i], cache_pos=cache_pos)
            new_caches.append(layer_cache)
        with program_part("lm_head"):
            x = RMSNorm(cfg.rms_norm_eps, name="norm")(x)
            head = _RawKernel(cfg.hidden_size, cfg.vocab_size, name="lm_head")()
            logits = jnp.einsum("bsh,hv->bsv", x, head.astype(x.dtype),
                                preferred_element_type=jnp.float32)
        if cache is not None:
            return logits, tuple(new_caches)
        return logits

    def init_params(self, rng, batch_size=1, seq_len=8):
        dummy = jnp.zeros((batch_size, seq_len), jnp.int32)
        return self.init(rng, dummy)["params"]
