"""LongCat-Flash decoder family (the language model of LongCat-Flash-Omni,
published config keys), serving-first.

A layer is a DOUBLE layer: two latent attentions (MLA), two dense SwiGLU MLPs
and one expert layer whose branch is shortcut-connected (ScMoE): it reads the
first half's normed input and joins the residual only at the layer's end.
Four RMSNorms a layer::

    h1 = x  + attn0(norm_in0(x))
    n1 = norm_post0(h1)
    m  = moe(n1)                                # the shortcut branch
    h2 = h1 + mlp0(n1)
    h3 = h2 + attn1(norm_in1(h2))
    y  = h3 + mlp1(norm_post1(h3)) + m

* MLA as openPangu's (models/pangu_ultra_moe.py ``PanguMLAttention``: a
  low-rank query, one joint latent of keys and values, one rotary key shared
  by all heads) with LongCat's low-rank scales: the query, both its parts, is
  multiplied by ``(hidden / q_lora_rank) ** 0.5`` (2 at the published widths:
  folded into ``sm_scale``, exactly, since it is a power of two) and the normed
  latent by ``(hidden / kv_lora_rank) ** 0.5`` (12 ** 0.5) before it is
  cached and expanded — the cache holds the SCALED latent; the rotary key is
  not scaled. Each attention has its own cache entry: ``2 x num_layers``
  entries of ``kv_lora_rank + qk_rope_head_dim`` values a token
  (``init_cache``).
* The router scores ``n_routed_experts + zero_expert_num`` columns by a
  float32 softmax, picks the ``moe_topk`` largest of ``score +
  e_score_correction_bias`` and gates each pick by its score (not
  renormalised), times ``routed_scaling_factor``. The last ``zero_expert_num``
  columns are zero-compute experts: a pick returns the token itself (ops/
  moe.py ``moe_held_apply``'s ``identity_experts``). The routed experts held
  here are ``held_experts``; what the absent ones would add is left out.
* Final RMSNorm, logits against the head's own matrix (untied).

Readings the config does not settle: rotary pairs are ``(i, i + rope/2)``
(models/llama.py ``apply_rotary``); no rope scaling, so the softmax scale is
``(nope + rope) ** -0.5`` (times the query's scale, ``sm_scale``).
``norm_topk_prob`` is false (the lineage's default). The media encoders and
the codec decoder are not part of this model.

The program's parts (observability/program_parts.py): ``embed``, ``attn_mla``
(inside it ``mla_q`` / ``mla_latent`` / ``mla_out``, ``kv_attn`` /
``kv_write``), ``mlp_dense``, ``moe_router`` / ``moe_experts`` and
``lm_head``. The model follows the generation contract of
``MixtralForCausalLM``: ``(input_ids, positions, cache, cache_pos) -> logits,
cache``.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import flax.linen as nn
import jax.numpy as jnp

from ..observability.program_parts import program_part
from .cohere2_moe import _ExpertStacks
from .llama import RMSNorm, init_latent_cache
from .pangu_ultra_moe import PanguMLAttention, _RawKernel, _SwiGLU


@dataclasses.dataclass
class LongcatFlashConfig:
    vocab_size: int = 131072
    hidden_size: int = 6144
    ffn_hidden_size: int = 12288           # each dense MLP
    expert_ffn_hidden_size: int = 2048     # one routed expert
    num_layers: int = 28                   # double layers
    num_attention_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000000.0
    n_routed_experts: int = 512
    zero_expert_num: int = 256
    moe_topk: int = 12
    routed_scaling_factor: float = 6.0
    norm_topk_prob: bool = False
    #: ``(first, count)`` of the routed experts whose weights live here
    #: (one rank of an expert-parallel deployment); None = all of them.
    held_experts: Optional[tuple] = None

    @property
    def num_experts(self) -> int:
        """Routed experts with weights (the zero-compute ones have none)."""
        return self.n_routed_experts

    @property
    def router_width(self) -> int:
        return self.n_routed_experts + self.zero_expert_num

    @property
    def held(self) -> tuple:
        return (0, self.n_routed_experts) if self.held_experts is None else tuple(self.held_experts)

    @property
    def cache_row_width(self) -> int:
        """Values the cache holds a token an attention: the latent and the rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def q_scale(self) -> float:
        return (self.hidden_size / self.q_lora_rank) ** 0.5 if self.mla_scale_q_lora else 1.0

    @property
    def latent_scale(self) -> float:
        return (self.hidden_size / self.kv_lora_rank) ** 0.5 if self.mla_scale_kv_lora else 1.0

    @property
    def sm_scale(self) -> float:
        """The softmax scale with the query's scale in it (both query parts)."""
        return self.q_scale * (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5

    @classmethod
    def tiny(cls, **overrides):
        """Test size: two double layers, 8 routed experts + 4 zero-compute
        ones, top-3; hidden 64 over ranks of 16 and 8, so the scales are 2
        and 8 ** 0.5."""
        cfg = cls(vocab_size=256, hidden_size=64, ffn_hidden_size=96, expert_ffn_hidden_size=32,
                  num_layers=2, num_attention_heads=4, q_lora_rank=16, kv_lora_rank=8,
                  qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
                  max_position_embeddings=512, rope_theta=10000.0, n_routed_experts=8,
                  zero_expert_num=4, moe_topk=3)
        return dataclasses.replace(cfg, **overrides)


class LongcatMoE(nn.Module):
    """The shortcut branch: a softmax router over the routed and the
    zero-compute experts, with a selection bias, over the held routed
    experts; scaled by ``routed_scaling_factor``. Sows the layer's pick
    counts, the zero-compute picks last, into ``moe_stats``."""
    config: LongcatFlashConfig

    @nn.compact
    def __call__(self, x):
        from ..ops.moe import moe_held_apply

        cfg = self.config
        first, count = cfg.held
        router = self.param("router", nn.initializers.lecun_normal(),
                            (cfg.hidden_size, cfg.router_width), jnp.float32)
        bias = self.param("e_score_correction_bias", nn.initializers.zeros,
                          (cfg.router_width,), jnp.float32)
        experts = _ExpertStacks(count, cfg.hidden_size, cfg.expert_ffn_hidden_size,
                                name="experts")()
        out, stats = moe_held_apply(
            experts, router, x, top_k=cfg.moe_topk, scores="softmax",
            normalize_gates=cfg.norm_topk_prob, held=(first, count),
            identity_experts=cfg.zero_expert_num, selection_bias=bias)
        self.sow("moe_stats", "picks", jnp.concatenate([stats["picks"], stats["zero_picks"][None]]))
        return out * cfg.routed_scaling_factor


class LongcatDoubleLayer(nn.Module):
    config: LongcatFlashConfig

    @nn.compact
    def __call__(self, x, positions, cache=None, cache_pos=None):
        cfg = self.config
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, name=name)          # noqa: E731
        mla = lambda name: PanguMLAttention(cfg, latent_scale=cfg.latent_scale,  # noqa: E731
                                            name=name)
        c0, c1 = (None, None) if cache is None else cache
        a, new0 = mla("attn0")(norm("norm_in0")(x), positions, cache=c0, cache_pos=cache_pos)
        h = x + a
        n = norm("norm_post0")(h)
        shortcut = LongcatMoE(cfg, name="moe")(n)
        with program_part("mlp_dense"):
            h = h + _SwiGLU(cfg.ffn_hidden_size, name="mlp0")(n)
        a, new1 = mla("attn1")(norm("norm_in1")(h), positions, cache=c1, cache_pos=cache_pos)
        h = h + a
        with program_part("mlp_dense"):
            h = h + _SwiGLU(cfg.ffn_hidden_size, name="mlp1")(norm("norm_post1")(h))
        return h + shortcut, (new0, new1)


class LongcatFlashForCausalLM(nn.Module):
    config: LongcatFlashConfig

    #: the variable collection the expert layers sow their pick counts into;
    #: the serving engine asks for it and folds it into its counters
    serving_stats_collection = "moe_stats"
    #: ... where the last of those counters is the zero-compute picks
    serving_zero_picks = True

    def init_cache(self, batch_size: int, max_len: int, dtype=jnp.bfloat16, ring_slack: int = 0):
        """The cache this family declares: one entry an ATTENTION, two a
        double layer (entries ``2i`` and ``2i + 1`` are layer ``i``'s
        ``attn0`` and ``attn1``), each a token's scaled latent and its rotary
        key, no head axis. No layer has a window: ``ring_slack`` is unused."""
        cfg = self.config
        return init_latent_cache(2 * cfg.num_layers, batch_size, max_len,
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
        for i in range(cfg.num_layers):
            x, layer_cache = LongcatDoubleLayer(cfg, name=f"layers_{i}")(
                x, positions, cache=None if cache is None else cache[2 * i: 2 * i + 2],
                cache_pos=cache_pos)
            new_caches.extend(layer_cache)
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
