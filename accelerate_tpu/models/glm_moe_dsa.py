"""GLM-5 decoder family (``model_type`` ``glm_moe_dsa``), serving-first.

One layer, from the published config keys, pre-norm:
``h = x + MLA(RMSNorm(x))``, ``y = h + MLP(RMSNorm(h))``.

* latent attention (MLA) as openPangu's (models/pangu_ultra_moe.py
  ``PanguMLAttention``, without sandwich norms): a low-rank query
  (``q_lora_rank``), one joint latent of keys and values (``kv_lora_rank``,
  RMSNormed) and one rotated key of ``qk_rope_head_dim`` shared by all heads.
  The cache holds the latent and the rotated key of every token a layer;
* learned sparse attention (DeepSeek-V3.2's DSA, which ``glm_moe_dsa``
  carries) on layers whose ``indexer_types`` entry is ``"full"``: the indexer
  (:class:`DsaIndexer`) scores every visible row ``s`` of query ``t`` as
  ``I(t, s) = sum_j w[t, j] relu(q_I[t, j] . k_I[s])`` — ``index_n_heads``
  queries of ``index_head_dim`` from MLA's normed low-rank query, one
  LayerNormed key a token (cached beside the latent: ``index_k``), weights
  ``w`` from the hidden state times ``index_n_heads ** -0.5 *
  index_head_dim ** -0.5`` — and MLA's softmax runs over the ``index_topk``
  rows of largest score (every visible row where there are no more;
  models/llama.py ``update_index_cache_and_select``). A ``"shared"`` layer
  has no indexer: it attends over the rows the nearest full layer before it
  picked (IndexShare). The config's ``indexer_types`` says which layer is
  which; nothing else selects the sparse path;
* ``mlp_layer_types`` ``"dense"``: a SwiGLU MLP of ``intermediate_size``;
  ``"sparse"``: a sigmoid-scored router of the model's full width that picks
  the top ``num_experts_per_tok`` of score + ``e_score_correction_bias``,
  gates each pick by its score, renormalised (``norm_topk_prob``) and scaled
  by ``routed_scaling_factor``, over the routed experts HELD here
  (``held_experts``; ops/moe.py ``moe_held_apply``), plus
  ``n_shared_experts`` always-on experts;
* final RMSNorm, logits against the head's own matrix (untied).

Readings the config does not settle: MLA's and the indexer's rotary pairs
are ``(i, i + rope/2)`` (models/llama.py ``apply_rotary``), the indexer's
rotated values are the first ``qk_rope_head_dim`` of its ``index_head_dim``
(V3.2's split); ``rope_type`` default, so MLA's softmax scale is
``(qk_nope_head_dim + qk_rope_head_dim) ** -0.5``; the indexer's key norm is
a LayerNorm with a bias, eps 1e-6 (V3.2's). Index keys are held in the
cache's type (V3.2 quantises them to float8 under a Hadamard rotation: a
deployment's precision choice). The multi-token-prediction module
(``num_nextn_predict_layers``) is not part of this model.

The program's parts (observability/program_parts.py): ``embed``,
``attn_mla`` (inside it ``mla_q`` / ``mla_latent`` / ``mla_out``,
``dsa_index`` / ``dsa_select`` and ``kv_attn`` / ``kv_write``),
``mlp_dense``, ``moe_router`` / ``moe_experts``, ``moe_shared`` and
``lm_head``. The model follows the generation contract of
``MixtralForCausalLM``: ``(input_ids, positions, cache, cache_pos) -> logits,
cache``.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..observability.program_parts import program_part
from .cohere2_moe import _ExpertStacks, _Kernel
from .llama import (RMSNorm, apply_rotary, rotary_embedding, update_index_cache_and_select)
from .pangu_ultra_moe import PanguMLAttention, _RawKernel, _SwiGLU


def published_indexer_types(num_layers: int = 78, offset: int = 3, freq: int = 4) -> tuple:
    """The published ``indexer_types`` list: layers before ``offset`` full,
    then one full layer closing each period of ``freq``."""
    return tuple("full" if i < offset or (i - offset) % freq == freq - 1 else "shared"
                 for i in range(num_layers))


@dataclasses.dataclass
class GlmMoeDsaConfig:
    vocab_size: int = 154880
    hidden_size: int = 6144
    intermediate_size: int = 12288         # a dense layer's MLP
    moe_intermediate_size: int = 2048      # one routed expert; the shared one likewise
    num_hidden_layers: int = 78
    num_attention_heads: int = 64
    q_lora_rank: int = 2048
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    index_n_heads: int = 32
    index_head_dim: int = 128
    index_topk: int = 2048
    max_position_embeddings: int = 1048576
    rms_norm_eps: float = 1e-5
    rope_theta: float = 8000000.0
    #: the router's width: the routed experts of the whole model.
    num_experts: int = 256
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    #: one entry a layer held here: "dense" or "sparse" (the published list,
    #: from the 78 layers' first: 3 dense)
    mlp_layer_types: tuple = ("dense",) * 3 + ("sparse",) * 75
    #: one entry a layer held here: "full" (own indexer) or "shared" (the
    #: indices of the nearest full layer before it)
    indexer_types: tuple = published_indexer_types()
    #: ``(first, count)`` of the routed experts whose weights live here
    #: (one rank of an expert-parallel deployment); None = all of them.
    held_experts: Optional[tuple] = None

    def __post_init__(self):
        n = self.num_hidden_layers
        if len(self.mlp_layer_types) != n or len(self.indexer_types) != n:
            raise ValueError(f"{n} layers need {n} mlp_layer_types and indexer_types, got "
                             f"{len(self.mlp_layer_types)} and {len(self.indexer_types)}")
        if self.indexer_types and self.indexer_types[0] != "full":
            raise ValueError("the first layer held has no full layer before it to share "
                             f"indices with: indexer_types[0] is {self.indexer_types[0]!r}")

    @property
    def held(self) -> tuple:
        return (0, self.num_experts) if self.held_experts is None else tuple(self.held_experts)

    @property
    def sm_scale(self) -> float:
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5

    @classmethod
    def tiny(cls, **overrides):
        """Test size: one dense layer and four expert layers whose indexers
        are [full, shared, shared, full, shared]; 8 experts top-2; ``index_topk``
        8, so a context of a few dozen tokens is selected from."""
        cfg = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
                   moe_intermediate_size=32, num_hidden_layers=5, num_attention_heads=4,
                   q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
                   v_head_dim=8, index_n_heads=4, index_head_dim=8, index_topk=8,
                   max_position_embeddings=512, rope_theta=10000.0, num_experts=8,
                   num_experts_per_tok=2, mlp_layer_types=("dense",) + ("sparse",) * 4,
                   indexer_types=("full", "shared", "shared", "full", "shared"))
        cfg.update(overrides)
        return cls(**cfg)


class _LayerNorm(nn.Module):
    """LayerNorm with a scale and a bias, in float32 (the indexer's key norm)."""
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (x.shape[-1],), jnp.float32)
        f = x.astype(jnp.float32)
        mean = f.mean(-1, keepdims=True)
        var = jnp.square(f - mean).mean(-1, keepdims=True)
        return ((f - mean) * jax.lax.rsqrt(var + self.eps) * scale + bias).astype(x.dtype)


class DsaIndexer(nn.Module):
    """The learned indexer of one full layer: its queries from MLA's normed
    low-rank query ``c_q``, its key and weights from the layer's normed
    input ``x``; the selection itself is models/llama.py
    ``update_index_cache_and_select``. Returns ``(selection, index_k)``."""
    config: GlmMoeDsaConfig

    @nn.compact
    def __call__(self, x, c_q, positions, cache, cache_pos):
        cfg = self.config
        B, S, _ = x.shape
        Hi, D, dr = cfg.index_n_heads, cfg.index_head_dim, cfg.qk_rope_head_dim
        with program_part("dsa_index"):
            cos, sin = rotary_embedding(positions, dr, cfg.rope_theta)

            def rotate(t):            # the first rope values of each head rotate
                turned = apply_rotary(t[..., :dr].astype(jnp.float32), cos, sin)
                return jnp.concatenate([turned.astype(t.dtype), t[..., dr:]], -1)

            q = rotate(_Kernel(Hi * D, name="wq_b")(c_q).reshape(B, S, Hi, D))
            k = _LayerNorm(name="k_norm")(_Kernel(D, name="wk")(x))
            k = rotate(k[:, :, None])[:, :, 0]
            w_kernel = _RawKernel(cfg.hidden_size, Hi, name="weights_proj")()
            w = (x.astype(jnp.float32) @ w_kernel.astype(jnp.float32)) * (Hi ** -0.5 * D ** -0.5)
        return update_index_cache_and_select(cache, q, w, k, cache_pos, cfg.index_topk)


class GlmMoE(nn.Module):
    """Router over all experts (sigmoid scores, picks by score + correction
    bias) + the held routed experts (scaled) + the shared experts. Sows the
    layer's pick counts into ``moe_stats``."""
    config: GlmMoeDsaConfig

    @nn.compact
    def __call__(self, x):
        from ..ops.moe import moe_held_apply

        cfg = self.config
        D, F = cfg.hidden_size, cfg.moe_intermediate_size
        first, count = cfg.held
        router = self.param("router", nn.initializers.lecun_normal(),
                            (D, cfg.num_experts), jnp.float32)
        bias = self.param("e_score_correction_bias", nn.initializers.zeros,
                          (cfg.num_experts,), jnp.float32)
        experts = _ExpertStacks(count, D, F, name="experts")()
        routed, stats = moe_held_apply(
            experts, router, x, top_k=cfg.num_experts_per_tok, scores="sigmoid",
            normalize_gates=cfg.norm_topk_prob, held=(first, count), selection_bias=bias)
        self.sow("moe_stats", "picks", stats["picks"])
        with program_part("moe_shared"):
            shared = _SwiGLU(cfg.n_shared_experts * F, name="shared_experts")(x)
        return routed * cfg.routed_scaling_factor + shared


class GlmBlock(nn.Module):
    config: GlmMoeDsaConfig
    layer_idx: int = 0

    @nn.compact
    def __call__(self, x, positions, cache=None, cache_pos=None, selection=None):
        """-> ``(y, new_cache, selection)``: the selection this layer's
        attention read (its own indexer's on a full layer, the one it was
        handed on a shared layer), for the shared layers after it."""
        cfg = self.config
        full = cfg.indexer_types[self.layer_idx] == "full"
        attn, new_cache, *picked = PanguMLAttention(
            cfg, indexer=DsaIndexer if full else None, name="self_attn")(
            RMSNorm(cfg.rms_norm_eps, name="input_layernorm")(x), positions, cache=cache,
            cache_pos=cache_pos, selection=None if full else selection)
        selection = picked[0] if picked else None     # a shared layer handed none reads all rows
        x = x + attn
        n = RMSNorm(cfg.rms_norm_eps, name="post_attention_layernorm")(x)
        if cfg.mlp_layer_types[self.layer_idx] == "dense":
            with program_part("mlp_dense"):
                mlp = _SwiGLU(cfg.intermediate_size, name="mlp")(n)
        else:
            mlp = GlmMoE(cfg, name="mlp")(n)
        return x + mlp, new_cache, selection


class GlmMoeDsaForCausalLM(nn.Module):
    config: GlmMoeDsaConfig

    #: the variable collection the MoE layers sow their pick counts into; the
    #: serving engine asks for it and folds it into its counters.
    serving_stats_collection = "moe_stats"

    @property
    def serving_index_topk(self) -> int:
        """Rows a query's latent attention reads at most (the engine's
        ``dsa_*`` counters; every layer attends over a selection)."""
        return self.config.index_topk

    def init_cache(self, batch_size: int, max_len: int, dtype=jnp.bfloat16, ring_slack: int = 0):
        """The cache this family declares (``big_modeling.cache_factory_for``
        asks the module first): a token's latent and its rotated key a layer,
        no head axis, and on a full layer its index key beside them
        (``{"index_k", "latent", "rope"}``; a shared layer's entry has no
        ``index_k``). No layer has a window: ``ring_slack`` is unused."""
        cfg = self.config

        def entry(kind):
            leaves = {"latent": jnp.zeros((batch_size, max_len, cfg.kv_lora_rank), dtype),
                      "rope": jnp.zeros((batch_size, max_len, cfg.qk_rope_head_dim), dtype)}
            if kind == "full":
                leaves["index_k"] = jnp.zeros((batch_size, max_len, cfg.index_head_dim), dtype)
            return leaves

        return tuple(entry(kind) for kind in cfg.indexer_types)

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
        new_caches, selection = [], None
        for i in range(cfg.num_hidden_layers):
            x, layer_cache, selection = GlmBlock(cfg, layer_idx=i, name=f"layers_{i}")(
                x, positions, cache=None if cache is None else cache[i], cache_pos=cache_pos,
                selection=selection)
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
