"""Cohere2-MoE decoder family (``model_type`` ``cohere2_moe``), serving-first.

One layer, from the published config keys:

* one bias-free LayerNorm feeds BOTH branches (``use_parallel_block``):
  ``y = x + attn(n) + routed(n) + shared(n)``;
* grouped-query attention whose width (heads x head_dim) is decoupled from
  the hidden size; ``sliding_attention`` layers rotate q/k by *interleaved*
  pairs ``(2i, 2i+1)`` (``rope_gptj``) and see a window, ``full_attention``
  layers carry NO positional encoding and see everything (causal);
* a router of the model's full width scores every expert by a sigmoid
  (``expert_selection_fn``), keeps the top-k and renormalises them
  (``norm_topk_prob``); the routed part is computed by the experts HELD here
  (``held_experts``, ops/moe.py ``moe_held_apply``) and what the absent
  experts would add is left out;
* ``num_shared_experts`` always-on SwiGLU experts, averaged
  (``shared_expert_combination_strategy`` "average");
* final LayerNorm, logits against the tied embedding times ``logit_scale``.

The serving engine holds the q and k projection kernels in another form than
the published one (``Cohere2MoeForCausalLM.served_form``): each head's
columns in half-split order, so that models/llama.py ``apply_rotary`` turns
the same pairs, and the q kernel ``[heads x head_dim, hidden]``. Checkpoints,
``generate``, the trainer and models/reference read the published form.

Attention's cache path is models/llama.py's (``update_kv_cache_and_attend``:
linear caches, rings for sliding layers outside the paged engine, the window
mask); the rotary layout and the absence of it on full layers are this
file's. The program's parts (observability/program_parts.py): ``embed``,
``attn_local`` / ``attn_global`` (models/llama.py's ``kv_attn`` / ``kv_write``
inside them), ``moe_router`` / ``moe_experts`` / ``moe_shared``, ``lm_head``;
``chipbench/op_scopes.py`` reads them out of a device trace. The model follows the generation contract of ``MixtralForCausalLM``:
``(input_ids, positions, cache, cache_pos) -> logits, cache``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..observability.program_parts import program_part
from .llama import (ServedForm, apply_rotary, multi_head_attention, rotary_embedding,
                    update_kv_cache_and_attend)

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass
class Cohere2MoeConfig:
    vocab_size: int = 262144
    hidden_size: int = 4096
    intermediate_size: int = 4096          # width of one routed / shared expert
    num_hidden_layers: int = 32
    num_attention_heads: int = 128
    num_key_value_heads: int = 8
    head_dim: int = 128
    max_position_embeddings: int = 200000
    layer_norm_eps: float = 1e-5
    rope_theta: float = 50000.0
    sliding_window: int = 4096
    #: per-layer kind; None = the published stack: three sliding layers,
    #: then one full layer, repeated (``layer_switch`` 4, ``local_attn_first``).
    layer_types: Optional[tuple] = None
    #: the router's width: the experts of the whole model.
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 4
    #: ``(first, count)`` of the routed experts whose weights live here
    #: (one rank of an expert-parallel deployment); None = all of them.
    held_experts: Optional[tuple] = None
    expert_selection_fn: str = "sigmoid"
    norm_topk_prob: bool = True
    logit_scale: float = 1.0
    use_flash_attention: bool = True

    def __post_init__(self):
        if self.layer_types is None:
            self.layer_types = tuple(FULL if (i + 1) % 4 == 0 else SLIDING
                                     for i in range(self.num_hidden_layers))
        self.layer_types = tuple(self.layer_types)[: self.num_hidden_layers]
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError("layer_types is shorter than num_hidden_layers")

    @property
    def held(self) -> tuple:
        return (0, self.num_experts) if self.held_experts is None else tuple(self.held_experts)

    def window_for(self, layer_idx: int):
        """Layer ``layer_idx``'s sliding window (None = full attention)."""
        return self.sliding_window if self.layer_types[layer_idx] == SLIDING else None

    @classmethod
    def tiny(cls, **overrides):
        """Test size: one period of four layers, window 8, 8 experts top-2."""
        cfg = cls(vocab_size=256, hidden_size=64, intermediate_size=32, num_hidden_layers=4,
                  num_attention_heads=8, num_key_value_heads=2, head_dim=16,
                  max_position_embeddings=512, sliding_window=8, num_experts=8,
                  num_experts_per_tok=2, num_shared_experts=2, use_flash_attention=False)
        return dataclasses.replace(cfg, **overrides)


class ScaleLayerNorm(nn.Module):
    """LayerNorm with a scale and no bias, computed in float32."""
    eps: float

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],), jnp.float32)
        h = x.astype(jnp.float32)
        h = h - h.mean(-1, keepdims=True)
        h = h * jax.lax.rsqrt(jnp.mean(jnp.square(h), -1, keepdims=True) + self.eps)
        return (h * scale.astype(jnp.float32)).astype(x.dtype)


def apply_rotary_interleaved(x, cos, sin):
    """x [B, S, heads, head_dim]; rotates the pairs ``(2i, 2i+1)`` (GPT-J
    layout), where models/llama.py ``apply_rotary`` pairs ``i`` with
    ``i + head_dim/2``. cos/sin: [B, S, head_dim/2]."""
    pairs = x.reshape(*x.shape[:-1], x.shape[-1] // 2, 2)
    even, odd = pairs[..., 0], pairs[..., 1]
    cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    out = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1)
    return out.reshape(x.shape)


class _Kernel(nn.Module):
    """A bias-free projection stored as ``<name>/kernel``, the layout
    nn.Dense gives (and hf_interop maps); computed in the input's type.
    ``outputs_first`` holds it ``[features, in]`` and contracts its second
    axis: the orientation the TPU compiler asks of the q kernel (16384 x 4096),
    which it otherwise copies into that orientation in every call (0.41 ms a
    layer in both serving programs: PERF.md section 6, PR 36)."""
    features: int
    outputs_first: bool = False

    @nn.compact
    def __call__(self, x):
        if self.outputs_first:
            kernel = self.param("kernel", nn.initializers.lecun_normal(in_axis=-1, out_axis=-2),
                                (self.features, x.shape[-1]), jnp.float32)
            return jnp.einsum("...h,nh->...n", x, kernel.astype(x.dtype))
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (x.shape[-1], self.features), jnp.float32)
        return x @ kernel.astype(x.dtype)


def regroup_rotary_pairs(kernel, head_dim: int, half_split: bool):
    """A kernel ``[in, heads x head_dim]`` with every head's columns regrouped:
    the interleaved rotary's pairs ``(2i, 2i+1)`` moved to where the half-split
    rotary looks for them, ``(i, i + head_dim/2)`` — a head's even columns,
    then its odd ones — or, ``half_split=False``, back. Applied to q and k
    alike it changes no score (a dot product does not depend on the order of
    its terms), and ``apply_rotary`` on the regrouped projection is
    ``apply_rotary_interleaved`` on the published one, regrouped."""
    split = (head_dim // 2, 2) if half_split else (2, head_dim // 2)
    heads = kernel.reshape(kernel.shape[0], -1, *split)
    return jnp.swapaxes(heads, -1, -2).reshape(kernel.shape)


class Cohere2Attention(nn.Module):
    config: Cohere2MoeConfig
    layer_idx: int = 0
    #: read the kernels in the served form (``served_form``)
    served: bool = False

    @nn.compact
    def __call__(self, x, positions, cache=None, cache_pos=None):
        cfg = self.config
        B, S, _ = x.shape
        n_q, n_kv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        window = cfg.window_for(self.layer_idx)
        with program_part("attn_local" if window is not None else "attn_global"):
            q = _Kernel(n_q * hd, outputs_first=self.served,
                        name="q_proj")(x).reshape(B, S, n_q, hd)
            k = _Kernel(n_kv * hd, name="k_proj")(x).reshape(B, S, n_kv, hd)
            v = _Kernel(n_kv * hd, name="v_proj")(x).reshape(B, S, n_kv, hd)
            if window is not None:          # full layers: no positional encoding
                cos, sin = rotary_embedding(positions, hd, cfg.rope_theta)     # float32
                rotate = apply_rotary if self.served else apply_rotary_interleaved
                q = rotate(q.astype(jnp.float32), cos, sin).astype(x.dtype)
                k = rotate(k.astype(jnp.float32), cos, sin).astype(x.dtype)
            new_cache = None
            if cache is not None:
                out, new_cache = update_kv_cache_and_attend(
                    cache, q, k, v, cache_pos, n_q // n_kv, sliding_window=window)
            else:
                out = multi_head_attention(q, k, v, causal=True,
                                           use_flash=cfg.use_flash_attention,
                                           sliding_window=window)
            out = _Kernel(cfg.hidden_size, name="o_proj")(out.reshape(B, S, n_q * hd))
        return out, new_cache


class _ExpertStacks(nn.Module):
    """Expert-major SwiGLU stacks ``[n, D, F]`` / ``[n, F, D]``."""
    n: int
    d_model: int
    width: int

    @nn.compact
    def __call__(self):
        init = nn.initializers.lecun_normal(batch_axis=(0,))
        n, d, f = self.n, self.d_model, self.width
        return {"gate_proj": self.param("gate_proj", init, (n, d, f), jnp.float32),
                "up_proj": self.param("up_proj", init, (n, d, f), jnp.float32),
                "down_proj": self.param("down_proj", init, (n, f, d), jnp.float32)}


class Cohere2MoeMLP(nn.Module):
    """Router over all experts + the held routed experts + the averaged
    shared experts. Sows the layer's pick counts into ``moe_stats`` (read by
    the serving engine when it asks for that collection; a no-op otherwise)."""
    config: Cohere2MoeConfig

    @nn.compact
    def __call__(self, x):
        from ..ops.moe import averaged_experts_apply, moe_held_apply

        cfg = self.config
        D, F = cfg.hidden_size, cfg.intermediate_size
        first, count = cfg.held
        router = self.param("router", nn.initializers.lecun_normal(),
                            (D, cfg.num_experts), jnp.float32)
        experts = _ExpertStacks(count, D, F, name="experts")()
        shared = _ExpertStacks(cfg.num_shared_experts, D, F, name="shared_experts")()
        # moe_held_apply names its two parts moe_router and moe_experts
        routed, stats = moe_held_apply(
            experts, router, x, top_k=cfg.num_experts_per_tok,
            scores=cfg.expert_selection_fn, normalize_gates=cfg.norm_topk_prob,
            held=(first, count))
        self.sow("moe_stats", "picks", stats["picks"])
        with program_part("moe_shared"):
            shared_out = averaged_experts_apply(shared, x)
        return routed, shared_out


class Cohere2MoeBlock(nn.Module):
    config: Cohere2MoeConfig
    layer_idx: int = 0
    served: bool = False

    @nn.compact
    def __call__(self, x, positions, cache=None, cache_pos=None):
        cfg = self.config
        normed = ScaleLayerNorm(cfg.layer_norm_eps, name="input_norm")(x)
        attn, new_cache = Cohere2Attention(cfg, self.layer_idx, self.served, name="self_attn")(
            normed, positions, cache=cache, cache_pos=cache_pos)
        routed, shared = Cohere2MoeMLP(cfg, name="mlp")(normed)
        return x + attn + routed + shared, new_cache          # the parallel block


class Cohere2MoeForCausalLM(nn.Module):
    config: Cohere2MoeConfig
    #: the tree is in the served form (``served_form``), not the published
    served: bool = False

    #: the variable collection the MoE layers sow their pick counts into; the
    #: serving engine asks for it and folds it into its counters.
    serving_stats_collection = "moe_stats"

    @nn.compact
    def __call__(self, input_ids, positions=None, cache=None, cache_pos=None):
        cfg = self.config
        if positions is None:
            start = 0 if cache_pos is None else cache_pos
            positions = start + jnp.arange(input_ids.shape[1], dtype=jnp.int32)[None, :]
            positions = jnp.broadcast_to(positions, input_ids.shape)
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, name="embed_tokens",
                         param_dtype=jnp.float32)
        with program_part("embed"):
            x = embed(input_ids)
        new_caches = []
        for i in range(cfg.num_hidden_layers):
            x, layer_cache = Cohere2MoeBlock(cfg, layer_idx=i, served=self.served, name=f"layers_{i}")(
                x, positions, cache=None if cache is None else cache[i], cache_pos=cache_pos)
            new_caches.append(layer_cache)
        with program_part("lm_head"):
            x = ScaleLayerNorm(cfg.layer_norm_eps, name="norm")(x)
            emb = self.variables["params"]["embed_tokens"]["embedding"]
            logits = jnp.einsum("bsh,vh->bsv", x, emb.astype(x.dtype),
                                preferred_element_type=jnp.float32) * cfg.logit_scale
        if cache is not None:
            return logits, tuple(new_caches)
        return logits

    def init_params(self, rng, batch_size=1, seq_len=8):
        dummy = jnp.zeros((batch_size, seq_len), jnp.int32)
        return self.init(rng, dummy)["params"]

    def served_form(self) -> Optional[ServedForm]:
        """The form in which the serving engine holds this family's weights
        (it asks once, at load): on sliding layers the columns of every q and
        k head in half-split order, on every layer the q kernel transposed to
        ``[heads x head_dim, hidden]``. In the published form both serving
        programs laid each 134 MB q kernel out anew in every call, once for
        the orientation and once more for the interleaved pairs. The K pages
        of a served engine hold keys in the permuted order; v, ``o_proj`` and
        every logit are the published form's. None for the module that
        already reads the served tree."""
        if self.served:
            return None
        cfg = self.config

        def reform(params, served: bool):
            # a leaf may be a pytree of arrays laid out alike (an int8 kernel
            # and its per-column scales): every array of it moves the same way
            out = dict(params)
            for i in range(cfg.num_hidden_layers):
                layer = params[f"layers_{i}"]
                attn = dict(layer["self_attn"])
                if cfg.window_for(i) is not None:            # full layers: no rotary
                    regroup = lambda a: regroup_rotary_pairs(a, cfg.head_dim, half_split=served)
                    attn["k_proj"] = {"kernel": jax.tree.map(regroup, attn["k_proj"]["kernel"])}
                else:
                    regroup = lambda a: a
                turn = (lambda a: regroup(a).T) if served else (lambda a: regroup(a.T))
                attn["q_proj"] = {"kernel": jax.tree.map(turn, attn["q_proj"]["kernel"])}
                out[f"layers_{i}"] = dict(layer, self_attn=attn)
            return out

        to_served = functools.partial(reform, served=True)
        to_published = functools.partial(reform, served=False)

        # parent=None: a module made inside a method would else become its child
        return ServedForm(Cohere2MoeForCausalLM(cfg, served=True, parent=None),
                          to_served, to_published)
