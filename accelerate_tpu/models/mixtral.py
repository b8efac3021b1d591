"""Mixtral-family sparse-MoE decoder, TPU-first.

The reference framework has no MoE model or runtime (its only MoE touchpoint
forwards module names to DeepSpeed, reference: accelerator.py:1736); this
family exercises the net-new expert-parallel path end-to-end: Llama backbone
(RMSNorm / RoPE / GQA attention shared from models/llama.py) with the MLP
replaced by the GShard-style sparse expert layer in ops/moe.py, expert
weights stacked ``[E, ...]`` and sharded over the ``ep`` mesh axis.

The model returns ``(logits, aux)`` where ``aux`` carries the router losses;
use :func:`mixtral_lm_loss` to fold them into training.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import flax.linen as nn

from ..observability.program_parts import program_part
from .llama import LlamaAttention, LlamaConfig, RMSNorm


@dataclasses.dataclass
class MixtralConfig(LlamaConfig):
    num_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.02
    router_z_coef: float = 0.001
    # multiplicative jitter on router logits during training (Switch §2.2);
    # active only when the caller provides a 'router' rng collection.
    router_noise_eps: float = 0.0
    # None = one routing group per data shard (ops/moe.py default_num_groups)
    num_expert_groups: Optional[int] = None
    # Qwen2-MoE-style knobs (HF qwen2_moe):
    # * norm_topk_prob: renormalize the selected top-k gate weights (None =
    #   GShard default, True iff top_k > 1; Qwen2-MoE ships False).
    # * shared_expert_intermediate_size: an always-on SwiGLU expert whose
    #   output is added scaled by a learned per-token sigmoid gate.
    # * mlp_only_layers: layer indices using a plain dense MLP of width
    #   dense_intermediate_size instead of the sparse expert layer.
    norm_topk_prob: Optional[bool] = None
    shared_expert_intermediate_size: Optional[int] = None
    mlp_only_layers: tuple = ()
    dense_intermediate_size: Optional[int] = None

    @classmethod
    def mixtral_8x7b(cls, **overrides):
        cfg = cls(
            vocab_size=32000, hidden_size=4096, intermediate_size=14336,
            num_hidden_layers=32, num_attention_heads=32, num_key_value_heads=8,
            max_position_embeddings=32768, rope_theta=1e6,
            num_experts=8, top_k=2,
        )
        return dataclasses.replace(cfg, **overrides)

    @classmethod
    def tiny_moe(cls, **overrides):
        cfg = cls(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=128, num_experts=4, top_k=2,
            num_expert_groups=1,
        )
        return dataclasses.replace(cfg, **overrides)


def _zero_router_losses() -> dict:
    return {"load_balance_loss": jnp.zeros((), jnp.float32),
            "router_z_loss": jnp.zeros((), jnp.float32)}


class MixtralSparseMLP(nn.Module):
    """Router + stacked SwiGLU experts, on one of ``ops.moe``'s two paths,
    chosen by what the call observes: without a cache (the trainer)
    ``moe_mlp_apply`` with ``capacity_factor`` slots an expert; under a cache
    (serving, ``generate``) ``moe_held_apply`` with every expert held — no
    token dropped, only routed rows computed.

    Capacity dropping is a *training* throughput trade (static shapes under
    load imbalance), and the path with a backward. Token counts differ
    between prefill/decode and a full forward, so only a path that drops
    nothing makes cached generation faithful to the model; the block passes
    ``cached=True`` when it is given a cache (the serving engine's programs,
    speculative verify, ``generate``). That path sows its pick counters
    (``moe_held_apply``'s ``stats["picks"]``) into ``moe_stats`` and returns
    zero router losses: nothing reads them under a cache."""

    config: MixtralConfig

    @nn.compact
    def __call__(self, x, cached: bool = False):
        from ..ops.moe import moe_held_apply, moe_mlp_apply

        cfg = self.config
        D, F, E = cfg.hidden_size, cfg.intermediate_size, cfg.num_experts
        router = self.param("router", nn.initializers.lecun_normal(), (D, E), jnp.float32)
        init = nn.initializers.lecun_normal(batch_axis=(0,))

        class Experts(nn.Module):
            @nn.compact
            def __call__(self_inner):
                return {
                    "gate_proj": self_inner.param("gate_proj", init, (E, D, F), jnp.float32),
                    "up_proj": self_inner.param("up_proj", init, (E, D, F), jnp.float32),
                    "down_proj": self_inner.param("down_proj", init, (E, F, D), jnp.float32),
                }

        experts = Experts(name="experts")()
        if cached:
            normalize = cfg.top_k > 1 if cfg.norm_topk_prob is None else cfg.norm_topk_prob
            out, stats = moe_held_apply(
                experts, router, x, top_k=cfg.top_k, scores="softmax",
                normalize_gates=normalize, held=None)
            self.sow("moe_stats", "picks", stats["picks"])
            aux = _zero_router_losses()
        else:
            router_noise_rng = (
                self.make_rng("router")
                if cfg.router_noise_eps > 0.0 and self.has_rng("router")
                else None
            )
            out, aux = moe_mlp_apply(
                experts,
                router,
                x,
                top_k=cfg.top_k,
                capacity_factor=cfg.capacity_factor,
                num_groups=cfg.num_expert_groups,
                router_noise_rng=router_noise_rng,
                router_noise_eps=cfg.router_noise_eps,
                normalize_gates=cfg.norm_topk_prob,
            )
        if cfg.shared_expert_intermediate_size:
            # Qwen2-MoE shared expert: always-on SwiGLU, sigmoid-gated per
            # token — rides alongside the routed experts, no dispatch.
            Fs = cfg.shared_expert_intermediate_size
            dense = lambda feats, name: nn.Dense(  # noqa: E731
                feats, use_bias=False, name=name, dtype=x.dtype, param_dtype=jnp.float32)
            with program_part("moe_shared"):
                gate_h = dense(Fs, "shared_gate_proj")(x)
                up_h = dense(Fs, "shared_up_proj")(x)
                shared = dense(D, "shared_down_proj")(jax.nn.silu(gate_h) * up_h)
                gate_logit = dense(1, "shared_expert_gate")(x)
                out = out + jax.nn.sigmoid(gate_logit.astype(jnp.float32)).astype(out.dtype) * shared
        return out, aux


class MixtralBlock(nn.Module):
    config: MixtralConfig
    layer_idx: int = 0

    @nn.compact
    def __call__(self, x, positions, cache=None, cache_pos=None):
        cfg = self.config
        attn = LlamaAttention(cfg, window=cfg.window_for(self.layer_idx),
                              name="self_attn")(
            RMSNorm(cfg.rms_norm_eps, name="input_norm")(x), positions,
            cache=cache, cache_pos=cache_pos,
        )
        new_cache = None
        if cache is not None:
            attn, new_cache = attn
        h = x + attn
        normed = RMSNorm(cfg.rms_norm_eps, name="post_attn_norm")(h)
        if self.layer_idx in cfg.mlp_only_layers:
            # Dense layer (Qwen2-MoE mlp_only_layers / decoder_sparse_step):
            # a plain SwiGLU of dense_intermediate_size, zero router losses.
            import dataclasses as _dc

            from .llama import LlamaMLP

            dense_cfg = _dc.replace(
                cfg, intermediate_size=cfg.dense_intermediate_size or cfg.intermediate_size)
            mlp_out = LlamaMLP(dense_cfg, name="mlp")(normed)
            aux = _zero_router_losses()
        else:
            mlp_out, aux = MixtralSparseMLP(cfg, name="mlp")(normed, cached=cache is not None)
        out = h + mlp_out
        return (out, aux) if cache is None else (out, aux, new_cache)


class MixtralForCausalLM(nn.Module):
    config: MixtralConfig

    #: the variable collection the expert layers sow their pick counts into
    #: under a cache; the serving engine asks for it and folds it into its
    #: counters (``moe_tile_fill``, ``moe_load_max_over_mean``).
    serving_stats_collection = "moe_stats"

    @nn.compact
    def __call__(self, input_ids, positions=None, cache=None, cache_pos=None):
        cfg = self.config
        if positions is None:
            start = 0 if cache_pos is None else cache_pos
            positions = start + jnp.arange(input_ids.shape[1], dtype=jnp.int32)[None, :]
            positions = jnp.broadcast_to(positions, input_ids.shape)
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, name="embed_tokens", param_dtype=jnp.float32)
        with program_part("embed"):
            x = embed(input_ids)
        block_cls = MixtralBlock
        if cfg.remat:
            from ..parallel.sharding import resolve_remat_policy

            block_cls = nn.remat(MixtralBlock, policy=resolve_remat_policy(cfg.remat_policy))
        lb = jnp.zeros((), jnp.float32)
        zl = jnp.zeros((), jnp.float32)
        new_caches = []
        for i in range(cfg.num_hidden_layers):
            if cache is None:
                x, aux = block_cls(cfg, layer_idx=i, name=f"layers_{i}")(x, positions)
            else:
                x, aux, layer_cache = block_cls(cfg, layer_idx=i, name=f"layers_{i}")(
                    x, positions, cache=cache[i], cache_pos=cache_pos
                )
                new_caches.append(layer_cache)
            lb = lb + aux["load_balance_loss"]
            zl = zl + aux["router_z_loss"]
        with program_part("lm_head"):
            x = RMSNorm(cfg.rms_norm_eps, name="norm")(x)
            if cfg.tie_word_embeddings:
                emb = self.variables["params"]["embed_tokens"]["embedding"]
                logits = x @ emb.T.astype(x.dtype)
            else:
                logits = nn.Dense(
                    cfg.vocab_size, use_bias=False, name="lm_head", dtype=x.dtype,
                    param_dtype=jnp.float32)(x)
        n = cfg.num_hidden_layers
        if cache is not None:
            # Decode path: router losses are a training quantity; return the
            # generation contract (logits, new_cache).
            return logits, tuple(new_caches)
        return logits, {"load_balance_loss": lb / n, "router_z_loss": zl / n}

    def init_params(self, rng, batch_size=1, seq_len=8):
        """Initialize a parameter pytree from a PRNG key (shape-driving args are traced-free)."""
        dummy = jnp.zeros((batch_size, seq_len), jnp.int32)
        return self.init(rng, dummy)["params"]


def mixtral_lm_loss(apply_fn, config: MixtralConfig):
    """Next-token cross-entropy + Switch router losses, weighted per config.

    The per-step rng (provided by ``compile_train_step``) feeds the 'router'
    rng collection, activating router jitter when
    ``config.router_noise_eps > 0``.
    """
    from .llama import masked_next_token_ce

    def loss_fn(params, batch, rng=None):
        variables = params if isinstance(params, dict) and "params" in params else {"params": params}
        rngs = {"router": rng} if (rng is not None and config.router_noise_eps > 0.0) else {}
        logits, aux = apply_fn(variables, batch["input_ids"], rngs=rngs)
        ce = masked_next_token_ce(logits, batch)
        return (
            ce
            + config.router_aux_coef * aux["load_balance_loss"]
            + config.router_z_coef * aux["router_z_loss"]
        )

    return loss_fn
