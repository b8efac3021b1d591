"""Plain reference of the ``pangu_ultra_moe`` family: one full forward pass in
``jax.numpy`` and float32 at ``highest`` matmul precision — no cache, no
kernels, no batching, no absorbed form: every head's key and value are
expanded from the latent and scored plainly, every held expert is computed
for every token and weighted by its gate (zero where the token did not pick
it).

It follows the published config (FreedomIntelligence/openPangu-Ultra-MoE-718B,
``config.json``). Readings the config does not settle:

* the router scores by a sigmoid, keeps the plain top-k over all experts (no
  groups, no correction bias), renormalises (``norm_topk_prob``) and scales by
  ``routed_scaling_factor``;
* rotary pairs are ``(i, i + rope/2)``; there is no rope scaling, so the
  softmax scale is ``(qk_nope_head_dim + qk_rope_head_dim) ** -0.5``;
* sandwich norms: ``a = x + post_attn_norm(attn(input_norm(x)))``,
  ``y = a + post_mlp_norm(mlp(pre_mlp_norm(a)))``;
* ``held = (first, count)`` gives the part of the routed sum that those
  experts give (the rest is left out); ``held=None`` is the uncut layer;
* the multi-token-prediction module is left out.

``params`` is the served model's tree (models/pangu_ultra_moe.py), ``cfg`` its
``PanguUltraMoeConfig``; a sliced expert stack goes with the matching ``held``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _mm(a, b):
    return jnp.matmul(a.astype(jnp.float32), b.astype(jnp.float32), precision=HIGHEST)


def rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale.astype(jnp.float32)


def rope(x, positions, theta):
    """x [T, heads, d]: rotate each pair (i, i + d/2) by positions * theta^(-2i/d)."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(n, p, cfg, positions):
    T, H, rank = n.shape[0], cfg.num_attention_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    c_q = rms_norm(_mm(n, p["q_a_proj"]["kernel"]), p["q_a_norm"]["scale"], cfg.rms_norm_eps)
    q = _mm(c_q, p["q_b_proj"]["kernel"]).reshape(T, H, dn + dr)
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], positions, cfg.rope_theta)], -1)
    kv = _mm(n, p["kv_a_proj"]["kernel"])
    c_kv = rms_norm(kv[:, :rank], p["kv_a_norm"]["scale"], cfg.rms_norm_eps)
    k_r = rope(kv[:, None, rank:], positions, cfg.rope_theta)            # one key for all heads
    up = _mm(c_kv, p["kv_b_proj"]["kernel"]).reshape(T, H, dn + dv)
    k = jnp.concatenate([up[..., :dn], jnp.broadcast_to(k_r, (T, H, dr))], -1)
    scores = jnp.einsum("ihd,jhd->hij", q, k, precision=HIGHEST) * (dn + dr) ** -0.5
    mask = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    probs = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), axis=-1)
    a = jnp.einsum("hij,jhd->ihd", probs, up[..., dn:], precision=HIGHEST).reshape(T, H * dv)
    return _mm(a, p["o_proj"]["kernel"])


def swiglu(n, gate, up, down):
    return _mm(jax.nn.silu(_mm(n, gate)) * _mm(n, up), down)


def gates_of(n, router, cfg):
    """[T, num_experts]: the renormalised, scaled top-k gate of each expert, else 0."""
    s = jax.nn.sigmoid(_mm(n, router))
    top, idx = jax.lax.top_k(s, cfg.num_experts_per_tok)
    if cfg.norm_topk_prob:
        top = top / (top.sum(-1, keepdims=True) + 1e-20)
    top = top * cfg.routed_scaling_factor
    return jnp.zeros_like(s).at[jnp.arange(n.shape[0])[:, None], idx].set(top)


def routed_part(n, p, cfg, held=None):
    """sum over the held experts e of gate_e * F_e(n); ``p["experts"]`` holds
    exactly those experts' stacks."""
    first, count = (0, cfg.num_experts) if held is None else held
    gates = gates_of(n, p["router"], cfg)
    out = jnp.zeros_like(n)
    for e in range(count):
        w = p["experts"]
        out = out + gates[:, first + e, None] * swiglu(
            n, w["gate_proj"][e], w["up_proj"][e], w["down_proj"][e])
    return out


def dense_mlp(n, p):
    return swiglu(n, p["gate_proj"]["kernel"], p["up_proj"]["kernel"], p["down_proj"]["kernel"])


def mlp_parts(n, p, cfg, layer_idx, held=None):
    """(routed, everything every rank computes alike) of one layer's MLP, before
    its post norm: the dense MLP of a leading layer has no routed part."""
    if layer_idx < cfg.first_k_dense_replace:
        return jnp.zeros_like(n), dense_mlp(n, p)
    return routed_part(n, p, cfg, held), dense_mlp(n, p["shared_experts"])


def layer(x, p, cfg, layer_idx, positions, held=None):
    eps = cfg.rms_norm_eps
    attn = attention(rms_norm(x, p["input_norm"]["scale"], eps), p["self_attn"], cfg, positions)
    a = x + rms_norm(attn, p["post_attn_norm"]["scale"], eps)
    routed, alike = mlp_parts(rms_norm(a, p["pre_mlp_norm"]["scale"], eps), p["mlp"], cfg,
                              layer_idx, held)
    return a + rms_norm(routed + alike, p["post_mlp_norm"]["scale"], eps)


def forward(params, ids, cfg, held=None, positions=None):
    """ids [T] -> logits [T, vocab] float32: one full causal forward pass."""
    with jax.default_matmul_precision("highest"):
        T = ids.shape[0]
        positions = jnp.arange(T) if positions is None else positions
        x = params["embed_tokens"]["embedding"].astype(jnp.float32)[ids]
        for i in range(cfg.num_hidden_layers):
            x = layer(x, params[f"layers_{i}"], cfg, i, positions, held)
        x = rms_norm(x, params["norm"]["scale"], cfg.rms_norm_eps)
        return _mm(x, params["lm_head"]["kernel"])
