"""Plain reference of the ``longcat_flash`` family: one full forward pass in
``jax.numpy`` and float32 at ``highest`` matmul precision — no cache, no
kernels, no batching, no absorbed form: every head's key and value are
expanded from the latent and scored plainly; the router scores all its
columns, every held expert is computed for every token and weighted by its
gate (zero where the token did not pick it), and the zero-compute experts'
picks add the token itself.

It follows the published config (meituan-longcat/LongCat-Flash-Omni,
``config.json``; the language model is LongCat-Flash's). Readings the config
does not settle:

* a double layer is ``h1 = x + attn0(norm_in0(x))``, ``n1 = norm_post0(h1)``,
  ``h2 = h1 + mlp0(n1)``, ``h3 = h2 + attn1(norm_in1(h2))``, ``y = h3 +
  mlp1(norm_post1(h3)) + moe(n1)`` (the shortcut-connected expert branch);
* MLA's low-rank scales: both query parts times ``(hidden / q_lora_rank) **
  0.5``, the normed latent times ``(hidden / kv_lora_rank) ** 0.5`` before
  ``kv_b_proj``, the rotary key unscaled; rotary pairs are ``(i, i +
  rope/2)``; no rope scaling, so the softmax scale is ``(nope + rope) ** -0.5``;
* the router: a softmax over ``n_routed_experts + zero_expert_num`` columns,
  the top ``moe_topk`` of ``score + e_score_correction_bias`` picked, each
  gated by its score, not renormalised, times ``routed_scaling_factor``; a
  pick on one of the last ``zero_expert_num`` columns adds ``gate * token``;
* ``held = (first, count)`` gives the part of the routed sum that those
  experts give (the rest is left out; the identity part is whole);
  ``held=None`` is the uncut layer.

``params`` is the served model's tree (models/longcat_flash.py), ``cfg`` its
``LongcatFlashConfig``; a sliced expert stack goes with the matching ``held``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .pangu_ultra_moe import HIGHEST, _mm, rms_norm, rope, swiglu


def attention(n, p, cfg, positions):
    T, H, rank = n.shape[0], cfg.num_attention_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    c_q = rms_norm(_mm(n, p["q_a_proj"]["kernel"]), p["q_a_norm"]["scale"], cfg.rms_norm_eps)
    q = _mm(c_q, p["q_b_proj"]["kernel"]).reshape(T, H, dn + dr)
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], positions, cfg.rope_theta)], -1)
    q = q * cfg.q_scale
    kv = _mm(n, p["kv_a_proj"]["kernel"])
    c_kv = rms_norm(kv[:, :rank], p["kv_a_norm"]["scale"], cfg.rms_norm_eps) * cfg.latent_scale
    k_r = rope(kv[:, None, rank:], positions, cfg.rope_theta)            # one key for all heads
    up = _mm(c_kv, p["kv_b_proj"]["kernel"]).reshape(T, H, dn + dv)
    k = jnp.concatenate([up[..., :dn], jnp.broadcast_to(k_r, (T, H, dr))], -1)
    scores = jnp.einsum("ihd,jhd->hij", q, k, precision=HIGHEST) * (dn + dr) ** -0.5
    mask = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    probs = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), axis=-1)
    a = jnp.einsum("hij,jhd->ihd", probs, up[..., dn:], precision=HIGHEST).reshape(T, H * dv)
    return _mm(a, p["o_proj"]["kernel"])


def dense_mlp(n, p):
    return swiglu(n, p["gate_proj"]["kernel"], p["up_proj"]["kernel"], p["down_proj"]["kernel"])


def gates_of(n, p, cfg):
    """[T, router columns]: each picked column's gate (its softmax score), else 0."""
    s = jax.nn.softmax(_mm(n, p["router"]), axis=-1)
    _, idx = jax.lax.top_k(s + p["e_score_correction_bias"], cfg.moe_topk)
    rows = jnp.arange(n.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, idx].set(s[rows, idx])


def moe_parts(n, p, cfg, held=None):
    """(routed part of the held experts, identity part) of the expert branch,
    before ``routed_scaling_factor``; ``p["experts"]`` holds exactly the held
    experts' stacks."""
    first, count = (0, cfg.n_routed_experts) if held is None else held
    gates = gates_of(n, p, cfg)
    routed = jnp.zeros_like(n)
    w = p["experts"]
    for e in range(count):
        routed = routed + gates[:, first + e, None] * swiglu(
            n, w["gate_proj"][e], w["up_proj"][e], w["down_proj"][e])
    identity = gates[:, cfg.n_routed_experts:].sum(-1, keepdims=True) * n
    return routed, identity


def moe(n, p, cfg, held=None):
    routed, identity = moe_parts(n, p, cfg, held)
    return (routed + identity) * cfg.routed_scaling_factor


def layer(x, p, cfg, positions, held=None):
    eps = cfg.rms_norm_eps
    h = x + attention(rms_norm(x, p["norm_in0"]["scale"], eps), p["attn0"], cfg, positions)
    n = rms_norm(h, p["norm_post0"]["scale"], eps)
    shortcut = moe(n, p["moe"], cfg, held)
    h = h + dense_mlp(n, p["mlp0"])
    h = h + attention(rms_norm(h, p["norm_in1"]["scale"], eps), p["attn1"], cfg, positions)
    h = h + dense_mlp(rms_norm(h, p["norm_post1"]["scale"], eps), p["mlp1"])
    return h + shortcut


def forward(params, ids, cfg, held=None, positions=None):
    """ids [T] -> logits [T, vocab] float32: one full causal forward pass."""
    with jax.default_matmul_precision("highest"):
        T = ids.shape[0]
        positions = jnp.arange(T) if positions is None else positions
        x = params["embed_tokens"]["embedding"].astype(jnp.float32)[ids]
        for i in range(cfg.num_layers):
            x = layer(x, params[f"layers_{i}"], cfg, positions, held)
        x = rms_norm(x, params["norm"]["scale"], cfg.rms_norm_eps)
        return _mm(x, params["lm_head"]["kernel"])
