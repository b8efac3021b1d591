"""Plain reference of the ``cohere2_moe`` family: one full forward pass in
``jax.numpy`` and float32 at ``highest`` matmul precision — no cache, no
kernels, no batching, every held expert computed for every token and
weighted by its gate (zero where the token did not pick it).

It follows the published config (CohereLabs/command-a-plus-05-2026,
``config.json``). Departures from, or readings of, that description:

* the width of a routed and of a shared expert is ``intermediate_size`` (the
  config has no key of its own for it);
* ``shared_expert_combination_strategy`` "average" is read as the mean of the
  shared experts' outputs, added to the routed sum;
* ``full_attention`` layers get no positional encoding ("global NoPE", the
  family's convention); sliding layers rotate interleaved pairs over all of
  ``head_dim`` (``rope_gptj``, ``rotary_pct`` 1);
* ``held = (first, count)`` gives the part of the routed sum that those
  experts give (the rest is left out); ``held=None`` is the uncut layer;
* the ``prefix_dense_*`` keys are unused (``first_k_dense_replace`` is 0) and
  the vision tower is left out.

``params`` is the served model's tree (models/cohere2_moe.py), ``cfg`` its
``Cohere2MoeConfig``; a sliced expert stack goes with the matching ``held``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _mm(a, b):
    return jnp.matmul(a.astype(jnp.float32), b.astype(jnp.float32), precision=HIGHEST)


def layer_norm(x, scale, eps):
    x = x - x.mean(-1, keepdims=True)
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale.astype(jnp.float32)


def rope_interleaved(x, positions, theta):
    """x [T, heads, d]: rotate each pair (2i, 2i+1) by positions * theta^(-2i/d)."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1)
    return out.reshape(x.shape)


def attention(n, p, cfg, window, positions):
    T = n.shape[0]
    H, G, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    q = _mm(n, p["q_proj"]["kernel"]).reshape(T, H, d)
    k = _mm(n, p["k_proj"]["kernel"]).reshape(T, G, d)
    v = _mm(n, p["v_proj"]["kernel"]).reshape(T, G, d)
    if window is not None:
        q = rope_interleaved(q, positions, cfg.rope_theta)
        k = rope_interleaved(k, positions, cfg.rope_theta)
    k, v = jnp.repeat(k, H // G, axis=1), jnp.repeat(v, H // G, axis=1)
    scores = jnp.einsum("ihd,jhd->hij", q, k, precision=HIGHEST) / jnp.sqrt(float(d))
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    mask = j <= i
    if window is not None:
        mask &= j > i - window
    probs = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), axis=-1)
    a = jnp.einsum("hij,jhd->ihd", probs, v, precision=HIGHEST).reshape(T, H * d)
    return _mm(a, p["o_proj"]["kernel"])


def swiglu(n, gate, up, down):
    return _mm(jax.nn.silu(_mm(n, gate)) * _mm(n, up), down)


def gates_of(n, router, cfg):
    """[T, num_experts]: the renormalised top-k gate of each expert, else 0."""
    logits = _mm(n, router)
    s = jax.nn.sigmoid(logits) if cfg.expert_selection_fn == "sigmoid" \
        else jax.nn.softmax(logits, axis=-1)
    top, idx = jax.lax.top_k(s, cfg.num_experts_per_tok)
    if cfg.norm_topk_prob:
        top = top / top.sum(-1, keepdims=True)
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


def shared_part(n, p, cfg):
    w = p["shared_experts"]
    outs = [swiglu(n, w["gate_proj"][s], w["up_proj"][s], w["down_proj"][s])
            for s in range(cfg.num_shared_experts)]
    return sum(outs) / cfg.num_shared_experts


def layer_parts(x, p, cfg, layer_idx, positions, held=None):
    """(attention, routed, shared) of one layer, each [T, hidden]; the layer's
    output is ``x + attention + routed + shared`` (the parallel block)."""
    n = layer_norm(x, p["input_norm"]["scale"], cfg.layer_norm_eps)
    return (attention(n, p["self_attn"], cfg, cfg.window_for(layer_idx), positions),
            routed_part(n, p["mlp"], cfg, held), shared_part(n, p["mlp"], cfg))


def forward(params, ids, cfg, held=None, positions=None):
    """ids [T] -> logits [T, vocab] float32: one full causal forward pass."""
    with jax.default_matmul_precision("highest"):
        T = ids.shape[0]
        positions = jnp.arange(T) if positions is None else positions
        emb = params["embed_tokens"]["embedding"].astype(jnp.float32)
        x = emb[ids]
        for i in range(cfg.num_hidden_layers):
            x = x + sum(layer_parts(x, params[f"layers_{i}"], cfg, i, positions, held))
        x = layer_norm(x, params["norm"]["scale"], cfg.layer_norm_eps)
        return cfg.logit_scale * _mm(x, emb.T)
