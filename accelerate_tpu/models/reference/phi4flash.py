"""Plain reference of the ``phi4flash`` family (Phi-4-mini-flash-reasoning,
arXiv:2507.06607): one full forward pass in ``jax.numpy`` and float32 at
``highest`` matmul precision — no cache, no kernels, no batching: the
selective scan is the recurrence stepped over time, differential attention is
two masked softmaxes a head pair written out, every cross-attention scores
the shared layer's keys plainly.

It follows the published config (microsoft/Phi-4-mini-flash-reasoning,
``config.json``) and these readings of the model's own code, which the config
does not carry: ``mamba_d_state`` 16, ``mamba_d_conv`` 4, ``mamba_expand`` 2,
``mamba_dt_rank`` ceil(hidden / 16); a convolution with bias and Mamba
projections without; attention projections with bias; heads pair ``(2n,
2n+1)``, query pair ``n`` reads key/value pair ``n // 2``; ``lambda_init(i) =
0.8 - 0.6 exp(-0.3 i)`` with ``i`` the layer's index; a windowed query at
``t`` sees keys ``t - window + 1 .. t``; layer ``n/2`` hands its scan output
(before the gate, ``D x'`` included) to the gated memory units, layer
``n/2 + 1`` its keys and values to the cross-attentions.

``params`` is the served model's tree (models/phi4flash.py): the published
layout, heads in the published order, but for ``A_log``, which is stored
``[d_state, d_inner]`` and transposed back here.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def layer_norm(x, p, eps):
    mean = x.mean(-1, keepdims=True)
    var = jnp.square(x - mean).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def mamba(u, p, cfg):
    """u [T, hidden] -> (out [T, hidden], scan output y [T, d_inner])."""
    T, d, N, R, K = u.shape[0], cfg.d_inner, cfg.mamba_d_state, cfg.dt_rank, cfg.mamba_d_conv
    xz = _mm(u, p["in_proj"]["kernel"])
    x, z = xz[:, :d], xz[:, d:]
    xp = jnp.concatenate([jnp.zeros((K - 1, d)), x])
    xc = jax.nn.silu(sum(xp[j:j + T] * p["conv_kernel"][j] for j in range(K)) + p["conv_bias"])
    dbc = _mm(xc, p["x_proj"]["kernel"])
    delta = jax.nn.softplus(_mm(dbc[:, :R], p["dt_proj"]) + p["dt_bias"])          # [T, d]
    A = -jnp.exp(p["A_log"].T)                                                      # [d, N]

    def step(h, inputs):
        delta_t, x_t, B_t, C_t = inputs
        h = jnp.exp(delta_t[:, None] * A) * h + (delta_t * x_t)[:, None] * B_t[None, :]
        return h, h @ C_t

    _, y = jax.lax.scan(step, jnp.zeros((d, N)), (delta, xc, dbc[:, R:R + N], dbc[:, R + N:]))
    y = y + p["D"] * xc
    return _mm(y * jax.nn.silu(z), p["out_proj"]["kernel"]), y


def diff_attention(q, k, v, p, cfg, layer, window):
    """q [T, H, hd], k, v [T, G, hd] in the PUBLISHED head order; causal,
    ``window`` keys back where given. Returns [T, H * hd]."""
    T, H, hd = q.shape
    pos = jnp.arange(T)
    mask = pos[None, :] <= pos[:, None]
    if window is not None:
        mask &= pos[None, :] > pos[:, None] - window
    lam_init = 0.8 - 0.6 * math.exp(-0.3 * layer)
    lam = (jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"]))
           - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"])) + lam_init)

    def softmax_of(qh, kh):
        scores = _mm(qh, kh.T) * hd ** -0.5
        return jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)

    outs = []
    for n in range(H // 2):
        pair = n // 2
        values = jnp.concatenate([v[:, 2 * pair], v[:, 2 * pair + 1]], axis=-1)    # [T, 2 hd]
        o1 = _mm(softmax_of(q[:, 2 * n], k[:, 2 * pair]), values)
        o2 = _mm(softmax_of(q[:, 2 * n + 1], k[:, 2 * pair + 1]), values)
        o = o1 - lam * o2
        o = o / jnp.sqrt(jnp.mean(o * o, -1, keepdims=True) + cfg.layer_norm_eps) * p["subln"]
        outs.append(o * (1.0 - lam_init))
    return _mm(jnp.concatenate(outs, axis=-1), p["o_proj"]["kernel"]) + p["o_proj"]["bias"]


def reference_logits(params, ids, cfg):
    """ids [T] -> logits [T, vocab] (float32): the whole model, plainly."""
    with jax.default_matmul_precision("highest"):
        T, H, G, hd = ids.shape[0], cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        eps = cfg.layer_norm_eps
        embedding = params["embed_tokens"]["embedding"].astype(jnp.float32)
        x = embedding[ids]
        memory = shared_k = shared_v = None
        for i in range(cfg.num_hidden_layers):
            layer = _f32(params[f"layers_{i}"])
            p, kind = layer["mixer"], cfg.mixer(i)
            u = layer_norm(x, layer["input_norm"], eps)
            if kind == "mamba":
                mixed, y = mamba(u, p, cfg)
                if i == cfg.memory_layer:
                    memory = y
            elif kind == "gmu":
                mixed = _mm(jax.nn.silu(_mm(u, p["in_proj"]["kernel"])) * memory,
                            p["out_proj"]["kernel"])
            elif kind == "attn":
                qkv = _mm(u, p["qkv_proj"]["kernel"]) + p["qkv_proj"]["bias"]
                q = qkv[:, :H * hd].reshape(T, H, hd)
                k = qkv[:, H * hd:(H + G) * hd].reshape(T, G, hd)
                v = qkv[:, (H + G) * hd:].reshape(T, G, hd)
                if i == cfg.shared_kv_layer:
                    shared_k, shared_v = k, v
                mixed = diff_attention(q, k, v, p, cfg, i, cfg.window_for(i))
            else:
                q = _mm(u, p["q_proj"]["kernel"]) + p["q_proj"]["bias"]
                mixed = diff_attention(q.reshape(T, H, hd), shared_k, shared_v,
                                       p, cfg, i, None)
            x = x + mixed
            n = layer_norm(x, layer["post_norm"], eps)
            gu = _mm(n, layer["mlp"]["gate_up_proj"]["kernel"])
            f = cfg.intermediate_size
            x = x + _mm(jax.nn.silu(gu[:, :f]) * gu[:, f:], layer["mlp"]["down_proj"]["kernel"])
        x = layer_norm(x, _f32(params["norm"]), eps)
        return _mm(x, embedding.T)
