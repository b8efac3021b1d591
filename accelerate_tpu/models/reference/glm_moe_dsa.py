"""Plain reference of the ``glm_moe_dsa`` family: one full forward pass in
``jax.numpy`` and float32 at ``highest`` matmul precision — no cache, no
kernels, no batching, no absorbed form, no key blocks: the indexer scores
every (query, key) pair at once and takes each query's top ``index_topk``
with ``jax.lax.top_k``; every head's key and value are expanded from the
latent and scored plainly under the causal mask AND the selection; every held
expert is computed for every token and weighted by its gate (zero where the
token did not pick it).

It follows the published config (zai-org/GLM-5.2, ``config.json``) and
DeepSeek-V3.2's description of the indexer. Departures and readings:

* the indexer: ``q_I = c_q W_qb`` (MLA's normed low-rank query), ``k_I =
  LayerNorm(x W_k)`` (scale and bias, eps 1e-6), the first
  ``qk_rope_head_dim`` values of each rotated as MLA's rotary key is (pairs
  ``(i, i + rope/2)``), ``w = x W_w * index_n_heads ** -0.5 *
  index_head_dim ** -0.5``, ``I(t, s) = sum_j w[t, j] relu(q_I[t, j] .
  k_I[s])`` for ``s <= t``; V3.2 rounds ``q_I`` and ``k_I`` to float8 under
  a Hadamard rotation, here they are float32;
* ``indexer_types``: a ``"shared"`` layer attends over the selection of the
  nearest ``"full"`` layer before it;
* the router scores by a sigmoid, picks the top-k of score +
  ``e_score_correction_bias`` (one group), gates each pick by its score,
  renormalised and scaled by ``routed_scaling_factor``;
* ``held = (first, count)`` gives the part of the routed sum that those
  experts give (the rest is left out); ``held=None`` is the uncut layer;
* the multi-token-prediction module is left out.

``params`` is the served model's tree (models/glm_moe_dsa.py), ``cfg`` its
``GlmMoeDsaConfig``; a sliced expert stack goes with the matching ``held``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .pangu_ultra_moe import HIGHEST, _mm, rms_norm, rope, swiglu


def layer_norm(x, p, eps=1e-6):
    mean = x.mean(-1, keepdims=True)
    var = jnp.square(x - mean).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def index_scores(n, c_q, p, cfg, positions):
    """[T, T]: the indexer's score of every key row for every query, -inf
    where the row is in the query's future."""
    T, Hi, D, dr = n.shape[0], cfg.index_n_heads, cfg.index_head_dim, cfg.qk_rope_head_dim
    q = _mm(c_q, p["wq_b"]["kernel"]).reshape(T, Hi, D)
    q = jnp.concatenate([rope(q[..., :dr], positions, cfg.rope_theta), q[..., dr:]], -1)
    k = layer_norm(_mm(n, p["wk"]["kernel"]), p["k_norm"])
    k = jnp.concatenate([rope(k[:, None, :dr], positions, cfg.rope_theta)[:, 0], k[:, dr:]], -1)
    w = _mm(n, p["weights_proj"]["kernel"]) * cfg.index_n_heads ** -0.5 * D ** -0.5
    dots = jnp.einsum("thd,sd->ths", q, k, precision=HIGHEST)
    scores = jnp.einsum("ths,th->ts", jax.nn.relu(dots), w, precision=HIGHEST)
    causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    return jnp.where(causal, scores, -jnp.inf)


def selection(scores, topk):
    """[T, T] bool: each query's ``topk`` visible rows of largest score (all
    of its visible rows where it has no more)."""
    T = scores.shape[0]
    _, idx = jax.lax.top_k(scores, min(topk, T))
    picked = jnp.zeros((T, T), bool).at[jnp.arange(T)[:, None], idx].set(True)
    return picked & (scores > -jnp.inf)


def attention(n, p, cfg, positions, selected):
    """One MLA in the plain form over the selected rows. Returns ``(out,
    selected)``: a full layer's own selection, or the one it was handed."""
    T, H, rank = n.shape[0], cfg.num_attention_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    c_q = rms_norm(_mm(n, p["q_a_proj"]["kernel"]), p["q_a_norm"]["scale"], cfg.rms_norm_eps)
    if "indexer" in p:
        selected = selection(index_scores(n, c_q, p["indexer"], cfg, positions), cfg.index_topk)
    elif selected is None:                         # a shared layer handed nothing: every row
        selected = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    q = _mm(c_q, p["q_b_proj"]["kernel"]).reshape(T, H, dn + dr)
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], positions, cfg.rope_theta)], -1)
    kv = _mm(n, p["kv_a_proj"]["kernel"])
    c_kv = rms_norm(kv[:, :rank], p["kv_a_norm"]["scale"], cfg.rms_norm_eps)
    k_r = rope(kv[:, None, rank:], positions, cfg.rope_theta)            # one key for all heads
    up = _mm(c_kv, p["kv_b_proj"]["kernel"]).reshape(T, H, dn + dv)
    k = jnp.concatenate([up[..., :dn], jnp.broadcast_to(k_r, (T, H, dr))], -1)
    scores = jnp.einsum("ihd,jhd->hij", q, k, precision=HIGHEST) * cfg.sm_scale
    probs = jax.nn.softmax(jnp.where(selected[None], scores, -jnp.inf), axis=-1)
    a = jnp.einsum("hij,jhd->ihd", probs, up[..., dn:], precision=HIGHEST).reshape(T, H * dv)
    return _mm(a, p["o_proj"]["kernel"]), selected


def dense_mlp(n, p):
    return swiglu(n, p["gate_proj"]["kernel"], p["up_proj"]["kernel"], p["down_proj"]["kernel"])


def gates_of(n, p, cfg):
    """[T, num_experts]: the renormalised, scaled gate of each picked expert, else 0."""
    s = jax.nn.sigmoid(_mm(n, p["router"]))
    _, idx = jax.lax.top_k(s + p["e_score_correction_bias"], cfg.num_experts_per_tok)
    rows = jnp.arange(n.shape[0])[:, None]
    top = s[rows, idx]
    if cfg.norm_topk_prob:
        top = top / top.sum(-1, keepdims=True)
    return jnp.zeros_like(s).at[rows, idx].set(top * cfg.routed_scaling_factor)


def mlp_parts(n, p, cfg, layer_idx, held=None):
    """(routed part of the held experts, everything every rank computes
    alike) of one layer's MLP: a dense layer has no routed part."""
    if cfg.mlp_layer_types[layer_idx] == "dense":
        return jnp.zeros_like(n), dense_mlp(n, p)
    first, count = (0, cfg.num_experts) if held is None else held
    gates = gates_of(n, p, cfg)
    routed, w = jnp.zeros_like(n), p["experts"]
    for e in range(count):
        routed = routed + gates[:, first + e, None] * swiglu(
            n, w["gate_proj"][e], w["up_proj"][e], w["down_proj"][e])
    return routed, dense_mlp(n, p["shared_experts"])


def layer(x, p, cfg, layer_idx, positions, selected=None, held=None):
    """-> ``(y, selected)``: the layer's output and the selection it read."""
    eps = cfg.rms_norm_eps
    a, selected = attention(rms_norm(x, p["input_layernorm"]["scale"], eps), p["self_attn"],
                            cfg, positions, selected)
    h = x + a
    routed, alike = mlp_parts(rms_norm(h, p["post_attention_layernorm"]["scale"], eps), p["mlp"],
                              cfg, layer_idx, held)
    return h + routed + alike, selected


def forward(params, ids, cfg, held=None, positions=None, selections=False):
    """ids [T] -> logits [T, vocab] float32: one full causal forward pass;
    with ``selections`` also every layer's ``[T, T]`` selection."""
    with jax.default_matmul_precision("highest"):
        T = ids.shape[0]
        positions = jnp.arange(T) if positions is None else positions
        x = params["embed_tokens"]["embedding"].astype(jnp.float32)[ids]
        selected, picked = None, []
        for i in range(cfg.num_hidden_layers):
            x, selected = layer(x, params[f"layers_{i}"], cfg, i, positions, selected, held)
            picked.append(selected)
        x = rms_norm(x, params["norm"]["scale"], cfg.rms_norm_eps)
        logits = _mm(x, params["lm_head"]["kernel"])
    return (logits, picked) if selections else logits
