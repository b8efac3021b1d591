"""Family ``glm_moe_dsa``: layers of GLM-5.2 (``model_type`` ``glm_moe_dsa``)
as a served model — pre-norm layers of latent attention (MLA: a low-rank
query, one joint latent of keys and values and one rotary key shared by all
heads; the cache holds ``kv_lora_rank + qk_rope_head_dim`` values a token a
layer) restricted to the rows a learned indexer picks (DeepSeek-V3.2's DSA:
``index_topk`` rows a query; a ``shared`` layer reads the picks of the full
layer before it), a leading dense SwiGLU layer, then layers with a
sigmoid-scored top-k router (picks by score + correction bias) over all
experts, the routed experts HELD on this chip and one shared expert; an
untied head.

* ``leaf_table`` / ``make_params``: the seeded weights in the served type;
* ``build_server``: the program under test, built the way ``accelerate-tpu
  serve`` builds it and fronted by its HTTP gateway;
* ``reference_logits``: the plain float32 reference of one full forward pass.
  It imports nothing of the program and is given nothing the program made.

The reference runs one request of up to ``max_len`` ids in blocks of rows, so
that a 29k-token request fits beside the weights: for each layer every row's
latent, rotary key and index key first, then for each block of queries its
index scores against every row it can see, ``jax.lax.top_k`` of them, the
picked latent rows gathered and attended (a head's key and value are the
latent times ``kv_b_proj``'s two halves: folded into the query and applied
after the weighted sum), and the block's MLP. Blocks past
``assumed.reference_tail`` rows after the last non-zero id are not computed
(their logits are 0): prompt ids are never 0 and no answer is longer.

Readings of the published config (each also under ``assumed`` in the
configuration file): see the file's ``index_share``, ``indexer``,
``rotary`` and ``router``. ``n_routed_experts`` in the file is the number of
routed experts held here, ``router_width`` the router's published width,
``held_experts`` = [first, first + count); ``held_layers`` names the
published layers served, whose entries of ``mlp_layer_types`` and
``indexer_types`` the program is given.
"""

from __future__ import annotations

import contextlib
import sys

import jax
import jax.numpy as jnp

from chipbench import harness
from chipbench import reference_ops as ops

Server = harness.load_module("models", "mixtral").Server    # the same fleet + gateway wrapper
expert_sum = harness.load_module("models", "cohere2_moe").expert_sum   # held experts, one at a time

ROW_BLOCK = 512       # rows whose projections and MLP run at once
QUERY_BLOCK = 128     # queries whose picked latent rows are gathered at once


def held(cfg: dict) -> tuple:
    first, stop = cfg["held_experts"]
    return int(first), int(stop) - int(first)


def layer_types(cfg: dict) -> list:
    """``(mlp type, indexer type)`` of each layer served."""
    return [(cfg["mlp_layer_types"][i], cfg["indexer_types"][i]) for i in cfg["held_layers"]]


def theta(cfg: dict) -> float:
    return float(cfg["rope_parameters"]["rope_theta"])


def leaf_table(cfg: dict) -> list:
    """[(path, shape, std)] in the layout of the program's parameter tree."""
    h, v, heads = cfg["hidden_size"], cfg["vocab_size"], cfg["num_attention_heads"]
    q_rank, rank, rope = cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    nope, v_dim = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    hi, di = cfg["index_n_heads"], cfg["index_head_dim"]
    f, count = cfg["moe_intermediate_size"], held(cfg)[1]
    width = cfg["router_width"]
    table = [(("embed_tokens", "embedding"), (v, h), 1.0)]
    for i, (mlp, index) in enumerate(layer_types(cfg)):
        layer = (f"layers_{i}",)
        attn = layer + ("self_attn",)
        table += [(layer + (name, "scale"), (h,), None)
                  for name in ("input_layernorm", "post_attention_layernorm")]
        table += [
            (attn + ("q_a_proj", "kernel"), (h, q_rank), h ** -0.5),
            (attn + ("q_a_norm", "scale"), (q_rank,), None),
            (attn + ("q_b_proj", "kernel"), (q_rank, heads * (nope + rope)), q_rank ** -0.5),
            (attn + ("kv_a_proj", "kernel"), (h, rank + rope), h ** -0.5),
            (attn + ("kv_a_norm", "scale"), (rank,), None),
            (attn + ("kv_b_proj", "kernel"), (rank, heads * (nope + v_dim)), rank ** -0.5),
            (attn + ("o_proj", "kernel"), (heads * v_dim, h), (heads * v_dim) ** -0.5),
        ]
        if index == "full":
            idx = attn + ("indexer",)
            table += [
                (idx + ("wq_b", "kernel"), (q_rank, hi * di), q_rank ** -0.5),
                (idx + ("wk", "kernel"), (h, di), h ** -0.5),
                (idx + ("k_norm", "scale"), (di,), None),
                (idx + ("k_norm", "bias"), (di,), 0.0),
                (idx + ("weights_proj", "kernel"), (h, hi), h ** -0.5),
            ]
        if mlp == "dense":
            table += swiglu_leaves(layer + ("mlp",), h, cfg["intermediate_size"])
        else:
            table += [
                (layer + ("mlp", "router"), (h, width), h ** -0.5),
                (layer + ("mlp", "e_score_correction_bias"), (width,), 1.0 / width),
                (layer + ("mlp", "experts", "gate_proj"), (count, h, f), h ** -0.5),
                (layer + ("mlp", "experts", "up_proj"), (count, h, f), h ** -0.5),
                (layer + ("mlp", "experts", "down_proj"), (count, f, h), f ** -0.5),
            ] + swiglu_leaves(layer + ("mlp", "shared_experts"), h,
                              cfg["n_shared_experts"] * f)
    # the head has its own matrix: columns of norm ~1 keep the logits of order 1
    return table + [(("norm", "scale"), (h,), None), (("lm_head", "kernel"), (h, v), h ** -0.5)]


def swiglu_leaves(path: tuple, h: int, width: int) -> list:
    return [(path + ("gate_proj", "kernel"), (h, width), h ** -0.5),
            (path + ("up_proj", "kernel"), (h, width), h ** -0.5),
            (path + ("down_proj", "kernel"), (width, h), width ** -0.5)]


def make_params(cfg: dict, seed: int, dtype=None):
    """The whole weight tree on the device in one jitted call from the seed,
    in the type it is served in."""
    dtype = jnp.dtype(dtype or cfg["assumed"]["weights_dtype"])
    table = leaf_table(cfg)
    return jax.jit(lambda key: ops.make_tree(table, key, dtype))(ops.seed_key(seed))


# ---------------------------------------------------------------------------
# The program under test
# ---------------------------------------------------------------------------

def build_server(cfg: dict, params) -> Server:
    from accelerate_tpu.commands import serve
    from accelerate_tpu.models.glm_moe_dsa import GlmMoeDsaConfig, GlmMoeDsaForCausalLM
    from accelerate_tpu.serving import ServingGateway

    a = cfg["assumed"]
    types = layer_types(cfg)
    module = GlmMoeDsaForCausalLM(GlmMoeDsaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"], q_lora_rank=cfg["q_lora_rank"],
        kv_lora_rank=cfg["kv_lora_rank"], qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        index_n_heads=cfg["index_n_heads"], index_head_dim=cfg["index_head_dim"],
        index_topk=cfg["index_topk"], max_position_embeddings=cfg["max_position_embeddings"],
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=theta(cfg),
        num_experts=cfg["router_width"], num_experts_per_tok=cfg["num_experts_per_tok"],
        n_shared_experts=cfg["n_shared_experts"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        norm_topk_prob=cfg["norm_topk_prob"],
        mlp_layer_types=tuple(m for m, _ in types), indexer_types=tuple(x for _, x in types),
        held_experts=held(cfg)))
    argv = ["--port", "0", "--max-slots", str(a["max_slots"]), "--max-len", str(a["max_len"]),
            "--prefill-chunk", str(a["prefill_chunk"])]
    if a.get("max_pages") is not None:
        argv += ["--max-pages", str(a["max_pages"])]
    args = serve.serve_command_parser().parse_args(argv)
    with contextlib.redirect_stdout(sys.stderr):      # its progress lines
        replica_set = serve.build_fleet(args, module, params)
    gateway = ServingGateway(replica_set, config=serve.gateway_config(args))
    gateway.start()
    return Server(replica_set, gateway)


# ---------------------------------------------------------------------------
# The plain reference
# ---------------------------------------------------------------------------

def f32(a):
    return a.astype(jnp.float32)


def rope_at(x, positions, theta_):
    """``ops.rope`` at given positions: x [T, heads, d], pairs (i, i + d/2)."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta_ ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def layer_norm(x, p, eps=1e-6):
    mean = x.mean(-1, keepdims=True)
    var = jnp.square(x - mean).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * f32(p["scale"]) + f32(p["bias"])


def keys_of(n, p, cfg, positions, full, mm):
    """A block of rows' cached values: the normed latent, the rotary key and
    (a full layer) the index key."""
    rank, dr, t = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"], theta(cfg)
    kv = mm(n, p["kv_a_proj"]["kernel"])
    c_kv = ops.rms_norm(kv[:, :rank], p["kv_a_norm"]["scale"], cfg["rms_norm_eps"])
    k_r = rope_at(kv[:, None, rank:], positions, t)[:, 0]
    if not full:
        return c_kv, k_r, None
    k_i = layer_norm(mm(n, p["indexer"]["wk"]["kernel"]), p["indexer"]["k_norm"])
    k_i = jnp.concatenate([rope_at(k_i[:, None, :dr], positions, t)[:, 0], k_i[:, dr:]], -1)
    return c_kv, k_r, k_i


def picks_of(n, c_q, p, cfg, positions, keys, mm):
    """[Q, index_topk] ids: each query's rows of largest index score among
    the rows it can see (ids past the query are picks of invisible rows)."""
    q_n, hi, di, dr = n.shape[0], cfg["index_n_heads"], cfg["index_head_dim"], cfg["qk_rope_head_dim"]
    q = mm(c_q, p["wq_b"]["kernel"]).reshape(q_n, hi, di)
    q = jnp.concatenate([rope_at(q[..., :dr], positions, theta(cfg)), q[..., dr:]], -1)
    w = mm(n, p["weights_proj"]["kernel"]) * hi ** -0.5 * di ** -0.5
    dots = jnp.einsum("qhd,sd->qhs", q, keys, precision=ops.HIGHEST)
    scores = jnp.einsum("qhs,qh->qs", jax.nn.relu(dots), w, precision=ops.HIGHEST)
    visible = jnp.arange(keys.shape[0])[None, :] <= positions[:, None]
    return jax.lax.top_k(jnp.where(visible, scores, -jnp.inf), cfg["index_topk"])[1]


def attend(n, p, cfg, positions, latent, rope, ids, mm):
    """MLA of a block of queries over its picked rows: [Q, hidden]."""
    q_n, heads, rank = n.shape[0], cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    c_q = ops.rms_norm(mm(n, p["q_a_proj"]["kernel"]), p["q_a_norm"]["scale"], cfg["rms_norm_eps"])
    q = mm(c_q, p["q_b_proj"]["kernel"]).reshape(q_n, heads, dn + dr)
    q_r = rope_at(q[..., dn:], positions, theta(cfg))
    up = f32(p["kv_b_proj"]["kernel"]).reshape(rank, heads, dn + dv)
    q_c = jnp.einsum("qhd,rhd->qhr", q[..., :dn], up[..., :dn], precision=ops.HIGHEST)
    c, r = latent[ids], rope[ids]                                          # [Q, k, rank / rope]
    scores = (jnp.einsum("qhr,qkr->qhk", q_c, c, precision=ops.HIGHEST)
              + jnp.einsum("qhd,qkd->qhk", q_r, r, precision=ops.HIGHEST)) * (dn + dr) ** -0.5
    scores = jnp.where((ids <= positions[:, None])[:, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("qhk,qkr->qhr", probs, c, precision=ops.HIGHEST)
    out = jnp.einsum("qhr,rhd->qhd", out, up[..., dn:], precision=ops.HIGHEST)
    return mm(out.reshape(q_n, heads * dv), p["o_proj"]["kernel"])


def mlp(n, p, cfg, kind, mm):
    if kind == "dense":
        return ops.swiglu(n, f32(p["gate_proj"]["kernel"]), f32(p["up_proj"]["kernel"]),
                          f32(p["down_proj"]["kernel"]), mm)
    first, count = held(cfg)
    scores = jax.nn.sigmoid(mm(n, p["router"]))
    _, top_i = jax.lax.top_k(scores + f32(p["e_score_correction_bias"]), cfg["num_experts_per_tok"])
    rows = jnp.arange(n.shape[0])[:, None]
    top = scores[rows, top_i]
    if cfg["norm_topk_prob"]:
        top = top / top.sum(-1, keepdims=True)
    gates = jnp.zeros_like(scores).at[rows, top_i].set(top * cfg["routed_scaling_factor"])
    shared = p["shared_experts"]
    return (expert_sum(n, p["experts"], gates[:, first:first + count], mm)
            + ops.swiglu(n, f32(shared["gate_proj"]["kernel"]), f32(shared["up_proj"]["kernel"]),
                         f32(shared["down_proj"]["kernel"]), mm))


def reference_logits(params, ids, cfg: dict, mm, with_picks: bool = False):
    """ids [S] -> logits [S, vocab slice] (float32): one full causal forward
    pass, rows up to ``reference_tail`` past the last non-zero id (the rest
    read 0); ``with_picks`` also returns each full layer's ``[S, index_topk]``
    picked row ids."""
    s, eps = ids.shape[0], cfg["rms_norm_eps"]
    rb = ROW_BLOCK if s % ROW_BLOCK == 0 else s
    qb = QUERY_BLOCK if rb % QUERY_BLOCK == 0 else rb
    last = jnp.max(jnp.where(ids != 0, jnp.arange(s), 0))
    blocks = jnp.minimum(-(-(last + 1 + cfg["assumed"]["reference_tail"]) // rb), s // rb)
    positions = jnp.arange(s)

    def rows(a, b, n=rb):
        return jax.lax.dynamic_slice_in_dim(a, b * n, n, axis=0)

    x = params["embed_tokens"]["embedding"][ids].astype(jnp.float32)
    ids_picked, picks = None, []
    for i, (kind, index) in enumerate(layer_types(cfg)):
        p = ops.f32_lazy(params[f"layers_{i}"])
        a, full = p["self_attn"], index == "full"

        def cache_rows(b, bufs):
            n = ops.rms_norm(rows(x, b), p["input_layernorm"]["scale"], eps)
            new = keys_of(n, a, cfg, rows(positions, b), full, mm)
            return tuple(jax.lax.dynamic_update_slice_in_dim(buf, v, b * rb, axis=0)
                         for buf, v in zip(bufs, new) if buf is not None) + (None,) * (not full)

        bufs = (jnp.zeros((s, cfg["kv_lora_rank"])), jnp.zeros((s, cfg["qk_rope_head_dim"])),
                jnp.zeros((s, cfg["index_head_dim"])) if full else None)
        latent, rope, index_k = jax.lax.fori_loop(0, blocks, cache_rows, bufs)
        picked = (jnp.zeros((s, cfg["index_topk"]), jnp.int32) if full else ids_picked)

        def one_block(b, carry):
            y, picked = carry
            xb, pos = rows(x, b), rows(positions, b)
            n = ops.rms_norm(xb, p["input_layernorm"]["scale"], eps)

            def one_query_block(j):
                nq, pq = rows(n, j, qb), rows(pos, j, qb)
                if full:
                    c_q = ops.rms_norm(mm(nq, a["q_a_proj"]["kernel"]), a["q_a_norm"]["scale"], eps)
                    chosen = picks_of(nq, c_q, a["indexer"], cfg, pq, index_k, mm)
                else:
                    chosen = jax.lax.dynamic_slice_in_dim(picked, b * rb + j * qb, qb, axis=0)
                return attend(nq, a, cfg, pq, latent, rope, chosen, mm), chosen

            out, chosen = jax.lax.map(one_query_block, jnp.arange(rb // qb))
            h = xb + out.reshape(rb, -1)
            h = h + mlp(ops.rms_norm(h, p["post_attention_layernorm"]["scale"], eps), p["mlp"],
                        cfg, kind, mm)
            y = jax.lax.dynamic_update_slice_in_dim(y, h, b * rb, axis=0)
            if full:
                picked = jax.lax.dynamic_update_slice_in_dim(
                    picked, chosen.reshape(rb, -1), b * rb, axis=0)
            return y, picked

        x, ids_picked = jax.lax.fori_loop(0, blocks, one_block, (jnp.zeros_like(x), picked))
        if full:
            picks.append(ids_picked)
    x = ops.rms_norm(x, params["norm"]["scale"].astype(jnp.float32), eps)
    logits = mm(x, params["lm_head"]["kernel"].astype(jnp.float32))
    return (logits, picks) if with_picks else logits
