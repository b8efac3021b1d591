"""Family ``pangu_ultra_moe``: the layer of openPangu-Ultra-MoE-718B
(``model_type`` ``pangu_ultra_moe``) as a served model — sandwich norms (four
RMSNorms a layer), latent attention (MLA: a low-rank query, one joint latent
for keys and values and one decoupled rotary key shared by all heads; the
cache holds ``kv_lora_rank + qk_rope_head_dim`` values a token a layer), a
leading dense SwiGLU layer, then layers with a sigmoid-scored top-k router
over all experts, the routed experts HELD on this chip (scaled by
``routed_scaling_factor``) and one shared expert; an untied head.

* ``leaf_table`` / ``make_params``: the seeded weights in the served type;
* ``build_server``: the program under test, built the way ``accelerate-tpu
  serve`` builds it and fronted by its HTTP gateway;
* ``reference_logits``: the plain float32 reference of one full forward pass,
  in the plain form (every head's key and value expanded from the latent). It
  imports nothing of the program and is given nothing the program made.

Readings of the published config (each also under ``assumed`` in the
configuration file): the router scores by a sigmoid and keeps the plain top-k
over all experts (no groups, no correction bias), renormalised and scaled;
rotary pairs are (i, i + 32) (``reference_ops.rope``); no rope scaling, so
the softmax scale is 192 ** -0.5; the sandwich order is ``a = x + post_attn(attn(input(x)))``,
``y = a + post_mlp(mlp(pre_mlp(a)))``; the multi-token-prediction module is
not loaded. ``n_routed_experts`` in the file is the number of routed experts
held here, ``router_width`` the router's published width, ``held_experts`` =
[first, first + count).
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


def held(cfg: dict) -> tuple:
    first, stop = cfg["held_experts"]
    return int(first), int(stop) - int(first)


def leaf_table(cfg: dict) -> list:
    """[(path, shape, std)] in the layout of the program's parameter tree."""
    h, v, heads = cfg["hidden_size"], cfg["vocab_size"], cfg["num_attention_heads"]
    q_rank, rank, rope = cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    nope, v_dim = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    f, count = cfg["moe_intermediate_size"], held(cfg)[1]
    # under sandwich norms every branch leaves with unit rms: so does a token's own row
    table = [(("embed_tokens", "embedding"), (v, h), 1.0)]
    for i in range(cfg["num_hidden_layers"]):
        layer = (f"layers_{i}",)
        attn = layer + ("self_attn",)
        table += [(layer + (name, "scale"), (h,), None)
                  for name in ("input_norm", "post_attn_norm", "pre_mlp_norm", "post_mlp_norm")]
        table += [
            (attn + ("q_a_proj", "kernel"), (h, q_rank), h ** -0.5),
            (attn + ("q_a_norm", "scale"), (q_rank,), None),
            (attn + ("q_b_proj", "kernel"), (q_rank, heads * (nope + rope)), q_rank ** -0.5),
            (attn + ("kv_a_proj", "kernel"), (h, rank + rope), h ** -0.5),
            (attn + ("kv_a_norm", "scale"), (rank,), None),
            (attn + ("kv_b_proj", "kernel"), (rank, heads * (nope + v_dim)), rank ** -0.5),
            (attn + ("o_proj", "kernel"), (heads * v_dim, h), (heads * v_dim) ** -0.5),
        ]
        if i < cfg["first_k_dense_replace"]:
            table += swiglu_leaves(layer + ("mlp",), h, cfg["intermediate_size"])
            continue
        table += [
            (layer + ("mlp", "router"), (h, cfg["router_width"]), h ** -0.5),
            (layer + ("mlp", "experts", "gate_proj"), (count, h, f), h ** -0.5),
            (layer + ("mlp", "experts", "up_proj"), (count, h, f), h ** -0.5),
            (layer + ("mlp", "experts", "down_proj"), (count, f, h), f ** -0.5),
        ]
        table += swiglu_leaves(layer + ("mlp", "shared_experts"), h, cfg["n_shared_experts"] * f)
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
    from accelerate_tpu.models.pangu_ultra_moe import (PanguUltraMoeConfig,
                                                        PanguUltraMoeForCausalLM)
    from accelerate_tpu.serving import ServingGateway

    a = cfg["assumed"]
    module = PanguUltraMoeForCausalLM(PanguUltraMoeConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        first_k_dense_replace=cfg["first_k_dense_replace"],
        num_attention_heads=cfg["num_attention_heads"], q_lora_rank=cfg["q_lora_rank"],
        kv_lora_rank=cfg["kv_lora_rank"], qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        max_position_embeddings=cfg["max_position_embeddings"],
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        num_experts=cfg["router_width"], num_experts_per_tok=cfg["num_experts_per_tok"],
        n_shared_experts=cfg["n_shared_experts"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        norm_topk_prob=cfg["norm_topk_prob"], held_experts=held(cfg)))
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

QUERY_BLOCK = 1024      # scores exist for HEAD_BLOCK heads and this many queries at a time
HEAD_BLOCK = 16         # 16 x 1024 x 8192 float32 scores are 0.5 GB; all 128 heads, 4.3 GB


def blocked_attention(q, k_nope, k_rope, v):
    """q [S, H, nope + rope]; k_nope [S, H, nope]; k_rope [S, rope] (one key
    for all heads); v [S, H, v_dim]. Causal softmax attention, one block of
    heads and one block of queries at a time."""
    s, h, d = q.shape
    nope = k_nope.shape[-1]
    block = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    hb = HEAD_BLOCK if h % HEAD_BLOCK == 0 else h
    q_nope = q[..., :nope].reshape(s // block, block, h // hb, hb, nope).transpose(2, 0, 3, 1, 4)
    q_rope = q[..., nope:].reshape(s // block, block, h // hb, hb, d - nope).transpose(2, 0, 3, 1, 4)
    kg = k_nope.reshape(s, h // hb, hb, nope).transpose(1, 2, 0, 3)             # [G, hb, S, nope]
    vg = v.reshape(s, h // hb, hb, v.shape[-1]).transpose(1, 2, 0, 3)           # [G, hb, S, v]
    key_pos = jnp.arange(s)

    def one_group(args):
        qn_blocks, qr_blocks, kk, vv = args

        def one_block(args):
            qn, qr, first = args                                                # [hb, block, .]
            mask = key_pos[None, :] <= (first + jnp.arange(block))[:, None]
            scores = (jnp.einsum("hqd,htd->hqt", qn, kk, precision=ops.HIGHEST)
                      + jnp.einsum("hqd,td->hqt", qr, k_rope, precision=ops.HIGHEST)) * d ** -0.5
            probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
            return jnp.einsum("hqt,htd->hqd", probs, vv, precision=ops.HIGHEST)

        return jax.lax.map(one_block, (qn_blocks, qr_blocks, jnp.arange(s // block) * block))

    out = jax.lax.map(one_group, (q_nope, q_rope, kg, vg))        # [G, nb, hb, block, v]
    return out.transpose(1, 3, 0, 2, 4).reshape(s, h * v.shape[-1])


def attention(n, p, cfg, mm):
    s, heads, rank = n.shape[0], cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope_dim, v_dim = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    c_q = ops.rms_norm(mm(n, p["q_a_proj"]["kernel"]), p["q_a_norm"]["scale"], eps)
    q = mm(c_q, p["q_b_proj"]["kernel"]).reshape(s, heads, nope + rope_dim)
    q = jnp.concatenate([q[..., :nope], ops.rope(q[..., nope:], theta)], axis=-1)
    kv = mm(n, p["kv_a_proj"]["kernel"])
    c_kv = ops.rms_norm(kv[:, :rank], p["kv_a_norm"]["scale"], eps)
    k_rope = ops.rope(kv[:, None, rank:], theta)[:, 0]                # one rotary key for all heads
    up = mm(c_kv, p["kv_b_proj"]["kernel"]).reshape(s, heads, nope + v_dim)
    out = blocked_attention(q, up[..., :nope], k_rope, up[..., nope:])
    return mm(out, p["o_proj"]["kernel"])


def dense_mlp(n, p, mm):
    return ops.swiglu(n, p["gate_proj"]["kernel"], p["up_proj"]["kernel"],
                      p["down_proj"]["kernel"], mm)


def moe_mlp(n, p, cfg, mm):
    """Every token is routed over all ``router_width`` experts; every HELD
    expert is computed for every token and weighted by its gate (zero where
    the token did not pick it); what the absent experts would add is left
    out, as in the program. The shared expert is added whole."""
    first, count = held(cfg)
    scores = jax.nn.sigmoid(mm(n, p["router"]))
    top, top_i = jax.lax.top_k(scores, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        top = top / (top.sum(-1, keepdims=True) + 1e-20)
    top = top * cfg["routed_scaling_factor"]
    gates = jnp.zeros_like(scores).at[jnp.arange(n.shape[0])[:, None], top_i].set(top)
    routed = expert_sum(n, p["experts"], gates[:, first:first + count], mm)
    return routed + dense_mlp(n, p["shared_experts"], mm)


def reference_logits(params, ids, cfg: dict, mm):
    """ids [S] -> logits [S, vocab slice] (float32): one full causal forward
    pass in the plain form of the layer."""
    eps = cfg["rms_norm_eps"]
    x = params["embed_tokens"]["embedding"][ids].astype(jnp.float32)
    for i in range(cfg["num_hidden_layers"]):
        layer = ops.f32_lazy(params[f"layers_{i}"])
        attn = attention(ops.rms_norm(x, layer["input_norm"]["scale"], eps),
                         layer["self_attn"], cfg, mm)
        x = x + ops.rms_norm(attn, layer["post_attn_norm"]["scale"], eps)
        n = ops.rms_norm(x, layer["pre_mlp_norm"]["scale"], eps)
        mlp = (dense_mlp(n, layer["mlp"], mm) if i < cfg["first_k_dense_replace"]
               else moe_mlp(n, layer["mlp"], cfg, mm))
        x = x + ops.rms_norm(mlp, layer["post_mlp_norm"]["scale"], eps)
    x = ops.rms_norm(x, params["norm"]["scale"].astype(jnp.float32), eps)
    return mm(x, params["lm_head"]["kernel"].astype(jnp.float32))
