"""Family ``longcat_flash``: the language model of LongCat-Flash-Omni as a
served model — double layers of two latent attentions (MLA with LongCat's
low-rank scales; the cache holds ``kv_lora_rank + qk_rope_head_dim`` values a
token an attention, two entries a layer), two dense SwiGLU MLPs and one
shortcut-connected expert branch whose router scores the routed experts and
the zero-compute ones by one softmax; the routed experts HELD on this chip;
an untied head.

* ``leaf_table`` / ``make_params``: the seeded weights in the served type;
* ``build_server``: the program under test, built the way ``accelerate-tpu
  serve`` builds it and fronted by its HTTP gateway;
* ``reference_logits``: the plain float32 reference of one full forward pass,
  in the plain form (every head's key and value expanded from the latent; the
  router over all its columns; every held expert computed for every token;
  a zero-compute pick adds the token itself). It imports nothing of the
  program and is given nothing the program made.

Readings of the published config (each also under ``assumed`` in the
configuration file): a double layer is ``h1 = x + attn0(norm_in0(x))``, ``n1
= norm_post0(h1)``, ``h2 = h1 + mlp0(n1)``, ``h3 = h2 + attn1(norm_in1(h2))``,
``y = h3 + mlp1(norm_post1(h3)) + moe(n1)``; the query is scaled by
``(hidden / q_lora_rank) ** 0.5`` and the normed latent by ``(hidden /
kv_lora_rank) ** 0.5``; the router is a softmax over ``router_width +
zero_expert_num`` columns, picks the top ``moe_topk`` of score + correction
bias and gates each pick by its score, not renormalised, times
``routed_scaling_factor``; rotary pairs are (i, i + 32)
(``reference_ops.rope``), no rope scaling, so the softmax scale is 192 **
-0.5. ``n_routed_experts`` in the file is the number of routed experts held
here, ``router_width`` their published number, ``held_experts`` = [first,
first + count).
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
blocked_attention = harness.load_module("models", "pangu_ultra_moe").blocked_attention


def held(cfg: dict) -> tuple:
    first, stop = cfg["held_experts"]
    return int(first), int(stop) - int(first)


def scales(cfg: dict) -> tuple:
    """(query scale, latent scale): ``(hidden / rank) ** 0.5`` each, where the
    configuration turns it on."""
    h = cfg["hidden_size"]
    return ((h / cfg["q_lora_rank"]) ** 0.5 if cfg["mla_scale_q_lora"] else 1.0,
            (h / cfg["kv_lora_rank"]) ** 0.5 if cfg["mla_scale_kv_lora"] else 1.0)


def columns(cfg: dict) -> int:
    """The router's columns: the routed experts, then the zero-compute ones."""
    return cfg["router_width"] + cfg["zero_expert_num"]


def leaf_table(cfg: dict) -> list:
    """[(path, shape, std)] in the layout of the program's parameter tree."""
    h, v, heads = cfg["hidden_size"], cfg["vocab_size"], cfg["num_attention_heads"]
    q_rank, rank, rope = cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    nope, v_dim = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    f, count = cfg["expert_ffn_hidden_size"], held(cfg)[1]
    # the low-rank scales make an up-projection's output as large as a
    # full-rank projection's under one init std: seeded with fan_in ** -0.5
    # divided by its scale, the scaled query and latent paths leave with unit
    # variance, as every other projection does
    q_scale, latent_scale = scales(cfg)
    table = [(("embed_tokens", "embedding"), (v, h), 1.0)]
    for i in range(cfg["num_layers"]):
        layer = (f"layers_{i}",)
        table += [(layer + (name, "scale"), (h,), None)
                  for name in ("norm_in0", "norm_post0", "norm_in1", "norm_post1")]
        for name in ("attn0", "attn1"):
            attn = layer + (name,)
            table += [
                (attn + ("q_a_proj", "kernel"), (h, q_rank), h ** -0.5),
                (attn + ("q_a_norm", "scale"), (q_rank,), None),
                (attn + ("q_b_proj", "kernel"), (q_rank, heads * (nope + rope)),
                 q_rank ** -0.5 / q_scale),
                (attn + ("kv_a_proj", "kernel"), (h, rank + rope), h ** -0.5),
                (attn + ("kv_a_norm", "scale"), (rank,), None),
                (attn + ("kv_b_proj", "kernel"), (rank, heads * (nope + v_dim)),
                 rank ** -0.5 / latent_scale),
                (attn + ("o_proj", "kernel"), (heads * v_dim, h), (heads * v_dim) ** -0.5),
            ]
        for name in ("mlp0", "mlp1"):
            table += swiglu_leaves(layer + (name,), h, cfg["ffn_hidden_size"])
        table += [
            (layer + ("moe", "router"), (h, columns(cfg)), h ** -0.5),
            (layer + ("moe", "e_score_correction_bias"), (columns(cfg),), 1.0 / columns(cfg)),
            (layer + ("moe", "experts", "gate_proj"), (count, h, f), h ** -0.5),
            (layer + ("moe", "experts", "up_proj"), (count, h, f), h ** -0.5),
            (layer + ("moe", "experts", "down_proj"), (count, f, h), f ** -0.5),
        ]
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
    from accelerate_tpu.models.longcat_flash import LongcatFlashConfig, LongcatFlashForCausalLM
    from accelerate_tpu.serving import ServingGateway

    a = cfg["assumed"]
    module = LongcatFlashForCausalLM(LongcatFlashConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        ffn_hidden_size=cfg["ffn_hidden_size"],
        expert_ffn_hidden_size=cfg["expert_ffn_hidden_size"], num_layers=cfg["num_layers"],
        num_attention_heads=cfg["num_attention_heads"], q_lora_rank=cfg["q_lora_rank"],
        kv_lora_rank=cfg["kv_lora_rank"], qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        mla_scale_q_lora=cfg["mla_scale_q_lora"], mla_scale_kv_lora=cfg["mla_scale_kv_lora"],
        max_position_embeddings=cfg["max_position_embeddings"],
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        n_routed_experts=cfg["router_width"], zero_expert_num=cfg["zero_expert_num"],
        moe_topk=cfg["moe_topk"], routed_scaling_factor=cfg["routed_scaling_factor"],
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


def attention(n, p, cfg, mm):
    """One MLA in the plain form, with LongCat's low-rank scales. Kernels are
    cast to float32 where they are used (a layer's, all in float32 at once,
    would not fit beside the served weights)."""
    s, heads, rank = n.shape[0], cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope_dim, v_dim = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    q_scale, latent_scale = scales(cfg)
    c_q = ops.rms_norm(mm(n, f32(p["q_a_proj"]["kernel"])), f32(p["q_a_norm"]["scale"]), eps)
    q = mm(c_q, f32(p["q_b_proj"]["kernel"])).reshape(s, heads, nope + rope_dim)
    q = jnp.concatenate([q[..., :nope], ops.rope(q[..., nope:], theta)], axis=-1) * q_scale
    kv = mm(n, f32(p["kv_a_proj"]["kernel"]))
    c_kv = ops.rms_norm(kv[:, :rank], f32(p["kv_a_norm"]["scale"]), eps) * latent_scale
    k_rope = ops.rope(kv[:, None, rank:], theta)[:, 0]                # one rotary key for all heads
    up = mm(c_kv, f32(p["kv_b_proj"]["kernel"])).reshape(s, heads, nope + v_dim)
    out = blocked_attention(q, up[..., :nope], k_rope, up[..., nope:])
    return mm(out, f32(p["o_proj"]["kernel"]))


def dense_mlp(n, p, mm):
    return ops.swiglu(n, f32(p["gate_proj"]["kernel"]), f32(p["up_proj"]["kernel"]),
                      f32(p["down_proj"]["kernel"]), mm)


def moe(n, p, cfg, mm):
    """Every token is routed over all the router's columns; every HELD expert
    is computed for every token and weighted by its gate (zero where the token
    did not pick it); what the absent routed experts would add is left out,
    as in the program; a pick on a zero-compute column adds the token."""
    first, count = held(cfg)
    scores = jax.nn.softmax(mm(n, f32(p["router"])), axis=-1)
    _, top_i = jax.lax.top_k(scores + f32(p["e_score_correction_bias"]), cfg["moe_topk"])
    rows = jnp.arange(n.shape[0])[:, None]
    gates = jnp.zeros_like(scores).at[rows, top_i].set(scores[rows, top_i])
    routed = expert_sum(n, p["experts"], gates[:, first:first + count], mm)
    identity = gates[:, cfg["router_width"]:].sum(-1, keepdims=True) * n
    return (routed + identity) * cfg["routed_scaling_factor"]


def reference_logits(params, ids, cfg: dict, mm):
    """ids [S] -> logits [S, vocab slice] (float32): one full causal forward
    pass in the plain form of the double layer."""
    eps = cfg["rms_norm_eps"]
    x = params["embed_tokens"]["embedding"][ids].astype(jnp.float32)
    for i in range(cfg["num_layers"]):
        p = params[f"layers_{i}"]
        h = x + attention(ops.rms_norm(x, f32(p["norm_in0"]["scale"]), eps), p["attn0"], cfg, mm)
        n = ops.rms_norm(h, f32(p["norm_post0"]["scale"]), eps)
        shortcut = moe(n, p["moe"], cfg, mm)
        h = h + dense_mlp(n, p["mlp0"], mm)
        h = h + attention(ops.rms_norm(h, f32(p["norm_in1"]["scale"]), eps), p["attn1"], cfg, mm)
        h = h + dense_mlp(ops.rms_norm(h, f32(p["norm_post1"]["scale"]), eps), p["mlp1"], mm)
        x = h + shortcut
    x = ops.rms_norm(x, f32(params["norm"]["scale"]), eps)
    return mm(x, f32(params["lm_head"]["kernel"]))
