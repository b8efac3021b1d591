"""Family ``cohere2_moe``: the parallel block of CohereLabs' Command A+
(``model_type`` ``cohere2_moe``) as a served model — one bias-free LayerNorm
feeding grouped-query attention (sliding layers with interleaved rotary
pairs, full layers with no positional encoding) and, beside it, a
sigmoid-scored top-k router over all experts, the routed experts HELD on this
chip and averaged shared experts; tied head.

* ``leaf_table`` / ``make_params``: the seeded weights in the served type;
* ``build_server``: the program under test, built the way ``accelerate-tpu
  serve`` builds it and fronted by its HTTP gateway;
* ``reference_logits``: the plain float32 reference of one full forward pass.
  It imports nothing of the program and is given nothing the program made.

Readings of the published config (each also under ``assumed`` in the
configuration file): an expert's width is ``intermediate_size``; "average" is
the mean of the shared experts' outputs, added to the routed sum; full layers
carry no positional encoding; ``prefix_dense_*`` is unused
(``first_k_dense_replace`` 0); the vision tower is left out. ``num_experts``
in the file is the number of routed experts held here, ``router_width`` the
router's published width, ``held_experts`` = [first, first + count).
"""

from __future__ import annotations

import contextlib
import sys

import jax
import jax.numpy as jnp

from chipbench import harness
from chipbench import reference_ops as ops

Server = harness.load_module("models", "mixtral").Server    # the same fleet + gateway wrapper


def held(cfg: dict) -> tuple:
    first, stop = cfg["held_experts"]
    return int(first), int(stop) - int(first)


def layer_kinds(cfg: dict) -> list:
    return list(cfg["layer_types"][: cfg["num_hidden_layers"]])


def leaf_table(cfg: dict) -> list:
    """[(path, shape, std)] in the layout of the program's parameter tree."""
    h, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    count, shared = held(cfg)[1], cfg["num_shared_experts"]
    # the head is the embedding (tied, logit_scale 1): rows of norm ~1 keep the logits of order 1
    table = [(("embed_tokens", "embedding"), (v, h), h ** -0.5)]
    for i in range(cfg["num_hidden_layers"]):
        layer = (f"layers_{i}",)
        table += [
            (layer + ("input_norm", "scale"), (h,), None),
            (layer + ("self_attn", "q_proj", "kernel"), (h, q), h ** -0.5),
            (layer + ("self_attn", "k_proj", "kernel"), (h, kv), h ** -0.5),
            (layer + ("self_attn", "v_proj", "kernel"), (h, kv), h ** -0.5),
            (layer + ("self_attn", "o_proj", "kernel"), (q, h), q ** -0.5),
            (layer + ("mlp", "router"), (h, cfg["router_width"]), h ** -0.5),
        ]
        for group, n in (("experts", count), ("shared_experts", shared)):
            table += [
                (layer + ("mlp", group, "gate_proj"), (n, h, f), h ** -0.5),
                (layer + ("mlp", group, "up_proj"), (n, h, f), h ** -0.5),
                (layer + ("mlp", group, "down_proj"), (n, f, h), f ** -0.5),
            ]
    return table + [(("norm", "scale"), (h,), None)]


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
    from accelerate_tpu.models.cohere2_moe import Cohere2MoeConfig, Cohere2MoeForCausalLM
    from accelerate_tpu.serving import ServingGateway

    a = cfg["assumed"]
    module = Cohere2MoeForCausalLM(Cohere2MoeConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        max_position_embeddings=cfg["max_position_embeddings"],
        layer_norm_eps=cfg["layer_norm_eps"], rope_theta=cfg["rope_theta"],
        sliding_window=cfg["sliding_window"], layer_types=tuple(layer_kinds(cfg)),
        num_experts=cfg["router_width"], num_experts_per_tok=cfg["num_experts_per_tok"],
        num_shared_experts=cfg["num_shared_experts"], held_experts=held(cfg),
        expert_selection_fn=cfg["expert_selection_fn"], norm_topk_prob=cfg["norm_topk_prob"],
        logit_scale=cfg["logit_scale"]))
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

QUERY_BLOCK = 1024      # scores exist for one KV group and this many queries at a time


def layer_norm(x, scale, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def rope_interleaved(x, theta):
    """x: [S, heads, head_dim] at positions 0..S-1; rotates the pairs
    (2i, 2i+1) of each head (``rope_gptj``), over all of head_dim."""
    s, _, d = x.shape
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1).reshape(x.shape)


def blocked_attention(q, k, v, window):
    """q: [S, H, D]; k, v: [S, G, D]. Causal (and windowed) softmax attention,
    one KV group and one block of queries at a time: at 8192 positions and 128
    heads the whole score tensor would be 34 GB."""
    s, h, d = q.shape
    g = k.shape[1]
    block = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    qg = q.reshape(s // block, block, g, h // g, d).transpose(2, 0, 3, 1, 4)   # [G, nb, R, block, D]
    kg, vg = k.transpose(1, 0, 2), v.transpose(1, 0, 2)                         # [G, S, D]
    key_pos = jnp.arange(s)

    def one_group(args):
        q_blocks, kk, vv = args

        def one_block(args):
            qq, first = args
            query_pos = first + jnp.arange(block)
            mask = key_pos[None, :] <= query_pos[:, None]
            if window is not None:
                mask &= key_pos[None, :] > query_pos[:, None] - window
            scores = jnp.einsum("rqd,td->rqt", qq, kk, precision=ops.HIGHEST) * d ** -0.5
            probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
            return jnp.einsum("rqt,td->rqd", probs, vv, precision=ops.HIGHEST)

        return jax.lax.map(one_block, (q_blocks, jnp.arange(s // block) * block))

    out = jax.lax.map(one_group, (qg, kg, vg))                    # [G, nb, R, block, D]
    return out.transpose(1, 3, 0, 2, 4).reshape(s, h * d)


def attention(n, p, cfg, kind, mm):
    s = n.shape[0]
    n_q, n_kv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    q = mm(n, p["q_proj"]["kernel"]).reshape(s, n_q, d)
    k = mm(n, p["k_proj"]["kernel"]).reshape(s, n_kv, d)
    v = mm(n, p["v_proj"]["kernel"]).reshape(s, n_kv, d)
    window = None
    if kind == "sliding_attention":           # full layers: no positional encoding, no window
        q, k = rope_interleaved(q, cfg["rope_theta"]), rope_interleaved(k, cfg["rope_theta"])
        window = cfg["sliding_window"]
    return mm(blocked_attention(q, k, v, window), p["o_proj"]["kernel"])


def expert_sum(n, stacks, weights, mm):
    """sum_e weights[:, e] * F_e(n) over the experts of ``stacks`` ([n, ...]),
    one expert at a time (its weights cast to float32 only while it runs)."""
    def one_expert(total, args):
        gate, up, down, w = args
        out = ops.swiglu(n, gate.astype(jnp.float32), up.astype(jnp.float32),
                         down.astype(jnp.float32), mm)
        return total + w[:, None] * out, None

    total, _ = jax.lax.scan(one_expert, jnp.zeros_like(n),
                            (stacks["gate_proj"], stacks["up_proj"], stacks["down_proj"],
                             weights.T))
    return total


def reference_logits(params, ids, cfg: dict, mm):
    """ids [S] -> logits [S, vocab slice] (float32): one full causal forward
    pass. Every token is routed over all ``router_width`` experts; every HELD
    expert is computed for every token and weighted by its gate (zero where
    the token did not pick it); what the absent experts would add is left
    out, as in the program."""
    eps, k = cfg["layer_norm_eps"], cfg["num_experts_per_tok"]
    first, count = held(cfg)
    embedding = params["embed_tokens"]["embedding"].astype(jnp.float32)
    x = embedding[ids]
    rows = jnp.arange(ids.shape[0])[:, None]
    for i, kind in enumerate(layer_kinds(cfg)):
        layer = ops.f32_lazy(params[f"layers_{i}"])
        n = layer_norm(x, layer["input_norm"]["scale"], eps)
        scores = mm(n, layer["mlp"]["router"])
        scores = (jax.nn.sigmoid(scores) if cfg["expert_selection_fn"] == "sigmoid"
                  else jax.nn.softmax(scores, axis=-1))
        top, top_i = jax.lax.top_k(scores, k)
        if cfg["norm_topk_prob"]:
            top = top / top.sum(-1, keepdims=True)
        gates = jnp.zeros_like(scores).at[rows, top_i].set(top)
        routed = expert_sum(n, layer["mlp"]["experts"], gates[:, first:first + count], mm)
        n_shared = cfg["num_shared_experts"]
        shared = expert_sum(n, layer["mlp"]["shared_experts"],
                            jnp.full((n.shape[0], n_shared), 1.0 / n_shared), mm)
        x = x + attention(n, layer["self_attn"], cfg, kind, mm) + routed + shared
    x = layer_norm(x, params["norm"]["scale"].astype(jnp.float32), eps)
    return cfg["logit_scale"] * mm(x, embedding.T)
