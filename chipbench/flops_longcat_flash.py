"""Operations and bytes the ``longcat_flash`` family needs, from shapes and
traffic alone (as flops.py: never from the program's HLO, so the counts read
the same work whatever implements it).

A double layer holds two latent attentions (each: the projections ``q_a``,
``q_b``, ``kv_a`` and ``o``, and the up-projection ``kv_b`` of the latent),
two dense SwiGLU MLPs, the router over ``router_width + zero_expert_num``
columns and ``count`` HELD routed experts of ``router_width``. A token passes
through every projection, both MLPs, the router and the held experts it
picked: ``k * count / columns`` of them in expectation (12 x 16 / 768 = 0.25).
A pick on a zero-compute expert adds the token itself: no weights, no FLOPs
counted (one multiply-add a token).

Latent attention is counted as flops_pangu_ultra_moe.py counts it: the LESSER
of its absorbed and expanded forms for each call shape, so that no
implementation can read above 100 %; here two attentions a layer, each with
its own cache entry.
"""

from __future__ import annotations

from chipbench.flops_pangu_ultra_moe import attention_flops, chunk_attention_flops

WEIGHT_BYTES = 2


def held_count(cfg: dict) -> int:
    first, stop = cfg["held_experts"]
    return int(stop) - int(first)


def columns(cfg: dict) -> int:
    """The router's columns: the routed experts, then the zero-compute ones."""
    return cfg["router_width"] + cfg["zero_expert_num"]


def attentions(cfg: dict) -> int:
    """Latent attentions of the model, one cache entry each: two a layer."""
    return 2 * cfg["num_layers"]


def row_width(cfg: dict) -> int:
    """Values the cache holds a token an attention."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def layer_params(cfg: dict) -> dict:
    """Matmul parameters of one double layer, by part: ``mla`` and ``kv_b``
    are ONE attention's (its four projections; the latent's up-projection,
    applied per query or per key row), ``dense`` ONE dense MLP, ``expert`` ONE
    routed expert."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, v = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    mla = (h * cfg["q_lora_rank"] + cfg["q_lora_rank"] * heads * (nope + rope)
           + h * row_width(cfg) + heads * v * h)
    return {"mla": mla, "kv_b": cfg["kv_lora_rank"] * heads * (nope + v),
            "dense": 3 * h * cfg["ffn_hidden_size"], "router": h * columns(cfg),
            "expert": 3 * h * cfg["expert_ffn_hidden_size"]}


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def held_picks_per_token(cfg: dict) -> float:
    """Expected picks of one token that land on held experts."""
    return cfg["moe_topk"] * held_count(cfg) / columns(cfg)


def zero_pick_share(cfg: dict) -> float:
    """Expected share of all picks that land on zero-compute experts."""
    return cfg["zero_expert_num"] / columns(cfg)


def token_matmul_flops(cfg: dict) -> float:
    """2 x the parameters one token passes through in ALL layers, the
    latents' up-projections left to the attention counts."""
    p = layer_params(cfg)
    layer = (2 * p["mla"] + 2 * p["dense"] + p["router"]
             + held_picks_per_token(cfg) * p["expert"])
    return 2.0 * cfg["num_layers"] * layer


def prefill_attention_flops(cfg: dict, prompt: int) -> float:
    """ALL attentions for a prompt prefilled in chunks of the configuration's
    ``prefill_chunk``."""
    chunk = cfg["assumed"]["prefill_chunk"]
    one = sum(chunk_attention_flops(cfg, o, min(chunk, prompt - o))
              for o in range(0, prompt, chunk))
    return attentions(cfg) * one


def token_flops(cfg: dict, context: float, with_head: bool = True) -> float:
    """Forward FLOPs of one decoded token at ``context`` keys (itself included)."""
    flops = token_matmul_flops(cfg) + attentions(cfg) * attention_flops(cfg, 1, context, context)
    return flops + (2.0 * head_params(cfg) if with_head else 0.0)


def request_flops(cfg: dict, prompt_len: int, first: bool, later_contexts) -> float:
    """FLOPs of the part of one request that fell in a window: the whole
    prompt with one head application if its first token did, and one decode
    step with the head for every later token (at the context it ran at)."""
    total = 0.0
    if first:
        total += (prompt_len * token_matmul_flops(cfg) + prefill_attention_flops(cfg, prompt_len)
                  + 2.0 * head_params(cfg))
    return total + sum(token_flops(cfg, c) for c in later_contexts)


def experts_touched(cfg: dict, tokens: float) -> float:
    """Held experts that ``tokens`` tokens are expected to touch in one layer,
    with top-k picks spread evenly over all columns: ``count * (1 - (1 -
    k/columns)^tokens)``."""
    share = cfg["moe_topk"] / columns(cfg)
    return held_count(cfg) * (1.0 - (1.0 - share) ** tokens)


def decode_tick_bytes(cfg: dict, active_slots: float, contexts) -> float:
    """Bytes one decode tick has to read: the weights every token passes (both
    attentions with ``kv_b``, both dense MLPs, the router, the head), the held
    experts ``active_slots`` tokens are expected to touch, each once, and the
    latent rows of their contexts in every attention's entry (``contexts``:
    the contexts of the window's decoded tokens; their mean is what a tick
    reads a slot)."""
    p = layer_params(cfg)
    contexts = list(contexts)
    layer = (2 * (p["mla"] + p["kv_b"]) + 2 * p["dense"] + p["router"]
             + experts_touched(cfg, active_slots) * p["expert"])
    weights = cfg["num_layers"] * layer + head_params(cfg)
    rows = active_slots * sum(contexts) / len(contexts)
    return WEIGHT_BYTES * (weights + attentions(cfg) * rows * row_width(cfg))
