"""Operations and bytes the ``pangu_ultra_moe`` family needs, from shapes and
traffic alone (as flops.py: never from the program's HLO, so the counts read
the same work whatever implements it).

One layer holds latent attention (the projections ``q_a``, ``q_b``, ``kv_a``
and ``o``, and the up-projection ``kv_b`` of the latent), then either the
dense MLP (a leading layer) or the router of the model's full width, the
shared expert and ``count`` HELD routed experts of ``router_width``. A token
passes through the four projections, the router, the shared expert and the
held experts it picked: ``k * count / router_width`` of them in expectation
(0.5 at 8 of 256 with 16 held).

Attention against a latent cache can be computed two ways, and the count
takes the LESSER for each call shape, so that no implementation can read
above 100 % of it: *absorbed* (``kv_b``'s key half folded into each query,
scores and the weighted sum over ``rank + rope`` / ``rank`` wide rows, the
value half applied after) or *expanded* (each visible row's per-head key and
value made once for the call, then plain attention). One query a call (a
decode tick) is absorbed; ``S`` queries sharing their rows are expanded once
``S`` passes ``2 rank (nope + v) / (4 rank - 2 nope - 2 v)`` (171 here).
"""

from __future__ import annotations

WEIGHT_BYTES = 2


def held_count(cfg: dict) -> int:
    first, stop = cfg["held_experts"]
    return int(stop) - int(first)


def expert_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def row_width(cfg: dict) -> int:
    """Values the cache holds a token a layer."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def layer_params(cfg: dict) -> dict:
    """Matmul parameters of one layer, by part; ``expert`` is ONE expert,
    ``proj`` the four projections every token passes, ``kv_b`` the latent's
    up-projection (applied per query or per key row, see the module text)."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, v = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    f = cfg["moe_intermediate_size"]
    proj = (h * cfg["q_lora_rank"] + cfg["q_lora_rank"] * heads * (nope + rope)
            + h * row_width(cfg) + heads * v * h)
    return {"proj": proj, "kv_b": cfg["kv_lora_rank"] * heads * (nope + v),
            "router": h * cfg["router_width"], "shared": cfg["n_shared_experts"] * 3 * h * f,
            "expert": 3 * h * f, "dense": 3 * h * cfg["intermediate_size"]}


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def held_picks_per_token(cfg: dict) -> float:
    """Expected picks of one token that land on held experts."""
    return cfg["num_experts_per_tok"] * held_count(cfg) / cfg["router_width"]


def token_matmul_flops(cfg: dict) -> float:
    """2 x the parameters one token passes through in ALL layers, the
    latent's up-projection left to :func:`attention_flops`."""
    p = layer_params(cfg)
    moe = p["router"] + p["shared"] + held_picks_per_token(cfg) * p["expert"]
    return 2.0 * (cfg["num_hidden_layers"] * p["proj"] + cfg["first_k_dense_replace"] * p["dense"]
                  + expert_layers(cfg) * moe)


def attention_flops(cfg: dict, queries: int, rows: float, pairs: float) -> float:
    """FLOPs of ONE layer's latent attention for one call: ``queries`` queries
    a head that share ``rows`` visible key rows and form ``pairs`` (query,
    visible key) pairs a head. The lesser of the two forms."""
    heads, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, v = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    absorbed = heads * (pairs * (2 * (rank + rope) + 2 * rank) + queries * 2 * rank * (nope + v))
    expanded = heads * (rows * 2 * rank * (nope + v) + pairs * (2 * (nope + rope) + 2 * v))
    return float(min(absorbed, expanded))


def chunk_attention_flops(cfg: dict, offset: int, queries: int) -> float:
    """One layer, one prefill chunk of ``queries`` positions at ``offset``:
    query i sees ``offset + i + 1`` rows, all see among ``offset + queries``."""
    pairs = queries * offset + queries * (queries + 1) / 2.0
    return attention_flops(cfg, queries, offset + queries, pairs)


def prefill_attention_flops(cfg: dict, prompt: int) -> float:
    """ALL layers' attention for a prompt prefilled in chunks of the
    configuration's ``prefill_chunk``."""
    chunk = cfg["assumed"]["prefill_chunk"]
    one = sum(chunk_attention_flops(cfg, o, min(chunk, prompt - o))
              for o in range(0, prompt, chunk))
    return cfg["num_hidden_layers"] * one


def token_flops(cfg: dict, context: float, with_head: bool = True) -> float:
    """Forward FLOPs of one decoded token at ``context`` keys (itself included)."""
    flops = token_matmul_flops(cfg) + cfg["num_hidden_layers"] * attention_flops(
        cfg, 1, context, context)
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
    with top-k picks spread evenly: ``count * (1 - (1 - k/E)^tokens)``."""
    share = cfg["num_experts_per_tok"] / cfg["router_width"]
    return held_count(cfg) * (1.0 - (1.0 - share) ** tokens)


def decode_tick_bytes(cfg: dict, active_slots: float, contexts) -> float:
    """Bytes one decode tick has to read: the weights every token passes
    (attention with ``kv_b``, the dense layer's MLP, router, shared expert,
    head), the held experts ``active_slots`` tokens are expected to touch,
    and the latent rows of their contexts (``contexts``: the contexts of the
    window's decoded tokens; their mean is what a tick reads a slot)."""
    p = layer_params(cfg)
    contexts = list(contexts)
    layers, moe_layers = cfg["num_hidden_layers"], expert_layers(cfg)
    weights = (layers * (p["proj"] + p["kv_b"]) + cfg["first_k_dense_replace"] * p["dense"]
               + moe_layers * (p["router"] + p["shared"]
                               + experts_touched(cfg, active_slots) * p["expert"])
               + head_params(cfg))
    rows = active_slots * sum(contexts) / len(contexts)
    return WEIGHT_BYTES * (weights + layers * rows * row_width(cfg))


def decode_attention_need(cfg: dict, active_slots: float, contexts) -> tuple:
    """(FLOPs, bytes) of ALL layers' latent attention in one decode tick, for
    the rows the queries can see: one query a slot at the mean context, the
    rows read once and ``kv_b`` once a layer."""
    contexts = list(contexts)
    mean = sum(contexts) / len(contexts)
    layers = cfg["num_hidden_layers"]
    flops = layers * active_slots * attention_flops(cfg, 1, mean, mean)
    nbytes = WEIGHT_BYTES * layers * (active_slots * mean * row_width(cfg)
                                      + layer_params(cfg)["kv_b"])
    return float(flops), float(nbytes)


def chunk_attention_need(cfg: dict, offset: float) -> tuple:
    """(FLOPs, bytes) of ALL layers' latent attention in one full prefill
    chunk at ``offset``: the visible rows read once, ``kv_b`` once a layer."""
    chunk, layers = cfg["assumed"]["prefill_chunk"], cfg["num_hidden_layers"]
    flops = layers * chunk_attention_flops(cfg, offset, chunk)
    nbytes = WEIGHT_BYTES * layers * ((offset + chunk) * row_width(cfg)
                                      + layer_params(cfg)["kv_b"])
    return float(flops), float(nbytes)
