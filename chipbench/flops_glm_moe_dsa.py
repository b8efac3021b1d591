"""Operations and bytes the ``glm_moe_dsa`` family needs, from shapes and
traffic alone (as flops.py: never from the program's HLO, so the counts read
the same work whatever implements it).

Every layer holds latent attention (the projections ``q_a``, ``q_b``,
``kv_a`` and ``o``, and ``kv_b``, the latent's up-projection); a ``full``
layer (``indexer_types``) also its indexer (``wq_b``, ``wk``,
``weights_proj``); a ``dense`` layer (``mlp_layer_types``) the dense MLP, a
``sparse`` one the router of ``router_width`` columns, the shared expert and
``count`` HELD routed experts, of which a token picks ``k * count /
router_width`` in expectation (8 x 16 / 256 = 0.5).

Attention is NEEDED over the rows the indexer picks alone: ``min(index_topk,
c)`` rows for a query that sees ``c``, in every layer (a shared layer reads
the picks of the full layer before it). It is counted as
flops_pangu_ultra_moe.py counts it, the LESSER of the absorbed and the
expanded form for the call's shape; a form that scores every visible row
under a mask does more than this, and the rest is waste. The indexer is
needed over every visible row of a full layer: ``index_n_heads`` dot
products of ``index_head_dim`` a row, the ReLU and the weighted sum.
"""

from __future__ import annotations

from chipbench.flops_pangu_ultra_moe import attention_flops

WEIGHT_BYTES = 2


def held_count(cfg: dict) -> int:
    first, stop = cfg["held_experts"]
    return int(stop) - int(first)


def layer_types(cfg: dict) -> list:
    """``(mlp type, indexer type)`` of each layer served."""
    return [(cfg["mlp_layer_types"][i], cfg["indexer_types"][i]) for i in cfg["held_layers"]]


def full_layers(cfg: dict) -> int:
    return sum(index == "full" for _, index in layer_types(cfg))


def row_width(cfg: dict) -> int:
    """Latent values the cache holds a token a layer (the index key aside)."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def layer_params(cfg: dict) -> dict:
    """Matmul parameters by part: ``proj`` the four projections every token
    passes, ``kv_b`` the latent's up-projection, ``indexer`` one full layer's
    indexer, ``expert`` ONE routed expert."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, v = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    q_rank, f = cfg["q_lora_rank"], cfg["moe_intermediate_size"]
    hi, di = cfg["index_n_heads"], cfg["index_head_dim"]
    proj = h * q_rank + q_rank * heads * (nope + rope) + h * row_width(cfg) + heads * v * h
    return {"proj": proj, "kv_b": cfg["kv_lora_rank"] * heads * (nope + v),
            "indexer": q_rank * hi * di + h * di + h * hi,
            "router": h * cfg["router_width"], "shared": cfg["n_shared_experts"] * 3 * h * f,
            "expert": 3 * h * f, "dense": 3 * h * cfg["intermediate_size"]}


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def held_picks_per_token(cfg: dict) -> float:
    """Expected picks of one token that land on held experts."""
    return cfg["num_experts_per_tok"] * held_count(cfg) / cfg["router_width"]


def token_matmul_flops(cfg: dict) -> float:
    """2 x the parameters one token passes through in ALL layers, the
    latent's up-projection left to the attention count."""
    p = layer_params(cfg)
    total = 0.0
    for mlp, index in layer_types(cfg):
        total += p["proj"] + (p["indexer"] if index == "full" else 0)
        total += p["dense"] if mlp == "dense" else (
            p["router"] + p["shared"] + held_picks_per_token(cfg) * p["expert"])
    return 2.0 * total


def index_flops(cfg: dict, pairs: float) -> float:
    """ONE full layer's index scores over ``pairs`` (query, visible row)
    pairs: the dot products of every index head, the ReLU-weighted sum."""
    return pairs * cfg["index_n_heads"] * (2 * cfg["index_head_dim"] + 2)


def selected(cfg: dict, context: float) -> float:
    """Rows a query that sees ``context`` rows reads."""
    return min(cfg["index_topk"], context)


def chunk_flops(cfg: dict, offset: int, queries: int) -> float:
    """Attention and index scores of ALL layers in one prefill chunk of
    ``queries`` positions at ``offset`` (query i sees ``offset + i + 1``
    rows and reads the picked ones of them)."""
    layers = len(cfg["held_layers"])
    sees = [offset + i + 1 for i in range(queries)]
    picked = sum(selected(cfg, c) for c in sees)
    return (layers * attention_flops(cfg, queries, offset + queries, picked)
            + full_layers(cfg) * index_flops(cfg, sum(sees)))


def token_flops(cfg: dict, context: float, with_head: bool = True) -> float:
    """Forward FLOPs of one decoded token at ``context`` rows (itself included)."""
    m = selected(cfg, context)
    flops = (token_matmul_flops(cfg) + len(cfg["held_layers"]) * attention_flops(cfg, 1, m, m)
             + full_layers(cfg) * index_flops(cfg, context))
    return flops + (2.0 * head_params(cfg) if with_head else 0.0)


def request_flops(cfg: dict, prompt_len: int, first: bool, later_contexts) -> float:
    """FLOPs of the part of one request that fell in a window: the whole
    prompt (in chunks of the configuration's ``prefill_chunk``) with one head
    application if its first token did, and one decode step with the head
    for every later token (at the context it ran at)."""
    total = 0.0
    if first:
        chunk = cfg["assumed"]["prefill_chunk"]
        total += (prompt_len * token_matmul_flops(cfg) + 2.0 * head_params(cfg)
                  + sum(chunk_flops(cfg, o, min(chunk, prompt_len - o))
                        for o in range(0, prompt_len, chunk)))
    return total + sum(token_flops(cfg, c) for c in later_contexts)


def experts_touched(cfg: dict, tokens: float) -> float:
    """Held experts that ``tokens`` tokens are expected to touch in one layer,
    with top-k picks spread evenly: ``count * (1 - (1 - k/E)^tokens)``."""
    share = cfg["num_experts_per_tok"] / cfg["router_width"]
    return held_count(cfg) * (1.0 - (1.0 - share) ** tokens)


def index_tick_need(cfg: dict, active_slots: float, contexts) -> tuple:
    """(FLOPs, bytes) of one decode tick's learned selection (the parts
    ``dsa_index`` and ``dsa_select``): on every full layer the indexer's
    projections of ``active_slots`` tokens and the index keys of every
    visible row, scored; on every layer the picked latent rows, read once
    (``contexts``: the window's decoded tokens' contexts; their mean is a
    slot's)."""
    contexts = list(contexts)
    mean = sum(contexts) / len(contexts)
    picked = sum(selected(cfg, c) for c in contexts) / len(contexts)
    indexer = layer_params(cfg)["indexer"]
    full = full_layers(cfg)
    flops = full * active_slots * (2.0 * indexer + index_flops(cfg, mean))
    nbytes = WEIGHT_BYTES * (full * (indexer + active_slots * mean * cfg["index_head_dim"])
                             + len(cfg["held_layers"]) * active_slots * picked * row_width(cfg))
    return float(flops), float(nbytes)


def decode_tick_bytes(cfg: dict, active_slots: float, contexts) -> float:
    """Bytes one decode tick has to read: the weights every token passes
    (attention with ``kv_b``, the indexers, the dense MLP, routers, shared
    experts, head), the held experts ``active_slots`` tokens are expected to
    touch, each once, the picked latent rows of every layer and the index
    keys of every visible row of the full layers (``contexts`` as
    :func:`index_tick_need`)."""
    p = layer_params(cfg)
    contexts = list(contexts)
    weights = head_params(cfg)
    for mlp, index in layer_types(cfg):
        weights += p["proj"] + p["kv_b"] + (p["indexer"] if index == "full" else 0)
        weights += p["dense"] if mlp == "dense" else (
            p["router"] + p["shared"] + experts_touched(cfg, active_slots) * p["expert"])
    mean = sum(contexts) / len(contexts)
    picked = sum(selected(cfg, c) for c in contexts) / len(contexts)
    rows = (len(cfg["held_layers"]) * picked * row_width(cfg)
            + full_layers(cfg) * mean * cfg["index_head_dim"])
    return WEIGHT_BYTES * (weights + active_slots * rows)
