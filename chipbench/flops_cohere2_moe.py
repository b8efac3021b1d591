"""Operations and bytes the ``cohere2_moe`` family needs, from shapes and
traffic alone (as flops.py: never from the program's HLO, so the counts read
the same work whatever implements it).

One layer holds attention (q, k, v, o), the router of the model's full width,
``num_shared_experts`` shared experts and ``count`` HELD routed experts of
``router_width``. A token passes through attention, the router, every shared
expert and the held experts it picked: ``k * count / router_width`` of them
in expectation (1 at 8 of 128 with 16 held). Attention at context ``c`` reads
``min(c, window)`` keys on a sliding layer and ``c`` on a full one; the head
is the held slice of the vocabulary.
"""

from __future__ import annotations

SLIDING = "sliding_attention"


def kinds(cfg: dict) -> list:
    return list(cfg["layer_types"][: cfg["num_hidden_layers"]])


def held_count(cfg: dict) -> int:
    first, stop = cfg["held_experts"]
    return int(stop) - int(first)


def widths(cfg: dict) -> tuple:
    """(query width, kv width) of attention."""
    return (cfg["num_attention_heads"] * cfg["head_dim"],
            cfg["num_key_value_heads"] * cfg["head_dim"])


def layer_params(cfg: dict) -> dict:
    """Matmul parameters of one layer, by part; ``expert`` is ONE expert."""
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    q, kv = widths(cfg)
    return {"attn": 2 * h * q + 2 * h * kv, "router": h * cfg["router_width"],
            "shared": cfg["num_shared_experts"] * 3 * h * f, "expert": 3 * h * f}


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def held_picks_per_token(cfg: dict) -> float:
    """Expected picks of one token that land on held experts."""
    return cfg["num_experts_per_tok"] * held_count(cfg) / cfg["router_width"]


def token_matmul_flops(cfg: dict) -> float:
    """2 x the parameters one token passes through in one layer."""
    p = layer_params(cfg)
    return 2.0 * (p["attn"] + p["router"] + p["shared"] + held_picks_per_token(cfg) * p["expert"])


def keys_seen(cfg: dict, kind: str, context: float) -> float:
    return min(context, cfg["sliding_window"]) if kind == SLIDING else context


def prefill_keys(cfg: dict, kind: str, prompt: int) -> float:
    """Sum over the positions 1..prompt of the keys each one reads."""
    w = cfg["sliding_window"]
    if kind != SLIDING or prompt <= w:
        return prompt * (prompt + 1) / 2.0
    return w * (w + 1) / 2.0 + (prompt - w) * w


def token_flops(cfg: dict, context: float, with_head: bool) -> float:
    """Forward FLOPs of one token at ``context`` keys (itself included)."""
    q, _ = widths(cfg)
    flops = sum(token_matmul_flops(cfg) + 4.0 * q * keys_seen(cfg, kind, context)
                for kind in kinds(cfg))
    return flops + (2.0 * head_params(cfg) if with_head else 0.0)


def request_flops(cfg: dict, prompt_len: int, first: bool, later_contexts) -> float:
    """FLOPs of the part of one request that fell in a window: the whole
    prompt with one head application if its first token did, and one decode
    step with the head for every later token (at the context it ran at)."""
    q, _ = widths(cfg)
    total = 0.0
    if first:
        total += prompt_len * len(kinds(cfg)) * token_matmul_flops(cfg)
        total += sum(4.0 * q * prefill_keys(cfg, kind, prompt_len) for kind in kinds(cfg))
        total += 2.0 * head_params(cfg)
    return total + sum(token_flops(cfg, c, True) for c in later_contexts)


def experts_touched(cfg: dict, tokens: float) -> float:
    """Held experts that ``tokens`` tokens are expected to touch in one layer,
    with top-k picks spread evenly: ``count * (1 - (1 - k/E)^tokens)``."""
    share = cfg["num_experts_per_tok"] / cfg["router_width"]
    return held_count(cfg) * (1.0 - (1.0 - share) ** tokens)


def moe_bytes(cfg: dict, tokens: float, weight_bytes: int = 2) -> float:
    """Expert weights one pass of ``tokens`` tokens through ALL layers has to
    read: the shared experts and the held experts expected to be touched."""
    p = layer_params(cfg)
    per_layer = p["shared"] + experts_touched(cfg, tokens) * p["expert"]
    return float(len(kinds(cfg)) * per_layer * weight_bytes)


def moe_flops(cfg: dict, tokens: float) -> float:
    """Expert FLOPs of the same pass: shared + the held picks."""
    p = layer_params(cfg)
    per_token = 2.0 * (p["shared"] + held_picks_per_token(cfg) * p["expert"])
    return float(len(kinds(cfg)) * tokens * per_token)


def decode_tick_bytes(cfg: dict, active_slots: float, contexts, weight_bytes: int = 2,
                      kv_bytes: int = 2) -> float:
    """Bytes one decode tick has to read: attention, router and head weights
    once, the expert weights of :func:`moe_bytes`, and the live KV rows of
    ``active_slots`` streams — window-capped on sliding layers. ``contexts``
    are the contexts of the window's decoded tokens (their mean row count
    per layer kind is what a tick reads per slot)."""
    p = layer_params(cfg)
    _, kv = widths(cfg)
    contexts = list(contexts)
    weights = len(kinds(cfg)) * (p["attn"] + p["router"]) + head_params(cfg)
    rows = sum(sum(keys_seen(cfg, kind, c) for c in contexts) / len(contexts)
               for kind in kinds(cfg))
    return (weights * weight_bytes + moe_bytes(cfg, active_slots, weight_bytes)
            + 2 * kv * active_slots * rows * kv_bytes)
