"""Operations and bytes the ``phi4flash`` family needs, from shapes and traffic
alone (as flops.py: never from the program's HLO, so the counts read the same
work whatever implements it).

32 layers, each with the dense SwiGLU MLP, and one of four mixers: a Mamba
layer (9: in/x/dt/out projections, a 4-tap convolution, the selective scan),
differential attention with its own K/V (9: one fused q/k/v projection and
the output projection; 8 of them see ``sliding_window`` keys, one sees all),
a gated memory unit (7: two projections) or differential cross-attention (7:
a query and an output projection, over the keys and values of the one
full-attention layer). The head is the embedding, tied.

Differential attention scores every query pair twice (all
``num_attention_heads`` heads score 64-wide keys) and weighs a 128-wide value
with each softmax: a (query, visible key) pair costs ``heads * (2 * 64 + 2 *
128)`` FLOPs an attention. The selective scan costs about 9 operations a
state element a step (two products and an exponential for the decay, two
products for the input term, the update's multiply-add, the read-out's
multiply-add) on ``d_inner x d_state`` elements.
"""

from __future__ import annotations

WEIGHT_BYTES = 2
SCAN_OPS_PER_ELEMENT = 9


def sizes(cfg: dict) -> dict:
    a = cfg["assumed"]
    h = cfg["hidden_size"]
    d = a["mamba_expand"] * h
    n = cfg["num_hidden_layers"]
    own = n // 4 + 1                       # attentions with their own K/V
    return {"h": h, "f": cfg["intermediate_size"], "d": d, "N": a["mamba_d_state"],
            "R": a["mamba_dt_rank"], "K": a["mamba_d_conv"],
            "heads": cfg["num_attention_heads"], "kv_heads": cfg["num_key_value_heads"],
            "hd": h // cfg["num_attention_heads"], "window": cfg["sliding_window"],
            "layers": n, "mamba": own, "attn": own, "windowed": own - 1,
            "gmu": n // 4 - 1, "cross": n // 4 - 1}


def kv_row_bytes(cfg: dict) -> int:
    """Bytes of ONE layer's key and value row of a token."""
    s = sizes(cfg)
    return 2 * s["kv_heads"] * s["hd"] * WEIGHT_BYTES


def layer_params(cfg: dict) -> dict:
    """Matmul parameters of one layer of each kind."""
    s = sizes(cfg)
    h, d = s["h"], s["d"]
    return {"mlp": 3 * h * s["f"],
            "mamba": h * 2 * d + d * (s["R"] + 2 * s["N"]) + s["R"] * d + d * h,
            "attn": h * (s["heads"] + 2 * s["kv_heads"]) * s["hd"] + h * h,
            "gmu": 2 * h * d, "cross": 2 * h * h}


def matmul_params(cfg: dict) -> int:
    """Matmul parameters one token passes in all layers, the head left out."""
    s, p = sizes(cfg), layer_params(cfg)
    return (s["layers"] * p["mlp"] + s["mamba"] * p["mamba"] + s["attn"] * p["attn"]
            + s["gmu"] * p["gmu"] + s["cross"] * p["cross"])


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def scan_flops_per_token(cfg: dict) -> float:
    """ONE Mamba layer's convolution and scan for one token."""
    s = sizes(cfg)
    return float(SCAN_OPS_PER_ELEMENT * s["d"] * s["N"] + 2 * s["K"] * s["d"])


def pair_flops(cfg: dict) -> int:
    """One (query, visible key) pair of one differential attention."""
    s = sizes(cfg)
    return s["heads"] * (2 * s["hd"] + 2 * 2 * s["hd"])


def visible_pairs(cfg: dict, context: float) -> float:
    """(query, key) pairs of one token at ``context`` keys (itself included)
    over ALL attentions: the windowed ones see at most the window."""
    s = sizes(cfg)
    return s["windowed"] * min(context, s["window"]) + (1 + s["cross"]) * context


def prompt_pairs(cfg: dict, prompt_len: int) -> float:
    """:func:`visible_pairs` summed over the contexts ``1 .. prompt_len``."""
    s = sizes(cfg)
    w = min(prompt_len, s["window"])
    windowed = w * (w + 1) / 2.0 + (prompt_len - w) * s["window"]
    return s["windowed"] * windowed + (1 + s["cross"]) * prompt_len * (prompt_len + 1) / 2.0


def token_base_flops(cfg: dict) -> float:
    """One token through every matmul, convolution and scan, no attention
    pair and no head."""
    return 2.0 * matmul_params(cfg) + sizes(cfg)["mamba"] * scan_flops_per_token(cfg)


def token_flops(cfg: dict, context: float, with_head: bool = True) -> float:
    flops = token_base_flops(cfg) + pair_flops(cfg) * visible_pairs(cfg, context)
    return flops + (2.0 * head_params(cfg) if with_head else 0.0)


def request_flops(cfg: dict, prompt_len: int, first: bool, later_contexts) -> float:
    """FLOPs of the part of one request that fell in a window: the whole
    prompt (every position at its own context) with one head application if
    its first token did, and one decode step with the head for every later
    token (at the context it ran at)."""
    s, base, pair, head = sizes(cfg), token_base_flops(cfg), pair_flops(cfg), 2.0 * head_params(cfg)
    later = list(later_contexts)
    pairs = (s["windowed"] * sum(min(c, s["window"]) for c in later)
             + (1 + s["cross"]) * sum(later))
    total = len(later) * (base + head) + pair * pairs
    if first:
        total += prompt_len * base + pair * prompt_pairs(cfg, prompt_len) + head
    return total


def recurrent_bytes_per_slot(cfg: dict) -> int:
    """What the Mamba layers hold a slot: the SSM state in float32 and the
    convolution's last inputs in the served type."""
    s = sizes(cfg)
    return s["mamba"] * (s["d"] * s["N"] * 4 + s["d"] * (s["K"] - 1) * WEIGHT_BYTES)


def shared_kv_decode_bytes(cfg: dict, active_slots: float, contexts) -> float:
    """Bytes the full-attention layer's rows are read in one decode tick: by
    the layer itself and by every cross-attention, each over the rows its
    query can see (the mean context of the window's decoded tokens)."""
    s = sizes(cfg)
    contexts = list(contexts)
    return (1 + s["cross"]) * active_slots * sum(contexts) / len(contexts) * kv_row_bytes(cfg)


def shared_kv_decode_need(cfg: dict, active_slots: float, contexts) -> tuple:
    """(FLOPs, bytes) of the same attentions."""
    s = sizes(cfg)
    contexts = list(contexts)
    mean = sum(contexts) / len(contexts)
    return (float((1 + s["cross"]) * active_slots * mean * pair_flops(cfg)),
            float(shared_kv_decode_bytes(cfg, active_slots, contexts)))


def decode_tick_bytes(cfg: dict, active_slots: float, contexts) -> float:
    """Bytes one decode tick has to read (and the state it writes back): every
    weight once (the head is the embedding), the full-attention layer's rows
    once a reader, the windowed layers' rows inside the window, and the
    running slots' recurrent state in and out."""
    s = sizes(cfg)
    contexts = list(contexts)
    windowed = sum(min(c, s["window"]) for c in contexts) / len(contexts)
    weights = WEIGHT_BYTES * (matmul_params(cfg) + head_params(cfg))
    return (weights + shared_kv_decode_bytes(cfg, active_slots, contexts)
            + s["windowed"] * active_slots * windowed * kv_row_bytes(cfg)
            + 2.0 * active_slots * recurrent_bytes_per_slot(cfg))


def chunk_scan_need(cfg: dict, steps: int) -> tuple:
    """(operations, bytes) of ALL Mamba layers' selective scans over one
    prefill chunk of ``steps`` tokens: the convolved input and the step size
    in (float32), B and C in, the scan output out, the state in and out once
    a chunk."""
    s = sizes(cfg)
    ops = s["mamba"] * SCAN_OPS_PER_ELEMENT * steps * s["d"] * s["N"]
    nbytes = s["mamba"] * 4 * (3 * steps * s["d"] + 2 * steps * s["N"] + 2 * s["d"] * s["N"])
    return float(ops), float(nbytes)
