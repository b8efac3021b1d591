"""Operations and bytes the algorithm needs, from shapes and traffic alone.

Never from the program's HLO or cost analysis: these counts read the same
work whatever later implements it. Recomputation (remat, the flash
backward's second QK^T) is not needed work and is not counted. A matmul of
``n`` parameters costs 2 FLOPs per parameter per token forward, and twice
that again backward. Causal attention at context ``c`` costs ``4*h*c``
FLOPs per token per layer forward (QK^T and PV, ``h`` = heads x head_dim);
averaged over a full sequence of ``S`` tokens that is ``2*h*S``.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).with_name("peaks.json")


def load_peaks(device_kind: str) -> dict:
    """The row of peaks.json for exactly this ``device_kind``; no default."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"device_kind {device_kind!r} is not in {PEAKS_FILE.name} "
                       f"(has {sorted(table)}); add a row with its source")
    return table[device_kind]


def attn_width(cfg: dict) -> int:
    head_dim = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    return cfg["num_attention_heads"] * head_dim


def kv_width(cfg: dict) -> int:
    head_dim = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    return cfg["num_key_value_heads"] * head_dim


def layer_matmul_params(cfg: dict) -> dict:
    """Matmul parameters of one layer: ``attn``, ``mlp`` (one expert, or the
    dense MLP) and ``router``."""
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    attn = 2 * h * attn_width(cfg) + 2 * h * kv_width(cfg)
    experts = cfg.get("num_local_experts", 0)
    return {"attn": attn, "mlp": 3 * h * f, "router": h * experts}


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def active_layer_params(cfg: dict) -> int:
    """Matmul parameters one token passes through in one layer (top-k
    experts of a sparse layer, the whole MLP of a dense one)."""
    p = layer_matmul_params(cfg)
    k = cfg.get("num_experts_per_tok", 1) if cfg.get("num_local_experts") else 1
    return p["attn"] + p["router"] + k * p["mlp"]


def resident_layer_params(cfg: dict, experts_read: float | None = None) -> float:
    """Matmul parameters of one layer a step has to read: every expert, or
    ``experts_read`` of them."""
    p = layer_matmul_params(cfg)
    n = cfg.get("num_local_experts") or 1
    n = n if experts_read is None else experts_read
    return p["attn"] + p["router"] + n * p["mlp"]


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward FLOPs per trained token: 6 x matmul parameters
    (embedding lookup out, head in) + causal attention ``6*L*h*S``."""
    layers = cfg["num_hidden_layers"]
    matmul = layers * active_layer_params(cfg) + head_params(cfg)
    return 6.0 * matmul + 6.0 * layers * attn_width(cfg) * seq


def flash_train_flops(cfg: dict, batch: int, seq: int) -> float:
    """Causal attention forward + backward over a batch, all layers: the
    attention term of :func:`train_flops_per_token` times the tokens."""
    return 6.0 * cfg["num_hidden_layers"] * attn_width(cfg) * seq * batch * seq


def flash_train_bytes(cfg: dict, batch: int, seq: int, bytes_per: int = 2) -> float:
    """Least HBM traffic of the same: forward reads q,k,v and writes o;
    backward reads q,k,v,o,do and writes dq,dk,dv."""
    q, kv = attn_width(cfg), kv_width(cfg)
    per_token = (2 * q + 2 * kv) + (3 * q + 2 * kv) + (q + 2 * kv)
    return float(cfg["num_hidden_layers"] * batch * seq * per_token * bytes_per)


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """Least time the chip could take, and which peak bounds it."""
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "bandwidth")


def serve_token_flops(cfg: dict, context: float, with_head: bool) -> float:
    """Forward FLOPs of one token at ``context`` keys (itself included):
    2 x active matmul parameters + attention, + the head when this position
    yields a token."""
    layers = cfg["num_hidden_layers"]
    flops = layers * (2.0 * active_layer_params(cfg) + 4.0 * attn_width(cfg) * context)
    return flops + (2.0 * head_params(cfg) if with_head else 0.0)


def serve_request_flops(cfg: dict, prompt_len: int, first: bool, later_contexts) -> float:
    """FLOPs of the part of one request that fell in a window: the whole
    prompt (mean context ``(p+1)/2``) with one head application if its first
    token did (``first``), and one decode step with the head for every later
    token in ``later_contexts`` (the context each was computed at)."""
    total = 0.0
    if first:
        total += prompt_len * serve_token_flops(cfg, (prompt_len + 1) / 2.0, False)
        total += 2.0 * head_params(cfg)
    for c in later_contexts:
        total += serve_token_flops(cfg, c, True)
    return total


def expected_experts_read(cfg: dict, active_slots: float) -> float:
    """Distinct experts a decode tick over ``active_slots`` tokens touches in
    one layer, with top-k picks spread evenly: ``E * (1 - (1 - k/E)^n)``."""
    e = cfg.get("num_local_experts") or 1
    k = cfg.get("num_experts_per_tok", 1)
    return e * (1.0 - (1.0 - k / e) ** active_slots)


def decode_tick_bytes(cfg: dict, active_slots: float, mean_context: float,
                      weight_bytes: int = 2, kv_bytes: int = 2) -> float:
    """Bytes one decode tick has to read: the resident matmul weights it
    touches once (embedding rows are a lookup) + the live KV rows."""
    layers = cfg["num_hidden_layers"]
    weights = layers * resident_layer_params(cfg, expected_experts_read(cfg, active_slots))
    weights += head_params(cfg)
    kv = layers * 2 * kv_width(cfg) * active_slots * mean_context
    return weights * weight_bytes + kv * kv_bytes
