"""Family ``phi4flash``: Phi-4-mini-flash-reasoning (``model_type``
``phi4flash``; arXiv:2507.06607, "SambaY") as a served model. 32 layers, each
``a = x + mixer(LN1(x))``, ``y = a + MLP(LN2(a))`` (LayerNorms with scale and
bias, a SwiGLU MLP of one fused gate/up projection), the mixer by the layer's
index ``i``: Mamba-1 (even ``i <= 16``; layer 16 also hands its scan output to
the gated memory units), differential attention with its own keys and values
(odd ``i <= 17``: a window of 512 up to 15, layer 17 full), a gated memory unit
(even ``i >= 18``) or differential CROSS-attention over layer 17's keys and
values (odd ``i >= 19``). No positional encoding; final LayerNorm; the head is
the embedding, tied.

* ``leaf_table`` / ``make_params``: the seeded weights in the served type;
* ``build_server``: the program under test, built the way ``accelerate-tpu
  serve`` builds it and fronted by its HTTP gateway;
* ``reference_logits``: the plain float32 reference of one full forward pass:
  the scan as the recurrence stepped over time, every softmax written out,
  one key/value pair of heads at a time so that a 4096-row request fits; the
  bfloat16 weights are widened a layer (the head: a slice of the vocabulary)
  at a time. It imports nothing of the program and is given nothing the
  program made.

Readings the published config does not carry (each also under ``assumed`` in
the configuration file): the model's own defaults ``mamba_d_state`` 16,
``mamba_d_conv`` 4, ``mamba_expand`` 2, ``mamba_dt_rank`` 160; a convolution
with bias, Mamba projections without, attention projections with; heads pair
``(2n, 2n+1)``, query pair ``n`` reads key/value pair ``n // 2`` and weighs
``V = [v_2p | v_2p+1]``; ``lambda_init(i) = 0.8 - 0.6 exp(-0.3 i)`` with ``i``
the layer's index; a windowed query at ``t`` sees keys ``t - 511 .. t``.

The program keeps the published layout (heads in the published order; it
scores a key pair as one 128-wide key against queries widened with zeros,
which is the same arithmetic) but for ``A_log``, stored ``[d_state, d_inner]``:
the weights are seeded in that layout and the reference transposes it back.
"""

from __future__ import annotations

import contextlib
import math
import sys

import jax
import jax.numpy as jnp

from chipbench import harness
from chipbench import reference_ops as ops

Server = harness.load_module("models", "mixtral").Server    # the same fleet + gateway wrapper


def mixer(cfg: dict, i: int) -> str:
    half = cfg["num_hidden_layers"] // 2
    if i <= half + 1:
        return "attn" if i % 2 else "mamba"
    return "cross" if i % 2 else "gmu"


def window_for(cfg: dict, i: int):
    return cfg["sliding_window"] if mixer(cfg, i) == "attn" and i < cfg["num_hidden_layers"] // 2 else None


def widths(cfg: dict) -> tuple:
    """``(hidden, d_inner, d_state, dt_rank, d_conv, heads, kv_heads, head_dim)``"""
    a, h = cfg["assumed"], cfg["hidden_size"]
    return (h, a["mamba_expand"] * h, a["mamba_d_state"], a["mamba_dt_rank"], a["mamba_d_conv"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"], h // cfg["num_attention_heads"])


# ---------------------------------------------------------------------------
# Seeded weights
# ---------------------------------------------------------------------------

def leaf_table(cfg: dict) -> list:
    """[(path, shape, std)] in the layout of the program's parameter tree.
    ``std`` None is ones (norm scales, ``D``); a number, normal(0, std);
    ``"zeros"``; ``"a_log"``: ``log(1 .. d_state)`` down the state axis;
    ``"dt_bias"``: the inverse softplus of a step size drawn log-uniformly
    from 1e-3 .. 1e-1 (the Mamba convention, under which a state neither dies
    nor blows up over thousands of steps)."""
    h, d, n_state, rank, conv, heads, kv_heads, hd = widths(cfg)
    f, std = cfg["intermediate_size"], cfg["assumed"]["init_std"]
    # the head is the embedding: rows of norm ~1 keep the logits of order 1
    table = [(("embed_tokens", "embedding"), (cfg["vocab_size"], h), h ** -0.5)]
    for i in range(cfg["num_hidden_layers"]):
        layer, mix = (f"layers_{i}",), (f"layers_{i}", "mixer")
        for norm in ("input_norm", "post_norm"):
            table += [(layer + (norm, "scale"), (h,), None), (layer + (norm, "bias"), (h,), "zeros")]
        kind = mixer(cfg, i)
        if kind == "mamba":
            table += [
                (mix + ("in_proj", "kernel"), (h, 2 * d), h ** -0.5),
                (mix + ("conv_kernel",), (conv, d), conv ** -0.5),
                (mix + ("conv_bias",), (d,), std["bias"]),
                (mix + ("x_proj", "kernel"), (d, rank + 2 * n_state), d ** -0.5),
                (mix + ("dt_proj",), (rank, d), rank ** -0.5),
                (mix + ("dt_bias",), (d,), "dt_bias"),
                (mix + ("A_log",), (n_state, d), "a_log"),
                (mix + ("D",), (d,), None),
                (mix + ("out_proj", "kernel"), (d, h), d ** -0.5),
            ]
        elif kind == "gmu":
            table += [(mix + ("in_proj", "kernel"), (h, d), h ** -0.5),
                      (mix + ("out_proj", "kernel"), (d, h), d ** -0.5)]
        else:
            name, out = (("qkv_proj", (heads + 2 * kv_heads) * hd) if kind == "attn"
                         else ("q_proj", heads * hd))
            table += [(mix + (name, "kernel"), (h, out), h ** -0.5),
                      (mix + (name, "bias"), (out,), std["bias"])]
            table += [(mix + (f"lambda_{n}",), (hd,), std["lambda"])
                      for n in ("q1", "k1", "q2", "k2")]
            table += [(mix + ("subln",), (2 * hd,), None),
                      (mix + ("o_proj", "kernel"), (h, h), h ** -0.5),
                      (mix + ("o_proj", "bias"), (h,), std["bias"])]
        table += [(layer + ("mlp", "gate_up_proj", "kernel"), (h, 2 * f), h ** -0.5),
                  (layer + ("mlp", "down_proj", "kernel"), (f, h), f ** -0.5)]
    return table + [(("norm", "scale"), (h,), None), (("norm", "bias"), (h,), "zeros")]


def make_leaf(key, index: int, shape, std, dtype):
    if std == "zeros":
        return jnp.zeros(shape, dtype)
    if std == "a_log":
        rows = jnp.log(jnp.arange(1, shape[0] + 1, dtype=jnp.float32))
        return jnp.broadcast_to(rows[:, None], shape).astype(dtype)
    if std == "dt_bias":
        u = jax.random.uniform(jax.random.fold_in(key, index), shape, jnp.float32)
        step = jnp.exp(u * (math.log(1e-1) - math.log(1e-3)) + math.log(1e-3))
        return (step + jnp.log(-jnp.expm1(-step))).astype(dtype)
    return ops.make_leaf(key, index, shape, std, dtype)


def make_params(cfg: dict, seed: int, dtype=None):
    """The whole weight tree on the device in one jitted call from the seed,
    in the type it is served in."""
    dtype = jnp.dtype(dtype or cfg["assumed"]["weights_dtype"])
    table = leaf_table(cfg)

    def build(key):
        tree: dict = {}
        for i, (path, shape, std) in enumerate(table):
            node = tree
            for name in path[:-1]:
                node = node.setdefault(name, {})
            node[path[-1]] = make_leaf(key, i, shape, std, dtype)
        return tree

    return jax.jit(build)(ops.seed_key(seed))


# ---------------------------------------------------------------------------
# The program under test
# ---------------------------------------------------------------------------

def build_server(cfg: dict, params) -> Server:
    from accelerate_tpu.commands import serve
    from accelerate_tpu.models.phi4flash import Phi4FlashConfig, Phi4FlashForCausalLM
    from accelerate_tpu.serving import ServingGateway

    a = cfg["assumed"]
    module = Phi4FlashForCausalLM(Phi4FlashConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"], num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"], sliding_window=cfg["sliding_window"],
        mb_per_layer=cfg["mb_per_layer"], layer_norm_eps=cfg["layer_norm_eps"],
        max_position_embeddings=cfg["max_position_embeddings"],
        mamba_d_state=a["mamba_d_state"], mamba_d_conv=a["mamba_d_conv"],
        mamba_expand=a["mamba_expand"], mamba_dt_rank=a["mamba_dt_rank"]))
    argv = ["--port", "0", "--max-slots", str(a["max_slots"]), "--max-len", str(a["max_len"]),
            "--prefill-chunk", str(a["prefill_chunk"]),
            "--prefix-cache-mb", str(a["prefix_cache_mb"])]
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

HEAD_BLOCKS = 8         # the vocabulary's rows are widened and multiplied a slice at a time


def layer_norm(x, p, eps):
    mean = x.mean(-1, keepdims=True)
    var = jnp.square(x - mean).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def mamba(u, p, cfg, mm):
    """u [S, hidden] -> (out [S, hidden], scan output y [S, d_inner]): the
    recurrence stepped over time from a zero state."""
    _, d, n_state, rank, conv, *_ = widths(cfg)
    s = u.shape[0]
    xz = mm(u, p["in_proj"]["kernel"])
    x, z = xz[:, :d], xz[:, d:]
    padded = jnp.concatenate([jnp.zeros((conv - 1, d), jnp.float32), x])
    xc = jax.nn.silu(sum(padded[j:j + s] * p["conv_kernel"][j] for j in range(conv))
                     + p["conv_bias"])
    dbc = mm(xc, p["x_proj"]["kernel"])
    delta = jax.nn.softplus(mm(dbc[:, :rank], p["dt_proj"]) + p["dt_bias"])        # [S, d]
    a = -jnp.exp(p["A_log"].T)                                                      # [d, N]

    def step(h, inputs):
        delta_t, x_t, b_t, c_t = inputs
        h = jnp.exp(delta_t[:, None] * a) * h + (delta_t * x_t)[:, None] * b_t[None, :]
        return h, jnp.sum(h * c_t[None, :], axis=-1)

    _, y = jax.lax.scan(step, jnp.zeros((d, n_state), jnp.float32),
                        (delta, xc, dbc[:, rank:rank + n_state], dbc[:, rank + n_state:]))
    y = y + p["D"] * xc
    return mm(y * jax.nn.silu(z), p["out_proj"]["kernel"]), y


def diff_attention(q, k, v, p, cfg, layer: int, window, mm):
    """q [S, heads, hd], k, v [S, kv_heads, hd] in the PUBLISHED head order.
    One key/value pair of heads at a time: its two query pairs, two masked
    softmaxes each over the pair's keys, each weighing ``[v_2p | v_2p+1]``;
    the second subtracted lambda times; RMSNorm over the 2 hd; the factor."""
    s, heads, hd = q.shape
    pos = jnp.arange(s)
    mask = pos[None, :] <= pos[:, None]
    if window is not None:
        mask &= pos[None, :] > pos[:, None] - window
    lam_init = 0.8 - 0.6 * math.exp(-0.3 * layer)
    lam = (jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"]))
           - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"])) + lam_init)

    def softmax_of(qh, kh):
        scores = jnp.einsum("sd,td->st", qh, kh, precision=ops.HIGHEST) * hd ** -0.5
        return jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)

    def one_kv_pair(args):
        q4, k2, v2 = args                       # [4, S, hd]: heads 4p .. 4p+3; [2, S, hd] x 2
        values = jnp.concatenate([v2[0], v2[1]], axis=-1)                          # [S, 2 hd]
        outs = []
        for n in range(2):                      # query pairs 2p and 2p + 1
            o1 = jnp.einsum("st,td->sd", softmax_of(q4[2 * n], k2[0]), values, precision=ops.HIGHEST)
            o2 = jnp.einsum("st,td->sd", softmax_of(q4[2 * n + 1], k2[1]), values, precision=ops.HIGHEST)
            o = o1 - lam * o2
            o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + cfg["layer_norm_eps"])
            outs.append(o * p["subln"] * (1.0 - lam_init))
        return jnp.concatenate(outs, axis=-1)                                       # [S, 4 hd]

    pairs = k.shape[1] // 2
    out = jax.lax.map(one_kv_pair, (
        q.transpose(1, 0, 2).reshape(pairs, 4, s, hd), k.transpose(1, 0, 2).reshape(pairs, 2, s, hd),
        v.transpose(1, 0, 2).reshape(pairs, 2, s, hd)))                             # [pairs, S, 4 hd]
    return mm(out.transpose(1, 0, 2).reshape(s, heads * hd), p["o_proj"]["kernel"]) + p["o_proj"]["bias"]


def tied_head(x, embedding, mm):
    """x [S, hidden] against the embedding's rows, ``HEAD_BLOCKS`` slices of
    the vocabulary at a time (each widened to float32 on its own)."""
    vocab, h = embedding.shape
    blocks = HEAD_BLOCKS if vocab % HEAD_BLOCKS == 0 else 1
    out = jax.lax.map(lambda rows: mm(x, rows.astype(jnp.float32).T),
                      embedding.reshape(blocks, vocab // blocks, h))                # [blocks, S, v]
    return out.transpose(1, 0, 2).reshape(x.shape[0], vocab)


def reference_logits(params, ids, cfg: dict, mm):
    """ids [S] -> logits [S, vocab] (float32): one full causal forward pass."""
    h, _, _, _, _, heads, kv_heads, hd = widths(cfg)
    s, f, eps = ids.shape[0], cfg["intermediate_size"], cfg["layer_norm_eps"]
    half = cfg["num_hidden_layers"] // 2
    x = params["embed_tokens"]["embedding"][ids].astype(jnp.float32)
    memory = shared_k = shared_v = None
    for i in range(cfg["num_hidden_layers"]):
        layer = ops.f32_lazy(params[f"layers_{i}"])
        p, kind = layer["mixer"], mixer(cfg, i)
        u = layer_norm(x, layer["input_norm"], eps)
        if kind == "mamba":
            mixed, y = mamba(u, p, cfg, mm)
            if i == half:
                memory = y
        elif kind == "gmu":
            mixed = mm(jax.nn.silu(mm(u, p["in_proj"]["kernel"])) * memory, p["out_proj"]["kernel"])
        elif kind == "attn":
            qkv = mm(u, p["qkv_proj"]["kernel"]) + p["qkv_proj"]["bias"]
            q = qkv[:, :heads * hd].reshape(s, heads, hd)
            k = qkv[:, heads * hd:(heads + kv_heads) * hd].reshape(s, kv_heads, hd)
            v = qkv[:, (heads + kv_heads) * hd:].reshape(s, kv_heads, hd)
            if i == half + 1:
                shared_k, shared_v = k, v
            mixed = diff_attention(q, k, v, p, cfg, i, window_for(cfg, i), mm)
        else:
            q = mm(u, p["q_proj"]["kernel"]) + p["q_proj"]["bias"]
            mixed = diff_attention(q.reshape(s, heads, hd), shared_k, shared_v,
                                   p, cfg, i, None, mm)
        x = x + mixed
        gu = mm(layer_norm(x, layer["post_norm"], eps), layer["mlp"]["gate_up_proj"]["kernel"])
        x = x + mm(jax.nn.silu(gu[:, :f]) * gu[:, f:], layer["mlp"]["down_proj"]["kernel"])
    x = layer_norm(x, ops.f32_lazy(params["norm"]), eps)
    return tied_head(x, params["embed_tokens"]["embedding"], mm)
