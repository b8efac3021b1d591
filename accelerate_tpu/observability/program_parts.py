"""The names of a compiled program's parts, one vocabulary for every family
(:data:`PROGRAM_PARTS`): ``embed``; ``attn_local`` / ``attn_global``,
``attn_mla`` (``mla_q``, ``mla_latent``, ``mla_out`` inside),
``attn_diff_local`` / ``attn_diff_global`` / ``attn_cross_shared``, and inside
each of them ``kv_attn`` and ``kv_write``; ``kv_view``; ``moe_router``,
``moe_experts``, ``moe_shared``; ``mlp_dense``; ``ssm_in``, ``ssm_conv``,
``ssm_scan``, ``ssm_out``, ``gmu``; ``lm_head``; ``sample``; the trainer's
``loss`` and ``optimizer``. ``with program_part(name):`` is
``jax.named_scope(name)`` for a name of the vocabulary — metadata written
while a program is traced, so it is always on: every operation made inside
carries the scope on its HLO ``op_name``, a profiler trace keeps that path in
each ``XLA Ops`` event's metadata (``tf_op``), and ``python -m
chipbench.op_scopes FILE`` turns a trace into device time by part. A new
family declares its parts through :func:`program_part`; a name that is not in
the vocabulary raises at trace time instead of vanishing from every table.

An ``op_name`` reads ``jit(_paged_decode_fn)/vmap(PanguUltraMoeForCausalLM)/
layers_1/self_attn/attn_mla/kv_attn/while/body/dot_general``: a scope reaches
the body of a loop, and a transformation wraps the scope next to it
(``vmap(sample)``, ``transpose(jvp(...))``). A scope changes neither the
lowered text nor the compiled code — and is therefore no part of the
persistent compile cache's key: an executable that a tree WITHOUT a scope
cached is handed to a tree with it, old names and all (compile fresh, or in a
cache directory of its own, before reading a new part out of a trace).
``jax.profiler.ProfileData`` hands out the stats of an event only, never those
of its metadata, which is why ``chipbench/op_scopes.py`` reads the metadata
from the trace file's bytes.

One printed table — a decode tick of the ``cohere2_moe`` serving cell on a
v5e (``own ms`` leaves a part's nested parts out; ``relayout`` is the leaf
copies under it; GB/s by XLA's own byte count, for reading only)::

  device time by part: decode, 139 whole executions, busy 13.8681 ms each
    part                      ms   share    own ms  relayout     GB/s
    embed                 0.0029   0.02%    0.0029    0.0000     91.5
    attn_local            2.0211  14.57%    1.1310    0.0327   1258.3
    attn_global           0.7111   5.13%    0.3785    0.0123   1301.9
    kv_attn               1.2227   8.82%    1.2227    0.0084   1836.3
    kv_write              0.0139   0.10%    0.0139    0.0000     56.5
    moe_router            0.0138   0.10%    0.0138    0.0008    346.5
    moe_experts           8.5249  61.47%    8.5249    0.0010    757.9
    moe_shared            2.1388  15.42%    2.1388    0.0000    756.0
    lm_head               0.3576   2.58%    0.3576    0.0000    751.5
    sample                0.0002   0.00%    0.0002    0.0000     11.3
    unscoped              0.0837   0.60%    0.0837    0.0755
    ambiguous             0.0000   0.00%    0.0000    0.0000
    relayout              0.1222   0.88%   (leaf data-formatting ops and the compiler's copies, under whatever part: the column)

================== ==========================================================
part               covers
================== ==========================================================
embed              the embedding lookup
attn_local,        a whole attention block (projections, rotary, cached
attn_global        attention, ``o_proj``) of a windowed / a full layer
attn_mla           the same for latent attention; inside it ``mla_q``,
                   ``mla_latent``, ``mla_out``
attn_diff_local,   the same for differential attention and for the
attn_diff_global,  cross-attentions that read another layer's cache entry
attn_cross_shared
kv_attn            every READ of a cache, nested in the family's ``attn_*``:
                   scores, softmax, value product, the merge over cached rows
                   (the tick's work list with its page gathers, a chunk's
                   key-block loop); declared once, in models/llama.py
kv_write           cache writes: new rows into a view or a ring
                   (models/llama.py), rows and pages into the pool and the
                   recurrent rows (serving/engine.py)
kv_view            gathers of ``max_len``-long dense views from the page pool
moe_router,        routing; the routed experts' products (ops/moe.py)
moe_experts
moe_shared         shared experts
mlp_dense          dense MLPs
ssm_in, ssm_conv,  a Mamba layer's four stages
ssm_scan, ssm_out
gmu                gated memory units
lm_head            final norm + head
sample             token selection, the rng split, the ``done`` / ``pos`` /
                   ``tok`` rows: a serving program's epilogue
loss, optimizer    the trainer: the loss function with its gradient; clip +
                   the optimizer's update
================== ==========================================================
"""

from __future__ import annotations

import jax

__all__ = ["PROGRAM_PARTS", "program_part"]

PROGRAM_PARTS = (
    "embed",
    "attn_local", "attn_global",
    "attn_mla", "mla_q", "mla_latent", "mla_out",
    "attn_diff_local", "attn_diff_global", "attn_cross_shared",
    "kv_attn", "kv_write", "kv_view",
    "moe_router", "moe_experts", "moe_shared",
    "mlp_dense",
    "ssm_in", "ssm_conv", "ssm_scan", "ssm_out", "gmu",
    "lm_head",
    "sample",
    "loss", "optimizer",
)


def program_part(name: str):
    """``jax.named_scope(name)`` for a part of :data:`PROGRAM_PARTS`."""
    if name not in PROGRAM_PARTS:
        raise ValueError(f"{name!r} is not a program part; the vocabulary is {PROGRAM_PARTS}")
    return jax.named_scope(name)
