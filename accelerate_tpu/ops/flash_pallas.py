"""Pallas TPU flash attention: tiled online-softmax forward + blockwise
backward, wrapped in a custom VJP so it trains.

The framework's hottest kernel. Replaces the fused attention the reference
gets from Megatron/TransformerEngine CUDA kernels. Memory: O(seq * block)
VMEM instead of the O(seq^2) logits the einsum path materializes in HBM.

Layout convention INSIDE this module: [batch, heads, seq, head_dim]
(the public wrapper transposes from the models' [B, S, H, D]).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128  # TPU lane width: scratch rows are kept as (block_q, LANES)


def _interpret() -> bool:
    """Pallas interpreter only where the backend is positively the CPU
    (tests). Any other backend gets the Mosaic lowering or its error; a
    test that compiles for a described chip patches this function."""
    return jax.default_backend() == "cpu"


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _block_visible(qi, ki, block_q: int, block_k: int, causal: bool, window):
    """Whether any (q_pos, k_pos) pair in block (qi, ki) is unmasked.

    Causal upper bound: the block's smallest k must not exceed its largest q.
    Window lower bound (k_pos > q_pos - w): the block's largest k must
    exceed its smallest q minus w."""
    visible = True
    if causal:
        visible = ki * block_k <= (qi + 1) * block_q - 1
    if window is not None:
        visible &= ki * block_k + block_k - 1 > qi * block_q - window
    return visible


# Banded grids: with a window only ~(block + w) of the key axis is visible
# per opposite-axis block, so the grid's inner dimension is shrunk to that
# band and the BlockSpec index_map offsets it to the band's start. Skipped
# blocks are then never DMA'd HBM->VMEM at all (a pl.when alone would still
# fetch them) — true O(S * w) compute AND memory traffic. The band start is
# clamped into range; clamp duplicates are rejected by the in-kernel
# `*_band_valid` check before any compute.

def _k_band(window, block_q: int, block_k: int, num_k: int):
    """(band_size, k_start(qi)) for q-major kernels (fwd, dq)."""
    if window is None:
        return num_k, lambda qi: 0
    band = min(num_k, (block_q + window - 1 + block_k - 1) // block_k + 1)
    # First k block that can contain k_pos > qi*block_q - window.
    return band, lambda qi: jnp.maximum(0, (qi * block_q - window + 1) // block_k)


def _q_band(window, block_q: int, block_k: int, num_q: int):
    """(band_size, q_start(ki)) for the k-major dk/dv kernel. With a causal
    window, visible q for k block ki are q in [ki*bk, ki*bk + bk - 1 + w)."""
    if window is None:
        return num_q, lambda ki: 0
    band = min(num_q, (block_k + window - 1 + block_q - 1) // block_q + 1)
    return band, lambda ki: (ki * block_k) // block_q


def _pair_mask(qi, ki, block_q: int, block_k: int, causal: bool, window):
    """In-block [block_q, block_k] boolean mask (True = keep)."""
    q_ids = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_ids = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), jnp.bool_)
    if causal:
        mask &= q_ids >= k_ids
    if window is not None:
        mask &= k_ids > q_ids - window
    return mask


def _fwd_kernel(*refs,
                sm_scale: float, causal: bool, window, block_q: int, block_k: int,
                num_k_blocks: int, band: int, has_segments: bool, softcap=None):
    if has_segments:
        q_ref, k_ref, v_ref, qs_ref, ks_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    _, k_start = _k_band(window, block_q, block_k, num_k_blocks)
    ki = k_start(qi) + kj
    band_valid = ki < num_k_blocks
    ki = jnp.minimum(ki, num_k_blocks - 1)

    @pl.when(kj == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    should_compute = band_valid & _block_visible(qi, ki, block_q, block_k, causal, window)

    @pl.when(should_compute)
    def _compute():
        q = q_ref[0, 0]  # [block_q, d]
        k = k_ref[0, 0]  # [block_k, d]
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale  # [block_q, block_k]

        if softcap is not None:
            # Gemma2 logit bounding — BEFORE masking (tanh(NEG_INF) would
            # otherwise saturate masked slots to -cap, un-masking them).
            s = softcap * jnp.tanh(s / softcap)
        if causal or window is not None or has_segments:
            mask = _pair_mask(qi, ki, block_q, block_k, causal, window)
            if has_segments:
                mask &= qs_ref[0][:, None] == ks_ref[0][None, :]
            s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:, :1]                       # [block_q, 1]
        l_prev = l_scr[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_next = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - m_next)                     # [block_q, block_k] fp32
        l_next = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        m_scr[:] = jnp.broadcast_to(m_next, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_next, l_scr.shape)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_scr[:] = acc_scr[:] * alpha + pv

    @pl.when(kj == band - 1)
    def _finalize():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        # logsumexp residual for the backward pass
        lse = m_scr[:, :1] + jnp.log(l_safe)
        lse_ref[0, 0] = jnp.broadcast_to(lse, lse_ref.shape[2:])


def _flash_fwd(q, k, v, sm_scale, causal, window, block_q, block_k, segment_ids=None,
               softcap=None):
    B, H, S_q, D = q.shape
    S_k = k.shape[2]
    num_q = S_q // block_q
    num_k = S_k // block_k
    band, k_start = _k_band(window, block_q, block_k, num_k)
    grid = (B, H, num_q, band)
    # GQA-native: K/V may carry fewer heads (G = H // rep); the index_map
    # points q head h at kv head h // rep, so the wide repeated copy the
    # einsum path would need is never materialized in HBM.
    rep = H // k.shape[1]

    def k_index(b, h, qi, kj):
        return (b, h // rep, jnp.minimum(k_start(qi) + kj, num_k - 1), 0)

    has_segments = segment_ids is not None
    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, num_k_blocks=num_k, band=band,
        has_segments=has_segments, softcap=softcap,
    )
    in_specs = [
        pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, kj: (b, h, qi, 0)),
        pl.BlockSpec((1, 1, block_k, D), k_index),
        pl.BlockSpec((1, 1, block_k, D), k_index),
    ]
    inputs = [q, k, v]
    if has_segments:
        # The same [B, S] array enters twice: q-block rows and k-block rows.
        in_specs += [
            pl.BlockSpec((1, block_q), lambda b, h, qi, kj: (b, qi)),
            pl.BlockSpec((1, block_k),
                         lambda b, h, qi, kj: (b, jnp.minimum(k_start(qi) + kj, num_k - 1))),
        ]
        inputs += [segment_ids, segment_ids]
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, kj: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_q, LANES), lambda b, h, qi, kj: (b, h, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S_q, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, S_q, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=_interpret(),
    )(*inputs)
    return out, lse


# ---------------------------------------------------------------------------
# Backward
#   dV = P^T dO
#   dP = dO V^T ;  dS = P * (dP - delta)  with delta = rowsum(dO * O)
#   dQ = dS K ;  dK = dS^T Q
# Two kernels: (1) dk/dv accumulating over q blocks; (2) dq accumulating
# over k blocks. P is recomputed blockwise from the lse residual.
# ---------------------------------------------------------------------------

def _bwd_dkdv_kernel(*refs, sm_scale, causal, window, block_q, block_k,
                     num_q_blocks, band: int, rep: int, has_segments: bool,
                     softcap=None):
    """Grid (B, G, num_k, rep * band): dim 1 is the *kv* head; the innermost
    dim walks the ``rep`` query heads sharing it r-major (inner = r * band +
    qj), accumulating all their dk/dv contributions in the same VMEM scratch.
    GQA thus writes narrow [B, G, S_k, D] grads in one pass — no H-wide
    partials in HBM, no bf16 rounding between per-head partial sums."""
    if has_segments:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qs_ref, ks_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
    ki = pl.program_id(2)
    inner = pl.program_id(3)
    qj = inner % band
    _, q_start = _q_band(window, block_q, block_k, num_q_blocks)
    qi = q_start(ki) + qj
    band_valid = qi < num_q_blocks
    qi = jnp.minimum(qi, num_q_blocks - 1)

    @pl.when(inner == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    should_compute = band_valid & _block_visible(qi, ki, block_q, block_k, causal, window)

    @pl.when(should_compute)
    def _compute():
        q = q_ref[0, 0]          # [bq, d]
        k = k_ref[0, 0]          # [bk, d]
        v = v_ref[0, 0]
        do = do_ref[0, 0]        # [bq, d]
        lse = lse_ref[0, 0][:, :1]      # [bq, 1]
        delta = delta_ref[0, 0][:, :1]  # [bq, 1]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale             # [bq, bk]
        if softcap is not None:
            s_cap = softcap * jnp.tanh(s / softcap)  # bounded: |s_cap| <= cap
            s = s_cap
        if causal or window is not None or has_segments:
            mask = _pair_mask(qi, ki, block_q, block_k, causal, window)
            if has_segments:
                mask &= qs_ref[0][:, None] == ks_ref[0][None, :]
            s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse)     # [bq, bk] fp32

        # dV += P^T dO
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        # dP = dO V^T
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta)                       # dL/ds_postcap  [bq, bk]
        if softcap is not None:
            # chain through s_post = cap * tanh(s_pre / cap):
            # ds_pre = ds_post * (1 - (s_post / cap)^2). Uses the PRE-mask
            # s_cap (bounded by cap) — the masked s is -1e30 and would
            # square to inf, turning p == 0 slots into 0 * inf = NaN.
            ds = ds * (1.0 - jnp.square(s_cap / softcap))
        ds = ds * sm_scale
        # dK += dS^T Q
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(inner == rep * band - 1)
    def _finalize():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(*refs, sm_scale, causal, window, block_q, block_k,
                   num_k_blocks, band: int, has_segments: bool, softcap=None):
    if has_segments:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qs_ref, ks_ref,
         dq_ref, dq_scr) = refs
    else:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr = refs
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    _, k_start = _k_band(window, block_q, block_k, num_k_blocks)
    ki = k_start(qi) + kj
    band_valid = ki < num_k_blocks
    ki = jnp.minimum(ki, num_k_blocks - 1)

    @pl.when(kj == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    should_compute = band_valid & _block_visible(qi, ki, block_q, block_k, causal, window)

    @pl.when(should_compute)
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0][:, :1]
        delta = delta_ref[0, 0][:, :1]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale
        if softcap is not None:
            s_cap = softcap * jnp.tanh(s / softcap)
            s = s_cap
        if causal or window is not None or has_segments:
            mask = _pair_mask(qi, ki, block_q, block_k, causal, window)
            if has_segments:
                mask &= qs_ref[0][:, None] == ks_ref[0][None, :]
            s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta)
        if softcap is not None:
            # pre-mask s_cap, not the masked s — see _bwd_dkdv_kernel.
            ds = ds * (1.0 - jnp.square(s_cap / softcap))
        ds = ds * sm_scale
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(kj == band - 1)
    def _finalize():
        dq_ref[0, 0] = dq_scr[:].astype(dq_ref.dtype)


def _flash_bwd(sm_scale, causal, window, block_q, block_k, softcap, residuals, d_out,
               segment_ids=None):
    q, k, v, out, lse = residuals
    do = d_out
    B, H, S_q, D = q.shape
    S_k = k.shape[2]
    num_q = S_q // block_q
    num_k = S_k // block_k
    has_segments = segment_ids is not None
    # GQA: kernels read the narrow K/V via h // rep; dk/dv are produced
    # per *query* head below and group-summed back to the kv heads.
    G = k.shape[1]
    rep = H // G

    # delta = rowsum(dO * O)  [B, H, S_q] broadcast to LANES for tiling.
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1, keepdims=True)
    delta = jnp.broadcast_to(delta, (B, H, S_q, LANES))

    band_q, q_start = _q_band(window, block_q, block_k, num_q)

    # Grid dim 1 is the KV head g; the innermost dim folds (r, qj) r-major.
    # Q-side blocks for (g, inner) belong to query head g * rep + r.
    def q_index(b, g, ki, inner):
        return (b, g * rep + inner // band_q,
                jnp.minimum(q_start(ki) + inner % band_q, num_q - 1), 0)

    dkdv_specs = [
        pl.BlockSpec((1, 1, block_q, D), q_index),
        pl.BlockSpec((1, 1, block_k, D), lambda b, g, ki, inner: (b, g, ki, 0)),
        pl.BlockSpec((1, 1, block_k, D), lambda b, g, ki, inner: (b, g, ki, 0)),
        pl.BlockSpec((1, 1, block_q, D), q_index),
        pl.BlockSpec((1, 1, block_q, LANES), q_index),
        pl.BlockSpec((1, 1, block_q, LANES), q_index),
    ]
    dkdv_inputs = [q, k, v, do, lse, delta]
    if has_segments:
        dkdv_specs += [
            pl.BlockSpec((1, block_q),
                         lambda b, g, ki, inner: (
                             b, jnp.minimum(q_start(ki) + inner % band_q, num_q - 1))),
            pl.BlockSpec((1, block_k), lambda b, g, ki, inner: (b, ki)),
        ]
        dkdv_inputs += [segment_ids, segment_ids]

    dkdv = pl.pallas_call(
        functools.partial(
            _bwd_dkdv_kernel, sm_scale=sm_scale, causal=causal, window=window,
            block_q=block_q, block_k=block_k, num_q_blocks=num_q, band=band_q,
            rep=rep, has_segments=has_segments, softcap=softcap,
        ),
        grid=(B, G, num_k, rep * band_q),
        in_specs=dkdv_specs,
        out_specs=[
            pl.BlockSpec((1, 1, block_k, D), lambda b, g, ki, inner: (b, g, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, g, ki, inner: (b, g, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, G, S_k, D), k.dtype),
            jax.ShapeDtypeStruct((B, G, S_k, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=_interpret(),
    )(*dkdv_inputs)
    dk, dv = dkdv

    band_k, k_start = _k_band(window, block_q, block_k, num_k)

    def k_index(b, h, qi, kj):
        return (b, h // rep, jnp.minimum(k_start(qi) + kj, num_k - 1), 0)

    dq_specs = [
        pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, kj: (b, h, qi, 0)),
        pl.BlockSpec((1, 1, block_k, D), k_index),
        pl.BlockSpec((1, 1, block_k, D), k_index),
        pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, kj: (b, h, qi, 0)),
        pl.BlockSpec((1, 1, block_q, LANES), lambda b, h, qi, kj: (b, h, qi, 0)),
        pl.BlockSpec((1, 1, block_q, LANES), lambda b, h, qi, kj: (b, h, qi, 0)),
    ]
    dq_inputs = [q, k, v, do, lse, delta]
    if has_segments:
        dq_specs += [
            pl.BlockSpec((1, block_q), lambda b, h, qi, kj: (b, qi)),
            pl.BlockSpec((1, block_k),
                         lambda b, h, qi, kj: (b, jnp.minimum(k_start(qi) + kj, num_k - 1))),
        ]
        dq_inputs += [segment_ids, segment_ids]

    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, sm_scale=sm_scale, causal=causal, window=window,
            block_q=block_q, block_k=block_k, num_k_blocks=num_k, band=band_k,
            has_segments=has_segments, softcap=softcap,
        ),
        grid=(B, H, num_q, band_k),
        in_specs=dq_specs,
        out_specs=pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, kj: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S_q, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=_interpret(),
    )(*dq_inputs)

    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp wrapper
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_bhsd(q, k, v, sm_scale, causal, window, block_q, block_k, softcap):
    out, _ = _flash_fwd(q, k, v, sm_scale, causal, window, block_q, block_k,
                        softcap=softcap)
    return out


def _fwd_rule(q, k, v, sm_scale, causal, window, block_q, block_k, softcap):
    out, lse = _flash_fwd(q, k, v, sm_scale, causal, window, block_q, block_k,
                          softcap=softcap)
    return out, (q, k, v, out, lse)


def _bwd_rule(sm_scale, causal, window, block_q, block_k, softcap, residuals, d_out):
    return _flash_bwd(sm_scale, causal, window, block_q, block_k, softcap,
                      residuals, d_out)


_flash_bhsd.defvjp(_fwd_rule, _bwd_rule)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash_bhsd_seg(q, k, v, segment_ids, sm_scale, causal, window, block_q, block_k,
                    softcap):
    out, _ = _flash_fwd(q, k, v, sm_scale, causal, window, block_q, block_k,
                        segment_ids=segment_ids, softcap=softcap)
    return out


def _seg_fwd_rule(q, k, v, segment_ids, sm_scale, causal, window, block_q, block_k,
                  softcap):
    out, lse = _flash_fwd(q, k, v, sm_scale, causal, window, block_q, block_k,
                          segment_ids=segment_ids, softcap=softcap)
    return out, (q, k, v, out, lse, segment_ids)


def _seg_bwd_rule(sm_scale, causal, window, block_q, block_k, softcap, residuals, d_out):
    q, k, v, out, lse, segment_ids = residuals
    dq, dk, dv = _flash_bwd(sm_scale, causal, window, block_q, block_k, softcap,
                            (q, k, v, out, lse), d_out, segment_ids=segment_ids)
    # Integer segment ids carry a float0 cotangent (no gradient flows).
    dseg = jnp.zeros(segment_ids.shape, jax.dtypes.float0)
    return dq, dk, dv, dseg


_flash_bhsd_seg.defvjp(_seg_fwd_rule, _seg_bwd_rule)


def pallas_flash_attention(q, k, v, causal: bool = True, block_q: int = 128, block_k: int = 128,
                           sm_scale: float | None = None, sliding_window: int | None = None,
                           segment_ids=None, logit_softcap: float | None = None):
    """Public entry. q/k/v: [batch, seq, heads, head_dim] (models layout).

    GQA-native: k/v may carry fewer heads than q (``n_q = rep * n_kv``).
    The fwd/dq kernels index the shared kv head directly (``h // rep`` in
    the BlockSpec index maps) and the dk/dv kernel grids over kv heads,
    accumulating the ``rep`` query heads in VMEM scratch — narrow
    [B, G, S, D] grads in one pass, no repeated K/V copy in HBM (the
    einsum path avoids the copy too, via a grouped contraction).

    ``sliding_window=w`` masks k_pos outside (q_pos - w, q_pos] and *skips*
    fully-masked K blocks, so long-sequence local attention (Mistral) costs
    O(S * w) instead of O(S^2).

    ``segment_ids`` [batch, seq] (packed sequences, data_loader.pack_sequences):
    pairs in different segments are masked inside the kernel, so packed
    training keeps flash's O(seq x block) memory instead of falling back to
    the einsum path's O(seq^2) logits."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    if sliding_window is not None and not causal:
        raise ValueError("sliding_window requires causal=True")
    if q.shape[2] % k.shape[2]:
        raise ValueError(f"q heads {q.shape[2]} not a multiple of kv heads {k.shape[2]}")
    S = q.shape[1]
    block_q = min(block_q, S)
    block_k = min(block_k, k.shape[1])
    # [B, S, H, D] -> [B, H, S, D]
    qt, kt, vt = (jnp.swapaxes(t, 1, 2) for t in (q, k, v))
    if segment_ids is not None:
        # Window and segment masks compose inside the kernel: the banded
        # grid skips out-of-window K blocks, the in-block mask ANDs the
        # segment equality — packed long-doc training for windowed models
        # keeps flash's O(S x w) asymptotics.
        out = _flash_bhsd_seg(qt, kt, vt, segment_ids.astype(jnp.int32),
                              sm_scale, causal, sliding_window, block_q, block_k,
                              logit_softcap)
    else:
        out = _flash_bhsd(qt, kt, vt, sm_scale, causal, sliding_window, block_q, block_k,
                          logit_softcap)
    return jnp.swapaxes(out, 1, 2)
