"""Pallas TPU flash attention: tiled online-softmax forward + blockwise
backward, wrapped in a custom VJP so it trains.

The framework's hottest kernel. Replaces the fused attention the reference
gets from Megatron/TransformerEngine CUDA kernels. Memory: O(seq * block)
VMEM instead of the O(seq^2) logits the einsum path materializes in HBM.

Layout convention INSIDE this module: [batch, heads, seq, head_dim]
(the public wrapper transposes from the models' [B, S, H, D]).
"""

from __future__ import annotations

import dataclasses
import functools
import logging

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

logger = logging.getLogger(__name__)

NEG_INF = -1e30
LANES = 128  # TPU lane width: scratch rows are kept as (block_q, LANES)


def _interpret() -> bool:
    """Pallas interpreter only where the backend is positively the CPU
    (tests). Any other backend gets the Mosaic lowering or its error; a
    test that compiles for a described chip patches this function."""
    return jax.default_backend() == "cpu"


# ---------------------------------------------------------------------------
# Tiling of the (q, k) plane
#
# A grid step costs a fixed ~0.35 us on a v5e (pipeline bookkeeping, DMA
# issue, rescaling the accumulator) whatever it computes; a 128 x 128 x 128
# step holds 0.04 us of matmul. So the kernels are bound by the COUNT of
# steps until a tile is several hundred wide, and `tile_plan` sizes tiles
# from the shape instead of taking them from the caller.
#
# Which tiles a step visits: every kernel walks one "major" axis tile by tile
# (q for fwd and dq, k for dk/dv) and, inside it, a band of the other axis
# that starts at the first tile with a visible pair. Steps of the band past
# the last visible tile still exist (the grid is a rectangle) but their
# block index is clamped to that last tile, so the index repeats, the
# pipeline fetches nothing, and `pl.when` skips the arithmetic. With a window
# the band is also shorter than the axis, so out-of-window tiles are not
# even steps.
# ---------------------------------------------------------------------------

TILE_SIZES = (1024, 512, 256, 128)   # candidates, 128 * 2**n, largest first
VMEM_BUDGET = 40 * 2**20             # what `_vmem_bytes` may reach, of a v5e's 128 MiB
# fp32 [block_q, block_k] values a kernel body holds at once (s, p | + dp, ds),
# with the bf16 copy that feeds the MXU counted as half.
_SCORE_TILES = {"fwd": 2.5, "dq": 4.5, "dkdv": 5.0}
KERNELS = tuple(_SCORE_TILES)


def _k_range(causal: bool, window, block_q: int, block_k: int, num_k: int, lib=jnp):
    """(band, first(qi), last(qi)): the k tiles a q tile of fwd / dq can see.
    ``first(qi) + j`` for j < band covers them; past ``last(qi)`` nothing is
    visible. Window lower bound: k_pos > q_pos - w; causal upper bound:
    k_pos <= q_pos."""
    if window is None:
        band, first = num_k, lambda qi: 0 * qi
    else:
        band = min(num_k, (block_q + window - 1 + block_k - 1) // block_k + 1)
        first = lambda qi: lib.maximum(0, (qi * block_q - window + 1) // block_k)  # noqa: E731
    if causal:
        last = lambda qi: lib.minimum(num_k - 1, ((qi + 1) * block_q - 1) // block_k)  # noqa: E731
    else:
        last = lambda qi: 0 * qi + (num_k - 1)  # noqa: E731
    return band, first, last


def _q_range(causal: bool, window, block_q: int, block_k: int, num_q: int, lib=jnp):
    """(band, first(ki), last(ki)): the q tiles a k tile of dk/dv is seen by —
    q in [k, k + w) for the tile's k's."""
    if window is None:
        band = num_q
        last = lambda ki: 0 * ki + (num_q - 1)  # noqa: E731
    else:
        band = min(num_q, (block_k + window - 1 + block_q - 1) // block_q + 1)
        last = lambda ki: lib.minimum(  # noqa: E731
            num_q - 1, (ki * block_k + block_k + window - 2) // block_q)
    first = (lambda ki: (ki * block_k) // block_q) if causal else (lambda ki: 0 * ki)
    return band, first, last


def _tile_crossed(qi, ki, block_q: int, block_k: int, causal: bool, window):
    """Whether a VISIBLE tile holds a masked pair: the diagonal or the
    window's edge runs through it. Interior tiles need no in-tile mask."""
    crossed = False
    if causal:
        crossed = (ki + 1) * block_k - 1 > qi * block_q
    if window is not None:
        crossed |= ki * block_k <= (qi + 1) * block_q - 1 - window
    return crossed


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


@dataclasses.dataclass(frozen=True)
class TilePlan:
    """How one kernel tiles one head's (q, k) plane. ``steps`` are grid steps
    per (batch, query head); the share that computes, and the share of those
    that needs the in-tile mask, are what the chosen tiles cost."""

    kernel: str            # "fwd" | "dq" | "dkdv"
    block_q: int
    block_k: int
    grid: tuple            # (tiles of the major axis, band of the other)
    compute_steps: int     # steps with a visible pair
    masked_steps: int      # of those, steps that apply the in-tile mask
    vmem_bytes: int        # estimate: pipelined blocks, scratch, body values
    vmem_limit_bytes: int  # what the call asks Mosaic for

    @property
    def steps(self) -> int:
        return self.grid[0] * self.grid[1]


def _vmem_bytes(kernel: str, block_q: int, block_k: int, D: int, itemsize: int,
                softcap: bool) -> int:
    """VMEM one kernel needs at these tiles: every pipelined block twice
    (double buffering), the fp32 scratch, and the score-sized values of the
    body. lse / delta rows are fp32 broadcast to LANES."""
    q_blk, k_blk = block_q * D * itemsize, block_k * D * itemsize
    row = block_q * LANES * 4
    if kernel == "fwd":      # in q, k, v; out o, lse; scratch m, l, acc
        blocks, scratch = 2 * q_blk + 2 * k_blk + row, 2 * row + block_q * D * 4
    elif kernel == "dq":     # in q, do, k, v, lse, delta; out dq; scratch dq
        blocks, scratch = 3 * q_blk + 2 * k_blk + 2 * row, block_q * D * 4
    else:                    # in q, do, k, v, lse, delta; out dk, dv; scratch dk, dv
        blocks, scratch = 2 * q_blk + 4 * k_blk + 2 * row, 2 * block_k * D * 4
    body = (_SCORE_TILES[kernel] + (1 if softcap else 0)) * block_q * block_k * 4
    return int(2 * blocks + scratch + body)


@functools.lru_cache(maxsize=None)
def tile_plan(S_q: int, S_k: int, D: int, dtype, window=None, has_segments: bool = False,
              kernel: str = "fwd", *, causal: bool = True, softcap: bool = False,
              block_q: int | None = None, block_k: int | None = None) -> TilePlan:
    """The tiles of one kernel, from what the call can see: sequence lengths,
    head width, dtype and mask kind. Per axis, the largest of ``TILE_SIZES``
    that divides the sequence, is no wider than the window's band and — with
    the other axis — fits ``VMEM_BUDGET``; a sequence that none divides is
    one tile. Where both axes cannot be largest, the wide side is the one the
    kernel gains most from (v5e readings at S 4096, D 128: fwd 512x1024 3.8 ms
    against 1024x512 5.8; dk/dv 4.5 against 5.2; dq 4.0 against 3.9): k for
    fwd, whose per-row softmax bookkeeping is paid once a step, and for dk/dv,
    which re-reads the q side once per k tile; q for dq. Explicit
    ``block_q`` / ``block_k`` pin a size (kernel tests), clipped to the
    sequence."""
    if kernel not in KERNELS:
        raise ValueError(f"unknown kernel {kernel!r}; expected one of {KERNELS}")
    itemsize = jnp.dtype(dtype).itemsize
    cap = max(TILE_SIZES) if window is None else max(window, min(TILE_SIZES))

    def sizes(pinned, S):
        if pinned is not None:
            return [min(pinned, S)]
        return [t for t in TILE_SIZES if t <= cap and S % t == 0] or [S]

    pairs = [(bq, bk) for bq in sizes(block_q, S_q) for bk in sizes(block_k, S_k)]
    fitting = [p for p in pairs
               if _vmem_bytes(kernel, *p, D, itemsize, softcap) <= VMEM_BUDGET] or pairs[-1:]
    bq, bk = max(fitting, key=lambda p: (p[0] * p[1], p[0] if kernel == "dq" else p[1]))

    num_q, num_k = S_q // bq, S_k // bk
    q_major = kernel != "dkdv"
    band, first, last = (_k_range(causal, window, bq, bk, num_k, lib=np) if q_major
                         else _q_range(causal, window, bq, bk, num_q, lib=np))
    major = np.arange(num_q if q_major else num_k)[:, None]
    minor = first(major) + np.arange(band)[None, :]
    qi, ki = (major, minor) if q_major else (minor, major)
    visible = minor <= last(major)
    masked = visible if has_segments else visible & _tile_crossed(qi, ki, bq, bk, causal, window)
    vmem = _vmem_bytes(kernel, bq, bk, D, itemsize, softcap)
    # Half again over the estimate, never under Mosaic's own default (16 MiB)
    # nor over three quarters of the VMEM (pinned tiles may estimate more).
    limit = min(96 * 2**20, max(16 * 2**20, vmem + vmem // 2))
    return TilePlan(kernel=kernel, block_q=bq, block_k=bk, grid=(int(major.size), int(band)),
                    compute_steps=int(visible.sum()), masked_steps=int(np.sum(masked)),
                    vmem_bytes=vmem, vmem_limit_bytes=limit)


def _compiler_params(plan: TilePlan):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=plan.vmem_limit_bytes,
    )


def _scores(q, k, qs_ref, ks_ref, qi, ki, *, sm_scale, softcap, masked: bool, causal, window,
            block_q: int, block_k: int):
    """fp32 [block_q, block_k] logits of one tile, and their pre-mask softcapped
    value (None without a cap). ``masked`` is static: interior tiles skip the
    two iotas, the compare and the select."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * sm_scale
    s_cap = None
    if softcap is not None:
        # Gemma2 logit bounding — BEFORE masking (tanh(NEG_INF) would
        # otherwise saturate masked slots to -cap, un-masking them).
        s = s_cap = softcap * jnp.tanh(s / softcap)
    if masked:
        mask = _pair_mask(qi, ki, block_q, block_k, causal, window)
        if qs_ref is not None:
            mask &= qs_ref[0, 0][:, None] == ks_ref[0, 0][None, :]
        s = jnp.where(mask, s, NEG_INF)
    return s, s_cap


def _on_visible_tiles(visible, qi, ki, body, *, causal, window, block_q, block_k,
                      has_segments: bool):
    """Run ``body(masked)`` on a visible tile: the masked variant where a mask
    edge can cross the tile, the plain one on interior tiles. A segment
    boundary can cross any tile, so packed batches always mask."""
    if has_segments:
        pl.when(visible)(lambda: body(True))
    elif not causal and window is None:
        pl.when(visible)(lambda: body(False))
    else:
        crossed = _tile_crossed(qi, ki, block_q, block_k, causal, window)
        pl.when(visible & crossed)(lambda: body(True))
        pl.when(visible & jnp.logical_not(crossed))(lambda: body(False))


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _fwd_kernel(*refs, sm_scale: float, causal: bool, window, block_q: int, block_k: int,
                num_k_blocks: int, band: int, has_segments: bool, softcap=None):
    if has_segments:
        q_ref, k_ref, v_ref, qs_ref, ks_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
        qs_ref = ks_ref = None
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    _, k_first, k_last = _k_range(causal, window, block_q, block_k, num_k_blocks)
    ki = k_first(qi) + kj
    visible = ki <= k_last(qi)

    @pl.when(kj == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _compute(masked: bool):
        q = q_ref[0, 0]  # [block_q, d]
        k = k_ref[0, 0]  # [block_k, d]
        v = v_ref[0, 0]
        s, _ = _scores(q, k, qs_ref, ks_ref, qi, ki, sm_scale=sm_scale, softcap=softcap,
                       masked=masked, causal=causal, window=window,
                       block_q=block_q, block_k=block_k)
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

    _on_visible_tiles(visible, qi, ki, _compute, causal=causal, window=window,
                      block_q=block_q, block_k=block_k, has_segments=has_segments)

    @pl.when(kj == band - 1)
    def _finalize():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        # logsumexp residual for the backward pass
        lse = m_scr[:, :1] + jnp.log(l_safe)
        lse_ref[0, 0] = jnp.broadcast_to(lse, lse_ref.shape[2:])


def _plan_for(kernel, q, k, causal, window, segment_ids, softcap, block_q, block_k) -> TilePlan:
    """``tile_plan`` of one kernel for [B, H, S, D] operands."""
    return tile_plan(q.shape[2], k.shape[2], q.shape[3], q.dtype.name, window,
                     segment_ids is not None, kernel, causal=causal,
                     softcap=softcap is not None, block_q=block_q, block_k=block_k)


def _operands(tensors, segment_ids, block_q: int, block_k: int, q_index, k_index):
    """(in_specs, inputs) of one kernel. ``tensors`` are (array, side) pairs in
    the kernel's argument order, side "q" or "k": a q-side array is blocked
    ``block_q`` rows by ``q_index``, a k-side one ``block_k`` by ``k_index``
    (both map the grid to (b, head, tile, 0)). Segment ids ``[B, 1, S]``, when
    given, follow twice — q rows, then k rows — on the same tiles."""
    block = {"q": (block_q, q_index), "k": (block_k, k_index)}
    specs = [pl.BlockSpec((1, 1, block[side][0], t.shape[3]), block[side][1])
             for t, side in tensors]
    inputs = [t for t, _ in tensors]
    if segment_ids is not None:
        def rows(index):
            return lambda *grid: (index(*grid)[0], 0, index(*grid)[2])

        specs += [pl.BlockSpec((1, 1, block_q), rows(q_index)),
                  pl.BlockSpec((1, 1, block_k), rows(k_index))]
        inputs += [segment_ids, segment_ids]
    return specs, inputs


def _flash_fwd(q, k, v, sm_scale, causal, window, block_q, block_k, segment_ids=None,
               softcap=None):
    B, H, S_q, D = q.shape
    plan = _plan_for("fwd", q, k, causal, window, segment_ids, softcap, block_q, block_k)
    block_q, block_k = plan.block_q, plan.block_k
    num_q, band = plan.grid
    num_k = k.shape[2] // block_k
    _, k_first, k_last = _k_range(causal, window, block_q, block_k, num_k)
    # GQA-native: K/V may carry fewer heads (G = H // rep); the index_map
    # points q head h at kv head h // rep, so the wide repeated copy the
    # einsum path would need is never materialized in HBM.
    rep = H // k.shape[1]

    def q_index(b, h, qi, kj):
        return (b, h, qi, 0)

    def k_index(b, h, qi, kj):
        return (b, h // rep, jnp.minimum(k_first(qi) + kj, k_last(qi)), 0)

    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, num_k_blocks=num_k, band=band,
        has_segments=segment_ids is not None, softcap=softcap,
    )
    in_specs, inputs = _operands([(q, "q"), (k, "k"), (v, "k")], segment_ids,
                                 block_q, block_k, q_index, k_index)
    out, lse = pl.pallas_call(
        kernel,
        grid=(B, H, num_q, band),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, block_q, D), q_index),
            pl.BlockSpec((1, 1, block_q, LANES), q_index),
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
        compiler_params=_compiler_params(plan),
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

def _ds(s_cap, p, dp, delta, sm_scale, softcap):
    """dL/d(q.k) of one tile from its probabilities and dP."""
    ds = p * (dp - delta)                       # dL/ds_postcap  [bq, bk]
    if softcap is not None:
        # chain through s_post = cap * tanh(s_pre / cap):
        # ds_pre = ds_post * (1 - (s_post / cap)^2). Uses the PRE-mask
        # s_cap (bounded by cap) — the masked s is -1e30 and would
        # square to inf, turning p == 0 slots into 0 * inf = NaN.
        ds = ds * (1.0 - jnp.square(s_cap / softcap))
    return ds * sm_scale


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
        qs_ref = ks_ref = None
    ki = pl.program_id(2)
    inner = pl.program_id(3)
    _, q_first, q_last = _q_range(causal, window, block_q, block_k, num_q_blocks)
    qi = q_first(ki) + inner % band
    visible = qi <= q_last(ki)

    @pl.when(inner == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _compute(masked: bool):
        q = q_ref[0, 0]          # [bq, d]
        k = k_ref[0, 0]          # [bk, d]
        v = v_ref[0, 0]
        do = do_ref[0, 0]        # [bq, d]
        lse = lse_ref[0, 0][:, :1]      # [bq, 1]
        delta = delta_ref[0, 0][:, :1]  # [bq, 1]
        s, s_cap = _scores(q, k, qs_ref, ks_ref, qi, ki, sm_scale=sm_scale, softcap=softcap,
                           masked=masked, causal=causal, window=window,
                           block_q=block_q, block_k=block_k)
        p = jnp.exp(s - lse)     # [bq, bk] fp32
        # dV += P^T dO
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        # dP = dO V^T
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = _ds(s_cap, p, dp, delta, sm_scale, softcap)
        # dK += dS^T Q
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    _on_visible_tiles(visible, qi, ki, _compute, causal=causal, window=window,
                      block_q=block_q, block_k=block_k, has_segments=has_segments)

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
        qs_ref = ks_ref = None
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    _, k_first, k_last = _k_range(causal, window, block_q, block_k, num_k_blocks)
    ki = k_first(qi) + kj
    visible = ki <= k_last(qi)

    @pl.when(kj == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _compute(masked: bool):
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0][:, :1]
        delta = delta_ref[0, 0][:, :1]
        s, s_cap = _scores(q, k, qs_ref, ks_ref, qi, ki, sm_scale=sm_scale, softcap=softcap,
                           masked=masked, causal=causal, window=window,
                           block_q=block_q, block_k=block_k)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = _ds(s_cap, p, dp, delta, sm_scale, softcap)
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    _on_visible_tiles(visible, qi, ki, _compute, causal=causal, window=window,
                      block_q=block_q, block_k=block_k, has_segments=has_segments)

    @pl.when(kj == band - 1)
    def _finalize():
        dq_ref[0, 0] = dq_scr[:].astype(dq_ref.dtype)


def _flash_dkdv(q, k, v, do, lse, delta, sm_scale, causal, window, block_q, block_k, softcap,
                segment_ids=None):
    B, H, _, D = q.shape
    G, S_k = k.shape[1], k.shape[2]
    rep = H // G
    plan = _plan_for("dkdv", q, k, causal, window, segment_ids, softcap, block_q, block_k)
    block_q, block_k = plan.block_q, plan.block_k
    num_k, band = plan.grid
    num_q = q.shape[2] // block_q
    _, q_first, q_last = _q_range(causal, window, block_q, block_k, num_q)

    # Grid dim 1 is the KV head g; the innermost dim folds (r, qj) r-major.
    # Q-side blocks for (g, inner) belong to query head g * rep + r.
    def q_index(b, g, ki, inner):
        return (b, g * rep + inner // band,
                jnp.minimum(q_first(ki) + inner % band, q_last(ki)), 0)

    def k_index(b, g, ki, inner):
        return (b, g, ki, 0)

    specs, inputs = _operands(
        [(q, "q"), (k, "k"), (v, "k"), (do, "q"), (lse, "q"), (delta, "q")], segment_ids,
        block_q, block_k, q_index, k_index)
    return pl.pallas_call(
        functools.partial(
            _bwd_dkdv_kernel, sm_scale=sm_scale, causal=causal, window=window,
            block_q=block_q, block_k=block_k, num_q_blocks=num_q, band=band,
            rep=rep, has_segments=segment_ids is not None, softcap=softcap,
        ),
        grid=(B, G, num_k, rep * band),
        in_specs=specs,
        out_specs=[pl.BlockSpec((1, 1, block_k, D), k_index)] * 2,
        out_shape=[
            jax.ShapeDtypeStruct((B, G, S_k, D), k.dtype),
            jax.ShapeDtypeStruct((B, G, S_k, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        compiler_params=_compiler_params(plan),
        interpret=_interpret(),
    )(*inputs)


def _flash_dq(q, k, v, do, lse, delta, sm_scale, causal, window, block_q, block_k, softcap,
              segment_ids=None):
    B, H, S_q, D = q.shape
    rep = H // k.shape[1]
    plan = _plan_for("dq", q, k, causal, window, segment_ids, softcap, block_q, block_k)
    block_q, block_k = plan.block_q, plan.block_k
    num_q, band = plan.grid
    num_k = k.shape[2] // block_k
    _, k_first, k_last = _k_range(causal, window, block_q, block_k, num_k)

    def q_index(b, h, qi, kj):
        return (b, h, qi, 0)

    def k_index(b, h, qi, kj):
        return (b, h // rep, jnp.minimum(k_first(qi) + kj, k_last(qi)), 0)

    specs, inputs = _operands(
        [(q, "q"), (k, "k"), (v, "k"), (do, "q"), (lse, "q"), (delta, "q")], segment_ids,
        block_q, block_k, q_index, k_index)
    return pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, sm_scale=sm_scale, causal=causal, window=window,
            block_q=block_q, block_k=block_k, num_k_blocks=num_k, band=band,
            has_segments=segment_ids is not None, softcap=softcap,
        ),
        grid=(B, H, num_q, band),
        in_specs=specs,
        out_specs=pl.BlockSpec((1, 1, block_q, D), q_index),
        out_shape=jax.ShapeDtypeStruct((B, H, S_q, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=_compiler_params(plan),
        interpret=_interpret(),
    )(*inputs)


def _flash_bwd(sm_scale, causal, window, block_q, block_k, softcap, residuals, d_out,
               segment_ids=None):
    q, k, v, out, lse = residuals
    # delta = rowsum(dO * O)  [B, H, S_q] broadcast to LANES for tiling.
    delta = jnp.sum(d_out.astype(jnp.float32) * out.astype(jnp.float32), axis=-1, keepdims=True)
    delta = jnp.broadcast_to(delta, lse.shape)
    args = (q, k, v, d_out, lse, delta, sm_scale, causal, window, block_q, block_k, softcap)
    dk, dv = _flash_dkdv(*args, segment_ids=segment_ids)
    dq = _flash_dq(*args, segment_ids=segment_ids)
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


@functools.lru_cache(maxsize=None)
def _log_plans(S_q, S_k, D, dtype, window, has_segments, causal, softcap, block_q,
               block_k) -> tuple:
    """The three kernels' plans for one call signature; logged the first time
    the signature is traced (the cache is the "once")."""
    plans = tuple(tile_plan(S_q, S_k, D, dtype, window, has_segments, kernel, causal=causal,
                            softcap=softcap, block_q=block_q, block_k=block_k)
                  for kernel in KERNELS)
    for p in plans:
        logger.debug(
            "flash %s S_q=%d S_k=%d D=%d %s window=%s segments=%s causal=%s: tiles %dx%d, "
            "grid %s = %d steps a head, %.0f%% compute, %.0f%% of those masked, vmem %.1f MiB",
            p.kernel, S_q, S_k, D, dtype, window, has_segments, causal, p.block_q, p.block_k,
            p.grid, p.steps, 100.0 * p.compute_steps / p.steps,
            100.0 * p.masked_steps / max(p.compute_steps, 1), p.vmem_bytes / 2**20)
    return plans


def pallas_flash_attention(q, k, v, causal: bool = True, block_q: int | None = None,
                           block_k: int | None = None, sm_scale: float | None = None,
                           sliding_window: int | None = None, segment_ids=None,
                           logit_softcap: float | None = None):
    """Public entry. q/k/v: [batch, seq, heads, head_dim] (models layout).

    ``block_q`` / ``block_k`` = None sizes each kernel's tiles from the
    shape (:func:`tile_plan`); integers pin one size for all three kernels
    (kernel tests).

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
    _log_plans(q.shape[1], k.shape[1], q.shape[3], q.dtype.name, sliding_window,
               segment_ids is not None, causal, logit_softcap is not None, block_q, block_k)
    # [B, S, H, D] -> [B, H, S, D]
    qt, kt, vt = (jnp.swapaxes(t, 1, 2) for t in (q, k, v))
    if segment_ids is not None:
        # Window and segment masks compose inside the kernel: the banded
        # grid skips out-of-window K blocks, the in-block mask ANDs the
        # segment equality — packed long-doc training for windowed models
        # keeps flash's O(S x w) asymptotics.
        # [B, 1, S]: a (1, 1, block) tile of it has a full second-to-last
        # dimension, which Mosaic needs; a (1, block) tile of [B, S] lowers
        # only at batch 1.
        out = _flash_bhsd_seg(qt, kt, vt, segment_ids.astype(jnp.int32)[:, None, :],
                              sm_scale, causal, sliding_window, block_q, block_k,
                              logit_softcap)
    else:
        out = _flash_bhsd(qt, kt, vt, sm_scale, causal, sliding_window, block_q, block_k,
                          logit_softcap)
    return jnp.swapaxes(out, 1, 2)
