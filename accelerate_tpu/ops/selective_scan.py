"""Pallas TPU selective scan: the Mamba-1 recurrence over many steps with its
state held on the chip.

``h_t = exp(dt_t * A) * h_{t-1} + (dt_t * x_t) * B_t``, ``y_t = h_t . C_t``,
all in float32, for ``x``, ``dt`` ``[b, S, d]``, ``A`` ``[N, d]``, ``B``, ``C``
``[b, S, N]`` and a carried state ``h0`` ``[b, N, d]``.

The grid walks ``b`` x blocks of ``width`` channels (both parallel) x blocks
of ``steps`` time steps (in order). The state block ``[N, width]`` lives in
VMEM scratch for all the time blocks of a channel block: it is read once
from ``h0`` and written once to ``h_S``, so no step's decay or input term
``[N, d]`` ever exists in HBM (the associative form wrote them for every
block of steps: 50x the scan's floor, PERF.md section 6). Within a
time block a loop takes the steps in groups of 8, one sublane row of ``x``,
``dt`` and ``y`` a step (they are viewed as ``[S / 8, 8, d]``, which is how
they lie). The state keeps its ``N`` states on sublanes and its channels on
lanes; ``B_t`` and ``C_t`` are read as scalars from SMEM and set down the
sublanes of one column, which every 128 channels of the block then share.

A step with ``dt == 0`` leaves the state bit for bit as it is
(``exp(0) * h + 0``): a prompt's padded tail, and the steps this wrapper pads
``S`` out to whole groups and blocks with.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_pallas import LANES, _interpret

GROUP = 8                 # steps a loop iteration takes: one (8, 128) tile of x, dt and y
MAX_STEPS = 256           # time steps a grid step holds (a serving chunk: one)
# channels a grid step holds, where they divide d: on a v5e 1280 to 5120 read alike, 512
# 1.4x and 128 4.5x slower (PERF.md section 6); wider unrolls a longer loop body
WIDTH = 1280


def scan_tiles(S: int, d: int) -> tuple:
    """``(steps, width)`` of one grid step for ``S`` steps over ``d``
    channels: all steps up to :data:`MAX_STEPS` (rounded up to a group),
    else blocks of that many; :data:`WIDTH` channels where they divide
    ``d``, else whole lane tiles of 128, else all of ``d``."""
    steps = min(-(-S // GROUP) * GROUP, MAX_STEPS)
    if d % WIDTH == 0:
        width = WIDTH
    elif d % LANES == 0:
        width = LANES
    else:
        width = d
    return steps, width


def _kernel(x_ref, dt_ref, A_ref, B_ref, C_ref, h0_ref, y_ref, h_ref, h_s, *, lanes):
    groups, _, width = x_ref.shape
    N = A_ref.shape[0]
    block = pl.program_id(2)

    @pl.when(block == 0)
    def _():
        h_s[...] = h0_ref[...]

    state_row = jax.lax.broadcasted_iota(jnp.int32, (N, lanes), 0)

    def column(ref, t):
        """Row ``t`` of ``[steps, N]`` scalars down the sublanes of one column."""
        col = jnp.zeros((N, lanes), jnp.float32)
        for n in range(N):
            col = jnp.where(state_row == n, ref[t * N + n], col)
        return col

    def one_group(g, carry):
        for s in range(GROUP):
            t = g * GROUP + s
            Bt, Ct = column(B_ref, t), column(C_ref, t)
            for c in range(0, width, lanes):
                cols = slice(c, c + lanes)
                dt = dt_ref[g, s:s + 1, cols]                               # [1, lanes]
                h = jnp.exp(dt * A_ref[:, cols]) * h_s[:, cols] + (dt * x_ref[g, s:s + 1, cols]) * Bt
                h_s[:, cols] = h
                y_ref[g, s:s + 1, cols] = jnp.sum(h * Ct, axis=0, keepdims=True)
        return carry

    jax.lax.fori_loop(0, groups, one_group, 0)

    @pl.when(block == pl.num_programs(2) - 1)
    def _():
        h_ref[...] = h_s[...]


def selective_scan(x, dt, A, B, C, h0):
    """The recurrence over all ``S`` steps of ``x``, ``dt`` ``[b, S, d]``
    from ``h0`` ``[b, N, d]``, with ``A`` ``[N, d]`` and ``B``, ``C`` ``[b,
    S, N]``, in one kernel call: ``h_t = exp(dt_t * A) * h_{t-1} + (dt_t *
    x_t) * B_t``, ``y_t = h_t . C_t``, every term in float32. Returns ``(y
    [b, S, d], h_S [b, N, d])``, float32; the state goes in and comes out of
    the call as it lies (``[b, N, d]``) and stays in VMEM between the two. A
    step with ``dt == 0`` leaves the state bit for bit as it is; the wrapper
    pads ``S`` with such steps up to whole time blocks and drops their
    outputs. Tiles: :func:`scan_tiles`. The body is jitted, so that the nine
    Mamba layers of a chunk trace and lower the kernel once between them, not
    once each (6 to 8 s of every set-up on a v5e host, PERF.md section 6)."""
    return _selective_scan(x, dt, A, B, C, h0, interpret=_interpret())


@functools.partial(jax.jit, static_argnames="interpret")
def _selective_scan(x, dt, A, B, C, h0, *, interpret):
    f32 = jnp.float32
    x, dt, A, B, C, h0 = (a.astype(f32) for a in (x, dt, A, B, C, h0))
    b, S, d = x.shape
    N = A.shape[0]
    steps, width = scan_tiles(S, d)
    padded = -(-S // steps) * steps
    if padded != S:
        pad = lambda a: jnp.pad(a, ((0, 0), (0, padded - S), (0, 0)))  # noqa: E731
        x, dt, B, C = map(pad, (x, dt, B, C))
    rows = lambda a: a.reshape(b, padded // GROUP, GROUP, d)             # noqa: E731 - as it lies
    by_step = pl.BlockSpec((None, steps // GROUP, GROUP, width), lambda i, j, k: (i, k, 0, j))
    scalars = pl.BlockSpec((None, steps * N), lambda i, j, k: (i, k), memory_space=pltpu.SMEM)
    state = pl.BlockSpec((None, N, width), lambda i, j, k: (i, 0, j))
    y, h = pl.pallas_call(
        functools.partial(_kernel, lanes=LANES if width % LANES == 0 else width),
        grid=(b, d // width, padded // steps),
        in_specs=[by_step, by_step, pl.BlockSpec((N, width), lambda i, j, k: (0, j)),
                  scalars, scalars, state],
        out_specs=[by_step, state],
        out_shape=[jax.ShapeDtypeStruct((b, padded // GROUP, GROUP, d), f32),
                   jax.ShapeDtypeStruct((b, N, d), f32)],
        scratch_shapes=[pltpu.VMEM((N, width), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="selective_scan",
    )(rows(x), rows(dt), A, B.reshape(b, padded * N), C.reshape(b, padded * N), h0)
    return y.reshape(b, padded, d)[:, :S], h
