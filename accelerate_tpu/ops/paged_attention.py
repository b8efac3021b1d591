"""Pallas TPU paged attention for the decode tick, over a page pool whose
leaves keep a token's heads side by side in one row (``[pages, 1, P, G *
hd]``: the form ``models/llama.py`` ``_paged_flat_kv_attend`` serves).

One call a reader. The pool's ``k`` and ``v`` leaves stay in HBM as they lie;
for each lane the kernel walks ITS LIVE PAGES ONLY (the pages that hold rows
before the token's own, from the window's first page on a windowed layer: a
loop whose trip count is traced), fetches a ``k`` and a ``v`` page into VMEM
with double-buffered async copies — the next page, or the next running
lane's first, is in flight while this one is scored — and reads each page
ONCE for both products. The page is multiplied AS IT LIES: the lane's
queries are placed, in VMEM, each in its KV head's ``hd`` columns of a ``G *
hd``-wide row of zeros, so the scores of all heads are one ``[H, G * hd] x
[P, G * hd]`` product and the weighted rows one ``[H, P] x [P, G * hd]``, of
which a head keeps its own columns when the lane is done. With one query row
a head every 128 x 128 tile of a page passes the MXU once either way; head
by head (``[n_rep, hd] x [P, hd]``, 2 G small products a page) the same
tiles took 2.9 us a page pair where this form takes 1.95, the DMA's own pace
(PERF.md section 6, PR 38). The mask by position and window, the running
(max, sum, weighted values) in float32. It returns that triple unnormalised;
the caller merges it with the token's own row, which is in no page yet.

Precision as the XLA work list states it: products on the wider of the
queries' and the rows' types accumulated in float32, softmax in float32,
probabilities cast to that type before the value product.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_pallas import LANES, NEG_INF, _interpret


def tpu_backend() -> bool:
    """The one question :func:`paged_attention_available` asks the process;
    a test that compiles for a described chip, or runs the kernel in the
    interpreter, patches this function."""
    return jax.default_backend() == "tpu"


def paged_attention_available(pool, scales, sharded: bool) -> bool:
    """Whether the decode tick's attention over ``pool`` (one cache entry's
    leaves) runs this kernel: the backend is the TPU, the leaves are ``k``
    and ``v`` flat rows ``[pages, 1, P, G * hd]`` of one shape and type whose
    width is whole lane tiles and whose page is whole sublane tiles, the pool
    has no scales (an int8 pool widens in the XLA list's steps) and no mesh
    axis shards it (a Mosaic kernel cannot be partitioned). From what the
    code can see alone: no option, no flag."""
    if not tpu_backend() or scales is not None or sharded:
        return False
    if not isinstance(pool, dict) or set(pool) != {"k", "v"}:
        return False
    k, v = pool["k"], pool["v"]
    return (k.ndim == 4 and k.shape == v.shape and k.dtype == v.dtype and k.shape[1] == 1
            and k.shape[3] % LANES == 0 and k.shape[2] % (32 // k.dtype.itemsize) == 0)


def live_pages(pos, live, pages_per_lane: int, page: int, sliding_window=None, lib=jnp):
    """``(first, count)`` per lane: the pages that hold pool rows one query at
    ``pos`` can see — ``models.llama.tick_key_extent``'s rule at page
    granularity: the rows before its own, from row ``pos - window + 1`` on a
    windowed layer; none for a lane no stream runs in."""
    from ..models.llama import tick_key_extent

    return tick_key_extent(pos, live, pages_per_lane * page, page, sliding_window, lib=lib)


def _kernel(table_ref, first_ref, count_ref, pos_ref, q_ref, k_hbm, v_hbm,
            m_ref, l_ref, acc_ref, k_buf, v_buf, sems, q_rows, m_run, l_run, acc_run,
            *, n_rep, window, cdt):
    S, H, hd = q_ref.shape
    P, width = k_buf.shape[1:]
    G = H // n_rep
    head_of = jax.lax.broadcasted_iota(jnp.int32, (H, 1), 0) // n_rep

    m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def next_running(s):
        """The first lane at or after ``s`` with a live page, else ``S``."""
        return jax.lax.while_loop(
            lambda s: jnp.logical_and(s < S, count_ref[jnp.minimum(s, S - 1)] == 0),
            lambda s: s + 1, s)

    def copies(lane, j, slot):
        page = table_ref[lane, first_ref[lane] + j]
        return (pltpu.make_async_copy(k_hbm.at[page, 0], k_buf.at[slot], sems.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[page, 0], v_buf.at[slot], sems.at[1, slot]))

    def start(lane, j, slot):
        for copy in copies(lane, j, slot):
            copy.start()

    first_lane = next_running(jnp.int32(0))

    @pl.when(first_lane < S)
    def _():
        start(first_lane, 0, 0)

    def one_lane(carry):
        lane, slot = carry
        n, q_pos = count_ref[lane], pos_ref[lane]
        row0 = first_ref[lane] * P
        after = next_running(lane + 1)
        # each query in its KV head's columns of a row of zeros: a page is
        # then multiplied as it lies, one product for all heads (the same
        # 128 x 128 tiles through the MXU as head by head, in 2 ops, not 2 G)
        q = q_ref[lane].astype(cdt)
        for g in range(G):
            q_rows[:, g * hd:(g + 1) * hd] = jnp.where(head_of == g, q, jnp.zeros_like(q))
        m_run[...] = jnp.full(m_run.shape, NEG_INF, jnp.float32)
        l_run[...] = jnp.zeros(l_run.shape, jnp.float32)
        acc_run[...] = jnp.zeros(acc_run.shape, jnp.float32)

        def one_page(j, slot):
            last = j + 1 == n
            to_lane, to_page = jnp.where(last, after, lane), jnp.where(last, 0, j + 1)

            @pl.when(to_lane < S)
            def _():
                start(to_lane, to_page, 1 - slot)

            k_pos = row0 + j * P + jax.lax.broadcasted_iota(jnp.int32, (1, P), 1)
            mask = k_pos < q_pos
            if window is not None:
                mask = jnp.logical_and(mask, k_pos > q_pos - window)
            k_copy, v_copy = copies(lane, j, slot)
            k_copy.wait()
            s = jax.lax.dot_general(q_rows[...], k_buf[slot].astype(cdt),
                                    (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)      # [H, P]
            s = jnp.where(mask, s, NEG_INF)
            m_prev = m_run[...]                                              # [H, 1]
            m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_run[...] = alpha * l_run[...] + p.sum(-1, keepdims=True)
            m_run[...] = m_new
            v_copy.wait()
            acc_run[...] = alpha * acc_run[...] + jnp.dot(
                p.astype(cdt), v_buf[slot].astype(cdt), preferred_element_type=jnp.float32)
            return 1 - slot

        slot = jax.lax.fori_loop(0, n, one_page, slot)
        # a head keeps its own columns of the weighted rows
        out = jnp.zeros((H, hd), jnp.float32)
        for g in range(G):
            out = out + jnp.where(head_of == g, acc_run[:, g * hd:(g + 1) * hd], 0.0)
        m_ref[lane], l_ref[lane], acc_ref[lane] = m_run[...], l_run[...], out
        return after, slot

    jax.lax.while_loop(lambda carry: carry[0] < S, one_lane, (first_lane, jnp.int32(0)))


def paged_flat_attention(q, pool_k, pool_v, table, pos, live, *, n_rep: int,
                         sliding_window=None):
    """Attention of one query a lane over the pool rows its stream holds,
    for all ``S`` lanes of a decode tick in one kernel call. ``q [S, H, hd]``
    (scaled; head ``g * n_rep + r`` reads KV head ``g``), ``pool_k`` /
    ``pool_v`` ``[pages, 1, P, G * hd]`` (left in HBM), ``table [S, Np]``
    pool ids, ``pos [S]``, ``live [S]``. Returns the UNNORMALISED running
    triple over the rows before each lane's own, in float32: ``(m [S, H], l
    [S, H], acc [S, H, hd])`` — ``(-1e30, 0, 0)`` for a lane that holds
    none, which any finite own row then outweighs exactly."""
    S, H, hd = q.shape
    G = H // n_rep
    P, width = pool_k.shape[2:]
    if pool_k.shape != pool_v.shape or pool_k.shape[1] != 1 or width != G * hd:
        raise ValueError(f"pool leaves {pool_k.shape} / {pool_v.shape} are not flat rows of "
                         f"{G} heads x {hd}")
    first, count = live_pages(pos, live, table.shape[1], P, sliding_window)
    cdt = jnp.promote_types(q.dtype, pool_k.dtype)
    in_vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    m, l, acc = pl.pallas_call(
        functools.partial(_kernel, n_rep=n_rep, window=sliding_window, cdt=cdt),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(),
            in_specs=[in_vmem, in_hbm, in_hbm],
            out_specs=[in_vmem, in_vmem, in_vmem],
            scratch_shapes=[pltpu.VMEM((2, P, width), pool_k.dtype),
                            pltpu.VMEM((2, P, width), pool_v.dtype),
                            pltpu.SemaphoreType.DMA((2, 2)),
                            pltpu.VMEM((H, width), cdt),
                            pltpu.VMEM((H, 1), jnp.float32),
                            pltpu.VMEM((H, 1), jnp.float32),
                            pltpu.VMEM((H, width), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((S, H, 1), jnp.float32),
                   jax.ShapeDtypeStruct((S, H, 1), jnp.float32),
                   jax.ShapeDtypeStruct((S, H, hd), jnp.float32)],
        interpret=_interpret(),
        name="paged_flat_attention",
    )(table.astype(jnp.int32), first.astype(jnp.int32), count.astype(jnp.int32),
      pos.astype(jnp.int32), q, pool_k, pool_v)
    return m[..., 0], l[..., 0], acc
