"""Attention kernels: Pallas flash attention for TPU.

The hot op of every transformer in the framework. The Pallas kernel (tiled
online-softmax over KV blocks, VMEM-resident accumulators) lives here;
models dispatch through :func:`flash_attention` which falls back to the
einsum path on non-TPU backends (tests run on CPU).

Replaces what the reference gets from Megatron/TransformerEngine fused CUDA
kernels (reference: utils/megatron_lm.py delegates attention entirely).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def flash_attention_available(q=None) -> bool:
    """True when the backend is the TPU and the shapes are tileable. A test
    that compiles for a described chip patches this function."""
    if jax.default_backend() != "tpu":
        return False
    if q is not None:
        # Kernel wants seq divisible by block size and head_dim <= 256.
        seq = q.shape[1]
        return seq >= 128 and seq % 128 == 0 and q.shape[-1] <= 256
    return True


def softcap_logits(logits, cap):
    """Gemma2-style logit bounding: ``cap * tanh(logits / cap)`` computed in
    fp32, returned in the input dtype. ``cap=None`` is the identity — the
    single implementation every softcap site shares (einsum path, cached
    decode, both model heads, the streamed executor's head)."""
    if cap is None:
        return logits
    return (cap * jnp.tanh(logits.astype(jnp.float32) / cap)).astype(logits.dtype)


def _einsum_attention(q, k, v, causal: bool, segment_ids=None, sliding_window=None,
                      sm_scale=None, logit_softcap=None):
    """XLA-fused reference path: [B, S, H, D] -> [B, S, H, D].

    GQA-native: when k/v carry fewer heads (``G`` with ``H = G * rep``) the
    queries contract *grouped* against the narrow K/V — no ``jnp.repeat``
    copy is ever materialized (same trick as llama._cached_attention).

    ``sliding_window=w`` (Mistral-style) restricts each query to the last
    ``w`` keys: k_pos in (q_pos - w, q_pos]. ``sm_scale`` overrides the
    1/sqrt(head_dim) logit scale; ``logit_softcap`` bounds logits via
    cap * tanh(s / cap) before masking (Gemma2)."""
    scale = q.shape[-1] ** -0.5 if sm_scale is None else sm_scale
    B, Sq, H, D = q.shape
    G = k.shape[2]
    if H != G:
        if H % G:
            raise ValueError(f"q heads {H} not a multiple of kv heads {G}")
        qg = (q * scale).reshape(B, Sq, G, H // G, D)
        logits = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k)
    else:
        logits = jnp.einsum("bqhd,bkhd->bhqk", q * scale, k)
    logits = softcap_logits(logits, logit_softcap)
    head_dims = logits.ndim - 3  # axes between batch and [q, k]
    big_neg = jnp.finfo(logits.dtype).min
    if causal or sliding_window is not None:
        q_len, k_len = q.shape[1], k.shape[1]
        q_pos = jnp.arange(q_len)[:, None]
        k_pos = jnp.arange(k_len)[None, :]
        mask = jnp.ones((q_len, k_len), bool)
        if causal:
            mask &= k_pos <= q_pos
        if sliding_window is not None:
            # The documented window is k_pos in (q_pos - w, q_pos] — both
            # bounds apply regardless of `causal`, so a non-causal caller
            # still gets a window, never unmasked future keys.
            mask &= (k_pos > q_pos - sliding_window) & (k_pos <= q_pos)
        logits = jnp.where(mask[(None,) * (head_dims + 1)], logits, big_neg)
    if segment_ids is not None:
        seg_mask = segment_ids[:, :, None] == segment_ids[:, None, :]
        logits = jnp.where(seg_mask[(slice(None),) + (None,) * head_dims], logits, big_neg)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    if H != G:
        out = jnp.einsum("bgrqk,bkgd->bqgrd", probs, v)
        return out.reshape(B, Sq, H, D)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def flash_attention(q, k, v, causal: bool = True, block_q: int | None = None,
                    block_k: int | None = None, sliding_window=None, segment_ids=None,
                    sm_scale=None, logit_softcap=None):
    """Flash attention entry point.

    Args are [batch, seq, heads, head_dim]. Dispatches to the Pallas kernel
    on TPU; einsum fallback elsewhere. ``segment_ids`` (packed sequences)
    are masked inside the kernel and compose with ``sliding_window``'s
    banded grid. ``sm_scale`` overrides 1/sqrt(head_dim); ``logit_softcap``
    (Gemma2) is applied inside the kernel pre-mask. The kernels size their
    tiles from the shape (``flash_pallas.tile_plan``); ``block_q`` /
    ``block_k`` pin a size for kernel tests.
    """
    if sliding_window is not None and not causal:
        # Validated here (not just in the kernel) so CPU-fallback runs fail
        # identically to TPU runs instead of silently clamping causally.
        raise ValueError("sliding_window requires causal=True")
    if not flash_attention_available(q):
        return _einsum_attention(q, k, v, causal, segment_ids=segment_ids,
                                 sliding_window=sliding_window, sm_scale=sm_scale,
                                 logit_softcap=logit_softcap)
    from .flash_pallas import pallas_flash_attention

    kernel = functools.partial(
        pallas_flash_attention, causal=causal, block_q=block_q, block_k=block_k,
        sliding_window=sliding_window, sm_scale=sm_scale, logit_softcap=logit_softcap)
    sharded = _per_shard_specs(q, k)
    if sharded is None:
        return kernel(q, k, v, segment_ids=segment_ids)
    # A Mosaic kernel cannot be partitioned by GSPMD ("Mosaic kernels cannot
    # be automatically partitioned. Please wrap the call in a shard_map"), so
    # on a mesh the kernel runs once per shard: attention is independent per
    # batch row and per head, and each device keeps the rows and heads it
    # already holds — no q/k/v gather at the boundary.
    mesh, qkv_spec, seg_spec = sharded
    segments = () if segment_ids is None else (segment_ids,)
    return jax.shard_map(
        lambda q, k, v, *seg: kernel(q, k, v, segment_ids=seg[0] if seg else None),
        mesh=mesh, in_specs=(qkv_spec,) * 3 + (seg_spec,) * len(segments),
        out_specs=qkv_spec, check_vma=False)(q, k, v, *segments)


def _per_shard_specs(q, k):
    """``(mesh, qkv_spec, segment_spec)`` for running the flash kernel per
    shard of global ``[B, S, H, D]`` arrays, or None when there is nothing
    to split over: no ambient mesh, a one-device mesh, or a caller that is
    already inside a ``shard_map`` body (the context-parallel paths).

    Batch splits over the data axes (``dp``, ``fsdp``) and heads over
    ``tp`` — the layout ``ops/ring_attention._qkv_spec`` uses — each only
    as far as it divides the dimension (GQA: both the query and the KV head
    counts). An axis that does not divide stays unsplit, which is correct
    and costs a gather."""
    from jax.sharding import PartitionSpec as P

    from ..state import current_mesh

    mesh = current_mesh()
    if (mesh is None or mesh.devices.size == 1
            or jax.sharding.get_abstract_mesh().manual_axes):
        return None
    batch_axes, ways = [], 1
    for ax in ("dp", "fsdp"):
        n = mesh.shape.get(ax, 1)
        if n > 1 and q.shape[0] % (ways * n) == 0:
            batch_axes.append(ax)
            ways *= n
    tp = mesh.shape.get("tp", 1)
    head_ax = "tp" if tp > 1 and q.shape[2] % tp == 0 and k.shape[2] % tp == 0 else None
    batch = tuple(batch_axes) or None
    return mesh, P(batch, None, head_ax, None), P(batch, None)
