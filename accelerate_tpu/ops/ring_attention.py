"""Context parallelism: ring attention and Ulysses (all-to-all) attention.

The reference has *no* long-context support — only a Megatron sequence-
parallel passthrough flag (reference: utils/dataclasses.py:1621-1624,
utils/launch.py:303-304). These kernels are net-new, designed for the TPU
mesh: sequence activations are sharded over the ``cp`` mesh axis and the
attention op itself moves data over ICI instead of materializing the full
sequence on any chip.

Two strategies, selectable per model (``attention_backend``):

* **Ring attention** (`ring_attention`): each device holds a contiguous
  [B, S/n, H, D] shard of q/k/v. KV shards rotate around the ring via
  ``lax.ppermute`` while a streaming online-softmax (f32 running max /
  denominator, flash-attention style) accumulates each query block's
  output. Peak memory is O(S/n); the KV transfer overlaps with the block
  matmul under XLA's async collective-permute. Works for any head count.

* **Ulysses attention** (`ulysses_attention`): two ``all_to_all`` reshards
  (seq-sharded -> head-sharded and back); in between, every device runs an
  ordinary *local* flash attention over the full sequence for H/n heads.
  Cheaper collectives than the ring for moderate S, but requires
  ``num_heads % cp == 0`` and O(S) activation memory per device.

Both are exact (match full attention to numerical tolerance) including
causal masking across shard boundaries via global position offsets.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

_BIG_NEG = -1e30


def _qkv_spec(mesh, axis_name: str):
    """[B, S, H, D] spec: batch over the data axes, seq over the cp axis,
    heads over tp (attention is per-head, so a tp-sharded head dim stays
    local to each shard_map body). Only names axes present in the mesh so
    dp/fsdp/tp stay sharded instead of being all-gathered at the shard_map
    boundary."""
    batch_axes = tuple(ax for ax in ("dp", "fsdp") if ax in mesh.shape and mesh.shape[ax] > 1)
    head_ax = "tp" if "tp" in mesh.shape and mesh.shape["tp"] > 1 else None
    return P(batch_axes or None, axis_name, head_ax, None)


def _ring_attention_shard(q, k, v, *, axis_name: str, axis_size: int, causal: bool,
                          inner_chunk: int):
    """Per-shard body (runs inside shard_map).

    q: [B, S_local, H, D]; k/v: [B, S_local, G, D] with G dividing H (GQA
    KV stays *unrepeated* — the ring rotates G-wide KV over ICI, H/G times
    less interconnect traffic than rotating expanded heads; the grouped
    einsum contracts queries against shared KV directly).
    Returns [B, S_local, H, D].
    """
    my_idx = jax.lax.axis_index(axis_name)
    B, q_len, H, D = q.shape
    k_len = k.shape[1]
    G = k.shape[2]
    rep = H // G
    scale = D ** -0.5

    q_pos = my_idx * q_len + jnp.arange(q_len, dtype=jnp.int32)
    # [B, q_len, G, rep, D] — grouped view for GQA contraction.
    qf = (q * scale).astype(jnp.float32).reshape(B, q_len, G, rep, D)

    # The arriving KV block is itself processed in sub-chunks so the logits
    # tile is [B, G, rep, q_len, sub] instead of [.., k_len] — at the
    # sequence lengths ring attention exists for, the full tile would be
    # gigabytes (e.g. cp=4, S=32k: 8k x 8k f32 per head). Falls back to one
    # sub-chunk when k_len doesn't divide.
    sub = min(inner_chunk, k_len)
    if k_len % sub:
        sub = k_len
    n_sub = k_len // sub

    # Accumulators in f32: running max m, denominator l, unnormalized out o.
    m0 = jnp.full((B, G, rep, q_len), _BIG_NEG, jnp.float32)
    l0 = jnp.zeros((B, G, rep, q_len), jnp.float32)
    o0 = jnp.zeros((B, G, rep, q_len, D), jnp.float32)

    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    def _tile_update(acc, k_t, v_t, k_pos):
        """Online-softmax merge of one [*, sub, G, D] KV tile."""
        m, l, o = acc
        logits = jnp.einsum("bqgrd,bkgd->bgrqk", qf, k_t.astype(jnp.float32))
        if causal:
            mask = q_pos[:, None] >= k_pos[None, :]
            logits = jnp.where(mask[None, None, None], logits, _BIG_NEG)
        m_new = jnp.maximum(m, logits.max(axis=-1))
        p = jnp.exp(logits - m_new[..., None])
        if causal:
            # Fully-masked rows would otherwise contribute exp(0)=1 terms
            # when m_new is still the sentinel.
            p = jnp.where(mask[None, None, None], p, 0.0)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        o_new = o * corr[..., None] + jnp.einsum(
            "bgrqk,bkgd->bgrqd", p, v_t.astype(jnp.float32)
        )
        return m_new, l_new, o_new

    @jax.checkpoint
    def block_update(acc, k_c, v_c, chunk):
        base = chunk * k_len
        if n_sub == 1:
            return _tile_update(acc, k_c, v_c, base + jnp.arange(k_len, dtype=jnp.int32))
        # [B, k_len, G, D] -> [n_sub, B, sub, G, D] for the inner scan.
        k_tiles = jnp.moveaxis(k_c.reshape(B, n_sub, sub, G, D), 1, 0)
        v_tiles = jnp.moveaxis(v_c.reshape(B, n_sub, sub, G, D), 1, 0)
        offsets = base + jnp.arange(n_sub, dtype=jnp.int32) * sub

        def sub_step(acc, tile):
            k_t, v_t, off = tile
            return _tile_update(acc, k_t, v_t, off + jnp.arange(sub, dtype=jnp.int32)), None

        acc, _ = jax.lax.scan(sub_step, acc, (k_tiles, v_tiles, offsets))
        return acc

    def step(carry, i):
        k_c, v_c, acc = carry
        # After i rotations device j holds the chunk that started on j - i.
        acc = block_update(acc, k_c, v_c, (my_idx - i) % axis_size)
        # Rotate KV to the next device. Both the matmuls and the permute only
        # read k_c/v_c, so XLA starts the async collective-permute alongside
        # the block compute and the transfer rides ICI under the matmul.
        k_c = jax.lax.ppermute(k_c, axis_name, perm)
        v_c = jax.lax.ppermute(v_c, axis_name, perm)
        return (k_c, v_c, acc), None

    # Scan the first axis_size-1 blocks (each ends with a rotation), then
    # consume the final block outside the loop so no dead rotation is issued.
    (k, v, acc), _ = jax.lax.scan(
        step, (k, v, (m0, l0, o0)), jnp.arange(axis_size - 1, dtype=jnp.int32)
    )
    _, l, o = block_update(acc, k, v, (my_idx - (axis_size - 1)) % axis_size)
    out = o / jnp.maximum(l, 1e-30)[..., None]        # [B, G, rep, q_len, D]
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, q_len, H, D)
    return out.astype(q.dtype)


def _expand_kv(q, k, v):
    """Repeat GQA KV heads to match q. Only needed when a tp axis must shard
    the head dim and G heads can't split over it — every dense attention
    path is otherwise narrow-KV-native."""
    if k.shape[2] == q.shape[2]:
        return k, v
    rep = q.shape[2] // k.shape[2]
    return jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)


def _ambient_inner_chunk() -> int:
    """ContextParallelPlugin.ring_inner_chunk when an AcceleratorState is
    live, else the plugin field's default (one source of truth)."""
    from ..state import AcceleratorState
    from ..utils.dataclasses import ContextParallelPlugin

    if AcceleratorState._shared_state:
        plugin = AcceleratorState().cp_plugin
        if plugin is not None:
            return int(plugin.ring_inner_chunk)
    return ContextParallelPlugin.ring_inner_chunk


def ring_attention(q, k, v, mesh=None, axis_name: str = "cp", causal: bool = True,
                   inner_chunk: int | None = None):
    """Exact ring attention over the ``axis_name`` mesh axis.

    Args are *global* [B, S, H, D] arrays (sharded or not — shard_map
    partitions them on the sequence dim). With a trivial axis (size 1 or no
    mesh) falls back to the plain attention dispatch. ``inner_chunk`` bounds
    the logits tile each step materializes ([B, G, H/G, S_local, inner_chunk]),
    keeping per-device memory O(S_local x inner_chunk) at any length;
    ``None`` reads ``ContextParallelPlugin.ring_inner_chunk`` (default 1024).
    """
    if inner_chunk is None:
        inner_chunk = _ambient_inner_chunk()
    mesh = _resolve_mesh(mesh)
    axis_size = _axis_size(mesh, axis_name)
    if axis_size == 1:
        from .attention import flash_attention

        # flash/einsum are GQA-native; narrow KV goes straight through.
        return flash_attention(q, k, v, causal=causal)

    if q.shape[2] % k.shape[2]:
        raise ValueError(
            f"ring_attention: q heads {q.shape[2]} not a multiple of kv heads {k.shape[2]}")
    tp = _axis_size(mesh, "tp")
    if tp > 1 and k.shape[2] % tp:
        # The head axis is tp-sharded inside shard_map; G-wide KV that can't
        # split over tp must enter expanded (costs bandwidth, keeps configs
        # that predate unrepeated-KV support working).
        k, v = _expand_kv(q, k, v)
    if q.shape[1] % axis_size:
        raise ValueError(
            f"ring_attention: seq len {q.shape[1]} not divisible by {axis_name}={axis_size}"
        )
    return _ring_fn(mesh, axis_name, axis_size, causal, inner_chunk)(q, k, v)


@functools.lru_cache(maxsize=None)
def _ring_fn(mesh, axis_name: str, axis_size: int, causal: bool, inner_chunk: int):
    """Cached jitted shard_map for one ring configuration.

    jit is required (the remat'd inner scan cannot evaluate eagerly inside
    shard_map) and must be cached here: a fresh jit-of-fresh-shard_map per
    call could never hit jax's compile cache, recompiling every invocation
    for eager callers.
    """
    spec = _qkv_spec(mesh, axis_name)
    fn = jax.shard_map(
        functools.partial(
            _ring_attention_shard, axis_name=axis_name, axis_size=axis_size, causal=causal,
            inner_chunk=inner_chunk,
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return jax.jit(fn)


def _ulysses_shard(q, k, v, *, axis_name: str, causal: bool, use_flash: bool):
    """Per-shard body: [B, S/n, H, D] -> all_to_all -> [B, S, H/n, D] ->
    local attention -> all_to_all back."""

    def seq_to_heads(x):
        # [B, S/n, H, D] -> [B, S, H/n, D]: ship head-group j to device j,
        # gather every device's seq chunk (tiled all_to_all concatenates
        # received pieces in source-device order, so the sequence stays
        # globally ordered).
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)

    def heads_to_seq(x):
        # inverse: [B, S, H/n, D] -> [B, S/n, H, D]
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=True)

    ql, kl, vl = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    # GQA KV crossed the wire unrepeated (G/cp heads per device) and STAYS
    # narrow: flash indexes the shared kv head in its BlockSpecs and the
    # einsum path contracts grouped, so no expansion on either side.
    from .attention import _einsum_attention, flash_attention, flash_attention_available

    if use_flash and flash_attention_available(ql):
        out = flash_attention(ql, kl, vl, causal=causal)
    else:
        out = _einsum_attention(ql, kl, vl, causal=causal)
    return heads_to_seq(out)


def ulysses_attention(
    q, k, v, mesh=None, axis_name: str = "cp", causal: bool = True, use_flash: bool = True
):
    """All-to-all (DeepSpeed-Ulysses-style) sequence-parallel attention.

    Requires num_heads (q and kv) divisible by the axis size. Falls back to
    the plain dispatch on a trivial axis.
    """
    mesh = _resolve_mesh(mesh)
    axis_size = _axis_size(mesh, axis_name)
    if axis_size == 1:
        from .attention import flash_attention

        # flash/einsum are GQA-native; narrow KV goes straight through.
        return flash_attention(q, k, v, causal=causal)

    tp = _axis_size(mesh, "tp")
    if (tp > 1 and k.shape[2] % tp) or (k.shape[2] // max(tp, 1)) % axis_size:
        # Unrepeated GQA KV that can't split over tp x cp: expand up front
        # (the pre-unrepeated-KV behavior) so such configs keep working.
        k, v = _expand_kv(q, k, v)
    local_q_heads, local_kv_heads = q.shape[2] // tp, k.shape[2] // tp
    if local_q_heads % axis_size or local_kv_heads % axis_size:
        raise ValueError(
            f"ulysses_attention: per-tp-shard heads q={local_q_heads}/kv={local_kv_heads} must "
            f"be divisible by {axis_name}={axis_size} (use ring_attention otherwise)"
        )
    if q.shape[1] % axis_size:
        raise ValueError(
            f"ulysses_attention: seq len {q.shape[1]} not divisible by {axis_name}={axis_size}"
        )
    spec = _qkv_spec(mesh, axis_name)
    fn = jax.shard_map(
        functools.partial(
            _ulysses_shard,
            axis_name=axis_name,
            causal=causal,
            use_flash=use_flash,
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)


def context_parallel_attention(
    q,
    k,
    v,
    mesh=None,
    axis_name: str = "cp",
    causal: bool = True,
    strategy: str = "auto",
    use_flash: bool = True,
):
    """Unified entry: pick a CP strategy for seq-sharded attention.

    strategy: 'auto' (ulysses when head counts divide, else ring), 'ring',
    or 'ulysses'.
    """
    mesh = _resolve_mesh(mesh)
    axis_size = _axis_size(mesh, axis_name)
    if strategy == "auto":
        tp = _axis_size(mesh, "tp")
        if (
            axis_size > 1
            and (q.shape[2] // tp) % axis_size == 0
            and (k.shape[2] // tp) % axis_size == 0
        ):
            strategy = "ulysses"
        else:
            strategy = "ring"
    if strategy == "ring":
        return ring_attention(q, k, v, mesh=mesh, axis_name=axis_name, causal=causal)
    if strategy == "ulysses":
        return ulysses_attention(
            q, k, v, mesh=mesh, axis_name=axis_name, causal=causal, use_flash=use_flash
        )
    raise ValueError(f"unknown context-parallel strategy {strategy!r}")


def _axis_size(mesh, axis_name: str) -> int:
    return int(mesh.shape[axis_name]) if mesh is not None and axis_name in mesh.shape else 1


def _resolve_mesh(mesh):
    """Explicit mesh, else the shared ambient resolver (state.current_mesh)."""
    from ..state import current_mesh

    return current_mesh(mesh)
