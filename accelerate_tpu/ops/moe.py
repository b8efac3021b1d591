"""Mixture-of-Experts with expert parallelism over the ``ep`` mesh axis.

The reference has no MoE implementation at all — its single MoE touchpoint is
forwarding leaf-module names to DeepSpeed (reference: accelerator.py:1736
``set_moe_leaf_modules``); the actual expert dispatch lives in DeepSpeed's
CUDA runtime. This module is net-new, designed the TPU way (GShard / Switch
Transformer formulation):

* **Static shapes.** Each expert processes a fixed ``capacity`` of token
  slots; tokens beyond capacity are dropped (their combine weight is zero, so
  the residual stream carries them unchanged). No ragged/dynamic dispatch —
  XLA gets pure einsums it can tile onto the MXU.
* **Dispatch/combine one-hots.** Routing produces a boolean dispatch tensor
  ``[groups, tokens, experts, capacity]`` and a float combine tensor of the
  same shape; moving tokens to experts and back is two einsums. With expert
  weights sharded ``[E, ...] -> P('ep', ...)`` and the expert-major
  intermediates constrained to ``P(..., 'ep', ...)``, XLA lowers the
  dispatch einsum into the all-to-all that CUDA MoE stacks hand-write.
* **Groups.** Tokens are routed within independent groups (the leading dim of
  the dispatch tensor). Dispatch memory is O(tokens² · k · cf / groups), so
  groups should scale with the token count; by default one group per
  data-shard (dp·fsdp·ep), matching each group to the tokens already local
  to a device.

Losses follow Switch Transformer: load-balance loss (experts × mean(fraction
routed · mean router prob)) and router z-loss (mean logsumexp² of logits).

That is the trainer's path (:func:`moe_mlp_apply`). Under a KV cache — the
serving engine's programs, ``generate`` — the expert layers of every family
go through :func:`moe_held_apply` instead: no capacity, no token dropped,
the experts compute the rows that were routed (forward only).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..observability.program_parts import program_part


def default_num_groups(num_tokens: int, mesh=None) -> int:
    """One routing group per data shard when it divides the token count."""
    from ..state import current_mesh

    mesh = current_mesh(mesh)
    if mesh is None:
        return 1
    shape = dict(mesh.shape)
    g = shape.get("dp", 1) * shape.get("fsdp", 1) * shape.get("ep", 1)
    return g if g > 0 and num_tokens % g == 0 else 1


def expert_capacity(tokens_per_group: int, num_experts: int, top_k: int, capacity_factor: float) -> int:
    """Slots per expert per group, padded up to a multiple of 8 for TPU tiling."""
    cap = int(math.ceil(top_k * tokens_per_group * capacity_factor / num_experts))
    return max(8, -(-cap // 8) * 8)


def top_k_routing(
    router_logits: jnp.ndarray,
    top_k: int,
    capacity: int,
    *,
    normalize_gates: Optional[bool] = None,
):
    """GShard top-k routing with per-expert capacity.

    Args:
      router_logits: ``[groups, tokens, experts]`` float32.
      top_k: experts per token (1 = Switch, 2 = Mixtral).
      capacity: slots per expert per group (static).
      normalize_gates: renormalize the selected top-k probabilities to sum to
        one per token (Mixtral semantics). Default: True iff ``top_k > 1`` —
        with ``top_k == 1`` normalization would collapse every gate to 1.0
        and cut the router off from the task-loss gradient; Switch semantics
        keep the raw router probability as the gate.

    Returns ``(dispatch, combine, aux)``:
      dispatch: ``[G, n, E, C]`` {0,1} — token→(expert, slot) assignment.
      combine:  ``[G, n, E, C]`` f32 — gate weight at the assigned slot.
      aux: dict with ``load_balance_loss``, ``router_z_loss``, and
        ``expert_fraction`` ``[E]`` (fraction of top-1 assignments).
    """
    G, n, E = router_logits.shape
    logits = router_logits.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, top_k)  # [G, n, k]
    if normalize_gates is None:
        normalize_gates = top_k > 1
    if normalize_gates:
        gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    onehot = jax.nn.one_hot(expert_idx, E, dtype=jnp.float32)  # [G, n, k, E]

    # Slot-major priority: every token's 1st choice outranks any 2nd choice.
    oh_slot = jnp.swapaxes(onehot, 1, 2).reshape(G, top_k * n, E)
    pos = jnp.cumsum(oh_slot, axis=1) - 1.0  # [G, k*n, E] 0-indexed arrival order
    keep = (pos < capacity) * oh_slot
    disp_slot = keep[..., None] * jax.nn.one_hot(
        jnp.clip(pos, 0, capacity - 1).astype(jnp.int32), capacity, dtype=jnp.float32
    )  # [G, k*n, E, C]

    gates_slot = jnp.swapaxes(gate_vals, 1, 2).reshape(G, top_k * n)
    combine_slot = disp_slot * gates_slot[..., None, None]

    # Back to token-major, merging the k choices (disjoint experts per token).
    dispatch = disp_slot.reshape(G, top_k, n, E, capacity).sum(axis=1)
    combine = combine_slot.reshape(G, top_k, n, E, capacity).sum(axis=1)

    # Switch losses over all groups jointly.
    top1 = jax.nn.one_hot(expert_idx[..., 0], E, dtype=jnp.float32)  # [G, n, E]
    fraction = top1.mean(axis=(0, 1))          # [E] fraction routed (top-1)
    prob_mean = probs.mean(axis=(0, 1))        # [E] mean router prob
    load_balance = E * jnp.sum(fraction * prob_mean)
    z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    aux = {
        "load_balance_loss": load_balance,
        "router_z_loss": z_loss,
        "expert_fraction": fraction,
    }
    return dispatch, combine, aux


def _constrain(t, spec, mesh):
    if mesh is None:
        return t
    # Keep only axes that are non-trivial in the mesh AND whose cumulative
    # product still divides the dimension (e.g. a single routing group can't
    # be sharded over dp*ep).
    shape = dict(mesh.shape)

    def _ok(entry, dim):
        if entry is None:
            return None
        axes = entry if isinstance(entry, tuple) else (entry,)
        kept: list = []
        prod = 1
        for ax in axes:
            size = shape.get(ax, 1)
            if size > 1 and dim % (prod * size) == 0:
                kept.append(ax)
                prod *= size
        return tuple(kept) if len(kept) > 1 else (kept[0] if kept else None)

    return jax.lax.with_sharding_constraint(
        t, NamedSharding(mesh, P(*[_ok(e, d) for e, d in zip(spec, t.shape)]))
    )


def moe_mlp_apply(
    expert_params: dict,
    router_kernel: jnp.ndarray,
    x: jnp.ndarray,
    *,
    top_k: int,
    capacity_factor: float,
    num_groups: Optional[int] = None,
    mesh=None,
    router_noise_rng=None,
    router_noise_eps: float = 0.0,
    normalize_gates: Optional[bool] = None,
):
    """Sparse expert MLP over ``x`` [batch, seq, d_model].

    ``expert_params``: ``gate_proj``/``up_proj`` ``[E, D, F]`` and
    ``down_proj`` ``[E, F, D]`` (SwiGLU experts, stacked expert-major —
    shard dim 0 over ``ep``). ``router_kernel``: ``[D, E]``.

    Returns ``(out [batch, seq, d_model], aux dict)``.
    """
    from ..state import current_mesh

    mesh = current_mesh(mesh)
    B, S, D = x.shape
    wg, wu, wd = expert_params["gate_proj"], expert_params["up_proj"], expert_params["down_proj"]
    E = wg.shape[0]
    N = B * S
    G = num_groups if num_groups is not None else default_num_groups(N, mesh)
    if N % G != 0:
        raise ValueError(f"tokens {N} not divisible by num_groups {G}")
    n = N // G
    C = expert_capacity(n, E, top_k, capacity_factor)

    tokens = x.reshape(G, n, D)
    tokens = _constrain(tokens, (("dp", "fsdp", "ep"), None, None), mesh)

    with program_part("moe_router"):
        logits = tokens.astype(jnp.float32) @ router_kernel.astype(jnp.float32)  # [G, n, E]
        if router_noise_rng is not None and router_noise_eps > 0.0:
            noise = jax.random.uniform(
                router_noise_rng, logits.shape, jnp.float32,
                1.0 - router_noise_eps, 1.0 + router_noise_eps,
            )
            logits = logits * noise
        dispatch, combine, aux = top_k_routing(logits, top_k, C, normalize_gates=normalize_gates)

    cdt = x.dtype
    with program_part("moe_experts"):
        expert_in = jnp.einsum("gnec,gnd->egcd", dispatch.astype(cdt), tokens)
        expert_in = _constrain(expert_in, ("ep", ("dp", "fsdp"), None, None), mesh)
        h = jax.nn.silu(jnp.einsum("egcd,edf->egcf", expert_in, wg.astype(cdt)))
        h = h * jnp.einsum("egcd,edf->egcf", expert_in, wu.astype(cdt))
        out_e = jnp.einsum("egcf,efd->egcd", h, wd.astype(cdt))
        out_e = _constrain(out_e, ("ep", ("dp", "fsdp"), None, None), mesh)
        out = jnp.einsum("gnec,egcd->gnd", combine.astype(jnp.float32), out_e.astype(jnp.float32))
        out = _constrain(out, (("dp", "fsdp", "ep"), None, None), mesh)
    return out.reshape(B, S, D).astype(x.dtype), aux


# ---------------------------------------------------------------------------
# An expert layer that is told which experts it holds
# ---------------------------------------------------------------------------

#: a call of at most this many tokens goes through every held expert in one
#: batched product (:func:`_held_dense`): a decode tick or a verify step,
#: whose few rows cost nothing beside the read of each stack (755 GB/s in both
#: serving cells' ticks). It is a threshold on the call, not a tile size.
HELD_DENSE_TOKENS = 32


def held_tile_rows(tokens: int, top_k: int, num_experts: int) -> int:
    """Rows of one expert tile in :func:`moe_held_apply`'s sorted path, from
    the call's shape: the power of two in [32, 256] next above twice the rows
    an expert is expected to get (``tokens * top_k / num_experts``), so that
    nearly every touched expert is ONE tile and its weights are read once.

    A tile costs the read of its expert's three matrices, whatever its rows,
    up to the chip's ridge (v5e: 197 TFLOP/s / 819 GB/s = 240 rows of bf16);
    past it the padding costs compute, so the tile stops at 256. An expert
    with n routed rows computes ceil(n / tile) tiles: rows computed are the
    rows routed + at most ``tile - 1`` of padding per touched expert. How
    many experts are held scales the number of tiles, not a tile's best
    size, and neither do D and F (both sides of the ridge scale with D * F):
    they are not arguments. v5e readings, ms a layer (PERF.md section 6, PR
    30): Command A+'s chunk (256 tokens, top-8 of 128, 16 held: 16 rows
    expected) -> 32; Mixtral's (256, top-2 of 8: 64 expected) -> 128."""
    want = 2 * tokens * top_k / num_experts
    tile = 32
    while tile < want and tile < 256:
        tile *= 2
    return tile


def route_top_k(router_logits: jnp.ndarray, top_k: int, *, scores: str = "softmax",
                normalize_gates: bool = True, selection_bias: Optional[jnp.ndarray] = None):
    """``[T, E]`` router logits -> ``(gates [T, k] f32, experts [T, k] i32)``.

    ``scores`` is ``"softmax"`` (Mixtral) or ``"sigmoid"`` (each expert scored
    on its own); the k largest scores are kept and, with ``normalize_gates``,
    divided by their sum. ``selection_bias`` ``[E]`` (a load-balancing
    correction) moves which experts are picked and not their gates: the k
    largest of ``score + bias`` are picked, each gated by its score alone."""
    logits = router_logits.astype(jnp.float32)
    if scores == "softmax":
        s = jax.nn.softmax(logits, axis=-1)
    elif scores == "sigmoid":
        s = jax.nn.sigmoid(logits)
    else:
        raise ValueError(f"scores must be 'softmax' or 'sigmoid' (got {scores!r})")
    if selection_bias is None:
        gates, experts = jax.lax.top_k(s, top_k)
    else:
        _, experts = jax.lax.top_k(s + selection_bias.astype(jnp.float32), top_k)
        gates = jnp.take_along_axis(s, experts, axis=-1)
    if normalize_gates:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates, experts.astype(jnp.int32)


def _swiglu_experts(x_e, wg, wu, wd):
    """x_e [E, R, D] through expert-major SwiGLU stacks -> [E, R, D]."""
    h = jax.nn.silu(jnp.einsum("erd,edf->erf", x_e, wg)) * jnp.einsum("erd,edf->erf", x_e, wu)
    return jnp.einsum("erf,efd->erd", h, wd)


def averaged_experts_apply(expert_params: dict, x: jnp.ndarray) -> jnp.ndarray:
    """Always-on experts combined by their mean: ``x`` [B, S, D] through every
    SwiGLU expert of the stacks ``[n, D, F]`` / ``[n, F, D]``, averaged."""
    B, S, D = x.shape
    wg, wu, wd = (expert_params[n].astype(x.dtype) for n in ("gate_proj", "up_proj", "down_proj"))
    x_e = jnp.broadcast_to(x.reshape(1, B * S, D), (wg.shape[0], B * S, D))
    out = _swiglu_experts(x_e, wg, wu, wd).astype(jnp.float32).mean(0)
    return out.reshape(B, S, D).astype(x.dtype)


def _held_dense(tokens, wg, wu, wd, gates, local, count):
    """Every held expert computes every token (T <= ``HELD_DENSE_TOKENS``, so
    no more rows than the least tile an expert), weighted by its gate — zero
    where the token did not pick it. One batched product per projection:
    each expert's weights are read once, also when ``jax.vmap`` adds the
    serving slots as a batch axis (the decode program)."""
    T, D = tokens.shape
    weight = (jax.nn.one_hot(local, count, dtype=jnp.float32)      # [T, k, count]; a pick
              * gates[..., None]).sum(1)                            # outside [0, count) is 0
    x_e = jnp.broadcast_to(tokens[None], (count, T, D))
    out_e = _swiglu_experts(x_e, wg, wu, wd)                        # [count, T, D]
    return jnp.einsum("te,etd->td", weight, out_e.astype(jnp.float32))


def _held_sorted(tokens, wg, wu, wd, gates, local, is_held, counts, tile):
    """Picks sorted by expert into tiles of ``tile`` rows that belong to one
    expert each; a loop with a DYNAMIC trip count runs one SwiGLU per
    occupied tile. Static buffers are sized for the worst case (every pick on
    a held expert: no token is ever dropped), the work done is the occupied
    tiles: rows routed + at most ``tile - 1`` of padding per touched expert.
    Forward only (the loop's bound is data-dependent). Returns the output
    and the number of tiles run."""
    T, D = tokens.shape
    k = gates.shape[1]
    count = wg.shape[0]
    P = T * k
    max_tiles = -(-P // tile) + count
    key = jnp.where(is_held, local, count).reshape(P)               # non-held picks sort last
    order = jnp.argsort(key, stable=True)
    sorted_key = key[order]
    tiles_e = -(-counts // tile)                                    # [count]
    tile_end = jnp.cumsum(tiles_e)
    n_tiles = tile_end[-1]
    row0_e = (tile_end - tiles_e) * tile                            # first padded row of e
    start_e = jnp.cumsum(counts) - counts                           # first sorted pick of e
    safe = jnp.minimum(sorted_key, count - 1)
    rank = jnp.arange(P, dtype=jnp.int32) - start_e[safe]
    rows = max_tiles * tile
    row_sorted = jnp.where(sorted_key < count, row0_e[safe] + rank, rows)   # rows = dropped
    row_token = jnp.full((rows,), T, jnp.int32).at[row_sorted].set(
        (order // k).astype(jnp.int32), mode="drop")
    x_pad = jnp.concatenate([tokens, jnp.zeros((1, D), tokens.dtype)])[row_token]
    tile_expert = jnp.minimum(
        jnp.searchsorted(tile_end, jnp.arange(max_tiles, dtype=jnp.int32), side="right"),
        count - 1).astype(jnp.int32)

    def one_tile(i, out_pad):
        e = tile_expert[i]
        x_t = jax.lax.dynamic_slice_in_dim(x_pad, i * tile, tile)
        g = jax.lax.dynamic_index_in_dim(wg, e, keepdims=False)
        u = jax.lax.dynamic_index_in_dim(wu, e, keepdims=False)
        d = jax.lax.dynamic_index_in_dim(wd, e, keepdims=False)
        y = (jax.nn.silu(x_t @ g) * (x_t @ u)) @ d
        return jax.lax.dynamic_update_slice_in_dim(out_pad, y, i * tile, 0)

    out_pad = jax.lax.fori_loop(0, n_tiles, one_tile, jnp.zeros((rows, D), tokens.dtype))
    # Back to token order by a gather: pick (t, j) reads its padded row, the
    # picks on absent experts read the trailing zero row.
    row_of_pick = jnp.zeros((P,), jnp.int32).at[order].set(row_sorted).reshape(T, k)
    out_rows = jnp.concatenate([out_pad, jnp.zeros((1, D), out_pad.dtype)])[row_of_pick]
    return jnp.einsum("tk,tkd->td", gates, out_rows.astype(jnp.float32)), n_tiles


def moe_held_apply(
    expert_params: dict,
    router_kernel: jnp.ndarray,
    x: jnp.ndarray,
    *,
    top_k: int,
    scores: str = "softmax",
    normalize_gates: bool = True,
    held: Optional[tuple] = None,
    identity_experts: int = 0,
    selection_bias: Optional[jnp.ndarray] = None,
):
    """The part of a sparse expert MLP that the experts held here give, with no
    token dropped and only routed rows computed: ``(out [B, S, D], stats)``,
    where ``stats["picks"]`` is an int32 ``[count + 3]`` — the picks that
    landed on each held expert, then all picks of the call, the rows routed
    through the sorted tiles and the rows computed there (tiles run x tile
    rows; both 0 on the dense path).

    ``router_kernel`` ``[D, E]`` has the model's full width: every token is
    routed over all ``E`` experts (:func:`route_top_k`; ``selection_bias``
    ``[E]`` moves the picks and not the gates). ``expert_params``
    holds SwiGLU stacks ``[count, D, F]`` / ``[count, F, D]`` of the experts
    ``first .. first + count - 1`` (``held = (first, count)``; None = all
    ``E``, starting at 0: the Mixtral family under a cache). The result is
    ``sum_{e in top-k, e held} g_e F_e(x)``: what the absent experts would
    add is left out, and nothing stands in for them.

    The last ``identity_experts`` of the router's columns are ZERO-COMPUTE
    experts: one that is picked returns the token itself, so a pick adds
    ``g_e x`` — one multiply-add a token, no weights, computed wherever the
    token is. Held experts are then among the first ``E - identity_experts``,
    the result has the identity term in it, and ``stats["zero_picks"]`` (an
    int32 scalar) counts those picks; ``picks`` keeps its layout (its "all
    picks" counts them too). ``T <=
    HELD_DENSE_TOKENS`` tokens (a decode tick, also under ``jax.vmap`` over
    serving slots) go through every held expert in one batched product that
    reads each expert's weights once; more tokens are sorted by expert into
    tiles of :func:`held_tile_rows` rows and a loop runs the occupied tiles
    only (see :func:`_held_sorted`; forward only).
    """
    B, S, D = x.shape
    wg, wu, wd = expert_params["gate_proj"], expert_params["up_proj"], expert_params["down_proj"]
    E = router_kernel.shape[-1]
    real = E - int(identity_experts)                 # experts with weights
    first, count = (0, real) if held is None else (int(held[0]), int(held[1]))
    if wg.shape[0] != count or first < 0 or first + count > real:
        raise ValueError(f"held={held} does not match {wg.shape[0]} expert stacks of a "
                         f"router over {real} experts with weights")
    T = B * S
    tokens = x.reshape(T, D)
    with program_part("moe_router"):
        logits = tokens.astype(jnp.float32) @ router_kernel.astype(jnp.float32)
        gates, experts = route_top_k(logits, top_k, scores=scores,
                                     normalize_gates=normalize_gates,
                                     selection_bias=selection_bias)
        local = experts - first
        is_held = (local >= 0) & (local < count)
        counts = (jax.nn.one_hot(jnp.where(is_held, local, count), count + 1, dtype=jnp.int32)
                  .sum((0, 1))[:count])
    cdt = x.dtype
    wg, wu, wd = wg.astype(cdt), wu.astype(cdt), wd.astype(cdt)
    with program_part("moe_experts"):
        if T <= HELD_DENSE_TOKENS:
            out = _held_dense(tokens, wg, wu, wd, gates, jnp.where(is_held, local, -1), count)
            routed = computed = jnp.zeros((), jnp.int32)
        else:
            tile = held_tile_rows(T, top_k, E)
            out, n_tiles = _held_sorted(tokens, wg, wu, wd, gates, local, is_held, counts, tile)
            routed, computed = counts.sum(), n_tiles * tile
        stats = {}
        if identity_experts:
            is_zero = experts >= real
            out = out + (jnp.where(is_zero, gates, 0.0).sum(-1, keepdims=True)
                         * tokens.astype(jnp.float32))
            stats["zero_picks"] = is_zero.sum(dtype=jnp.int32)
    stats["picks"] = jnp.concatenate([counts, jnp.stack([jnp.int32(T * top_k), routed, computed])])
    return out.reshape(B, S, D).astype(x.dtype), stats
