"""Mesh-sliced tensor-parallel execution for the serving engine.

One serving replica stops being one chip and becomes one *slice*: a
disjoint group of ``tp`` devices carrying a tensor-parallel shard of the
params, the per-slot KV cache, and the LoRA adapter bank, behind the same
three warm executables. The design is pure GSPMD (PAPERS.md: sharding as
compiler annotations, not hand-written collectives) — nothing in the
engine's program *functions* changes; this module only decides WHERE every
array lives and re-jits the same functions with
``jax.jit(..., in_shardings=..., out_shardings=...)``:

* **Params** — the Megatron column/row layout from
  :mod:`accelerate_tpu.parallel.sharding` (the exact rules the training
  side already uses), so a model trained under ``tp=N`` serves under the
  same partitioning with zero re-derivation.
* **KV cache** — each slot's cache rows shard on the *heads* dimension
  (the first non-length feature axis divisible by ``tp``): attention is
  embarrassingly parallel over kv-heads, so prefill/decode run their
  per-head work locally and only the row-parallel output projection
  all-reduces, exactly like training TP.
* **AdapterBank** — each stacked LoRA leaf shards to match its base
  kernel's layout: column-parallel targets shard ``b`` on ``d_out``,
  row-parallel targets shard ``a`` on ``d_in``; ``scale`` replicates.
  Row writes (load/evict) stay a single compiled
  ``dynamic_update_slice`` per leaf, now writing into sharded stacks.
* **Slot membership, pos/tok/rng/done rows** — replicated DATA, same as
  single-chip: membership stays a traced argument, never a shape, so the
  zero-recompile discipline survives sharding unchanged.

The cross-slice story rides on the host: under a mesh, prefix-cache
blocks are ``device_get`` host arrays (chunk-aligned, exactly the
portable redistribution unit of "Memory-efficient array redistribution
through portable collective communication", PAPERS.md) — a block saved by
one slice restores into any other slice's shardings via the restore
program's ``in_shardings``, which is what makes a fleet-shared
:class:`~.scheduler.PrefixCache` and token-exact cross-slice failover
possible.

Entry points: :class:`SlicePlan` (carve ``jax.devices()`` into disjoint
``tp``-wide slices and build each slice's mesh) and :class:`SliceExec`
(derive every sharding and wrap the engine's program functions). The
engine's ``tp=`` / ``mesh=`` kwargs and ``ReplicaSet.from_mesh`` route
through here; see ``docs/usage_guides/serving.md``.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Sequence

__all__ = ["SlicePlan", "SliceExec", "validate_serving_mesh"]


def _non_tp_product(mesh) -> int:
    return math.prod(s for ax, s in mesh.shape.items() if ax != "tp")


def validate_serving_mesh(mesh):
    """A serving slice mesh is tensor-parallel only: every non-``tp`` axis
    must be trivial. dp-style replication belongs to :class:`ReplicaSet`
    (independent engines), not to one engine's mesh — a dp>1 engine mesh
    would silently waste chips decoding the same batch. Raises
    ``ValueError`` with the fix spelled out."""
    if "tp" not in mesh.shape:
        raise ValueError(
            f"serving mesh must carry a 'tp' axis (got axes {dict(mesh.shape)}); "
            "build it with SlicePlan.plan(tp=...) or MeshConfig(tp=...)")
    extra = _non_tp_product(mesh)
    if extra != 1:
        raise ValueError(
            "serving engine meshes are tensor-parallel only, but this mesh "
            f"has non-tp extent {extra} ({dict(mesh.shape)}). Use "
            "ReplicaSet.from_mesh(tp=..., num_slices=...) for data-parallel "
            "replicas — each replica is its own tp-only slice.")
    return mesh


@dataclass(frozen=True)
class SlicePlan:
    """Disjoint tensor-parallel device slices: ``slices[i]`` is the device
    tuple backing replica ``i``. Built by :meth:`plan`; each slice's
    :class:`~jax.sharding.Mesh` (canonical axis names, ``tp`` innermost,
    from :class:`~accelerate_tpu.parallel.mesh.MeshConfig`) comes from
    :meth:`build_mesh`."""

    tp: int
    slices: tuple

    @classmethod
    def plan(cls, tp: int, *, num_slices: Optional[int] = None,
             devices: Optional[Sequence] = None) -> "SlicePlan":
        """Carve ``devices`` (default ``jax.devices()``) into
        ``num_slices`` disjoint groups of ``tp`` consecutive devices
        (consecutive = ICI-adjacent under the topology-aware device order,
        so intra-slice collectives stay nearest-neighbor). ``num_slices``
        defaults to every full slice the device count affords."""
        import jax

        if tp < 1:
            raise ValueError(f"tp must be >= 1 (got {tp})")
        devices = list(devices if devices is not None else jax.devices())
        afford = len(devices) // tp
        if afford < 1:
            raise ValueError(
                f"tp={tp} needs at least {tp} devices (have {len(devices)})")
        n = afford if num_slices is None else int(num_slices)
        if n < 1 or n > afford:
            raise ValueError(
                f"num_slices={num_slices} out of range: {len(devices)} "
                f"devices afford at most {afford} slices of tp={tp}")
        groups = tuple(tuple(devices[i * tp:(i + 1) * tp]) for i in range(n))
        return cls(tp=tp, slices=groups)

    def __len__(self) -> int:
        return len(self.slices)

    def build_mesh(self, index: int):
        """The slice's tp-only mesh over the canonical logical axes (all
        axes present, non-tp sizes 1 — so every PartitionSpec in the
        framework can name any axis)."""
        from ..parallel.mesh import MeshConfig

        return MeshConfig(dp=1, tp=self.tp,
                          devices=self.slices[index]).build()

    def __repr__(self):
        ids = [[getattr(d, "id", d) for d in s] for s in self.slices]
        return f"SlicePlan(tp={self.tp}, slices={ids})"


class SliceExec:
    """Sharding derivation + program compilation for ONE slice.

    Owns the slice mesh and produces, for the engine's fixed state layout:

    * ``param_shardings(params)`` — TP PartitionSpecs via the training
      rules (:func:`~accelerate_tpu.parallel.sharding.infer_param_shardings`
      with a tp-size plugin).
    * ``state_shardings(state, cache_length_axes)`` — KV leaves sharded on
      their heads axis, every per-slot scalar row replicated.
    * ``block_shardings(...)`` / ``bank_shardings(bank)`` — the prefix-
      cache chunk block and stacked-LoRA layouts.
    * ``jit(fn, in_shardings, out_shardings, donate)`` — the thin
      ``jax.jit`` wrapper all three warm programs go through.

    Everything is computed once at engine construction; the per-call cost
    of the mesh path is zero beyond the collectives XLA schedules.
    """

    def __init__(self, mesh):
        validate_serving_mesh(mesh)
        self.mesh = mesh
        self.tp = int(mesh.shape["tp"])
        from jax.sharding import NamedSharding, PartitionSpec

        self._NS, self._P = NamedSharding, PartitionSpec
        #: replicated-over-the-slice placement (scalars, ids, masks, rng).
        self.replicated = NamedSharding(mesh, PartitionSpec())

    # -- params ----------------------------------------------------------
    def param_shardings(self, params):
        """NamedSharding pytree for the model params under this slice's
        ``tp`` axis — the same Megatron column/row rules training uses
        (``infer_param_shardings``), with FSDP off: a serving slice holds
        whole TP shards, resharding-on-load handles any training-time
        fsdp factor."""
        from ..parallel.sharding import infer_param_shardings
        from ..utils.dataclasses import TensorParallelPlugin

        return infer_param_shardings(
            params, self.mesh,
            tp_plugin=TensorParallelPlugin(tp_size=self.tp))

    # -- KV cache --------------------------------------------------------
    def heads_axis(self, template_shape: tuple, length_axis: int) -> Optional[int]:
        """The shard axis for one KV leaf, template-relative (the per-slot
        ``factory(1, max_len)`` leaf, e.g. ``[1, L, n_kv, hd]``): the
        first non-length axis of extent > 1 divisible by ``tp`` — kv-heads
        for every built-in family, head_dim as the fallback when GQA left
        too few kv-heads to split. None means the leaf replicates (and a
        tp slice buys no KV memory on it)."""
        if self.tp == 1:
            return None
        for ax, size in enumerate(template_shape):
            if ax == length_axis:
                continue
            if size > 1 and size % self.tp == 0:
                return ax
        return None

    def cache_leaf_shardings(self, template_leaves, length_axes,
                             with_slot_axis: bool):
        """Flat list of NamedShardings, one per KV leaf. ``template_leaves``
        are one page's (or one chunk block's) cache leaves (``eval_shape``
        structs are fine); ``with_slot_axis`` prepends the pool's leading
        page dimension (replicated — pages are data-parallel rows, never
        split across a slice's chips)."""
        out = []
        for leaf, lax in zip(template_leaves, length_axes):
            ax = self.heads_axis(tuple(leaf.shape), lax)
            if ax is None:
                out.append(self.replicated)
                continue
            shift = 1 if with_slot_axis else 0
            spec = [None] * (len(leaf.shape) + shift)
            spec[ax + shift] = "tp"
            out.append(self._NS(self.mesh, self._P(*spec)))
        return out

    def state_shardings(self, state, template_leaves, length_axes):
        """Shardings pytree matching the engine state dict exactly: the
        KV subtree (the page ``pool``) per-leaf heads-sharded, every other
        row (pos/tok/rng/done/adapter_idx — the membership-as-data
        arrays) replicated so host writes and mask flips stay
        collective-free. A pool leaf is ``[num_pages+1, P, heads, hd]``:
        the leading page axis is replicated, the heads axis split.

        A speculative engine's DRAFT page pool (``dpool``) deliberately
        lands in the replicated bucket with the scalar rows: the draft is
        small, its K-step scan is latency- not FLOP-bound, and keeping its
        params and KV whole on every chip means the draft scan runs with
        zero collectives — only the wide target verify pays (and benefits
        from) the tp sharding. This is the GSPMD composition the
        speculative ``_spec`` program relies on: replicated draft feeding
        a tp-sharded verify needs no new communication machinery.

        A QUANTIZED engine's per-page scale arrays (``pscale``/``dpscale``,
        ``[n_leaves, num_pages+1]`` f32) also fall through to the
        replicated bucket: one scalar per page is tiny, and replicating it
        lets the heads-sharded int8 page rows dequantize chip-locally —
        no code here needs to know the pool is quantized at all."""
        import jax

        kv_sh = jax.tree.unflatten(
            jax.tree.structure(state["pool"]),
            self.cache_leaf_shardings(template_leaves, length_axes,
                                      with_slot_axis=True))
        # Non-KV entries expand to a full subtree of replicated shardings
        # (not a prefix leaf): ``place`` tree-maps state against this
        # strictly, and the draft pool is a pytree, not a row.
        return {key: (kv_sh if key == "pool"
                      else jax.tree.map(lambda _: self.replicated,
                                        state[key]))
                for key in state}

    def block_shardings(self, cache_structure, template_leaves, length_axes):
        """Shardings for one prefix-cache chunk block (a per-slot cache
        slice of width C: same axes as the template, no slot axis)."""
        import jax

        return jax.tree.unflatten(
            cache_structure,
            self.cache_leaf_shardings(template_leaves, length_axes,
                                      with_slot_axis=False))

    # -- adapter bank ----------------------------------------------------
    def bank_shardings(self, bank):
        """Shardings pytree for ``bank.stacks``: each target module's
        stacked LoRA factors shard to MATCH the base kernel's Megatron
        layout (the same ``ShardingRules`` regexes) — column-parallel
        targets shard ``b``'s ``d_out``, row-parallel targets shard
        ``a``'s ``d_in``; everything else (and any non-divisible dim)
        replicates. The bank row axis (dim 0) is never split: a row write
        must stay one ``dynamic_update_slice`` per leaf."""
        import jax

        from ..adapters.lora import adapter_module_paths
        from ..parallel.sharding import ShardingRules

        rules = ShardingRules()
        shardings = jax.tree.map(lambda _: self.replicated, bank.stacks)
        for dotted in adapter_module_paths(bank.stacks):
            tp_dim = rules.tp_dim_for(dotted.replace(".", "/") + "/kernel")
            mod = _get_mod(bank.stacks, dotted)
            a_sh, b_sh = self.replicated, self.replicated
            if tp_dim == -1 and mod["b"].shape[2] % self.tp == 0:
                b_sh = self._NS(self.mesh, self._P(None, None, "tp"))
            elif tp_dim == -2 and mod["a"].shape[1] % self.tp == 0:
                a_sh = self._NS(self.mesh, self._P(None, "tp", None))
            tgt = _get_mod(shardings, dotted)
            tgt["a"], tgt["b"] = a_sh, b_sh
        return shardings

    # -- compilation -----------------------------------------------------
    def jit(self, fn, in_shardings, out_shardings, donate_argnums=()):
        """``jax.jit`` with this slice's placements — the only compile
        entry the mesh path uses, so every warm program records its
        shardings in one place. in_shardings entries may be pytree
        prefixes (a single NamedSharding covers a whole subtree)."""
        import jax

        return jax.jit(fn, in_shardings=in_shardings,
                       out_shardings=out_shardings,
                       donate_argnums=donate_argnums)

    def place(self, tree, shardings):
        """Initial distribution: ``device_put`` every leaf onto its
        sharding (reshards committed arrays — e.g. params prepared under
        a training fsdp x tp mesh land in this slice's serving layout)."""
        import jax

        return jax.tree.map(lambda x, s: jax.device_put(x, s),
                            tree, shardings)

    def per_chip_bytes(self, tree) -> int:
        """Largest per-device byte footprint of ``tree`` across the slice
        (max over shards per leaf — the HBM-planning number the per-chip
        KV math in docs/performance.md predicts)."""
        import jax

        total = 0
        for leaf in jax.tree.leaves(tree):
            shards = getattr(leaf, "addressable_shards", None)
            if shards:
                total += max(s.data.nbytes for s in shards)
            else:
                total += getattr(leaf, "nbytes", 0)
        return total


def _get_mod(tree, dotted: str):
    node = tree
    for part in dotted.split("."):
        node = node[part]
    return node
