"""Parameter-sharding policy engine: FSDP / tensor-parallel as PartitionSpecs.

This replaces the reference's torch-FSDP wrap (reference: accelerator.py:
1455-1570 — param flattening, all-gather forward, reduce-scatter backward
implemented in torch C++) and Megatron's mpu (reference: utils/megatron_lm.py)
with *declarative* GSPMD sharding: each parameter leaf gets a PartitionSpec
over the mesh axes; XLA inserts and schedules the all-gathers/reduce-scatters
that the torch runtimes hand-code.

Policies:
* FSDP: shard the largest divisible dimension of each (big-enough) leaf over
  the ``fsdp`` axis (the scaling-book "weight sharding" recipe — equivalent
  to ZeRO-3 when reshard_after_forward, ZeRO-1/2 when not).
* TP: regex path rules mapping Megatron column/row-parallel layouts onto the
  ``tp`` axis.
* Both compose: a leaf can be sharded on fsdp AND tp along different dims.
"""

from __future__ import annotations

import logging
import re
from typing import Any, Callable, Optional

import numpy as np

logger = logging.getLogger(__name__)


def resolve_remat_policy(name: str):
    """Map a remat-policy name to a jax.checkpoint policy.

    "dots": matmul outputs saveable (recompute only the cheap elementwise
    work — the standard training trade). "nothing": full recompute, minimum
    activation memory. "everything": save all (remat is a no-op; debugging).
    """
    import jax

    policies = {
        "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        "nothing": jax.checkpoint_policies.nothing_saveable,
        "everything": jax.checkpoint_policies.everything_saveable,
    }
    if name not in policies:
        raise ValueError(f"unknown remat_policy {name!r}; expected {sorted(policies)}")
    return policies[name]


def _leaf_path_str(path) -> str:
    """jax KeyPath -> 'a/b/c' string for regex matching."""
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        elif hasattr(k, "name"):
            parts.append(str(k.name))
        else:
            parts.append(str(k))
    return "/".join(parts)


def _spec_for_leaf(
    shape: tuple[int, ...],
    fsdp_size: int,
    tp_size: int,
    tp_dim: Optional[int],
    min_size_to_shard: int,
    prefer_last_dim_fsdp: bool = False,
    stack_axis: Optional[str] = None,
    stack_axis_size: int = 1,
):
    """Compose a PartitionSpec for one parameter leaf.

    A stacked-layout axis (``pp`` for pipeline stages, ``ep`` for experts)
    claims dim 0 first; TP (if a rule matched) claims ``tp_dim``; FSDP then
    shards the largest remaining dimension divisible by the fsdp axis size.
    """
    from jax.sharding import PartitionSpec

    ndim = len(shape)
    spec: list = [None] * ndim
    if stack_axis is not None and ndim > 0 and stack_axis_size > 1 and shape[0] % stack_axis_size == 0:
        spec[0] = stack_axis
    if tp_size > 1 and tp_dim is not None and ndim > 0:
        d = tp_dim % ndim
        if spec[d] is None and shape[d] % tp_size == 0:
            spec[d] = "tp"

    if fsdp_size > 1 and int(np.prod(shape) if ndim else 1) >= min_size_to_shard:
        # Candidate dims: not already claimed, divisible by fsdp axis.
        candidates = [
            d for d in range(ndim) if spec[d] is None and shape[d] % fsdp_size == 0 and shape[d] >= fsdp_size
        ]
        if candidates:
            order = sorted(candidates, key=lambda d: (shape[d], -d) if not prefer_last_dim_fsdp else (shape[d], d))
            best = order[-1]
            spec[best] = "fsdp"

    while spec and spec[-1] is None:
        spec.pop()
    return PartitionSpec(*spec)


class ShardingRules:
    """Ordered (regex, tp_dim|PartitionSpec) rules for tensor parallelism.

    Megatron mapping for transformers (net-new design; the reference delegates
    this entirely to Megatron's CUDA/mpu stack):
      * qkv / gate / up projections -> column parallel (shard output dim)
      * attention-out / down projection -> row parallel (shard input dim)
      * embeddings -> shard vocab (column)
      * layernorms / biases / scalars -> replicated
    """

    DEFAULT_TP_RULES: list[tuple[str, Any]] = [
        (r"(q_proj|k_proj|v_proj|qkv|query|key|value|wq|wk|wv)(/kernel|/w)?$", -1),   # column
        (r"(gate_proj|up_proj|fc1|intermediate|w1|w3|mlp_in)(/kernel|/w)?$", -1),      # column
        (r"(o_proj|out_proj|attn_out|dense_out|wo)(/kernel|/w)?$", -2),                # row
        (r"(down_proj|fc2|w2|mlp_out)(/kernel|/w)?$", -2),                             # row
        (r"(embed|embedding|wte|word_embeddings|lm_head)(/kernel|/embedding|/w)?$", -1),
        (r"(norm|ln|layernorm|layer_norm|scale|bias)", None),                          # replicate
    ]

    def __init__(self, rules: Optional[list[tuple[str, Any]]] = None, use_defaults: bool = True):
        self.rules = list(rules or [])
        if use_defaults:
            self.rules += self.DEFAULT_TP_RULES

    def tp_dim_for(self, path: str) -> Optional[int]:
        for pattern, dim in self.rules:
            if re.search(pattern, path, flags=re.IGNORECASE):
                return dim
        return None


# Parameter subtrees whose dim 0 is a stacked layout axis: pipeline stages
# (leaves [L, ...], models/llama.py PipelinedLlamaForCausalLM) and MoE experts
# (leaves [E, ...], ops/moe.py). Matched against the '/'-joined leaf path.
DEFAULT_STACK_RULES: list[tuple[str, str]] = [
    (r"(^|/)(blocks|stacked_layers|stages)(/|$)", "pp"),
    (r"(^|/)(experts|expert_)(/|$|\w)", "ep"),
]


def infer_param_shardings(
    params,
    mesh,
    fsdp_plugin=None,
    tp_plugin=None,
    pp_plugin=None,
    ep_plugin=None,
    extra_rules: Optional[list[tuple[str, Any]]] = None,
    stack_rules: Optional[list[tuple[str, str]]] = None,
):
    """Pytree of NamedSharding for every parameter leaf.

    The declarative core of the framework: given the mesh and the active
    plugins, decide where every parameter lives. Replaces
    reference:accelerator.py:1455-1570 (FSDP wrap) + Megatron layout code.
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    fsdp_size = mesh.shape.get("fsdp", 1)
    tp_size = mesh.shape.get("tp", 1)
    pp_size = mesh.shape.get("pp", 1) if pp_plugin is not None else 1
    ep_size = mesh.shape.get("ep", 1) if ep_plugin is not None else 1
    min_size = getattr(fsdp_plugin, "min_weight_size_to_shard", 2**14) if fsdp_plugin is not None else 2**62
    if fsdp_plugin is None:
        fsdp_size_eff = 1
    elif getattr(fsdp_plugin, "sharding_strategy", "FULL_SHARD") == "NO_SHARD":
        fsdp_size_eff = 1
    else:
        fsdp_size_eff = fsdp_size

    rules = ShardingRules(
        rules=(getattr(tp_plugin, "rules", None) or []) + (extra_rules or []),
        use_defaults=True,
    ) if (tp_plugin is not None and tp_size > 1) else None
    active_stack_rules = [
        (pat, ax)
        for pat, ax in (stack_rules if stack_rules is not None else DEFAULT_STACK_RULES)
        if {"pp": pp_size, "ep": ep_size}.get(ax, 1) > 1
    ]

    def _leaf_spec(path, leaf):
        shape = tuple(getattr(leaf, "shape", ()) or ())
        path_str = _leaf_path_str(path)
        tp_dim = rules.tp_dim_for(path_str) if rules is not None else None
        stack_axis = None
        for pat, ax in active_stack_rules:
            if re.search(pat, path_str, flags=re.IGNORECASE):
                stack_axis = ax
                break
        spec = _spec_for_leaf(
            shape, fsdp_size_eff, tp_size if rules is not None else 1, tp_dim, min_size,
            stack_axis=stack_axis,
            stack_axis_size={"pp": pp_size, "ep": ep_size}.get(stack_axis, 1),
        )
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(_leaf_spec, params)


def infer_opt_state_shardings(
    opt_state,
    mesh,
    params=None,
    param_shardings=None,
    axis: Optional[str] = None,
    min_size_to_shard: int = 2**11,
):
    """Pytree of NamedSharding for every optimizer-state leaf (ZeRO-1/2).

    The cross-replica weight-update sharding of arXiv:2004.13336, expressed
    declaratively (SimpleFSDP-style): moment tensors get the data-parallel
    axis on their largest divisible dimension, so each replica stores and
    updates 1/dp of the Adam state and GSPMD lowers the step to
    reduce-scatter(grads) -> shard-local update -> all-gather(params).

    Policy per leaf:
      * scalars / counts / leaves below ``min_size_to_shard`` -> replicated
        (beyond their param's own sharding, which is always inherited);
      * leaves that mirror a parameter (optax ``mu``/``nu`` subtrees carry
        the param path as a suffix) first inherit that param's spec, so
        tp/fsdp layouts compose;
      * the ``axis`` ("dp" by default, "fsdp" when the mesh has no dp) then
        claims the largest still-unclaimed dimension divisible by its size;
      * no such dimension -> the leaf keeps its inherited spec (replicated
        over the zero axis), counted in a one-line report.
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    if axis is None:
        axis = "dp" if mesh.shape.get("dp", 1) > 1 else "fsdp"
    axis_size = mesh.shape.get(axis, 1)

    # param path suffix -> (shape, spec): optax state trees (mu/nu, masked
    # chains, ...) wrap the param tree, so a state leaf's path ends with its
    # param's path. Longest suffix with a matching shape wins.
    suffix_specs: dict[tuple, tuple] = {}
    if params is not None and param_shardings is not None:
        s_leaves = jax.tree_util.tree_leaves(
            param_shardings, is_leaf=lambda x: hasattr(x, "spec")
        )
        p_leaves = jax.tree_util.tree_leaves_with_path(params)
        if len(p_leaves) == len(s_leaves):
            for (path, leaf), sh in zip(p_leaves, s_leaves):
                key = tuple(_leaf_path_str((k,)) for k in path)
                suffix_specs[key] = (tuple(getattr(leaf, "shape", ()) or ()), sh.spec)
    suffix_lens = sorted({len(k) for k in suffix_specs}, reverse=True)

    stats = {"sharded": 0, "inherited": 0, "small": 0, "indivisible": 0}
    fallbacks: list[str] = []

    def _leaf_spec(path, leaf):
        shape = tuple(getattr(leaf, "shape", ()) or ())
        ndim = len(shape)
        pkey = tuple(_leaf_path_str((k,)) for k in path)
        base: list = [None] * ndim
        for k in suffix_lens:
            if k <= len(pkey):
                hit = suffix_specs.get(pkey[-k:])
                if hit is not None and hit[0] == shape:
                    for d, ax in enumerate(hit[1][:ndim]):
                        base[d] = ax
                    break
        size = int(np.prod(shape)) if ndim else 1
        if ndim == 0 or size < min_size_to_shard or axis_size <= 1:
            stats["small"] += 1
            return NamedSharding(mesh, PartitionSpec(*_trim(base)))
        claimed = {a for e in base if e is not None
                   for a in (e if isinstance(e, tuple) else (e,))}
        if axis in claimed:
            stats["inherited"] += 1  # param already sharded over the zero axis
            return NamedSharding(mesh, PartitionSpec(*_trim(base)))
        candidates = [
            d for d in range(ndim)
            if base[d] is None and shape[d] % axis_size == 0 and shape[d] >= axis_size
        ]
        if not candidates:
            stats["indivisible"] += 1
            fallbacks.append(_leaf_path_str(path))
            return NamedSharding(mesh, PartitionSpec(*_trim(base)))
        best = max(candidates, key=lambda d: (shape[d], -d))
        base[best] = axis
        stats["sharded"] += 1
        return NamedSharding(mesh, PartitionSpec(*_trim(base)))

    def _trim(spec: list) -> list:
        out = list(spec)
        while out and out[-1] is None:
            out.pop()
        return out

    shardings = jax.tree_util.tree_map_with_path(_leaf_spec, opt_state)
    logger.info(
        "opt-state zero sharding over %r (size %d): %d sharded, %d inherited, "
        "%d scalar/small replicated, %d non-divisible replicated%s",
        axis, axis_size, stats["sharded"], stats["inherited"], stats["small"],
        stats["indivisible"],
        (" (" + ", ".join(fallbacks[:4])
         + (", ..." if len(fallbacks) > 4 else "") + ")") if fallbacks else "",
    )
    return shardings


def replicated_sharding(mesh):
    from jax.sharding import NamedSharding, PartitionSpec

    return NamedSharding(mesh, PartitionSpec())


def shard_params(params, shardings):
    """Place parameters according to their shardings (initial distribution).

    For multi-host, params must already be identical on every host (same seed
    init or loaded checkpoint); device_put with a NamedSharding then slices
    consistently.
    """
    import jax

    return jax.tree_util.tree_map(lambda p, s: jax.device_put(p, s), params, shardings)


def sharding_summary(shardings) -> dict[str, int]:
    """Histogram of PartitionSpecs, for logging/tests."""
    import jax

    counts: dict[str, int] = {}
    for leaf in jax.tree_util.tree_leaves(
        shardings, is_leaf=lambda x: hasattr(x, "spec")
    ):
        key = str(leaf.spec)
        counts[key] = counts.get(key, 0) + 1
    return counts
