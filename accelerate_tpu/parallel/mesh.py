"""Logical device mesh construction.

TPU-native replacement for the reference's process-group machinery
(reference: src/accelerate/state.py:709-766 picks a torch.distributed backend
and creates one flat world group; Megatron then carves tp/pp/dp subgroups).
Here the *mesh is the backend*: one `jax.sharding.Mesh` with named axes
(dp, fsdp, tp, cp, ep, pp); collectives are XLA ops over mesh axes and ride
ICI (with an optional DCN-major axis for multi-slice).
"""

from __future__ import annotations

import math
import os
from dataclasses import dataclass, field, fields
from typing import Optional, Sequence

import numpy as np

from ..utils.constants import MESH_AXES
from ..utils.environment import env_var


@dataclass
class MeshConfig:
    """Declarative mesh shape over the canonical logical axes.

    Any axis set to -1 absorbs the remaining devices (at most one -1; if none
    is given and the product of the explicit axes does not cover all devices,
    ``dp`` absorbs the remainder). Axis sizes of 1 are kept in the mesh so
    every PartitionSpec in the framework can always name every axis.

    Multi-slice: ``dcn_axis`` names the logical axis laid out across slices
    (data-center network); it is made major in device order so that all other
    axes ride ICI. Defaults to "dp".
    """

    dp: int = -1
    fsdp: int = 1
    tp: int = 1
    cp: int = 1
    ep: int = 1
    pp: int = 1
    dcn_axis: str = "dp"
    devices: Optional[Sequence] = None       # explicit device list (tests)
    allow_split_physical_axes: bool = True
    #: ZeRO-1/2-style cross-replica optimizer-state sharding: Adam moments
    #: are partitioned over the dp (or, failing that, fsdp) axis so each
    #: replica holds 1/dp of the optimizer state. Consumed by
    #: ``Accelerator.prepare`` (parallel/sharding.py
    #: ``infer_opt_state_shardings``); also settable per-run via the FSDP
    #: plugin or ACCELERATE_TPU_MESH_ZERO_SHARDING=1.
    zero_sharding: bool = False

    @classmethod
    def from_env(cls) -> "MeshConfig":
        """Build from ACCELERATE_TPU_MESH_* env vars set by the launcher."""
        kwargs = {}
        for ax in MESH_AXES:
            v = os.environ.get(env_var(f"MESH_{ax.upper()}"))
            if v is not None:
                kwargs[ax] = int(v)
        if env_var("MESH_DCN_AXIS") in os.environ:
            kwargs["dcn_axis"] = os.environ[env_var("MESH_DCN_AXIS")]
        v = os.environ.get(env_var("MESH_ZERO_SHARDING"))
        if v is not None:
            kwargs["zero_sharding"] = v.lower() not in ("0", "false", "")
        return cls(**kwargs)

    def axis_sizes(self, num_devices: int) -> dict[str, int]:
        """Resolve -1 axes against the device count."""
        sizes = {ax: getattr(self, ax) for ax in MESH_AXES}
        unknown = [ax for ax, s in sizes.items() if s == -1]
        known = math.prod(s for s in sizes.values() if s != -1)
        if len(unknown) > 1:
            raise ValueError(f"At most one mesh axis may be -1, got {unknown}")
        if unknown:
            if num_devices % known != 0:
                raise ValueError(
                    f"{num_devices} devices not divisible by explicit axes product {known} "
                    f"({ {ax: s for ax, s in sizes.items() if s != -1} })"
                )
            sizes[unknown[0]] = num_devices // known
        else:
            total = math.prod(sizes.values())
            if total != num_devices:
                if num_devices % total == 0:
                    sizes["dp"] *= num_devices // total
                else:
                    raise ValueError(
                        f"Mesh axes product {total} does not divide device count {num_devices}"
                    )
        return sizes

    def build(self, devices: Optional[Sequence] = None):
        """Construct the `jax.sharding.Mesh`.

        On real TPU topologies, uses ``mesh_utils.create_device_mesh`` so that
        axis order maps onto the physical torus (minimizing ICI hops for the
        innermost axes: tp innermost, then cp/ep, fsdp, dp outermost — matching
        collective intensity: TP all-reduces every layer, DP once per step).
        For multi-process (multi-slice / multi-host DCN) jobs, uses
        ``create_hybrid_device_mesh`` with the dcn axis major.
        """
        import jax
        from jax.sharding import Mesh

        devices = list(devices if devices is not None else (self.devices or jax.devices()))
        sizes = self.axis_sizes(len(devices))
        if self.dcn_axis not in MESH_AXES:
            raise ValueError(f"dcn_axis must be one of {MESH_AXES}, got {self.dcn_axis!r}")
        # Device-order axis layout: slowest-varying first. dp outermost (least
        # communication), tp innermost (most communication -> nearest neighbors).
        axis_order = ("pp", "dp", "fsdp", "ep", "cp", "tp")
        shape = tuple(sizes[ax] for ax in axis_order)

        mesh_devices = None
        on_tpu = any("TPU" in str(getattr(d, "device_kind", "")) for d in devices[:1])
        if on_tpu:
            from jax.experimental import mesh_utils

            n_slices = getattr(devices[0], "num_slices", None)
            if jax.process_count() > 1 or (n_slices or 1) > 1:
                dcn_idx = axis_order.index(self.dcn_axis)
                n_groups = max(jax.process_count(), n_slices or 1)
                if shape[dcn_idx] % n_groups == 0 and n_groups > 1:
                    dcn_shape = [1] * len(shape)
                    dcn_shape[dcn_idx] = n_groups
                    ici_shape = list(shape)
                    ici_shape[dcn_idx] //= n_groups
                    mesh_devices = mesh_utils.create_hybrid_device_mesh(
                        ici_shape, dcn_shape, devices=devices,
                        allow_split_physical_axes=self.allow_split_physical_axes,
                    )
            if mesh_devices is None:
                try:
                    mesh_devices = mesh_utils.create_device_mesh(
                        shape, devices=devices,
                        allow_split_physical_axes=self.allow_split_physical_axes,
                    )
                except (ValueError, NotImplementedError, AssertionError) as e:
                    # A device subset that is not a box of the torus (e.g. a
                    # diagonal pair of a 2x2) has no topology-aware placement;
                    # fall back but say so — placement affects ICI hop counts.
                    import logging

                    logging.getLogger(__name__).warning(
                        "Topology-aware mesh placement failed (%s); using row-major device order.", e
                    )
                    mesh_devices = np.array(devices).reshape(shape)
        else:
            # Host-platform (CPU testing) / GPU: placement is moot.
            mesh_devices = np.array(devices).reshape(shape)

        return Mesh(mesh_devices, axis_order)

    def non_trivial_axes(self) -> dict[str, int]:
        return {ax: getattr(self, ax) for ax in MESH_AXES if getattr(self, ax) not in (1,)}

    def __str__(self):
        parts = ", ".join(f"{ax}={getattr(self, ax)}" for ax in MESH_AXES)
        return f"MeshConfig({parts})"


def make_mesh(config: MeshConfig | None = None, devices=None):
    """Convenience: build a mesh from a config (or an all-data-parallel default)."""
    return (config or MeshConfig()).build(devices=devices)


def mesh_batch_size_multiple(mesh) -> int:
    """Number of ways a global batch is split (product of batch-like axes + cp for tokens)."""
    from ..utils.constants import BATCH_AXES

    return math.prod(mesh.shape[ax] for ax in BATCH_AXES if ax in mesh.shape)
