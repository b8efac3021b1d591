"""Process/device/mesh state singletons.

Capability parity with the reference's ``state.py`` (reference:
src/accelerate/state.py — PartialState :114, AcceleratorState :815,
GradientState :1111), redesigned for JAX's execution model:

* The reference runs **one process per accelerator** and builds a flat
  torch.distributed world. JAX runs **one process per host**, each driving
  all its local chips; global arrays span hosts automatically. So
  ``num_processes`` here is the *host* count (what matters for data loading
  and logging), while ``num_devices`` is the chip count (what matters for
  sharding math). The reference conflates the two; we keep both.
* Backend selection (reference: state.py:709-766 picks nccl/xla/gloo/...)
  collapses to ``jax.distributed.initialize`` + a Mesh (parallel/mesh.py).
"""

from __future__ import annotations

import logging
import os
import threading
import warnings
from contextlib import contextmanager
from functools import cache, partial, wraps
from typing import Any, Callable, Optional

from .parallel.mesh import MeshConfig
from .utils.dataclasses import (
    DistributedInitKwargs,
    DistributedType,
    FullyShardedDataParallelPlugin,
    GradientAccumulationPlugin,
    PrecisionType,
)
from .utils.environment import env_var, parse_choice_from_env, parse_flag_from_env

logger = logging.getLogger(__name__)

# Only used on the host-platform testing path.
_CPU_DEVICE_COUNT_FLAG = "--xla_force_host_platform_device_count"


def is_initialized() -> bool:
    """Whether a PartialState has been constructed (reference: state.py:102)."""
    return PartialState._shared_state != {}


@cache
def _thread_resources():
    """jax's per-thread record of the active ``with mesh:`` context.

    jax 0.9.0 has no public accessor for it (``jax.sharding.get_mesh()``
    only covers ``jax.set_mesh``), so this keeps the alias deprecated since
    0.8.2 and silences only its import warning, once per process.
    """
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        from jax.interpreters.pxla import thread_resources
    return thread_resources


def current_mesh(mesh=None):
    """The ambient device mesh, or None.

    Resolution order: an explicit ``mesh`` argument, the mesh of an active
    ``with mesh:`` context, then `AcceleratorState`'s mesh. The single
    resolver used by every mesh-aware op (pipeline, ring attention, MoE) so
    they all agree on what "ambient" means.
    """
    if mesh is not None:
        return mesh
    phys = _thread_resources().env.physical_mesh
    if not phys.empty:
        return phys
    if AcceleratorState._shared_state:
        m = AcceleratorState().mesh
        if m is not None:
            return m
    return None


class PartialState:
    """One-per-process truth about the distributed environment (reference: state.py:114).

    Borg pattern (reference: state.py:153): every instance shares state, so any
    part of the framework can do ``PartialState()`` and see the same world.
    """

    _shared_state: dict[str, Any] = {}
    _known_attrs = [
        "_cpu",
        "backend",
        "device",
        "debug",
        "distributed_type",
        "fork_launched",
        "local_process_index",
        "num_processes",
        "process_index",
        "num_devices",
        "local_devices",
        "devices",
    ]

    def __init__(self, cpu: bool = False, **kwargs):
        self.__dict__ = self._shared_state
        if self.initialized:
            return

        import jax

        init_kwargs = kwargs.pop("init_kwargs", None)
        if cpu:
            # Host-platform execution for debugging/tests.
            os.environ.setdefault("JAX_PLATFORMS", "cpu")
        self._cpu = cpu
        self.debug = parse_flag_from_env(env_var("DEBUG"))
        self.fork_launched = parse_flag_from_env(env_var("FORK_LAUNCHED"))

        # Multi-host bring-up: the launcher exports coordinator env vars; on
        # GCE TPU pods jax.distributed.initialize() autodetects. Single-host
        # runs skip it entirely.
        self._maybe_init_distributed(init_kwargs)

        self.devices = jax.devices()
        self.local_devices = jax.local_devices()
        self.num_devices = len(self.devices)
        self.num_processes = jax.process_count()
        self.process_index = jax.process_index()
        self.local_process_index = self.process_index  # one process per host
        self.device = self.local_devices[0]
        self.backend = jax.default_backend()

        if self.backend == "tpu":
            self.distributed_type = DistributedType.TPU if self.num_devices > 1 else DistributedType.NO
        elif self.backend == "cpu" and self.num_devices > 1:
            self.distributed_type = DistributedType.MULTI_CPU
        elif self.backend in ("gpu", "cuda", "rocm"):
            self.distributed_type = DistributedType.MULTI_GPU if self.num_devices > 1 else DistributedType.NO
        else:
            self.distributed_type = DistributedType.NO

    def _maybe_init_distributed(self, init_kwargs: Optional[DistributedInitKwargs]):
        import jax

        coordinator = os.environ.get(env_var("COORDINATOR_ADDRESS"))
        n_proc = os.environ.get(env_var("NUM_PROCESSES"))
        proc_id = os.environ.get(env_var("PROCESS_ID"))
        want_init = coordinator is not None or (init_kwargs is not None and init_kwargs.coordinator_address)
        if init_kwargs is None:
            init_kwargs = DistributedInitKwargs()
        if want_init:
            try:
                jax.distributed.initialize(
                    coordinator_address=init_kwargs.coordinator_address or coordinator,
                    num_processes=init_kwargs.num_processes or (int(n_proc) if n_proc else None),
                    process_id=init_kwargs.process_id or (int(proc_id) if proc_id else None),
                    local_device_ids=init_kwargs.local_device_ids,
                    initialization_timeout=int(init_kwargs.initialization_timeout.total_seconds()),
                )
            except (RuntimeError, ValueError) as e:  # already initialized
                logger.debug("jax.distributed.initialize skipped: %s", e)

    # ------------------------------------------------------------------
    # Introspection
    # ------------------------------------------------------------------

    def __repr__(self):
        return (
            f"Distributed environment: {self.distributed_type}{('  Backend: ' + self.backend)}\n"
            f"Num processes (hosts): {self.num_processes}\n"
            f"Num devices (chips): {self.num_devices}\n"
            f"Process index: {self.process_index}\n"
            f"Device: {self.device}\n"
        )

    @staticmethod
    def _reset_state():
        """Reset singletons — for tests (reference: state.py:182)."""
        PartialState._shared_state.clear()

    @property
    def initialized(self) -> bool:
        """True once the singleton has been constructed in this process."""
        return self._shared_state != {}

    @property
    def use_distributed(self) -> bool:
        """True in any multi-device setting (reference: state.py:308)."""
        return self.num_devices > 1 or self.num_processes > 1

    @property
    def is_main_process(self) -> bool:
        """True on global rank 0."""
        return self.process_index == 0

    @property
    def is_local_main_process(self) -> bool:
        """True on each machine's rank-0 process."""
        return self.local_process_index == 0

    @property
    def is_last_process(self) -> bool:
        """True on the highest-ranked process."""
        return self.process_index == self.num_processes - 1

    # ------------------------------------------------------------------
    # Process control (reference: state.py:342-545)
    # ------------------------------------------------------------------

    def wait_for_everyone(self, tag: str = "accelerate_tpu_barrier"):
        """Cross-host barrier (reference: state.py:342 torch barrier -> here
        multihost_utils.sync_global_devices)."""
        if self.num_processes > 1:
            from jax.experimental import multihost_utils

            multihost_utils.sync_global_devices(tag)

    def _goes_first(self, is_main: bool, tag: str):
        if not is_main:
            self.wait_for_everyone(tag + "_pre")
        yield
        if is_main:
            self.wait_for_everyone(tag + "_pre")
        self.wait_for_everyone(tag + "_post")

    @contextmanager
    def main_process_first(self):
        """Main host runs the block first (reference: state.py:477)."""
        yield from self._goes_first(self.is_main_process, "main_first")

    @contextmanager
    def local_main_process_first(self):
        """Each machine's main process runs the block before its peers."""
        yield from self._goes_first(self.is_local_main_process, "local_main_first")

    def on_main_process(self, function: Callable = None):
        """Decorator: run only on the main process (reference: state.py:518)."""
        if function is None:
            return partial(self.on_main_process)

        @wraps(function)
        def execute_on_main_process(*args, **kwargs):
            if self.is_main_process:
                return function(*args, **kwargs)
            return None

        return execute_on_main_process

    def on_local_main_process(self, function: Callable = None):
        """Decorator: run only on each machine's main process."""
        if function is None:
            return partial(self.on_local_main_process)

        @wraps(function)
        def execute_on_local_main_process(*args, **kwargs):
            if self.is_local_main_process:
                return function(*args, **kwargs)
            return None

        return execute_on_local_main_process

    def on_process(self, function: Callable = None, process_index: int = None):
        """Decorator: run only on one specific rank."""
        if function is None:
            return partial(self.on_process, process_index=process_index)
        if process_index is None:
            process_index = 0

        @wraps(function)
        def execute_on_process(*args, **kwargs):
            if self.process_index == process_index:
                return function(*args, **kwargs)
            return None

        return execute_on_process

    def on_last_process(self, function: Callable):
        """Decorator: run only on the last process."""
        return self.on_process(function, process_index=self.num_processes - 1)

    @contextmanager
    def split_between_processes(self, inputs, apply_padding: bool = False):
        """Split a list/tuple/dict/array between host processes (reference: state.py:388).

        Each host receives its contiguous slice; with ``apply_padding`` the
        last items are repeated so every host gets the same count (needed when
        the result feeds ``gather``).
        """
        if self.num_processes == 1:
            yield inputs
            return
        length = len(inputs)
        num_samples_per_process = length // self.num_processes
        num_extras = length % self.num_processes

        start = num_samples_per_process * self.process_index + min(self.process_index, num_extras)
        end = start + num_samples_per_process + (1 if self.process_index < num_extras else 0)

        def _split_values(obj, start, end):
            if isinstance(obj, (list, tuple)):
                result = obj[start:end]
                if apply_padding and num_extras > 0:
                    target = num_samples_per_process + 1
                    while len(result) < target:
                        result = list(result) + [obj[-1]]
                return result
            elif isinstance(obj, dict):
                return {k: _split_values(v, start, end) for k, v in obj.items()}
            else:
                import numpy as np

                if hasattr(obj, "shape"):
                    result = obj[start:end]
                    if apply_padding and num_extras > 0:
                        target = num_samples_per_process + 1
                        if result.shape[0] < target:
                            pad = np.repeat(result[-1:], target - result.shape[0], axis=0)
                            result = np.concatenate([result, pad], axis=0)
                    return result
                return obj

        yield _split_values(inputs, start, end)

    def print(self, *args, **kwargs):
        """Print once per job (reference: state.py:557)."""
        if self.is_main_process:
            print(*args, **kwargs)

    def destroy_process_group(self):
        """Tear down the multi-host runtime (reference: state.py:333)."""
        import jax

        if self.num_processes > 1:
            try:
                jax.distributed.shutdown()
            except Exception:
                pass

    # Parity helper: the reference's `set_device` pins CUDA devices; JAX
    # processes own all local chips, so this is a documented no-op.
    def set_device(self):
        """Parity no-op: JAX addresses all local devices; nothing to pin."""
        return None


class AcceleratorState:
    """Adds mixed precision, mesh, and parallelism policy on top of PartialState
    (reference: state.py:815)."""

    _shared_state: dict[str, Any] = {}
    _known_attrs = PartialState._known_attrs + [
        "mixed_precision",
        "dynamo_plugin",
        "mesh",
        "mesh_config",
        "fsdp_plugin",
        "tp_plugin",
        "cp_plugin",
        "pp_plugin",
        "ep_plugin",
        "deepspeed_plugin",
        "megatron_lm_plugin",
    ]

    def __init__(
        self,
        mixed_precision: str = None,
        cpu: bool = False,
        mesh_config: Optional[MeshConfig] = None,
        fsdp_plugin=None,
        tp_plugin=None,
        cp_plugin=None,
        pp_plugin=None,
        ep_plugin=None,
        deepspeed_plugin=None,
        megatron_lm_plugin=None,
        _from_accelerator: bool = False,
        **kwargs,
    ):
        self.__dict__ = self._shared_state
        if self.initialized:
            if mixed_precision is not None and mixed_precision != self.mixed_precision:
                raise ValueError(
                    "AcceleratorState already initialized with mixed_precision="
                    f"{self.mixed_precision!r}; cannot re-init with {mixed_precision!r}. "
                    "Call AcceleratorState._reset_state() first (tests) or construct once."
                )
            return

        self._partial = PartialState(cpu, **kwargs)
        # Mirror PartialState attrs (reference: state.py:859-870 via __getattr__)
        if mixed_precision is None:
            mixed_precision = parse_choice_from_env(env_var("MIXED_PRECISION"), "no")
        mixed_precision = str(mixed_precision).lower()
        if mixed_precision not in PrecisionType.list():
            raise ValueError(f"mixed_precision must be one of {PrecisionType.list()}, got {mixed_precision}")
        self.mixed_precision = mixed_precision

        # Translate external-engine configs onto mesh policies
        # (reference rewrites distributed_type at state.py:902-921).
        self.deepspeed_plugin = deepspeed_plugin
        self.megatron_lm_plugin = megatron_lm_plugin
        if deepspeed_plugin is not None and fsdp_plugin is None:
            fsdp_plugin = deepspeed_plugin.to_fsdp_plugin()
        if megatron_lm_plugin is not None:
            mtp, mpp, mfsdp = megatron_lm_plugin.to_plugins()
            tp_plugin = tp_plugin or mtp
            pp_plugin = pp_plugin or mpp
            fsdp_plugin = fsdp_plugin or mfsdp

        self.fsdp_plugin = fsdp_plugin
        self.tp_plugin = tp_plugin
        self.cp_plugin = cp_plugin
        self.pp_plugin = pp_plugin
        self.ep_plugin = ep_plugin

        # Build the mesh. Copy the config so plugin translation never mutates
        # the caller's dataclass.
        import copy as _copy

        mesh_config = _copy.copy(mesh_config) if mesh_config is not None else MeshConfig.from_env()
        if fsdp_plugin is not None and mesh_config.fsdp == 1 and mesh_config.dp == -1:
            # FSDP default: shard over ALL devices on the fsdp axis.
            mesh_config.fsdp = -1
            mesh_config.dp = 1
        if fsdp_plugin is None and mesh_config.fsdp not in (0, 1):
            # The converse: a requested fsdp mesh axis (launch --fsdp N /
            # ACCELERATE_TPU_MESH_FSDP) implies the sharding policy — without
            # a plugin the axis would silently act as extra data parallelism
            # with fully replicated params.
            fsdp_plugin = FullyShardedDataParallelPlugin()
            self.fsdp_plugin = fsdp_plugin
        if tp_plugin is not None and tp_plugin.tp_size > 1:
            mesh_config.tp = tp_plugin.tp_size
        if cp_plugin is not None and cp_plugin.cp_size > 1:
            mesh_config.cp = cp_plugin.cp_size
        if pp_plugin is not None and pp_plugin.pp_size > 1:
            mesh_config.pp = pp_plugin.pp_size
        if ep_plugin is not None and ep_plugin.ep_size > 1:
            mesh_config.ep = ep_plugin.ep_size
        self.mesh_config = mesh_config
        self.mesh = mesh_config.build()

        # Rewrite distributed_type to reflect the governing policy.
        dt = self._partial.distributed_type
        if deepspeed_plugin is not None:
            dt = DistributedType.DEEPSPEED
        elif megatron_lm_plugin is not None:
            dt = DistributedType.MEGATRON_LM
        elif fsdp_plugin is not None:
            dt = DistributedType.FSDP
        elif tp_plugin is not None and tp_plugin.tp_size > 1:
            dt = DistributedType.TENSOR_PARALLEL
        elif pp_plugin is not None and pp_plugin.pp_size > 1:
            dt = DistributedType.PIPELINE_PARALLEL
        self.distributed_type = dt

    def __getattr__(self, name):
        # Delegate process-level attrs to PartialState (borg-shared).
        if name in PartialState._known_attrs or name in (
            "is_main_process",
            "is_local_main_process",
            "is_last_process",
            "use_distributed",
            "wait_for_everyone",
            "split_between_processes",
            "main_process_first",
            "local_main_process_first",
            "on_main_process",
            "on_local_main_process",
            "print",
            "destroy_process_group",
        ):
            return getattr(PartialState(), name)
        raise AttributeError(f"'AcceleratorState' object has no attribute '{name}'")

    def __repr__(self):
        return PartialState().__repr__() + f"Mixed precision type: {self.mixed_precision}\nMesh: {dict(self.mesh.shape)}\n"

    @property
    def initialized(self) -> bool:
        return self._shared_state != {}

    @staticmethod
    def _reset_state(reset_partial_state: bool = False):
        AcceleratorState._shared_state.clear()
        if reset_partial_state:
            PartialState._reset_state()


class GradientState:
    """Cross-object gradient-accumulation channel (reference: state.py:1111).

    Dataloaders self-register here so `end_of_dataloader`/`remainder` steer
    the sync decision; unlike the reference, the *device-side* accumulation
    counter lives in the jitted step's carry — this object only holds the
    host-side schedule.
    """

    _shared_state: dict[str, Any] = {}

    def __init__(self, gradient_accumulation_plugin: Optional[GradientAccumulationPlugin] = None):
        self.__dict__ = self._shared_state
        if not self.initialized:
            self.sync_gradients = True
            self.active_dataloader = None
            self.dataloader_references = [None]
            self.plugin_kwargs = (
                gradient_accumulation_plugin.to_kwargs() if gradient_accumulation_plugin is not None else {}
            )
            self._is_xla_gradients_synced = True  # parity attr; always in sync under jit
        if gradient_accumulation_plugin is not None and self.plugin_kwargs != gradient_accumulation_plugin.to_kwargs():
            self.plugin_kwargs = gradient_accumulation_plugin.to_kwargs()

    @property
    def num_steps(self) -> int:
        """Microbatches per optimizer update (accumulation window)."""
        return self.plugin_kwargs.get("num_steps", 1)

    @property
    def adjust_scheduler(self) -> bool:
        """Whether prepared schedulers should step only on sync boundaries."""
        return self.plugin_kwargs.get("adjust_scheduler", True)

    @property
    def sync_with_dataloader(self) -> bool:
        """Whether epoch ends force a sync regardless of window position."""
        return self.plugin_kwargs.get("sync_with_dataloader", True)

    @property
    def sync_each_batch(self) -> bool:
        """Force gradient sync on every microbatch (memory-saving mode)."""
        return self.plugin_kwargs.get("sync_each_batch", False)

    @property
    def initialized(self) -> bool:
        return GradientState._shared_state != {}

    @property
    def end_of_dataloader(self) -> bool:
        """True while the active loader is on its final batch."""
        if not self.in_dataloader:
            return False
        return self.active_dataloader.end_of_dataloader

    @property
    def remainder(self) -> int:
        """Tail samples beyond the last full global batch (-1 = unknown length)."""
        if not self.in_dataloader:
            return -1
        return self.active_dataloader.remainder

    @property
    def in_dataloader(self) -> bool:
        """True while any prepared loader is being iterated."""
        return self.active_dataloader is not None

    def __repr__(self):
        return (
            f"Sync Gradients: {self.sync_gradients}\n"
            f"At end of current dataloader: {self.end_of_dataloader}\n"
            f"Extra samples added: {self.remainder}\n"
            f"Gradient accumulation plugin: {self.plugin_kwargs}\n"
        )

    def _set_sync_gradients(self, sync_gradients: bool):
        self.sync_gradients = sync_gradients

    def _add_dataloader(self, dataloader):
        self.active_dataloader = dataloader
        self.dataloader_references.append(dataloader)

    def _remove_dataloader(self, dataloader):
        # Defensive: a GC'd loader generator may call this after a test reset
        # the singleton state.
        refs = self.__dict__.get("dataloader_references")
        if refs is None:
            return
        if dataloader in refs:
            refs.remove(dataloader)
        self.active_dataloader = refs[-1] if refs else None

    @staticmethod
    def _reset_state():
        GradientState._shared_state.clear()
