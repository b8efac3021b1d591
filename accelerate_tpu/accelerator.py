"""The Accelerator: top-level orchestration API.

Capability parity with the reference's ``accelerator.py`` (reference:
src/accelerate/accelerator.py — Accelerator :160, prepare :1211, backward
:2164, accumulate :1046, clip_grad_norm_ :2292, gather_for_metrics :2408,
save_state :2915, load_state :3081, autocast :3383, profile :3423,
set_trigger/check_trigger :2198-2255, join_uneven_inputs :1091,
free_memory :3219).

TPU-native redesign (SURVEY.md §7 design stance): instead of mutating torch
modules and hooking autograd, ``prepare`` *captures* a pure apply-fn +
parameter pytree into compiled steps with explicit GSPMD sharding:

* ``model(params-free call)`` → jitted forward with the precision policy.
* ``accelerator.backward(loss_fn, batch)`` → jitted value_and_grad; the
  global-batch mean makes XLA emit the data-parallel gradient reduction, so
  there is no DDP/no_sync machinery — "not syncing" is simply not applying
  the optimizer (gradients accumulate in a device-side buffer).
* The fused fast path ``compile_train_step`` folds forward+backward+
  accumulate(scan)+clip+update into ONE executable with donated buffers —
  this is the path benchmarks use.
"""

from __future__ import annotations

import contextlib
import functools
import inspect
import math
import warnings
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .data_loader import DataLoaderShard, batch_sharding, prepare_data_loader, skip_first_batches
from .optimizer import AcceleratedOptimizer
from .parallel.mesh import MeshConfig
from .parallel.sharding import infer_param_shardings, replicated_sharding, shard_params, sharding_summary
from .observability.program_parts import program_part
from .precision import Policy, policy_for, scale_loss
from .scheduler import AcceleratedScheduler, LRScheduler
from .state import AcceleratorState, GradientState, PartialState
from .utils.dataclasses import (
    AutocastKwargs,
    DataLoaderConfiguration,
    DistributedInitKwargs,
    DistributedType,
    FullyShardedDataParallelPlugin,
    GradientAccumulationPlugin,
    GradScalerKwargs,
    JitConfig,
    PrecisionType,
    ProfileKwargs,
    ProjectConfiguration,
)
from .utils.operations import (
    broadcast,
    concatenate,
    convert_to_fp32,
    gather,
    gather_object,
    pad_across_processes,
    recursively_apply,
    reduce,
    send_to_device,
)


def _is_optax_tx(obj) -> bool:
    return hasattr(obj, "init") and hasattr(obj, "update") and not hasattr(obj, "apply")


def _is_flax_module(obj) -> bool:
    try:
        import flax.linen as nn

        return isinstance(obj, nn.Module)
    except ImportError:
        return False


def _is_dataloader_like(obj) -> bool:
    from collections.abc import Mapping

    return (
        hasattr(obj, "__iter__")
        and not isinstance(obj, (Mapping, list, tuple, str))
        and not _is_flax_module(obj)
    )


def _is_scheduler_like(obj) -> bool:
    return hasattr(obj, "step") and hasattr(obj, "get_last_lr")


class Model:
    """A model = pure apply_fn + parameter pytree.

    Construct from a flax module (``Model(module, params)``) or any pure
    function (``Model(apply_fn, params)`` with signature
    ``apply_fn(params, *inputs, rngs=None)``).
    """

    def __init__(self, module_or_fn, params, apply_kwargs: Optional[dict] = None):
        if _is_flax_module(module_or_fn):
            self.module = module_or_fn
            _apply = module_or_fn.apply

            def apply_fn(p, *args, **kwargs):
                variables = p if isinstance(p, dict) and "params" in p else {"params": p}
                return _apply(variables, *args, **kwargs)

            self.apply_fn = apply_fn
        else:
            self.module = None
            self.apply_fn = module_or_fn
        self.params = params
        self.apply_kwargs = apply_kwargs or {}


class AcceleratedModel:
    """A prepared model: sharded params + policy-compiled forward
    (the counterpart of the reference's wrapped torch module)."""

    def __init__(self, model: Model, policy: Policy, mesh, param_shardings, autocast_enabled: bool = True):
        self.module = model.module
        self.apply_fn = model.apply_fn
        self.params = model.params
        self.policy = policy if autocast_enabled else Policy()
        self.mesh = mesh
        self.param_shardings = param_shardings
        self._fwd_jit = None
        self.training = True

    def eval(self):
        """Switch to inference mode (dropout off via deterministic apply)."""
        self.training = False
        return self

    def train(self, mode: bool = True):
        """Switch training mode (reference nn.Module.train parity)."""
        self.training = mode
        return self

    def __call__(self, *args, **kwargs):
        """Jitted inference forward: params cast to compute dtype, outputs to
        fp32 (reference: autocast-wrap forward + fp32 outputs,
        accelerator.py:1389-1398).

        Non-array kwargs (flags like ``deterministic=True``) are treated as
        STATIC — each combination gets its own compiled executable — so
        Python control flow on them inside the module works.
        """
        import numpy as _np

        traced_kwargs = {k: v for k, v in kwargs.items() if isinstance(v, (jax.Array, _np.ndarray))}
        static_kwargs = {k: v for k, v in kwargs.items() if k not in traced_kwargs}
        try:
            static_key = tuple(sorted(static_kwargs.items()))
        except TypeError:  # unhashable static value: fall back to eager apply
            out = self.apply_fn(self.policy.cast_to_compute(self.params), *args, **kwargs)
            return self.policy.cast_to_output(out)

        if self._fwd_jit is None:
            self._fwd_jit = {}
        if static_key not in self._fwd_jit:
            apply_fn, policy = self.apply_fn, self.policy
            frozen_static = dict(static_kwargs)

            @jax.jit
            def fwd(params, args, traced):
                out = apply_fn(policy.cast_to_compute(params), *args, **traced, **frozen_static)
                return policy.cast_to_output(out)

            self._fwd_jit[static_key] = fwd
        return self._fwd_jit[static_key](self.params, args, traced_kwargs)

    def state_dict(self):
        """The current parameter pytree (reference state_dict parity)."""
        return self.params

    def load_state_dict(self, params):
        """Replace params, re-placing them into this model's shardings."""
        self.params = shard_params(params, self.param_shardings) if self.param_shardings is not None else params


class Accelerator:
    """Creates the distributed/mesh environment and prepares objects for it
    (reference: accelerator.py:160)."""

    def __init__(
        self,
        device_placement: bool = True,
        split_batches: bool = False,
        mixed_precision: PrecisionType | str | None = None,
        gradient_accumulation_steps: int = 1,
        cpu: bool = False,
        dataloader_config: Optional[DataLoaderConfiguration] = None,
        deepspeed_plugin=None,
        fsdp_plugin: Optional[FullyShardedDataParallelPlugin] = None,
        megatron_lm_plugin=None,
        tp_plugin=None,
        cp_plugin=None,
        pp_plugin=None,
        ep_plugin=None,
        mesh_config: Optional[MeshConfig] = None,
        rng_types: Optional[list] = None,
        log_with=None,
        project_dir: Optional[str] = None,
        project_config: Optional[ProjectConfiguration] = None,
        gradient_accumulation_plugin: Optional[GradientAccumulationPlugin] = None,
        step_scheduler_with_optimizer: bool = True,
        kwargs_handlers: Optional[list] = None,
        dynamo_backend=None,
        jit_config: Optional[JitConfig] = None,
        seed: int = 0,
    ):
        self.project_configuration = project_config or ProjectConfiguration(project_dir=project_dir)
        if project_dir is not None and self.project_configuration.project_dir is None:
            self.project_configuration.set_directories(project_dir)

        # Persistent compile cache: a relaunched trainer (preemption,
        # --max_restarts) skips recompilation entirely.
        from .utils.platforms import enable_compilation_cache

        enable_compilation_cache()

        # kwargs handlers (reference: accelerator.py:347-381)
        self.autocast_handler: Optional[AutocastKwargs] = None
        self.scaler_handler: Optional[GradScalerKwargs] = None
        self.init_handler: Optional[DistributedInitKwargs] = None
        self.profile_handler: Optional[ProfileKwargs] = None
        for handler in kwargs_handlers or []:
            if isinstance(handler, AutocastKwargs):
                self.autocast_handler = handler
            elif isinstance(handler, GradScalerKwargs):
                self.scaler_handler = handler
            elif isinstance(handler, DistributedInitKwargs):
                self.init_handler = handler
            elif isinstance(handler, ProfileKwargs):
                self.profile_handler = handler

        self.state = AcceleratorState(
            mixed_precision=str(mixed_precision) if mixed_precision is not None else None,
            cpu=cpu,
            mesh_config=mesh_config,
            fsdp_plugin=fsdp_plugin,
            tp_plugin=tp_plugin,
            cp_plugin=cp_plugin,
            pp_plugin=pp_plugin,
            ep_plugin=ep_plugin,
            deepspeed_plugin=deepspeed_plugin,
            megatron_lm_plugin=megatron_lm_plugin,
            _from_accelerator=True,
            init_kwargs=self.init_handler,
        )

        if gradient_accumulation_plugin is None:
            gradient_accumulation_plugin = GradientAccumulationPlugin(num_steps=gradient_accumulation_steps)
        self.gradient_state = GradientState(gradient_accumulation_plugin)

        self.device_placement = device_placement
        self.split_batches = split_batches
        self.dataloader_config = dataloader_config or DataLoaderConfiguration(split_batches=split_batches)
        self.step_scheduler_with_optimizer = step_scheduler_with_optimizer
        self.rng_types = rng_types or ["numpy", "python"]
        self.jit_config = jit_config or JitConfig()

        self.policy = policy_for(self.state.mixed_precision)
        self._use_loss_scaling = self.state.mixed_precision == "fp16"

        self._models: list[AcceleratedModel] = []
        self._optimizers: list[AcceleratedOptimizer] = []
        self._schedulers: list[AcceleratedScheduler] = []
        self._dataloaders: list[DataLoaderShard] = []
        self._custom_objects: list = []
        self.step = 0  # accumulation step counter (reference: accelerator.py:1020)
        self._rng_key = jax.random.PRNGKey(seed)
        from collections import OrderedDict

        from .serving.metrics import GatewayStats, ServingStats
        from .utils.profiling import PipelineStats

        # Shared across every prepared loader so step-time breakdowns
        # (data_wait_ms/stage_ms/queue depth) aggregate in one place.
        self.pipeline_stats = PipelineStats()
        # Shared by ServingEngine(accelerator=...) instances so serving
        # counters (TTFT, queue wait, tokens/sec, occupancy) surface through
        # log(include_serving=True) / serving_metrics() / profile().
        self.serving_stats = ServingStats()
        # Same sharing for ServingGateway(accelerator=...): HTTP counters
        # (requests by status class, streams, in-flight) surface through
        # log(include_gateway=True) / gateway_metrics() / profile().
        self.gateway_stats = GatewayStats()
        self._backward_cache: OrderedDict = OrderedDict()
        self._backward_cache_size = 16
        self._fused_cache: dict = {}
        self.flag_tensor = None
        self._log_with = log_with
        self.trackers: list = []
        from .logging import get_logger

        self.logger = get_logger(__name__)

    # ------------------------------------------------------------------
    # State passthrough (reference: accelerator.py properties)
    # ------------------------------------------------------------------

    @property
    def mesh(self):
        """The live jax.sharding.Mesh every prepared object is laid out over."""
        return self.state.mesh

    @property
    def distributed_type(self):
        """The governing strategy (reference DistributedType parity)."""
        return self.state.distributed_type

    @property
    def num_processes(self):
        """Process (host) count in the world."""
        return self.state.num_processes

    @property
    def process_index(self):
        """This process's global rank."""
        return self.state.process_index

    @property
    def local_process_index(self):
        """This process's rank on its machine."""
        return self.state.local_process_index

    @property
    def device(self):
        """This process's first addressable device."""
        return self.state.device

    @property
    def is_main_process(self):
        """True on global rank 0."""
        return self.state.is_main_process

    @property
    def is_local_main_process(self):
        """True on each machine's rank-0 process."""
        return self.state.is_local_main_process

    @property
    def is_last_process(self):
        """True on the highest-ranked process."""
        return self.state.is_last_process

    @property
    def mixed_precision(self):
        """The active precision policy name ("no"/"bf16"/"fp16"/"fp8")."""
        return self.state.mixed_precision

    @property
    def use_distributed(self):
        """True in any multi-process world."""
        return self.state.use_distributed

    @property
    def sync_gradients(self):
        """True when the current accumulation window ends at this step."""
        return self.gradient_state.sync_gradients

    @property
    def gradient_accumulation_steps(self):
        """Microbatches per optimizer update."""
        return self.gradient_state.num_steps

    @gradient_accumulation_steps.setter
    def gradient_accumulation_steps(self, num_steps: int):
        self.gradient_state.plugin_kwargs.update({"num_steps": num_steps})

    @property
    def even_batches(self):
        """Default tail-padding behavior for prepared loaders (reference: :571)."""
        return self.dataloader_config.even_batches

    @even_batches.setter
    def even_batches(self, value: bool):
        self.dataloader_config.even_batches = value

    @property
    def project_dir(self):
        """Root directory for checkpoints/logs (ProjectConfiguration)."""
        return self.project_configuration.project_dir

    def on_main_process(self, function):
        """Decorator: run ``function`` on global rank 0 only (reference: :2665)."""
        return PartialState().on_main_process(function)

    def on_local_main_process(self, function):
        """Decorator: run ``function`` on each machine's rank 0 only."""
        return PartialState().on_local_main_process(function)

    def on_process(self, function=None, process_index=None):
        """Decorator: run ``function`` on one specific rank only."""
        return PartialState().on_process(function, process_index=process_index)

    def wait_for_everyone(self):
        """Cross-process barrier (reference: :2810)."""
        PartialState().wait_for_everyone()

    def print(self, *args, **kwargs):
        """print() on the main process only."""
        PartialState().print(*args, **kwargs)

    def split_between_processes(self, inputs, apply_padding: bool = False):
        """Context yielding this process's slice of ``inputs`` (reference: :740)."""
        return PartialState().split_between_processes(inputs, apply_padding=apply_padding)

    # ------------------------------------------------------------------
    # prepare (reference: accelerator.py:1211)
    # ------------------------------------------------------------------

    def prepare(self, *args, device_placement=None):
        """Prepare models/optimizers/dataloaders/schedulers in one call,
        returning them in the same order (reference: accelerator.py:1211).

        Models may be passed as a :class:`Model`, or as a flax module
        followed immediately by its params pytree (the pair is consumed as
        one model).
        """
        # Fuse (module, params) adjacent pairs into Model objects.
        from collections.abc import Mapping

        fused_args: list = []
        skip_next = False
        for i, obj in enumerate(args):
            if skip_next:
                skip_next = False
                continue
            # Params may be dicts or flax FrozenDicts (any Mapping).
            if _is_flax_module(obj) and i + 1 < len(args) and isinstance(args[i + 1], Mapping):
                fused_args.append(Model(obj, args[i + 1]))
                skip_next = True
            else:
                fused_args.append(obj)

        prepared = [self._prepare_one(obj) for obj in fused_args]

        # Bind optimizers to models in order of appearance: the k-th optimizer
        # pairs with the k-th model (reference pairs them implicitly via the
        # params the user constructed the optimizer with).
        models = [p for p in prepared if isinstance(p, AcceleratedModel)]
        opts_in_order = [p for p in prepared if isinstance(p, AcceleratedOptimizer)]
        for k, opt in enumerate(opts_in_order):
            if opt._model is None and models:
                bound = models[k] if k < len(models) else models[0]
                opt._model = bound
                if opt.opt_state is None:
                    opt.init_state(bound.params)

        # Bind schedulers to optimizers (reference: prepare_scheduler :2123).
        opts = [p for p in prepared if isinstance(p, AcceleratedOptimizer)]
        for sched in (p for p in prepared if isinstance(p, AcceleratedScheduler)):
            if not sched.optimizers and opts:
                sched.optimizers = opts

        return prepared[0] if len(prepared) == 1 else tuple(prepared)

    def _prepare_one(self, obj):
        if isinstance(obj, (AcceleratedModel, AcceleratedOptimizer, AcceleratedScheduler, DataLoaderShard)):
            return obj
        if isinstance(obj, Model):
            return self.prepare_model(obj)
        if _is_optax_tx(obj):
            return self.prepare_optimizer(obj)
        if _is_scheduler_like(obj):
            return self.prepare_scheduler(obj)
        if _is_dataloader_like(obj):
            return self.prepare_data_loader(obj)
        return obj

    def prepare_model(self, model: Model, device_placement: Optional[bool] = None, evaluation_mode: bool = False):
        """Shard + place model params per the active parallelism policy
        (reference: accelerator.py:1349)."""
        if not isinstance(model, Model):
            raise TypeError(
                "prepare_model expects an accelerate_tpu.Model (apply_fn/module + params); "
                f"got {type(model)}. Pass Model(module, params)."
            )
        shardings = infer_param_shardings(
            model.params,
            self.mesh,
            fsdp_plugin=self.state.fsdp_plugin,
            tp_plugin=self.state.tp_plugin,
            pp_plugin=self.state.pp_plugin,
            ep_plugin=self.state.ep_plugin,
        )
        if device_placement if device_placement is not None else self.device_placement:
            model.params = shard_params(model.params, shardings)
        autocast_enabled = self.autocast_handler.enabled if self.autocast_handler is not None else True
        wrapped = AcceleratedModel(model, self.policy, self.mesh, shardings, autocast_enabled=autocast_enabled)
        if evaluation_mode:
            wrapped.eval()
        self._models.append(wrapped)
        self.logger.debug("Param sharding summary: %s", sharding_summary(shardings))
        return wrapped

    def prepare_optimizer(self, tx, device_placement: Optional[bool] = None):
        """Wrap an optax transformation (reference: prepare_optimizer :2082).

        With ``fsdp_plugin.cpu_offload=True`` (or a DeepSpeed config naming a
        cpu offload device — reference: accelerator.py:1806-1809) the
        optimizer state lives in pinned host memory between steps
        (parallel/host_offload.py).
        """
        fsdp = self.state.fsdp_plugin
        offload = bool(fsdp is not None and fsdp.cpu_offload)
        if offload:
            from .parallel.host_offload import supports_host_memory

            if not supports_host_memory():
                warnings.warn(
                    "fsdp_plugin.cpu_offload=True but this backend exposes no "
                    "pinned_host memory space; optimizer state stays in device memory.",
                    stacklevel=2,
                )
                offload = False
        opt = AcceleratedOptimizer(
            tx,
            scaler_kwargs=self.scaler_handler,
            use_loss_scaling=self._use_loss_scaling,
            mesh=self.mesh,
            offload_to_host=offload,
            zero_sharding=self.zero_sharding,
        )
        self._optimizers.append(opt)
        return opt

    @property
    def zero_sharding(self) -> bool:
        """Whether optimizer state is ZeRO-sharded over the dp/fsdp axis —
        set on :class:`MeshConfig`, the FSDP plugin, or via DeepSpeed
        ``zero_stage >= 1`` (utils/dataclasses.py)."""
        mesh_cfg = getattr(self.state, "mesh_config", None)
        fsdp = self.state.fsdp_plugin
        return bool(
            getattr(mesh_cfg, "zero_sharding", False)
            or (fsdp is not None and getattr(fsdp, "zero_sharding", False))
        )

    def prepare_scheduler(self, scheduler):
        wrapped = AcceleratedScheduler(
            scheduler,
            optimizers=[],
            step_with_optimizer=self.step_scheduler_with_optimizer,
            split_batches=self.dataloader_config.split_batches,
        )
        self._schedulers.append(wrapped)
        return wrapped

    def prepare_data_loader(self, data_loader, device_placement=None, slice_fn_for_dispatch=None):
        cfg = self.dataloader_config
        dl = prepare_data_loader(
            data_loader,
            mesh=self.mesh,
            split_batches=cfg.split_batches,
            put_on_device=device_placement if device_placement is not None else self.device_placement,
            rng_types=self.rng_types,
            dispatch_batches=cfg.dispatch_batches,
            even_batches=cfg.even_batches,
            use_seedable_sampler=cfg.use_seedable_sampler,
            data_seed=cfg.data_seed,
            non_blocking=cfg.non_blocking,
            use_stateful_dataloader=cfg.use_stateful_dataloader,
            prefetch_size=cfg.prefetch_size,
            async_prefetch=cfg.async_prefetch,
            num_workers=cfg.num_workers,
        )
        dl.pipeline_stats = self.pipeline_stats
        self._dataloaders.append(dl)
        return dl

    def input_pipeline_metrics(self) -> dict:
        """Aggregated input-pipeline breakdown over every prepared loader:
        ``data_wait_ms`` (step loop blocked on data), ``stage_ms`` (collate +
        host→device), ``queue_depth``. Log it alongside loss — a rising
        ``data_wait_ms`` is MFU leaking to the host input path."""
        return self.pipeline_stats.summary()

    def serving_metrics(self) -> dict:
        """Aggregated serving-engine counters (TTFT, queue wait, decode
        tokens/sec, slot occupancy, batch efficiency) for every
        ``ServingEngine(accelerator=self)``; see
        ``serving.metrics.ServingStats.summary``."""
        return self.serving_stats.summary()

    def gateway_metrics(self) -> dict:
        """Aggregated HTTP-gateway counters (requests by status class,
        SSE streams, in-flight) for every
        ``ServingGateway(accelerator=self)``; see
        ``serving.metrics.GatewayStats.summary``."""
        return self.gateway_stats.summary()

    # ------------------------------------------------------------------
    # Gradient accumulation (reference: accelerator.py:1020-1090)
    # ------------------------------------------------------------------

    def _do_sync(self):
        if self.gradient_state.sync_with_dataloader and self.gradient_state.end_of_dataloader:
            self.step = 0
            self.gradient_state._set_sync_gradients(True)
        else:
            self.step += 1
            sync = (self.step % self.gradient_state.num_steps) == 0
            self.gradient_state._set_sync_gradients(sync or self.gradient_state.sync_each_batch)

    @contextlib.contextmanager
    def accumulate(self, *models):
        """Context marking one microbatch (reference: accumulate :1046).

        Unlike torch DDP there is no communication to skip — "not syncing"
        just means the optimizer defers its update.
        """
        self._do_sync()
        yield

    @contextlib.contextmanager
    def no_sync(self, model=None):
        """Parity context (reference: :931): forces accumulation for the block."""
        prev = self.gradient_state.sync_gradients
        self.gradient_state._set_sync_gradients(False)
        try:
            yield
        finally:
            self.gradient_state._set_sync_gradients(prev)

    @contextlib.contextmanager
    def join_uneven_inputs(self, joinables, even_batches=None):
        """Train/evaluate on uneven inputs (reference: :1091).

        Overrides ``even_batches`` on every prepared HOST-side map-style
        dataloader's batch sampler for the context's duration (reference
        behavior: :1136-1157), plus the config default for loaders prepared
        inside the context. Device-staged loaders are deliberately skipped
        (with a warning): their per-batch multi-host dispatch would deadlock
        on an uneven tail. ``joinables`` is accepted for API parity; there is no
        torch Join to wrap — gradient synchronization here happens inside
        compiled steps over global arrays, which REQUIRE every process to
        dispatch the same programs. The supported uneven pattern is
        therefore: iterate locally (per-process batch counts may differ —
        run no per-batch collectives), then aggregate once after the loop
        with ``gather_for_metrics(..., use_gather_object=True)`` /
        ``pad_across_processes``. Exercised by
        ``test_utils/scripts/test_script.py::check_uneven_tail`` in the
        real multi-process lane.
        """
        restore: list[tuple] = []
        prev_default = self.dataloader_config.even_batches
        n_loaders_at_entry = len(self._dataloaders)
        if even_batches is not None:
            restore.append((self.dataloader_config, prev_default))
            self.dataloader_config.even_batches = even_batches
            untoggleable = 0
            for dl in self._dataloaders:
                sampler = getattr(dl.base_dataloader, "batch_sampler", None)
                if hasattr(sampler, "even_batches") and not getattr(dl, "stage_to_device", False):
                    restore.append((sampler, sampler.even_batches))
                    sampler.even_batches = even_batches
                elif self.num_processes > 1:
                    # Device-staged loaders are NOT toggled: uneven tails mean
                    # per-process batch counts differ, and every device batch
                    # implies a multi-host dispatch all processes must join —
                    # toggling would trade padding for a distributed deadlock.
                    # Prepare the eval loader with device_placement=False to
                    # opt in (see the contract above). Dispatcher/iterable
                    # loaders have nothing to toggle (reference warns too,
                    # :1150-1155). Single-process loaders never pad, so the
                    # override is vacuously in effect for them.
                    untoggleable += 1
            if untoggleable:
                warnings.warn(
                    f"even_batches override skipped {untoggleable} prepared "
                    f"loader(s): device-staged loaders would deadlock on uneven "
                    f"tails (prepare with device_placement=False to opt in); "
                    f"dispatcher/iterable loaders have nothing to toggle."
                )
        try:
            yield
        finally:
            for obj, prev in restore:
                obj.even_batches = prev
            if even_batches is not None:
                # Loaders prepared INSIDE the context baked the override into
                # their samplers; restore them to the pre-context default so
                # the toggle really is scoped to the context's duration.
                for dl in self._dataloaders[n_loaders_at_entry:]:
                    sampler = getattr(dl.base_dataloader, "batch_sampler", None)
                    if hasattr(sampler, "even_batches"):
                        sampler.even_batches = prev_default

    # ------------------------------------------------------------------
    # backward (reference: accelerator.py:2164)
    # ------------------------------------------------------------------

    def next_rng_key(self):
        """Split and return a fresh PRNG key from the accelerator's stream."""
        self._rng_key, sub = jax.random.split(self._rng_key)
        return sub

    def _loss_fn_accepts_rng(self, loss_fn) -> bool:
        try:
            sig = inspect.signature(loss_fn)
            return len(sig.parameters) >= 3
        except (TypeError, ValueError):
            return False

    def backward(self, loss_fn: Callable, batch, model: Optional[AcceleratedModel] = None,
                 optimizer: Optional[AcceleratedOptimizer] = None, **kwargs):
        """Compute gradients of ``loss_fn(params, batch[, rng])`` and
        accumulate them (reference: backward :2164).

        * divides the loss by ``gradient_accumulation_steps`` (reference :2186)
        * applies the compute-dtype policy to params (autocast equivalent)
        * scales the loss under fp16 (reference: scaler.scale(loss).backward())
        * data-parallel reduction is implicit: the loss averages over the
          global sharded batch, XLA inserts the psum in the backward pass.

        Returns the (unscaled, fp32) loss value.
        """
        model = model or (self._models[0] if self._models else None)
        optimizer = optimizer or (self._optimizers[0] if self._optimizers else None)
        if model is None or optimizer is None:
            raise RuntimeError("backward() needs a prepared model and optimizer (call prepare first).")
        if optimizer._model is None:
            optimizer._model = model
        elif optimizer._model is not model:
            raise RuntimeError(
                "This optimizer is bound to a different model than the one passed to backward(). "
                "Pass matching model=/optimizer= arguments (prepare binds the k-th optimizer "
                "to the k-th model)."
            )
        if optimizer.opt_state is None:
            optimizer.init_state(model.params)

        # Key by the function object itself (prevents GC id-reuse; closures
        # with identical code but different captured values must NOT share a
        # compiled step) AND the accumulation count baked into it. The cache
        # is capped: passing a fresh lambda every step recompiles each time —
        # reuse one loss_fn object in hot loops.
        key = (loss_fn, self.gradient_state.num_steps)
        if key not in self._backward_cache:
            policy = self.policy
            accepts_rng = self._loss_fn_accepts_rng(loss_fn)
            num_steps = self.gradient_state.num_steps

            def compute_loss(params, batch, rng, scale):
                cparams = policy.cast_to_compute(params)
                out = loss_fn(cparams, batch, rng) if accepts_rng else loss_fn(cparams, batch)
                loss, aux = out if isinstance(out, tuple) else (out, None)
                raw_loss = loss
                if num_steps > 1:
                    loss = loss / num_steps
                if scale is not None:
                    loss = loss * scale.astype(loss.dtype)
                return loss.astype(jnp.float32), (raw_loss, aux)

            grad_fn = jax.value_and_grad(compute_loss, has_aux=True)

            @jax.jit
            def backward_step(params, batch, rng, scale):
                (_, (raw_loss, aux)), grads = grad_fn(params, batch, rng, scale)
                return raw_loss, aux, grads

            self._backward_cache_put(key, backward_step)

        scale = optimizer.loss_scale.scale if optimizer.loss_scale is not None else None
        raw_loss, aux, grads = self._backward_cache_get(key)(model.params, batch, self.next_rng_key(), scale)
        optimizer.accumulate_grads(grads)
        self._last_aux = aux
        return raw_loss

    def _backward_cache_put(self, key, step):
        """Insert a compiled backward step, evicting the LEAST RECENTLY USED
        entry at capacity (hits refresh recency via ``move_to_end``, so a hot
        loss_fn is never evicted by churn in rarely-used ones)."""
        if len(self._backward_cache) >= self._backward_cache_size:
            self._backward_cache.popitem(last=False)
        self._backward_cache[key] = step

    def _backward_cache_get(self, key):
        self._backward_cache.move_to_end(key)
        return self._backward_cache[key]

    # ------------------------------------------------------------------
    # Gradient clipping (reference: accelerator.py:2292)
    # ------------------------------------------------------------------

    @staticmethod
    @jax.jit
    def _clip_by_global_norm(grads, max_norm, inv_scale):
        """Unscale (fp16) + clip by global norm; jit-cached across calls."""
        grads = jax.tree_util.tree_map(
            lambda g: (g.astype(jnp.float32) * inv_scale).astype(g.dtype), grads
        )
        leaves = jax.tree_util.tree_leaves(grads)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in leaves))
        factor = jnp.minimum(1.0, max_norm / (gnorm + 1e-6))
        return jax.tree_util.tree_map(lambda g: (g * factor).astype(g.dtype), grads), gnorm

    def clip_grad_norm_(self, parameters=None, max_norm: float = 1.0, norm_type: float = 2.0):
        """Clip accumulated grads by global norm; returns the pre-clip norm of
        the first clipped optimizer (reference: clip_grad_norm_ :2292 —
        FSDP/XLA variants collapse into one jitted global-norm here, since
        grads are already global arrays). fp16 grads are unscaled first
        (reference: unscale_gradients :2264) and the optimizer is told not to
        unscale again at step()."""
        first_norm = None
        for opt in self._optimizers:
            if opt.acc_grads is None:
                continue
            if opt.loss_scale is not None and not opt._grads_already_unscaled:
                inv_scale = 1.0 / opt.loss_scale.scale
                opt._grads_already_unscaled = True
            else:
                inv_scale = jnp.asarray(1.0, jnp.float32)
            opt.acc_grads, gnorm = Accelerator._clip_by_global_norm(
                opt.acc_grads, jnp.asarray(max_norm, jnp.float32), inv_scale
            )
            if first_norm is None:
                first_norm = gnorm
        return first_norm

    def clip_grad_value_(self, parameters=None, clip_value: float = 1.0):
        """Clip accumulated grads elementwise (reference: :2344)."""
        for opt in self._optimizers:
            if opt.acc_grads is None:
                continue
            opt.acc_grads = jax.tree_util.tree_map(
                lambda g: jnp.clip(g, -clip_value, clip_value), opt.acc_grads
            )

    # ------------------------------------------------------------------
    # Fused train step (the fast path)
    # ------------------------------------------------------------------

    def compile_train_step(
        self,
        loss_fn: Callable,
        model: Optional[AcceleratedModel] = None,
        optimizer: Optional[AcceleratedOptimizer] = None,
        accumulation_steps: Optional[int] = None,
        max_grad_norm: Optional[float] = None,
        donate: bool = True,
        grad_reduce_dtype=None,
    ) -> Callable:
        """Build ONE jitted step: grads (+scan over microbatches), clip,
        optimizer update, loss-scale update — with buffer donation.

        If ``accumulation_steps > 1``, the step expects each batch leaf to
        have a leading ``[accumulation_steps, ...]`` microbatch dimension and
        runs a ``lax.scan`` over it (compiler-friendly accumulation — the
        GradientState bookkeeping the reference does in Python happens inside
        the executable).

        Returns ``step(batch) -> metrics`` operating on the bound model/
        optimizer state in-place.

        ``grad_reduce_dtype`` (e.g. ``jnp.bfloat16``) differentiates with
        respect to the compute-cast parameters so gradients — and therefore
        the implicit cross-replica all-reduce GSPMD inserts over the dp
        axis — stay in that dtype, halving gradient communication volume
        vs fp32 (the reference's DDP ``bf16_compress_hook``,
        examples/by_feature/ddp_comm_hook.py; there it compresses the
        bucket, here the reduction itself runs narrow). Gradients are
        upcast to fp32 AFTER the reduction for clipping/optimizer. The
        cross-replica sum runs in the narrow dtype — the same accuracy
        trade the torch hook makes; leave None for fp32 reductions.

        Because the step differentiates with respect to the cast params,
        ``grad_reduce_dtype`` is also the FORWARD compute dtype when it
        differs from the mixed-precision policy's (e.g.
        ``mixed_precision='no'`` with ``grad_reduce_dtype=bf16`` runs the
        forward in bf16, a wider accuracy change than the torch hook's
        communication-only compression) — a warning is emitted for such
        mismatches. With matching dtypes (bf16/bf16, fp16/fp16) it is
        communication-narrowing only.

        With ``fsdp_plugin.activation_checkpointing=True`` the whole loss
        computation is rematerialized (``jax.checkpoint`` with the
        dots-saveable policy) regardless of any model-level remat config
        (reference: accelerator.py:1485-1499 applies FSDP activation
        checkpointing to the wrapped module). With
        ``fsdp_plugin.cpu_offload=True`` the step is split into a grad
        executable (no optimizer state resident) and an update executable
        (no activations live), with the state streamed from/to pinned host
        memory at the boundary (parallel/host_offload.py).
        """
        model = model or self._models[0]
        optimizer = optimizer or self._optimizers[0]
        if optimizer._model is None:
            optimizer._model = model
        if optimizer.opt_state is None:
            optimizer.init_state(model.params)
        accum = accumulation_steps if accumulation_steps is not None else self.gradient_state.num_steps
        policy = self.policy
        if (grad_reduce_dtype is not None
                and jnp.dtype(grad_reduce_dtype) != jnp.dtype(policy.compute_dtype)):
            warnings.warn(
                f"grad_reduce_dtype={jnp.dtype(grad_reduce_dtype).name} differs from the "
                f"mixed-precision compute dtype {jnp.dtype(policy.compute_dtype).name}: the "
                "forward will also run in the reduce dtype (the step differentiates w.r.t. "
                "the cast params), which changes accuracy beyond communication narrowing. "
                "Match the dtypes to narrow only the gradient all-reduce.",
                stacklevel=2,
            )
        accepts_rng = self._loss_fn_accepts_rng(loss_fn)
        tx = optimizer.tx
        has_scale = optimizer.loss_scale is not None
        scaler_kwargs = optimizer.scaler_kwargs
        fsdp = self.state.fsdp_plugin
        remat_loss = bool(fsdp is not None and fsdp.activation_checkpointing)
        offload = optimizer.offload_to_host
        from .ops.quant import fp8_meta_mask, has_fp8_meta

        fp8_mask = fp8_meta_mask(model.params) if has_fp8_meta(model.params) else None

        def loss_and_grads(params, microbatch, rng, scale):
            def compute(p):
                cp = p if grad_reduce_dtype is not None else policy.cast_to_compute(p)
                out = loss_fn(cp, microbatch, rng) if accepts_rng else loss_fn(cp, microbatch)
                loss, aux = out if isinstance(out, tuple) else (out, None)
                scaled = loss / accum
                if scale is not None:
                    scaled = scaled * scale.astype(scaled.dtype)
                return scaled.astype(jnp.float32), loss

            if remat_loss:
                from .parallel.sharding import resolve_remat_policy

                compute = jax.checkpoint(
                    compute, policy=resolve_remat_policy(fsdp.remat_policy)
                )
            if grad_reduce_dtype is not None:
                # Differentiate w.r.t. the CAST params: cotangents (and the
                # implicit dp all-reduce) stay in the narrow dtype; upcast
                # only after, for clipping/optimizer.
                from .precision import _cast_floating

                cp0 = _cast_floating(policy.cast_to_compute(params), grad_reduce_dtype)
                (scaled, loss), grads = jax.value_and_grad(compute, has_aux=True)(cp0)
                grads = jax.tree_util.tree_map(
                    lambda g, p: (g.astype(p.dtype)
                                  if jnp.issubdtype(p.dtype, jnp.floating) else g),
                    grads, params)
                return loss, grads
            (scaled, loss), grads = jax.value_and_grad(compute, has_aux=True)(params)
            return loss, grads

        @program_part("loss")
        def grad_phase(params, loss_scale, batch, rng):
            scale = loss_scale.scale if has_scale else None
            if accum > 1:
                def scan_body(carry, microbatch):
                    acc_grads, loss_sum, i = carry
                    sub = jax.random.fold_in(rng, i)
                    loss, grads = loss_and_grads(params, microbatch, sub, scale)
                    acc_grads = jax.tree_util.tree_map(jnp.add, acc_grads, grads)
                    return (acc_grads, loss_sum + loss, i + 1), None

                zero_grads = jax.tree_util.tree_map(lambda p: jnp.zeros_like(p), params)
                (grads, loss_sum, _), _ = jax.lax.scan(
                    scan_body, (zero_grads, jnp.zeros((), jnp.float32), 0), batch
                )
                loss = loss_sum / accum
            else:
                loss, grads = loss_and_grads(params, batch, rng, scale)
            return grads, loss

        # ZeRO (optimizer.zero_sharding): the update pins its outputs with
        # sharding constraints. The constraint on params is load-bearing:
        # without it GSPMD propagates the moments' dp sharding onto the
        # updated params, breaking the donation alias; with it the update
        # lowers to reduce-scatter(grads) -> shard-local Adam ->
        # all-gather(params), and per-replica opt-state bytes are 1/dp.
        # (Constraints inside the traced function, not jit in/out_shardings:
        # explicitly-sharded jits segfault after a persistent-compile-cache
        # round-trip on the CPU backend, and the inputs are already committed
        # to these layouts at init_state time.)
        zero_sh = optimizer.opt_state_shardings
        zero_p_sh = None
        if zero_sh is not None:
            zero_p_sh = model.param_shardings
            if zero_p_sh is None:
                repl = replicated_sharding(self.mesh)
                zero_p_sh = jax.tree_util.tree_map(lambda _: repl, model.params)

        # Without ZeRO the update still pins params and optimizer state to the
        # layouts they have now: left free, GSPMD hands some leaves back laid
        # out differently (replicated norm scales come out tp-sharded), the
        # next call sees new input shardings, and the whole step compiles a
        # second time at step 2. Host-offloaded state changes memory kind
        # across the update and keeps its own path.
        pinned = None
        if zero_sh is None and not offload and self.mesh.devices.size > 1:
            from jax.sharding import NamedSharding

            def _layouts(tree):
                return [x.sharding if isinstance(getattr(x, "sharding", None), NamedSharding)
                        else None for x in jax.tree_util.tree_leaves(tree)]

            pinned = (_layouts(model.params), _layouts(optimizer.opt_state))

        def _pin(tree, layouts):
            leaves, treedef = jax.tree_util.tree_flatten(tree)
            return jax.tree_util.tree_unflatten(treedef, [
                x if s is None else jax.lax.with_sharding_constraint(x, s)
                for x, s in zip(leaves, layouts)])

        def update_phase(params, opt_state, loss_scale, grads, loss):
            import optax

            if has_scale:
                from .precision import grads_finite, unscale_grads, update_loss_scale

                grads = unscale_grads(grads, loss_scale)
                finite = grads_finite(grads)
            else:
                finite = jnp.asarray(True)

            def clip(grads):
                """``(clipped gradients, their global norm)``; ``(grads, None)``
                where no ``max_grad_norm`` is set."""
                if max_grad_norm is None:
                    return grads, None
                # fp8 statistics leaves carry updated amax/scale values in
                # their "gradients" (ops/quant.py): they must neither enter
                # the norm nor be scaled by the clip factor.
                if fp8_mask is not None:
                    leaves = [
                        g
                        for g, is_meta in zip(
                            jax.tree_util.tree_leaves(grads),
                            jax.tree_util.tree_leaves(fp8_mask),
                        )
                        if not is_meta
                    ]
                else:
                    leaves = jax.tree_util.tree_leaves(grads)
                gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in leaves))
                factor = jnp.minimum(1.0, max_grad_norm / (gnorm + 1e-6))
                if fp8_mask is not None:
                    grads = jax.tree_util.tree_map(
                        lambda g, is_meta: g if is_meta else (g * factor).astype(g.dtype),
                        grads,
                        fp8_mask,
                    )
                else:
                    grads = jax.tree_util.tree_map(lambda g: (g * factor).astype(g.dtype), grads)
                return grads, gnorm

            with program_part("optimizer"):
                grads, gnorm = clip(grads)
                updates, new_opt_state = tx.update(grads, opt_state, params)
                new_params = optax.apply_updates(params, updates)
            if has_scale:
                from .precision import update_loss_scale as _uls

                new_params = jax.tree_util.tree_map(lambda n, o: jnp.where(finite, n, o), new_params, params)
                new_opt_state = jax.tree_util.tree_map(
                    lambda n, o: jnp.where(finite, n, o) if hasattr(n, "dtype") else n, new_opt_state, opt_state
                )
                new_scale = _uls(loss_scale, finite, scaler_kwargs)
            else:
                new_scale = loss_scale

            metrics = {"loss": loss.astype(jnp.float32)}
            if gnorm is not None:
                metrics["grad_norm"] = gnorm
            if has_scale:
                metrics["loss_scale"] = new_scale.scale
                metrics["finite"] = finite
            if zero_sh is not None:
                new_params = jax.lax.with_sharding_constraint(new_params, zero_p_sh)
                new_opt_state = jax.lax.with_sharding_constraint(new_opt_state, zero_sh)
            elif pinned is not None:
                new_params = _pin(new_params, pinned[0])
                new_opt_state = _pin(new_opt_state, pinned[1])
            return new_params, new_opt_state, new_scale, metrics

        def train_step(params, opt_state, loss_scale, batch, rng):
            grads, loss = grad_phase(params, loss_scale, batch, rng)
            return update_phase(params, opt_state, loss_scale, grads, loss)

        def _check_accum_shape(batch):
            if accum > 1:
                bad = [
                    np.shape(leaf)
                    for leaf in jax.tree_util.tree_leaves(batch)
                    if np.ndim(leaf) == 0 or np.shape(leaf)[0] != accum
                ]
                if bad:
                    raise ValueError(
                        f"compile_train_step(accumulation_steps={accum}) expects every batch "
                        f"leaf to have a leading microbatch dim of {accum}; got leading dims "
                        f"{[s[0] if s else None for s in bad]}. Reshape to [accum, micro, ...]."
                    )

        def _record(metrics):
            if has_scale:
                # Don't sync here: record the device-side finite flag; the
                # steps_applied/step_was_skipped properties drain it lazily.
                optimizer._pending_finite.append(metrics["finite"])
                optimizer._last_finite = metrics["finite"]
            else:
                optimizer._steps_applied += 1
            return metrics

        if not offload:
            jitted = jax.jit(train_step, donate_argnums=(0, 1) if donate else ())

            def step(batch):
                _check_accum_shape(batch)
                rng = self.next_rng_key()
                new_params, new_opt_state, new_scale, metrics = jitted(
                    model.params, optimizer.opt_state, optimizer.loss_scale, batch, rng
                )
                model.params = new_params
                optimizer.opt_state = new_opt_state
                optimizer.loss_scale = new_scale
                return _record(metrics)

            step._jitted = jitted  # expose for AOT/benchmark introspection
            return step

        # Host-offloaded optimizer state: two executables. The grad phase
        # never sees the optimizer state, so HBM peaks at params +
        # activations + grads; the update phase holds params + grads + state
        # but no activations. Grads are donated into the update.
        from .parallel.host_offload import to_device, to_host

        jitted_grads = jax.jit(grad_phase)
        jitted_update = jax.jit(
            update_phase, donate_argnums=(0, 1, 3) if donate else ()
        )

        def step(batch):
            _check_accum_shape(batch)
            rng = self.next_rng_key()
            grads, loss = jitted_grads(model.params, optimizer.loss_scale, batch, rng)
            opt_in = to_device(optimizer.opt_state, self.mesh)
            new_params, new_opt_state, new_scale, metrics = jitted_update(
                model.params, opt_in, optimizer.loss_scale, grads, loss
            )
            model.params = new_params
            optimizer.opt_state = to_host(new_opt_state, self.mesh)
            optimizer.loss_scale = new_scale
            return _record(metrics)

        step._jitted = jitted_update  # expose for AOT/benchmark introspection
        step._jitted_grads = jitted_grads
        return step

    # ------------------------------------------------------------------
    # Collectives / metrics (reference: accelerator.py:2360-2479)
    # ------------------------------------------------------------------

    def gather(self, tensor):
        """Gather a pytree across processes, concatenated on dim 0 (reference: :2378)."""
        return gather(tensor)

    def gather_for_metrics(self, input_data, use_gather_object: bool = False):
        """Gather, dropping duplicate tail samples added for even batching
        (reference: gather_for_metrics :2408 using GradientState.remainder)."""
        try:
            recursively_apply(lambda x: x, input_data, error_on_other_type=True)
            all_tensors = True
        except TypeError:
            all_tensors = False

        if use_gather_object or not all_tensors:
            data = gather_object(input_data)
        else:
            data = self.gather(input_data)

        if self.gradient_state.end_of_dataloader and self.gradient_state.remainder > 0:
            remainder = self.gradient_state.remainder

            def _adjust_samples(tensor):
                # Gathered objects may be ragged lists (np.ndim would choke
                # converting them); arrays slice on their batch dim, 0-d
                # scalars pass through (the remainder describes a batch dim
                # they don't have).
                if isinstance(tensor, (list, tuple)):
                    return tensor[:remainder]
                if getattr(tensor, "ndim", 0) == 0:
                    return tensor
                return tensor[:remainder]

            if use_gather_object or not all_tensors:
                return _adjust_samples(data)
            return recursively_apply(_adjust_samples, data)
        return data

    def reduce(self, tensor, reduction="sum", scale=1.0):
        """Reduce a pytree across processes (sum/mean, reference: :2517)."""
        return reduce(tensor, reduction, scale)

    def pad_across_processes(self, tensor, dim=0, pad_index=0, pad_first=False):
        """Pad each process's tensor to the max length before gathering ragged data (reference: :2467)."""
        return pad_across_processes(tensor, dim=dim, pad_index=pad_index, pad_first=pad_first)

    def unwrap_model(self, model, keep_fp32_wrapper: bool = True):
        """Return the inner Model (reference: unwrap_model delegates to
        extract_model_from_parallel — same layering here)."""
        from .utils.other import extract_model_from_parallel

        return extract_model_from_parallel(model, keep_fp32_wrapper=keep_fp32_wrapper)

    def get_state_dict(self, model, unwrap: bool = True):
        """Full (host-gathered) parameter pytree (reference: :3291 — the
        ZeRO-3 consolidation equivalent is fetching the addressable global
        arrays)."""
        params = model.params if isinstance(model, AcceleratedModel) else model
        return jax.tree_util.tree_map(lambda x: np.asarray(jax.device_get(x)), params)

    # ------------------------------------------------------------------
    # Cross-process trigger (reference: accelerator.py:2198-2255)
    # ------------------------------------------------------------------

    def set_trigger(self):
        self.flag_tensor = True

    def check_trigger(self) -> bool:
        """True if ANY process called set_trigger (early stopping, NaN
        breakpoints)."""
        flag = np.array([1 if self.flag_tensor else 0], dtype=np.int64)
        if self.num_processes > 1:
            from jax.experimental import multihost_utils

            total = int(multihost_utils.process_allgather(flag, tiled=False).sum())
        else:
            total = int(flag[0])
        if total > 0:
            self.flag_tensor = None
            return True
        return False

    # ------------------------------------------------------------------
    # Preemption (graceful save-and-restart; completes the elastic story
    # with `accelerate-tpu launch --max_restarts` + auto-resume)
    # ------------------------------------------------------------------

    #: exit code signalling "preempted after saving" — launchers and pod
    #: schedulers treat nonzero as restart-eligible; 75 is EX_TEMPFAIL.
    PREEMPTED_EXIT_CODE = 75

    def install_preemption_handler(self, signals=None):
        """Catch SIGTERM (the preemption notice on TPU pods and most
        schedulers) and latch :attr:`preemption_requested`. The training
        loop checks it at step boundaries and winds down::

            accelerator.install_preemption_handler()
            for batch in loader:
                if accelerator.preemption_requested:
                    accelerator.save_state()
                    sys.exit(accelerator.PREEMPTED_EXIT_CODE)
                step(batch)

        ``launch --max_restarts`` (or the pod scheduler) then relaunches,
        and ``load_state()`` resumes from the just-saved checkpoint. The
        reference delegates this to torch elastic's restart-the-world
        (reference: commands/launch.py:775-799); the handler only sets a
        flag, so a signal mid-XLA-dispatch is safe."""
        import signal as _signal

        self._preemption_requested = False
        for sig in signals or (_signal.SIGTERM,):
            _signal.signal(sig, self._on_preemption_signal)

    def _on_preemption_signal(self, signum, frame):
        self._preemption_requested = True

    @property
    def preemption_requested(self) -> bool:
        """True once a preemption signal arrived (see
        :meth:`install_preemption_handler`)."""
        return getattr(self, "_preemption_requested", False)

    # ------------------------------------------------------------------
    # Autocast / profile (reference: accelerator.py:3383, 3423)
    # ------------------------------------------------------------------

    @contextlib.contextmanager
    def autocast(self, autocast_handler: Optional[AutocastKwargs] = None):
        """Parity context. In JAX the dtype policy is baked into compiled
        fns; this context exposes the active policy for manual use."""
        yield self.policy

    def profile(self, profile_handler: Optional[ProfileKwargs] = None):
        """Context manager capturing a jax.profiler trace (reference: :3423).

        Trace directory precedence: the handler's ``output_trace_dir`` (the
        user's explicit choice), then the project's ``logging_dir``, then
        ``./jax_trace``.
        """
        handler = profile_handler or self.profile_handler or ProfileKwargs()
        log_dir = (handler.output_trace_dir
                   or self.project_configuration.logging_dir or "./jax_trace")
        # The device trace and the host-side breakdowns (input pipeline,
        # serving engine) tell one story; sessions built here snapshot
        # data_wait/stage and serving counters per step().
        return (handler.build(log_dir=log_dir)
                .attach_pipeline_stats(self.pipeline_stats)
                .attach_serving_stats(self.serving_stats)
                .attach_gateway_stats(self.gateway_stats))

    # ------------------------------------------------------------------
    # Memory / lifecycle (reference: accelerator.py:3219-3270)
    # ------------------------------------------------------------------

    def free_memory(self, *objects):
        """Drop every prepared-object reference and free device buffers (reference: :3219)."""
        from .utils.memory import release_memory

        self._models.clear()
        self._optimizers.clear()
        self._schedulers.clear()
        self._dataloaders.clear()
        self._backward_cache.clear()
        self._fused_cache.clear()
        self.step = 0
        return release_memory(*objects)

    def clear(self, *objects):
        """Alias of free_memory (reference: :3270)."""
        return self.free_memory(*objects)

    def register_for_checkpointing(self, *objects):
        """Track custom stateful objects for save_state/load_state
        (reference: :3349)."""
        invalid = [o for o in objects if not (hasattr(o, "state_dict") and hasattr(o, "load_state_dict"))]
        if invalid:
            raise ValueError(
                f"All `objects` must have `state_dict`/`load_state_dict`: got invalid {invalid}"
            )
        self._custom_objects.extend(objects)

    def skip_first_batches(self, dataloader, num_batches: int = 0):
        """Fast-forward a prepared loader for mid-epoch resume (reference: :3440)."""
        return skip_first_batches(dataloader, num_batches)

    # save_state/load_state live in checkpointing.py and are bound here to
    # keep this module focused.
    def save_state(self, output_dir: Optional[str] = None, **save_model_kwargs):
        """Checkpoint params/optimizer/RNG/loaders/custom objects (reference: :2915).

        Pass ``blocking=False`` for an async checkpoint: arrays are
        snapshotted to host synchronously, the filesystem write streams in
        the background, and training continues. Durability points:
        :meth:`wait_for_checkpoint`, the next save/load, or process exit."""
        from .checkpointing import save_accelerator_state

        return save_accelerator_state(self, output_dir, **save_model_kwargs)

    def wait_for_checkpoint(self):
        """Block until every in-flight async ``save_state(blocking=False)``
        is durable on disk."""
        from .checkpointing import wait_for_saves

        wait_for_saves()

    def load_state(self, input_dir: Optional[str] = None, **load_model_kwargs):
        """Restore a save_state checkpoint, resharding on topology changes (reference: :3081)."""
        from .checkpointing import load_accelerator_state

        return load_accelerator_state(self, input_dir, **load_model_kwargs)

    def save_model(self, model, save_directory: str, max_shard_size: str = "10GB", safe_serialization: bool = True):
        """Export params as (sharded) safetensors for serving (reference: :2848)."""
        from .checkpointing import save_model as _save_model

        return _save_model(self, model, save_directory, max_shard_size, safe_serialization)

    # Tracking API (tracking.py) ----------------------------------------
    def init_trackers(self, project_name: str, config: Optional[dict] = None, init_kwargs: Optional[dict] = None):
        """Start every configured experiment tracker (reference: :2568)."""
        from .tracking import filter_trackers, resolve_trackers

        self.trackers = resolve_trackers(
            getattr(self, "_log_with", None), project_name, self.project_configuration.logging_dir,
            config=config, init_kwargs=init_kwargs or {},
        )

    def log(self, values: dict, step: Optional[int] = None, log_kwargs: Optional[dict] = None,
            include_input_pipeline: bool = False, include_serving: bool = False,
            include_gateway: bool = False):
        """Log scalars to every active tracker, main process only (reference: :2625).

        ``include_input_pipeline=True`` merges the aggregated loader
        breakdown (``input_pipeline/data_wait_ms`` etc.) into the payload;
        ``include_serving=True`` does the same for serving-engine counters
        (``serving/ttft_ms`` etc.), and ``include_gateway=True`` for the
        HTTP gateway's counters (``gateway/http_requests`` etc.)."""
        if include_input_pipeline:
            from .tracking import with_input_pipeline_metrics

            values = with_input_pipeline_metrics(values, self.pipeline_stats)
        if include_serving:
            from .tracking import with_serving_metrics

            values = with_serving_metrics(values, self.serving_stats)
        if include_gateway:
            from .tracking import with_gateway_metrics

            values = with_gateway_metrics(values, self.gateway_stats)
        for tracker in self.trackers:
            tracker.log(values, step=step, **((log_kwargs or {}).get(tracker.name, {})))

    def get_tracker(self, name: str, unwrap: bool = False):
        """Fetch one active tracker by name; ``unwrap`` returns the raw client run."""
        for tracker in self.trackers:
            if tracker.name == name:
                return tracker.tracker if unwrap else tracker
        raise ValueError(f"{name} is not an available tracker: {[t.name for t in self.trackers]}")

    def end_training(self):
        """Drain in-flight async checkpoint saves, then flush/close all
        trackers and barrier (reference: :2645). The save drain comes first:
        a script that calls ``end_training()`` and exits must not drop an
        Orbax write that is still in flight."""
        from . import checkpointing

        checkpointing.wait_for_saves()
        for tracker in self.trackers:
            tracker.finish()
        self.wait_for_everyone()
