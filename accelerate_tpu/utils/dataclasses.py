"""Enums, kwargs handlers, and plugin configuration dataclasses.

Capability parity with the reference's ``utils/dataclasses.py`` (reference:
src/accelerate/utils/dataclasses.py — DistributedType :530, PrecisionType
:686, RNGType :702, DataLoaderConfiguration :733, ProjectConfiguration :790,
GradientAccumulationPlugin :838, KwargsHandler :45, AutocastKwargs :90,
GradScalerKwargs :209, InitProcessGroupKwargs :240, FP8RecipeKwargs :277,
ProfileKwargs :400, DeepSpeedPlugin :923, FullyShardedDataParallelPlugin
:1260, MegatronLMPlugin :1609).

Redesigned TPU-first: parallelism "plugins" are *sharding policies* over a
logical device mesh (GSPMD), not wrappers delegating to external engines.
DeepSpeed/Megatron configs are accepted and translated onto mesh policies so
users migrating from the reference keep their configs.
"""

from __future__ import annotations

import copy
import enum
import functools
import os
import warnings
from dataclasses import dataclass, field
from datetime import timedelta
from typing import Any, Callable, Iterable, Literal, Optional

from .environment import env_var, parse_flag_from_env


class EnumWithContains(enum.EnumMeta):
    """Enum metaclass supporting ``"value" in MyEnum`` (reference: utils/dataclasses.py:516)."""

    def __contains__(cls, item):
        try:
            cls(item)
        except ValueError:
            return False
        return True


class BaseEnum(str, enum.Enum, metaclass=EnumWithContains):
    def __str__(self):
        return self.value

    @classmethod
    def list(cls):
        return list(map(str, cls))


class DistributedType(BaseEnum):
    """The flavor of distributed execution (reference: utils/dataclasses.py:530).

    On TPU every flavor is realized as a GSPMD sharding over one jax Mesh; the
    enum records *which policy family* configured the mesh, for API parity.
    """

    NO = "NO"
    MULTI_CPU = "MULTI_CPU"          # host-platform multi-device (testing)
    TPU = "TPU"                      # single- or multi-chip TPU, data-parallel default
    FSDP = "FSDP"                    # param/grad/opt-state sharded over the fsdp axis
    TENSOR_PARALLEL = "TENSOR_PARALLEL"
    PIPELINE_PARALLEL = "PIPELINE_PARALLEL"
    DEEPSPEED = "DEEPSPEED"          # translated ZeRO config -> fsdp-axis policy
    MEGATRON_LM = "MEGATRON_LM"      # translated 3D config -> dp/tp/pp mesh policy
    MULTI_GPU = "MULTI_GPU"          # jax on GPU backends (untested, best-effort)


class PrecisionType(BaseEnum):
    """Mixed-precision modes (reference: utils/dataclasses.py:686)."""

    NO = "no"
    FP32 = "fp32"
    FP16 = "fp16"
    BF16 = "bf16"
    FP8 = "fp8"


class RNGType(BaseEnum):
    """RNG streams that can be synchronized (reference: utils/dataclasses.py:702).

    JAX's explicit ``jax.random`` keys replace torch's five implicit streams;
    NUMPY/PYTHON remain for host-side data pipelines.
    """

    JAX = "jax"
    NUMPY = "numpy"
    PYTHON = "python"
    GENERATOR = "generator"


class LoggerType(BaseEnum):
    """Experiment trackers (reference: utils/dataclasses.py:664)."""

    ALL = "all"
    TENSORBOARD = "tensorboard"
    WANDB = "wandb"
    COMETML = "comet_ml"
    MLFLOW = "mlflow"
    AIM = "aim"
    CLEARML = "clearml"
    DVCLIVE = "dvclive"
    JSONL = "jsonl"                  # TPU-native lightweight file tracker


class ComputeBackend(BaseEnum):
    """Replacement for the reference's DynamoBackend (utils/dataclasses.py:610).

    On JAX everything is compiled; the choice is *how*.
    """

    JIT = "jit"                      # jax.jit (default; always on)
    AOT = "aot"                      # ahead-of-time lowered+compiled executable
    EAGER = "eager"                  # disable_jit, for debugging only


class CustomDtype(BaseEnum):
    """Sub-byte / non-native dtypes for size accounting (reference: utils/dataclasses.py:713)."""

    FP8_E4M3 = "fp8_e4m3"
    FP8_E5M2 = "fp8_e5m2"
    INT4 = "int4"
    INT2 = "int2"


# ---------------------------------------------------------------------------
# Kwargs handlers (reference: utils/dataclasses.py:45-503)
# ---------------------------------------------------------------------------

@dataclass
class KwargsHandler:
    """Base for objects that tweak a subsystem's kwargs (reference: utils/dataclasses.py:45)."""

    def to_dict(self):
        """Plain-dict view of the handler's fields."""
        return copy.deepcopy(self.__dict__)

    def to_kwargs(self):
        """Return only the non-default values."""
        default_dict = self.__class__().to_dict()
        this_dict = self.to_dict()
        return {k: v for k, v in this_dict.items() if default_dict[k] != v}


@dataclass
class AutocastKwargs(KwargsHandler):
    """Controls the compute-dtype policy (reference: utils/dataclasses.py:90).

    JAX has no autocast context; instead a dtype *policy* (param/compute/output
    dtypes) is baked into the compiled step. ``enabled=False`` forces fp32
    compute for a specific prepared model.
    """

    enabled: bool = True
    cache_enabled: bool = True  # accepted for API parity; meaningless under jit


class DDPCommunicationHookType(BaseEnum):
    """Reference enum (utils/dataclasses.py DDPCommunicationHookType) kept
    for import parity. GSPMD emits gradient collectives inside the compiled
    step; there is no DDP allreduce to hook, so only NO is meaningful."""

    NO = "no"
    FP16 = "fp16"
    BF16 = "bf16"
    POWER_SGD = "power_sgd"
    BATCHED_POWER_SGD = "batched_power_sgd"


@dataclass
class DistributedDataParallelKwargs(KwargsHandler):
    """Reference parity (utils/dataclasses.py DistributedDataParallelKwargs).

    Every field configures torch DDP's allreduce machinery, which does not
    exist here — GSPMD schedules gradient reduction inside the compiled
    train step, bucketing and overlap included. Accepted so migrating
    scripts keep constructing it; non-default values warn that they have
    no effect rather than silently pretending to.
    """

    dim: int = 0
    broadcast_buffers: bool = True
    bucket_cap_mb: int = 25
    find_unused_parameters: bool = False
    check_reduction: bool = False
    gradient_as_bucket_view: bool = False
    static_graph: bool = False
    comm_hook: DDPCommunicationHookType = DDPCommunicationHookType.NO
    comm_wrapper: DDPCommunicationHookType = DDPCommunicationHookType.NO
    comm_state_option: dict = field(default_factory=dict)

    def __post_init__(self):
        import dataclasses as _dc

        # Compare against field defaults directly: to_kwargs() builds a
        # default instance, which would re-enter this __post_init__.
        non_default = [
            f.name for f in _dc.fields(self)
            if getattr(self, f.name) != (
                f.default_factory() if f.default is _dc.MISSING else f.default
            )
        ]
        if non_default:
            warnings.warn(
                f"DistributedDataParallelKwargs({', '.join(sorted(non_default))}) has no "
                "effect on TPU: gradient reduction is compiled into the train step by "
                "GSPMD (bucketing/overlap included); there is no DDP engine to configure."
            )


@dataclass
class GradScalerKwargs(KwargsHandler):
    """Dynamic loss-scaling config for fp16 (reference: utils/dataclasses.py:209).

    bf16 needs no scaling on TPU (same exponent range as fp32); this exists for
    fp16 parity and is implemented as a pure optax-style transform
    (:mod:`accelerate_tpu.optimizer`), not a mutable GradScaler object.
    """

    init_scale: float = 65536.0
    growth_factor: float = 2.0
    backoff_factor: float = 0.5
    growth_interval: int = 2000
    enabled: bool = True


@dataclass
class DistributedInitKwargs(KwargsHandler):
    """Multi-host runtime init knobs (reference InitProcessGroupKwargs, utils/dataclasses.py:240).

    Maps onto ``jax.distributed.initialize`` instead of
    ``torch.distributed.init_process_group``.
    """

    coordinator_address: Optional[str] = None
    num_processes: Optional[int] = None
    process_id: Optional[int] = None
    local_device_ids: Optional[list] = None
    initialization_timeout: timedelta = field(default_factory=lambda: timedelta(seconds=300))


# Back-compat alias matching the reference class name.
InitProcessGroupKwargs = DistributedInitKwargs


@dataclass
class FP8RecipeKwargs(KwargsHandler):
    """FP8 training recipe (reference: utils/dataclasses.py:277).

    TPU-native: delayed-scaling fp8 matmuls via XLA's fp8 dot support
    (e4m3 forward / e5m2 backward), implemented in ops/quant.py rather than
    TransformerEngine/MS-AMP.
    """

    backend: Literal["XLA", "PALLAS"] = "XLA"
    margin: int = 0
    interval: int = 16
    fp8_format: Literal["E4M3", "E5M2", "HYBRID"] = "HYBRID"
    amax_history_len: int = 1024
    amax_compute_algo: Literal["max", "most_recent"] = "most_recent"
    use_autocast_during_eval: bool = False


@dataclass
class ProfileKwargs(KwargsHandler):
    """Profiler configuration (reference: utils/dataclasses.py:400-503).

    Wraps ``jax.profiler`` (XPlane/TensorBoard traces) instead of
    torch.profiler/Kineto.
    """

    activities: Optional[list] = None          # accepted for parity; jax traces all
    schedule_option: Optional[dict[str, int]] = None  # {wait, warmup, active, repeat, skip_first}
    on_trace_ready: Optional[Callable] = None
    record_shapes: bool = False
    profile_memory: bool = False
    with_stack: bool = False
    with_flops: bool = False
    output_trace_dir: Optional[str] = None
    create_perfetto_link: bool = False
    create_perfetto_trace: bool = False

    def build(self, log_dir: str | None = None):
        """Create a profiler session object (reference builds torch.profiler at :480)."""
        from .profiling import ProfileSession  # local import to avoid cycle

        return ProfileSession(self, log_dir=log_dir or self.output_trace_dir)


@dataclass
class GradientAccumulationPlugin(KwargsHandler):
    """Gradient accumulation config (reference: utils/dataclasses.py:838)."""

    num_steps: int = 1
    adjust_scheduler: bool = True
    sync_with_dataloader: bool = True
    sync_each_batch: bool = False


@dataclass
class DataLoaderConfiguration(KwargsHandler):
    """Dataloader behavior knobs (reference: utils/dataclasses.py:733)."""

    split_batches: bool = False
    dispatch_batches: Optional[bool] = None
    even_batches: bool = True
    use_seedable_sampler: bool = True
    non_blocking: bool = True        # async host->device transfer (always async in jax)
    use_stateful_dataloader: bool = True
    data_seed: Optional[int] = None
    prefetch_size: int = 2           # staged batches the pipeline keeps ahead
    async_prefetch: bool = True      # background worker pulls/collates/stages
    num_workers: int = 1             # staging threads (pulling is always serial)

    def __post_init__(self):
        if self.prefetch_size < 1:
            raise ValueError(f"prefetch_size must be >= 1, got {self.prefetch_size}")
        if self.num_workers < 1:
            raise ValueError(f"num_workers must be >= 1, got {self.num_workers}")


@dataclass
class ProjectConfiguration(KwargsHandler):
    """Checkpoint/output directory layout (reference: utils/dataclasses.py:790)."""

    project_dir: Optional[str] = None
    logging_dir: Optional[str] = None
    automatic_checkpoint_naming: bool = False
    total_limit: Optional[int] = None
    iteration: int = 0
    save_on_each_node: bool = False

    def set_directories(self, project_dir: str | None = None):
        """Derive checkpoint/logging dirs from ``project_dir``."""
        self.project_dir = project_dir
        if self.logging_dir is None:
            self.logging_dir = project_dir

    def __post_init__(self):
        if self.logging_dir is None:
            self.logging_dir = self.project_dir


@dataclass
class JitConfig(KwargsHandler):
    """Compilation knobs (replaces the reference's TorchDynamoPlugin, utils/dataclasses.py:887)."""

    backend: ComputeBackend = ComputeBackend.JIT
    donate_state: bool = True            # donate params/opt-state buffers to the step
    remat_policy: Optional[str] = None   # None|"full"|"dots_saveable"|"nothing_saveable"

    def __post_init__(self):
        if isinstance(self.backend, str):
            self.backend = ComputeBackend(self.backend.lower())


# ---------------------------------------------------------------------------
# Parallelism plugins — sharding policies over the mesh
# ---------------------------------------------------------------------------

@dataclass
class FullyShardedDataParallelPlugin(KwargsHandler):
    """FSDP as a GSPMD policy (reference: utils/dataclasses.py:1260-1606).

    Instead of torch-FSDP's flat-param runtime, parameters/gradients/optimizer
    state are sharded over the ``fsdp`` mesh axis with NamedSharding; XLA
    schedules the all-gathers (forward) and reduce-scatters (backward) that
    torch-FSDP hand-implements in C++.
    """

    # Parity knobs (reference sharding strategies, utils/constants.py:36)
    sharding_strategy: Literal["FULL_SHARD", "SHARD_GRAD_OP", "NO_SHARD", "HYBRID_SHARD"] = "FULL_SHARD"
    reshard_after_forward: bool = True          # FULL_SHARD vs SHARD_GRAD_OP
    state_dict_type: Literal["FULL_STATE_DICT", "SHARDED_STATE_DICT"] = "SHARDED_STATE_DICT"
    cpu_offload: bool = False                   # host-DRAM optimizer/params offload
    activation_checkpointing: bool = False      # jax.checkpoint on block boundaries
    # Which intermediates survive the forward when activation_checkpointing
    # is on: "dots" (matmul outputs saveable — recompute elementwise only),
    # "nothing" (full recompute, minimum memory), "everything" (save all —
    # remat becomes a no-op; debugging).
    remat_policy: str = "dots"
    min_weight_size_to_shard: int = 2**14       # small tensors stay replicated
    shard_largest_dim: bool = True              # shard dim with max size divisible by axis
    #: ZeRO-1/2: shard optimizer state (Adam moments) over the dp axis so
    #: each replica holds 1/dp of it (parallel/sharding.py
    #: infer_opt_state_shardings). Orthogonal to sharding_strategy, which
    #: governs params/grads over the fsdp axis.
    zero_sharding: bool = False
    use_orig_params: bool = True                # parity no-op (params are always "orig" pytrees)
    sync_module_states: bool = True             # parity no-op (GSPMD arrays are globally consistent)
    forward_prefetch: bool = True               # parity no-op (XLA overlaps automatically)
    backward_prefetch: bool = True              # parity no-op
    param_dtype: Optional[str] = None           # not applied: see __post_init__ warning
    auto_wrap_policy: Optional[Any] = None      # parity no-op: sharding is per-leaf, not per-wrap

    def __post_init__(self):
        env = os.environ
        self.sharding_strategy = env.get("FSDP_SHARDING_STRATEGY", self.sharding_strategy)
        self.state_dict_type = env.get("FSDP_STATE_DICT_TYPE", self.state_dict_type)
        if "FSDP_OFFLOAD_PARAMS" in env:
            self.cpu_offload = parse_flag_from_env("FSDP_OFFLOAD_PARAMS")
        if "FSDP_ACTIVATION_CHECKPOINTING" in env:
            self.activation_checkpointing = parse_flag_from_env("FSDP_ACTIVATION_CHECKPOINTING")
        if "FSDP_ZERO_SHARDING" in env:
            self.zero_sharding = parse_flag_from_env("FSDP_ZERO_SHARDING")
        if "FSDP_MIN_NUM_PARAMS" in env:
            # Reference parity (utils/dataclasses.py size_based_auto_wrap):
            # the smallest tensor worth sharding, as a param count.
            self.min_weight_size_to_shard = int(env["FSDP_MIN_NUM_PARAMS"])
        if self.sharding_strategy == "NO_SHARD":
            self.min_weight_size_to_shard = 1 << 62  # nothing shards
        if self.sharding_strategy == "SHARD_GRAD_OP":
            self.reshard_after_forward = False
        # Knobs with no consumer must say so, not look functional.
        if self.param_dtype is not None:
            warnings.warn(
                "FullyShardedDataParallelPlugin.param_dtype is not applied: master "
                "params stay fp32 and the compute dtype comes from mixed_precision. "
                "Set Accelerator(mixed_precision=...) instead.",
                stacklevel=2,
            )
        if self.auto_wrap_policy is not None:
            warnings.warn(
                "FullyShardedDataParallelPlugin.auto_wrap_policy is ignored: GSPMD "
                "sharding is decided per-leaf by size/shape rules "
                "(min_weight_size_to_shard, shard_largest_dim), not by module wrapping.",
                stacklevel=2,
            )


@dataclass
class TensorParallelPlugin(KwargsHandler):
    """Tensor-parallel policy: Megatron-style column/row sharded matmuls via GSPMD.

    Net-new relative to the reference (which delegates TP to Megatron).
    Sharding rules live in :mod:`accelerate_tpu.parallel.sharding`.
    """

    tp_size: int = 1
    sequence_parallelism: bool = True   # shard activations on seq dim between TP ops
    rules: Optional[list[tuple[str, Any]]] = None  # extra (regex, PartitionSpec) rules


@dataclass
class ContextParallelPlugin(KwargsHandler):
    """Sequence/context parallelism for long sequences (net-new; SURVEY.md §5).

    Shards the sequence dimension of activations over the ``cp`` axis and runs
    ring attention (Pallas kernel with ppermute'd KV blocks) so attention sees
    the full context.
    """

    cp_size: int = 1
    mode: Literal["ring", "all_gather"] = "ring"
    causal: bool = True
    #: Ring attention's inner tile width: each arriving KV block is consumed
    #: in sub-tiles of this many keys, bounding the logits tile at
    #: [B, H, S_local, ring_inner_chunk] (ops/ring_attention.py).
    ring_inner_chunk: int = 1024

    def __post_init__(self):
        if self.ring_inner_chunk < 1:
            raise ValueError(
                f"ring_inner_chunk must be >= 1, got {self.ring_inner_chunk}")


@dataclass
class PipelineParallelPlugin(KwargsHandler):
    """Pipeline parallelism over the ``pp`` axis (reference: inference.py / Megatron PP).

    GPipe-style schedule expressed as a ``lax.scan`` over microbatches with
    ``shard_map`` stage placement.
    """

    pp_size: int = 1
    num_microbatches: int = 1
    schedule: Literal["gpipe", "1f1b"] = "gpipe"


@dataclass
class ExpertParallelPlugin(KwargsHandler):
    """MoE expert parallelism over the ``ep`` axis (net-new; reference only has a DS hook)."""

    ep_size: int = 1
    capacity_factor: float = 1.25
    num_experts: Optional[int] = None


@dataclass
class DeepSpeedPlugin(KwargsHandler):
    """DeepSpeed-config *translator* (reference: utils/dataclasses.py:923-1259).

    Accepts a ZeRO config (dict or json path) and maps it onto mesh policies:
    stage 0 -> pure DP; stage 1/2 -> optimizer/grad sharding (fsdp axis,
    params replicated); stage 3 -> full FSDP; offload -> host-DRAM placement.
    The DeepSpeed *engine* is not used — XLA is the engine.
    """

    hf_ds_config: Optional[Any] = None
    config_file: Optional[str] = None
    zero_stage: Optional[int] = None
    gradient_accumulation_steps: Optional[int] = None
    gradient_clipping: Optional[float] = None
    offload_optimizer_device: Optional[str] = None   # "none"|"cpu"
    offload_param_device: Optional[str] = None
    zero3_init_flag: Optional[bool] = None
    zero3_save_16bit_model: Optional[bool] = None

    def __post_init__(self):
        if self.config_file is None:
            self.config_file = os.environ.get(env_var("DEEPSPEED_CONFIG_FILE"), None)
        if self.config_file is not None and self.hf_ds_config is None:
            import json

            with open(self.config_file) as f:
                self.hf_ds_config = json.load(f)
        cfg = self.hf_ds_config or {}
        zero = cfg.get("zero_optimization", {})
        if self.zero_stage is None:
            self.zero_stage = int(os.environ.get(env_var("DEEPSPEED_ZERO_STAGE"), zero.get("stage", 2)))
        if self.gradient_accumulation_steps is None:
            gas = cfg.get("gradient_accumulation_steps", 1)
            self.gradient_accumulation_steps = gas if gas != "auto" else 1
        if self.gradient_clipping is None:
            gc = cfg.get("gradient_clipping", None)
            self.gradient_clipping = None if gc in (None, "auto") else float(gc)
        if self.offload_optimizer_device is None:
            self.offload_optimizer_device = zero.get("offload_optimizer", {}).get("device", "none")
        if self.offload_param_device is None:
            self.offload_param_device = zero.get("offload_param", {}).get("device", "none")

    def _schedule_fn(self):
        """step -> lr callable from the ``"scheduler"`` section, or None.
        Supports DeepSpeed's WarmupLR (log or linear warmup, then constant)
        and WarmupDecayLR (warmup then linear decay to zero)."""
        cfg = (self.hf_ds_config or {}).get("scheduler")
        if not cfg:
            return None
        p = {k: v for k, v in cfg.get("params", {}).items() if v != "auto"}
        lo = float(p.get("warmup_min_lr", 0.0))
        hi = float(p.get("warmup_max_lr", 1e-3))
        warmup = int(p.get("warmup_num_steps", 0))
        typ = str(cfg.get("type", "WarmupLR")).lower()
        # Branchless (jnp.where) because the schedule doubles as the optax
        # learning rate inside the jitted update, where ``step`` is traced.
        import math

        import jax.numpy as jnp

        # DeepSpeed's WarmupLR defaults to *log* warmup; "linear" is opt-in.
        # Exact DeepSpeed gammas: log -> log(1+step)/log(max(2, warmup))
        # (reaches 1.0 at step warmup-1), linear -> step/warmup.
        warmup_type = str(p.get("warmup_type", "log")).lower()
        if warmup_type not in ("log", "linear"):
            raise ValueError(f"unsupported DeepSpeed warmup_type {warmup_type!r}")

        def ramp(step):
            if warmup_type == "linear":
                frac = step / max(warmup, 1)
            else:
                frac = jnp.log(1.0 + step) / math.log(max(2, warmup))
            return lo + (hi - lo) * jnp.minimum(frac, 1.0)

        if typ == "warmuplr":
            def schedule(step):
                return jnp.where(step >= warmup, hi, ramp(step))
        elif typ == "warmupdecaylr":
            total = int(p.get("total_num_steps", max(warmup, 1)))

            def schedule(step):
                frac = (total - step) / max(total - warmup, 1)
                decayed = hi * jnp.clip(frac, 0.0, 1.0)
                return jnp.where(step < warmup, ramp(step),
                                 hi if total <= warmup else decayed)
        else:
            raise ValueError(f"unsupported DeepSpeed scheduler type {cfg.get('type')!r}")
        return schedule

    def build_optimizer(self):
        """optax transform from the config's ``"optimizer"`` section, or None.

        The reference's DummyOptim workflow (utils/deepspeed.py:225-270):
        the user passes a placeholder and the engine builds the real
        optimizer from the json. Here the json builds the optax chain
        directly — pass the result to ``Accelerator.prepare``. When the
        config also carries a ``"scheduler"`` section, its schedule becomes
        the optax learning rate (jax-idiomatic: LR follows the update count
        inside the executable), so the warmup/decay actually applies.
        "auto" values fall back to DeepSpeed's defaults.
        """
        import optax

        cfg = (self.hf_ds_config or {}).get("optimizer")
        if not cfg:
            return None
        p = {k: v for k, v in cfg.get("params", {}).items() if v != "auto"}
        lr = self._schedule_fn() or float(p.get("lr", 1e-3))
        betas = p.get("betas", (0.9, 0.999))
        eps = float(p.get("eps", 1e-8))
        wd = float(p.get("weight_decay", 0.0))
        typ = str(cfg.get("type", "AdamW")).lower()
        if typ in ("adam", "adamw"):
            # DeepSpeed's FusedAdam defaults to adam_w_mode=True, so "Adam"
            # with weight_decay is decoupled AdamW there too; plain adam only
            # when no decay is requested.
            if typ == "adam" and wd == 0.0:
                return optax.adam(lr, b1=float(betas[0]), b2=float(betas[1]), eps=eps)
            return optax.adamw(lr, b1=float(betas[0]), b2=float(betas[1]), eps=eps,
                               weight_decay=wd)
        if typ == "sgd":
            return optax.sgd(lr, momentum=float(p.get("momentum", 0.0)))
        if typ == "lion":
            return optax.lion(lr, b1=float(betas[0]), b2=float(betas[1]),
                              weight_decay=wd)
        raise ValueError(f"unsupported DeepSpeed optimizer type {cfg.get('type')!r}")

    def build_scheduler(self):
        """LRScheduler over the config's schedule, or None (the
        DummyScheduler workflow). Reporting surface only
        (``get_last_lr``): when built via :meth:`build_optimizer`, the same
        schedule is already the optimizer's learning rate."""
        schedule = self._schedule_fn()
        if schedule is None:
            return None
        from ..scheduler import LRScheduler

        return LRScheduler(schedule)

    def to_fsdp_plugin(self) -> FullyShardedDataParallelPlugin:
        """Translate the ZeRO stage onto an FSDP sharding policy."""
        if self.zero_stage >= 3:
            strategy = "FULL_SHARD"
        elif self.zero_stage >= 1:
            strategy = "SHARD_GRAD_OP"   # params gathered for fwd+bwd; opt state sharded
        else:
            strategy = "NO_SHARD"
        return FullyShardedDataParallelPlugin(
            sharding_strategy=strategy,
            cpu_offload=(self.offload_optimizer_device == "cpu" or self.offload_param_device == "cpu"),
            # ZeRO stage >= 1 is, definitionally, optimizer-state sharding:
            # partition the moments over the dp axis.
            zero_sharding=self.zero_stage >= 1,
        )


@dataclass
class MegatronLMPlugin(KwargsHandler):
    """Megatron-LM-config translator (reference: utils/dataclasses.py:1609-1921).

    tp/pp/dp degrees map directly onto mesh axes; sequence parallelism maps to
    the TP plugin's activation sharding; distributed optimizer maps to
    fsdp-axis optimizer-state sharding.
    """

    tp_degree: int = 1
    pp_degree: int = 1
    num_micro_batches: int = 1
    sequence_parallelism: bool = False
    use_distributed_optimizer: bool = False
    gradient_clipping: Optional[float] = 1.0
    recompute_activations: bool = False

    def to_plugins(self):
        """Translate Megatron degrees into (tp, pp, fsdp) plugins."""
        tp = TensorParallelPlugin(tp_size=self.tp_degree, sequence_parallelism=self.sequence_parallelism)
        pp = PipelineParallelPlugin(pp_size=self.pp_degree, num_microbatches=self.num_micro_batches)
        fsdp = None
        if self.use_distributed_optimizer:
            fsdp = FullyShardedDataParallelPlugin(sharding_strategy="SHARD_GRAD_OP")
        return tp, pp, fsdp


def add_model_config_to_megatron_parser(model_config, plugin: Optional[MegatronLMPlugin] = None):
    """Stamp a model config's dimensions into a Megatron-style arg dict.

    The reference builds megatron argparse args from ``model.config`` per
    family (reference: utils/dataclasses.py:1939-2068 — gpt/bert/t5 each
    copy layers/hidden/heads/positions/vocab into megatron names). Here the
    mesh translation consumes the same dimensions, so this returns them
    under the reference's arg names and validates them against the
    plugin's degrees — the checks Megatron would raise at engine setup
    (hidden/heads divisible by tp, layers by pp) fail here, before any
    compilation.

    Args:
      model_config: an HF-style config object or plain dict (``hidden_size``
        / ``n_embd``, ``num_hidden_layers`` / ``n_layer``, ...).
      plugin: degrees to validate against (default: an unsharded plan).

    Returns ``(plugin, megatron_args dict)``.
    """
    plugin = plugin or MegatronLMPlugin()
    get = (model_config.get if isinstance(model_config, dict)
           else lambda k, d=None: getattr(model_config, k, d))

    def first(*names, required=True):
        for n in names:
            v = get(n)
            if v is not None:
                return v
        if required:
            raise ValueError(f"model config provides none of {names}")
        return None

    args = {
        "num_layers": int(first("num_hidden_layers", "n_layer", "num_layers")),
        "hidden_size": int(first("hidden_size", "n_embd", "d_model")),
        "num_attention_heads": int(first("num_attention_heads", "n_head", "num_heads")),
        "max_position_embeddings": int(first(
            "max_position_embeddings", "n_positions", required=False) or 0) or None,
        "orig_vocab_size": int(first("vocab_size")),
    }
    if args["hidden_size"] % plugin.tp_degree:
        raise ValueError(
            f"hidden_size {args['hidden_size']} not divisible by tp_degree {plugin.tp_degree}")
    if args["num_attention_heads"] % plugin.tp_degree:
        raise ValueError(
            f"num_attention_heads {args['num_attention_heads']} not divisible by "
            f"tp_degree {plugin.tp_degree}")
    if args["num_layers"] % plugin.pp_degree:
        raise ValueError(
            f"num_layers {args['num_layers']} not divisible by pp_degree {plugin.pp_degree}")
    return plugin, args
