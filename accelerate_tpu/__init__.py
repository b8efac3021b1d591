"""accelerate_tpu — a TPU-native training-portability framework.

Brand-new implementation with the capabilities of HuggingFace Accelerate
(reference mounted at /root/reference, snapshot 2024-10-08), built
TPU-first on JAX/XLA: GSPMD sharding over a named device mesh, optax
optimizers, orbax-style sharded checkpoints, Pallas kernels for attention
and quantization. See SURVEY.md for the capability blueprint.
"""

__version__ = "0.2.0"

from .accelerator import AcceleratedModel, Accelerator, Model
from .adapters import (
    AdapterBank,
    AdapterBankFull,
    LoRAConfig,
    LoRATrainState,
    UnknownAdapterError,
    init_lora_params,
    load_adapter,
    merge_adapter,
    prepare_lora,
    save_adapter,
)
from .big_modeling import (
    BlockSpec,
    UserCpuOffloadHook,
    cpu_offload_with_hook,
    init_on_device,
    StreamedModel,
    cpu_offload,
    disk_offload,
    dispatch_model,
    init_empty_weights,
    load_checkpoint_and_dispatch,
    load_hf_checkpoint_and_dispatch,
    load_checkpoint_in_model,
)
from .data_loader import NumpyDataLoader, prepare_data_loader, skip_first_batches
from .generation import (
    assisted_generate,
    beam_search_generate,
    generate,
    greedy_generate,
    prompt_lookup_generate,
    seq2seq_generate,
)
from .inference import PipelinedInferencer, prepare_pipeline, prepare_pippy
from .serving import Request, RequestStatus, ServingEngine, ServingStats
from .launchers import debug_launcher, notebook_launcher
from .local_sgd import LocalSGD
from .logging import get_logger
from .optimizer import AcceleratedOptimizer
from .precision import Policy, policy_for
from .scheduler import AcceleratedScheduler, LRScheduler
from .state import AcceleratorState, GradientState, PartialState
from .parallel.mesh import MeshConfig, make_mesh
from .utils.dataclasses import (
    AutocastKwargs,
    ContextParallelPlugin,
    DataLoaderConfiguration,
    DDPCommunicationHookType,
    DeepSpeedPlugin,
    DistributedDataParallelKwargs,
    DistributedInitKwargs,
    DistributedType,
    ExpertParallelPlugin,
    FP8RecipeKwargs,
    FullyShardedDataParallelPlugin,
    GradientAccumulationPlugin,
    GradScalerKwargs,
    InitProcessGroupKwargs,
    MegatronLMPlugin,
    PipelineParallelPlugin,
    ProfileKwargs,
    ProjectConfiguration,
    TensorParallelPlugin,
)
from .utils.modeling import (
    calculate_maximum_sizes,
    compute_module_sizes,
    get_balanced_memory,
    get_max_memory,
    infer_auto_device_map,
)
from .utils.imports import is_rich_available
from .utils.memory import find_executable_batch_size
from .utils.random import set_seed, synchronize_rng_states

if is_rich_available():
    # Exact reference-surface parity: `from accelerate import rich` works
    # when rich is installed (reference: __init__.py:49-50).
    from .utils import rich  # noqa: F401
