"""Capability probes.

Parity with the reference's ``utils/imports.py`` (~45 ``is_*`` probes,
reference: src/accelerate/utils/imports.py). On a JAX/TPU stack most CUDA-era
probes collapse; what remains is platform detection (tpu/cpu/gpu backends,
multi-host), optional tracker/integration libraries, and IO formats.
"""

from __future__ import annotations

import importlib.metadata
import importlib.util
import os
from functools import lru_cache


def _is_package_available(pkg_name: str, metadata_name: str | None = None) -> bool:
    exists = importlib.util.find_spec(pkg_name) is not None
    if exists and metadata_name is not None:
        try:
            importlib.metadata.metadata(metadata_name)
            return True
        except importlib.metadata.PackageNotFoundError:
            return False
    return exists


@lru_cache(maxsize=None)
def is_jax_available() -> bool:
    return _is_package_available("jax")


@lru_cache(maxsize=None)
def is_flax_available() -> bool:
    return _is_package_available("flax")


@lru_cache(maxsize=None)
def is_optax_available() -> bool:
    return _is_package_available("optax")


@lru_cache(maxsize=None)
def is_orbax_available() -> bool:
    return _is_package_available("orbax")


@lru_cache(maxsize=None)
def is_safetensors_available() -> bool:
    return _is_package_available("safetensors")


@lru_cache(maxsize=None)
def is_torch_available() -> bool:
    """torch is only an optional *data-source* dependency (DataLoader interop)."""
    return _is_package_available("torch")


@lru_cache(maxsize=None)
def is_transformers_available() -> bool:
    return _is_package_available("transformers")


@lru_cache(maxsize=None)
def is_datasets_available() -> bool:
    return _is_package_available("datasets")


@lru_cache(maxsize=None)
def is_einops_available() -> bool:
    return _is_package_available("einops")


@lru_cache(maxsize=None)
def is_grain_available() -> bool:
    return _is_package_available("grain")


# ---------------------------------------------------------------------------
# Trackers (reference: tracking.py integrations)
# ---------------------------------------------------------------------------

@lru_cache(maxsize=None)
def is_tensorboard_available() -> bool:
    return _is_package_available("tensorboardX") or _is_package_available("tensorboard")


@lru_cache(maxsize=None)
def is_wandb_available() -> bool:
    return _is_package_available("wandb")


@lru_cache(maxsize=None)
def is_comet_ml_available() -> bool:
    return _is_package_available("comet_ml")


@lru_cache(maxsize=None)
def is_mlflow_available() -> bool:
    return _is_package_available("mlflow")


@lru_cache(maxsize=None)
def is_aim_available() -> bool:
    return _is_package_available("aim")


@lru_cache(maxsize=None)
def is_clearml_available() -> bool:
    return _is_package_available("clearml")


@lru_cache(maxsize=None)
def is_dvclive_available() -> bool:
    return _is_package_available("dvclive")


@lru_cache(maxsize=None)
def is_rich_available() -> bool:
    return _is_package_available("rich")


@lru_cache(maxsize=None)
def is_tqdm_available() -> bool:
    return _is_package_available("tqdm")


@lru_cache(maxsize=None)
def is_pandas_available() -> bool:
    return _is_package_available("pandas")


@lru_cache(maxsize=None)
def is_boto3_available() -> bool:
    return _is_package_available("boto3")


# ---------------------------------------------------------------------------
# Platform probes (replaces the reference's cuda/xpu/npu/mlu/musa zoo,
# reference: utils/imports.py:157 is_torch_xla_available)
# ---------------------------------------------------------------------------

def _jax_backend() -> str:
    import jax

    return jax.default_backend()


def is_tpu_available() -> bool:
    """True when the default JAX backend drives real TPU chips."""
    if not is_jax_available():
        return False
    if os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
        return False
    return _jax_backend() == "tpu"


def is_gpu_available() -> bool:
    if not is_jax_available():
        return False
    return _jax_backend() in ("gpu", "cuda", "rocm")


def is_cpu_only() -> bool:
    return not is_tpu_available() and not is_gpu_available()


def is_multi_host() -> bool:
    """True when JAX runs as one process of a multi-process job."""
    if not is_jax_available():
        return False
    import jax

    try:
        return jax.process_count() > 1
    except Exception:
        return False


def is_pallas_available() -> bool:
    """Pallas TPU lowering is available (always bundled with jax>=0.4.x)."""
    if not is_jax_available():
        return False
    return importlib.util.find_spec("jax.experimental.pallas") is not None


def is_ipython_available() -> bool:
    return _is_package_available("IPython")


def is_notebook() -> bool:
    """Running inside a Jupyter kernel (for notebook_launcher detection)."""
    if not is_ipython_available():
        return False
    try:
        from IPython import get_ipython

        ip = get_ipython()
        return ip is not None and "IPKernelApp" in getattr(ip, "config", {})
    except Exception:
        return False
