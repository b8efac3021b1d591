"""Test configuration: run everything on an 8-device virtual CPU mesh.

Mirrors the reference's strategy of testing distributed logic without a
cluster (SURVEY.md §4): JAX's host-platform device-count emulation is the
"fake backend" the reference lacks. The suite asks for the CPU explicitly
(env var for child interpreters, jax.config for this process).
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in flags:
    flags += " --xla_force_host_platform_device_count=8"
if "--xla_backend_optimization_level" not in flags:
    # Tests are compile-bound (hundreds of tiny jit graphs on one CPU core);
    # skipping backend optimization passes cuts the suite's wall time ~2.7x
    # without changing semantics. Never set outside tests.
    flags += " --xla_backend_optimization_level=0"
os.environ["XLA_FLAGS"] = flags.strip()
os.environ["JAX_PLATFORMS"] = "cpu"

# Persistent XLA compile cache across test runs AND across the suite's many
# child interpreters (CLI/example/multiprocess tests inherit the env var):
# the suite is compile-bound, and a warm cache cuts ~30-40% of wall time.
# Keyed by HLO + flags, so correctness is unaffected. A directory placed
# from outside wins; otherwise the checkout's fixed, git-ignored .jax_cache
# (the same default as accelerate_tpu.utils.platforms).
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"))
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.3")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture
def mesh_8():
    """An 8-device (fsdp=4, tp=2) mesh over the virtual CPU devices."""
    import numpy as np
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()).reshape(4, 2), ("fsdp", "tp"))


@pytest.fixture(autouse=True)
def reset_state():
    """Reset the state singletons between tests (reference: AccelerateTestCase,
    test_utils/testing.py:479)."""
    yield
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

    AcceleratorState._reset_state()
    GradientState._reset_state()
    PartialState._reset_state()
