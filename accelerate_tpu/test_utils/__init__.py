"""Test harness utilities (reference: src/accelerate/test_utils/ —
testing.py require_* decorators :132-443, AccelerateTestCase :479,
training fixtures training.py:22-50).

Distributed test bodies live in ``scripts/`` so they can run standalone
under the real launcher or emulated devices, mirroring the reference's
subprocess-relaunch pattern (SURVEY.md §4 pattern 2).
"""

from __future__ import annotations

import functools
import os
import unittest

from .training import RegressionData, init_mlp, mlp_apply, mse_loss  # noqa: F401


def _jax():
    import jax

    return jax


def require_tpu(test_case):
    """Skip unless a real TPU backend is attached (reference: require_tpu :263)."""
    import jax

    return unittest.skipUnless(jax.default_backend() == "tpu", "test requires TPU")(test_case)


def require_multi_device(test_case):
    """Skip unless >1 device (real or emulated) (reference: require_multi_device :304)."""
    import jax

    return unittest.skipUnless(jax.device_count() > 1, "test requires multiple devices")(test_case)


def require_multi_process(test_case):
    """Skip unless a multi-host job (reference: require_multi_gpu-ish gating)."""
    import jax

    return unittest.skipUnless(jax.process_count() > 1, "test requires multiple processes")(test_case)


def require_orbax(test_case):
    try:
        import orbax.checkpoint  # noqa: F401

        ok = True
    except ImportError:
        ok = False
    return unittest.skipUnless(ok, "test requires orbax")(test_case)


def require_transformers(test_case):
    try:
        import transformers  # noqa: F401

        ok = True
    except ImportError:
        ok = False
    return unittest.skipUnless(ok, "test requires transformers")(test_case)


def use_emulated_devices(count: int = 8):
    """Force this process onto N virtual CPU devices. Must run before the
    first JAX backend use (the framework's fake-backend strategy,
    SURVEY.md §4 takeaway)."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = f"{flags} --xla_force_host_platform_device_count={count}".strip()
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")


class AccelerateTestCase(unittest.TestCase):
    """Resets the state singletons between tests (reference:
    AccelerateTestCase, test_utils/testing.py:479)."""

    def tearDown(self):
        super().tearDown()
        from ..state import AcceleratorState, GradientState, PartialState

        AcceleratorState._reset_state()
        GradientState._reset_state()
        PartialState._reset_state()


def slow(test_case):
    """Gate long tests behind RUN_SLOW=1 (reference: testing.py slow decorator)."""
    run_slow = os.environ.get("RUN_SLOW", "0") == "1"
    return unittest.skipUnless(run_slow, "test is slow; set RUN_SLOW=1")(test_case)


def assert_allclose_tree(a, b, rtol=1e-5, atol=1e-6):
    import jax
    import numpy as np

    for pa, pb in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        np.testing.assert_allclose(np.asarray(pa), np.asarray(pb), rtol=rtol, atol=atol)
