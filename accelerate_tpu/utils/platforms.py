"""Explicit platform choices: nothing here guesses or falls back.

A process runs on the device JAX gives it. The helpers below are for callers
that *ask* for something: tests and host-only tools that want the CPU
(:func:`force_cpu_platform`, :func:`request_virtual_cpu_devices`), result
producers that name the chip they ran on (:func:`device_kind`), and every
entry point that compiles (:func:`enable_compilation_cache`).
"""

from __future__ import annotations

import os
import re

_DEVICE_COUNT_FLAG = "--xla_force_host_platform_device_count"

#: Where the persistent compile cache lives when ``JAX_COMPILATION_CACHE_DIR``
#: is unset: a fixed path in the checkout (git-ignored). The path is part of
#: the cache key, so it is never made from a temporary name, a pid or a time.
DEFAULT_COMPILATION_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def request_virtual_cpu_devices(n: int) -> None:
    """Ask XLA's host platform for ``n`` virtual devices.

    Takes effect only if the CPU client has not been created yet; setting the
    flag after that is a silent no-op, so call this as early as possible.
    An existing smaller request is raised to ``n``; never shrunk.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    match = re.search(rf"{_DEVICE_COUNT_FLAG}=(\d+)", flags)
    if match is None:
        os.environ["XLA_FLAGS"] = f"{flags} {_DEVICE_COUNT_FLAG}={n}".strip()
    elif int(match.group(1)) < n:
        os.environ["XLA_FLAGS"] = (
            flags[: match.start()] + f"{_DEVICE_COUNT_FLAG}={n}" + flags[match.end():]
        )


def force_cpu_platform(num_virtual_devices: int | None = None) -> None:
    """Pin this process (and children) to the host CPU platform.

    For callers that were asked for the CPU: tests, dry runs on virtual
    devices, and host-only tools (``estimate-memory``, ``merge-weights``)
    that must never take the chip from a running server. Safe to call after
    ``import jax`` as long as no device query has run yet; the env var makes
    spawned subprocesses inherit the pin.
    """
    if num_virtual_devices:
        request_virtual_cpu_devices(num_virtual_devices)
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")


def enable_compilation_cache() -> str:
    """Turn on jax's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, places the cache from outside:
    jax reads the variable itself and nothing here (or anywhere else in the
    package) overrides it. Unset, the cache is the fixed
    :data:`DEFAULT_COMPILATION_CACHE`. The one place the package touches
    ``jax_compilation_cache_dir``: ``serve``, the ``Accelerator``,
    ``bench.py`` and ``chip_smoke.py`` all come through here.

    An entry is keyed with the program's metadata. jax leaves it out by
    default, and the parts a program names (``observability/program_parts.py``)
    are metadata: an executable that a tree without a scope cached would be
    handed to the tree that declares it, old ``op_name``s and all, and a device
    trace would show the other tree's parts (seen on the chip, PERF.md
    section 6, PR 37). The price: source locations are metadata too, so an
    entry is found again by the same files at the same path, as a program
    with Mosaic kernels always was.
    """
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILATION_CACHE)
    return DEFAULT_COMPILATION_CACHE


def device_kind() -> str:
    """``device_kind`` of device 0 as jax reports it (e.g. "TPU v5 lite"):
    the string every result names its chip by."""
    import jax

    return jax.devices()[0].device_kind
