"""`accelerate-tpu env` diagnostic dump (reference: commands/env.py)."""

from __future__ import annotations

import argparse
import platform

from .config.config_args import default_config_file, load_config_from_file


def env_command(args) -> int:
    import jax

    import accelerate_tpu

    # Reports the device jax gives this process. On a host whose chip a
    # trainer or server already holds, run it with JAX_PLATFORMS=cpu: a chip
    # belongs to one process at a time.
    info = {
        "platform": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "device_count": jax.device_count(),
        "devices": [str(d) for d in jax.devices()],
        "process_count": jax.process_count(),
    }
    lines = {
        "accelerate_tpu version": accelerate_tpu.__version__,
        "Platform": platform.platform(),
        "Python version": platform.python_version(),
        "jax version": jax.__version__,
        "Backend": info["platform"],
        "Device kind": info["device_kind"],
        "Device count": info["device_count"],
        "Devices": ", ".join(info["devices"]),
        "Process count": info["process_count"],
    }
    try:
        import flax

        lines["flax version"] = flax.__version__
    except ImportError:
        pass
    try:
        import optax

        lines["optax version"] = optax.__version__
    except ImportError:
        pass

    print("\nCopy-and-paste the text below in your GitHub issue\n")
    for k, v in lines.items():
        print(f"- {k}: {v}")

    from pathlib import Path

    cfg_path = Path(args.config_file) if args.config_file else default_config_file()
    if cfg_path.exists():
        cfg = load_config_from_file(args.config_file)
        print(f"- accelerate-tpu config ({cfg_path}):")
        for k, v in cfg.to_dict().items():
            print(f"\t- {k}: {v}")
    else:
        print(f"- accelerate-tpu config: not found ({cfg_path})")
    return 0


def env_command_parser(subparsers=None):
    description = "Print environment information for bug reports"
    if subparsers is not None:
        parser = subparsers.add_parser("env", description=description)
    else:
        parser = argparse.ArgumentParser("accelerate-tpu env", description=description)
    parser.add_argument("--config_file", default=None)
    if subparsers is not None:
        parser.set_defaults(func=env_command)
    return parser
