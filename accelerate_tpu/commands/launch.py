"""`accelerate-tpu launch` (reference: commands/launch.py :140-1184).

TPU-first redesign of the launch layer. The reference forks one process per
GPU via torch elastic (`multi_gpu_launcher` :774) or per TPU core via
`xmp.spawn` (`tpu_launcher` :862). JAX inverts this: **one process per
host**, all local chips driven by that process, multi-host rendezvous via
`jax.distributed.initialize`. So:

* single host  → one subprocess with mesh/precision env (reference
  `simple_launcher` :762 is the right shape, not the elastic agent)
* TPU pod      → same command on every host; host identity comes from TPU
  metadata (JAX autodetects) or explicit coordinator env vars; the
  `--gcloud` path SSHes the command to all pod workers like the reference's
  `tpu_pod_launcher` :893 does via xla_dist
* debugging    → `--use_cpu_emulation` runs the script on N virtual CPU
  devices (the framework's fake backend; SURVEY.md §4 takeaway)

Everything is communicated through ``ACCELERATE_TPU_*`` env vars, mirroring
the reference's env-var bridge (utils/launch.py :184-313).
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys

from .config.config_args import ClusterConfig, load_config_from_file


def launch_command_parser(subparsers=None):
    description = "Launch a training script on this host's TPU devices (or a pod)"
    if subparsers is not None:
        parser = subparsers.add_parser("launch", description=description, allow_abbrev=False)
    else:
        parser = argparse.ArgumentParser("accelerate-tpu launch", description=description,
                                         allow_abbrev=False)
    parser.add_argument("--config_file", default=None, help="Config YAML to launch with")
    parser.add_argument("--mixed_precision", default=None, choices=["no", "bf16", "fp16"])
    parser.add_argument("--debug", action="store_true", default=None,
                        help="Enable collective shape checking (reference: launch --debug)")
    # Mesh overrides.
    parser.add_argument("--dp", type=int, default=None, help="data-parallel mesh axis")
    parser.add_argument("--fsdp", type=int, default=None, help="param-shard (ZeRO/FSDP) mesh axis")
    parser.add_argument("--tp", type=int, default=None, help="tensor-parallel mesh axis")
    parser.add_argument("--cp", type=int, default=None, help="context-parallel mesh axis")
    parser.add_argument("--ep", type=int, default=None, help="expert-parallel mesh axis")
    parser.add_argument("--pp", type=int, default=None, help="pipeline-parallel mesh axis")
    # Multi-host.
    parser.add_argument("--num_machines", type=int, default=None, help="number of hosts")
    parser.add_argument("--machine_rank", type=int, default=None, help="this host's id")
    parser.add_argument("--main_process_ip", default=None)
    parser.add_argument("--main_process_port", type=int, default=None)
    parser.add_argument("--gcloud", action="store_true",
                        help="Run the command on every worker of --tpu_name via gcloud ssh "
                             "(reference: tpu_pod_launcher :893)")
    parser.add_argument("--tpu_name", default=None)
    parser.add_argument("--tpu_zone", default=None)
    # Local multi-process (emulated multi-host).
    parser.add_argument("--num_processes", type=int, default=None,
                        help="Spawn N local processes rendezvousing via "
                             "jax.distributed (CPU emulation; exercises real "
                             "multi-process semantics on one machine)")
    # Fault tolerance (reference: torch elastic max_restarts, launchers.py:49-54).
    parser.add_argument("--max_restarts", type=int, default=0,
                        help="Relaunch the script up to N times on nonzero exit "
                             "(preemption/fault recovery; scripts resume from "
                             "their latest checkpoint)")
    parser.add_argument("--restart_backoff", type=float, default=2.0,
                        help="Seconds to wait before a restart (doubles each time)")
    # Debug backend.
    parser.add_argument("--use_cpu_emulation", action="store_true", default=None,
                        help="Run on N virtual CPU devices instead of TPU")
    parser.add_argument("--emulated_device_count", type=int, default=None)
    parser.add_argument("--module", action="store_true",
                        help="Interpret the script as a python module (python -m)")
    parser.add_argument("training_script", help="Script to launch")
    parser.add_argument("training_script_args", nargs=argparse.REMAINDER,
                        help="Arguments passed through to the script")
    if subparsers is not None:
        parser.set_defaults(func=launch_command)
    return parser


_OVERRIDES = [
    ("mixed_precision", "mixed_precision"), ("debug", "debug"),
    ("dp", "mesh_dp"), ("fsdp", "mesh_fsdp"), ("tp", "mesh_tp"),
    ("cp", "mesh_cp"), ("ep", "mesh_ep"), ("pp", "mesh_pp"),
    ("num_machines", "num_machines"), ("machine_rank", "machine_rank"),
    ("main_process_ip", "main_process_ip"), ("main_process_port", "main_process_port"),
    ("tpu_name", "tpu_name"), ("tpu_zone", "tpu_zone"),
    ("use_cpu_emulation", "use_cpu_emulation"),
    ("emulated_device_count", "emulated_device_count"),
]


def _resolve_config(args) -> ClusterConfig:
    """Config file + CLI flags → effective config (reference:
    _validate_launch_command :972 merge semantics — CLI wins)."""
    cfg = load_config_from_file(args.config_file)
    for arg_name, cfg_name in _OVERRIDES:
        val = getattr(args, arg_name, None)
        if val is not None:
            setattr(cfg, cfg_name, val)
    return cfg


def _build_command(args) -> list[str]:
    cmd = [sys.executable]
    if args.module:
        cmd += ["-m", args.training_script]
    else:
        cmd += [args.training_script]
    return cmd + list(args.training_script_args)


def simple_launcher(args, cfg: ClusterConfig) -> int:
    """One subprocess on this host (reference: simple_launcher :762)."""
    env = {**os.environ, **cfg.launch_env()}
    cmd = _build_command(args)
    proc = subprocess.run(cmd, env=env)
    return proc.returncode


def _free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def multi_process_launcher(args, cfg: ClusterConfig) -> int:
    """N local processes, one jax.distributed world (CPU emulation).

    The reference tests its multi-worker semantics by forking real processes
    (reference: tests/test_multigpu.py:50-52); this is the launch-side
    support: each child gets a process id + shared coordinator address, and
    `PartialState` rendezvouses them into one world. Local devices per child
    come from ``--emulated_device_count`` (default 1 in this mode — NOT the
    config file's single-process default), so the global device count is
    ``num_processes x emulated_device_count``.
    """
    from ..utils.environment import env_var

    n = args.num_processes
    cfg.use_cpu_emulation = True  # a single local TPU cannot be shared
    # The config-file default (8) targets single-process emulation; an
    # explicit flag wins, otherwise one device per process.
    cfg.emulated_device_count = args.emulated_device_count or 1
    coordinator = f"127.0.0.1:{_free_port()}"
    base_env = {**os.environ, **cfg.launch_env()}
    cmd = _build_command(args)
    procs = []
    for i in range(n):
        env = dict(base_env)
        env[env_var("COORDINATOR_ADDRESS")] = coordinator
        env[env_var("NUM_PROCESSES")] = str(n)
        env[env_var("PROCESS_ID")] = str(i)
        procs.append(subprocess.Popen(cmd, env=env))
    rcs = [p.wait() for p in procs]
    return max(rcs, key=abs) if rcs else 0


def launch_with_restarts(run, args) -> int:
    """Retry wrapper: relaunch on nonzero exit up to ``--max_restarts`` with
    exponential backoff (reference: torch elastic's max_restarts,
    launchers.py:49-54 — restart-the-world semantics, which is also how TPU
    pods recover: scripts resume from their latest checkpoint via
    ProjectConfiguration.automatic_checkpoint_naming + load_state)."""
    import time

    backoff = max(args.restart_backoff, 0.0)
    attempt = 0
    while True:
        os.environ["ACCELERATE_TPU_RESTART_COUNT"] = str(attempt)
        rc = run()
        if rc == 0 or attempt >= args.max_restarts:
            return rc
        attempt += 1
        print(f"[accelerate-tpu launch] exit code {rc}; restart {attempt}/"
              f"{args.max_restarts} in {backoff:.1f}s", file=sys.stderr)
        time.sleep(backoff)
        backoff = min(backoff * 2, 60.0)


def gcloud_pod_launcher(args, cfg: ClusterConfig) -> int:
    """Replicate the command onto every pod worker via `gcloud compute tpus
    tpu-vm ssh --worker=all` (reference: tpu_pod_launcher :893 /
    commands/tpu.py). On the workers, JAX's TPU runtime autodetects host
    identity, so no per-worker env differs."""
    if not cfg.tpu_name:
        print("--gcloud requires --tpu_name (or tpu_name in the config file)", file=sys.stderr)
        return 2
    inner_env = " ".join(f"{k}={v!r}" for k, v in cfg.launch_env().items())
    inner_cmd = " ".join(_build_command(args))
    remote = f"cd {os.getcwd()} && {inner_env} {inner_cmd}"
    cmd = ["gcloud", "compute", "tpus", "tpu-vm", "ssh", cfg.tpu_name,
           "--worker=all", f"--command={remote}"]
    if cfg.tpu_zone:
        cmd.insert(5, f"--zone={cfg.tpu_zone}")
    print("Running:", " ".join(cmd))
    return subprocess.run(cmd).returncode


def validate_launch(args, cfg: ClusterConfig) -> list[str]:
    """Pre-flight checks before any process is spawned (reference:
    _validate_launch_command :972). Returns a list of human-readable
    problems; empty means launch."""
    problems = []
    if not args.module and not os.path.exists(args.training_script):
        problems.append(f"training script not found: {args.training_script}")
    # MeshConfig's contract: any ONE axis may be -1 (absorb the remaining
    # devices); everything else must be positive.
    absorbing = []
    for axis in ("mesh_dp", "mesh_fsdp", "mesh_tp", "mesh_cp", "mesh_ep", "mesh_pp"):
        val = getattr(cfg, axis)
        if val is None:
            continue
        if val == -1:
            absorbing.append(axis)
        elif val < 1:
            problems.append(f"{axis} must be positive or -1 (all remaining), got {val}")
    if len(absorbing) > 1:
        problems.append(f"only one mesh axis may be -1, got {absorbing}")
    if args.num_processes is not None and args.num_processes < 1:
        problems.append(f"--num_processes must be >= 1, got {args.num_processes}")
    if args.max_restarts < 0:
        problems.append(f"--max_restarts must be >= 0, got {args.max_restarts}")
    n_machines = cfg.num_machines or 1
    if cfg.machine_rank is not None and not 0 <= cfg.machine_rank < n_machines:
        problems.append(
            f"machine_rank {cfg.machine_rank} out of range for num_machines {n_machines}")
    if n_machines > 1 and not cfg.main_process_ip and not cfg.tpu_name:
        problems.append(
            "multi-host launch needs a rendezvous: set main_process_ip/port "
            "(or tpu_name for TPU-metadata autodetection)")
    if args.num_processes and args.num_processes > 1 and n_machines > 1:
        problems.append(
            "--num_processes (local CPU emulation) and num_machines > 1 "
            "(real multi-host) are mutually exclusive")
    return problems


def launch_command(args) -> int:
    cfg = _resolve_config(args)
    problems = validate_launch(args, cfg)
    if problems:
        for p in problems:
            print(f"[accelerate-tpu launch] error: {p}", file=sys.stderr)
        return 2
    if args.gcloud or (cfg.compute_environment == "TPU_POD" and cfg.tpu_name
                       and cfg.machine_rank == 0):
        # Pod preemption is the main restart customer — wrap this path too.
        return launch_with_restarts(lambda: gcloud_pod_launcher(args, cfg), args)
    if args.num_processes and args.num_processes > 1:
        return launch_with_restarts(lambda: multi_process_launcher(args, cfg), args)
    return launch_with_restarts(lambda: simple_launcher(args, cfg), args)


def main():
    parser = launch_command_parser()
    args = parser.parse_args()
    return launch_command(args)


if __name__ == "__main__":
    sys.exit(main() or 0)
