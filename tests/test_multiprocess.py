"""Real multi-process lane: N processes, one jax.distributed world, launched
through the actual CLI (reference pattern: tests/test_multigpu.py:50-52
forking real workers + test_utils/scripts/test_script.py:770-829).

Also covers the elastic-ish launch semantics: --max_restarts relaunch on
failure and checkpoint auto-resume (reference: torch elastic max_restarts,
launchers.py:49-54)."""

import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


def _launch(args, timeout=600, env_extra=None):
    env = {**os.environ}
    # Scripts may live outside the repo (tmp_path); keep the package importable.
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
    env.update(env_extra or {})
    cmd = [sys.executable, "-m", "accelerate_tpu.commands.accelerate_cli", "launch", *args]
    return subprocess.run(
        cmd, capture_output=True, text=True, timeout=timeout, cwd=str(REPO), env=env
    )


class TestMultiProcessLaunch:
    def test_omnibus_two_processes(self):
        res = _launch([
            "--num_processes", "2", "--emulated_device_count", "2",
            "--module", "accelerate_tpu.test_utils.scripts.test_script",
        ])
        assert res.returncode == 0, res.stdout[-3000:] + res.stderr[-3000:]
        assert "All omnibus checks passed" in res.stdout
        assert "2 process(es)" in res.stdout

    def test_ops_two_processes(self):
        res = _launch([
            "--num_processes", "2", "--emulated_device_count", "2",
            "--module", "accelerate_tpu.test_utils.scripts.test_ops_multiprocess",
        ])
        assert res.returncode == 0, res.stdout[-3000:] + res.stderr[-3000:]
        assert "All multi-process ops checks passed" in res.stdout
        for check in ("gather ok", "gather(global array) ok", "gather_object ok",
                      "broadcast ok", "reduce ok", "pad_across_processes ok",
                      "broadcast_object_list ok", "split_between_processes ok",
                      "checkpoint round-trip ok", "debug shape sanitizer ok"):
            assert check in res.stdout, f"missing: {check}"

    def test_composed_mesh_four_processes(self):
        """4 processes x 2 devices, dp=2 x fsdp=4 — every axis crosses
        process boundaries (reference: test_multigpu.py scales worlds with
        the device count)."""
        res = _launch([
            "--num_processes", "4", "--emulated_device_count", "2",
            "--dp", "2", "--fsdp", "4",
            "--module", "accelerate_tpu.test_utils.scripts.test_composed_mesh",
        ], timeout=600, env_extra={"FSDP_MIN_NUM_PARAMS": "64"})
        assert res.returncode == 0, res.stdout[-3000:] + res.stderr[-3000:]
        assert "composed-mesh checks passed" in res.stdout
        assert "fsdp sharding ok" in res.stdout
        assert "gather_for_metrics over composed mesh ok" in res.stdout


class TestMultiHostShape:
    """2 hosts x 4 devices — the pod-launcher shape (one process per HOST,
    several local devices), vs the other lane's one-device-per-process
    worlds (VERDICT r3 item 8)."""

    def test_two_machines_four_devices_each(self):
        """Two concurrent `launch --num_machines 2 --machine_rank R` runs —
        exactly how two pod hosts start — must rendezvous into one world
        and pass the topology/global-array/reduction checks."""
        import socket

        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        import threading

        results = {}

        def host(rank):
            results[rank] = _launch([
                "--num_machines", "2", "--machine_rank", str(rank),
                "--main_process_ip", "127.0.0.1", "--main_process_port", str(port),
                "--use_cpu_emulation", "--emulated_device_count", "4",
                "--module", "accelerate_tpu.test_utils.scripts.test_pod_shape",
            ], env_extra={"ATPU_TEST_EXPECT_RANK": str(rank)})

        threads = [threading.Thread(target=host, args=(r,)) for r in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for rank, res in results.items():
            assert res.returncode == 0, (
                f"rank {rank}: " + res.stdout[-3000:] + res.stderr[-3000:])
            assert "All pod-shape checks passed" in res.stdout
        assert "make_array_from_process_local_data ok" in results[0].stdout

    def test_notebook_launcher_multihost(self):
        """The same world assembled by notebook_launcher(num_nodes=2) — the
        multi-host notebook coordinator plumbing (launchers.py)."""
        import socket

        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        import re

        env = {**os.environ}
        # The pytest conftest pins 8 virtual devices; the child wants 4 per
        # host and the device-count flag is raise-only, so scrub it here.
        env["XLA_FLAGS"] = re.sub(
            r"--xla_force_host_platform_device_count=\d+", "",
            env.get("XLA_FLAGS", "")).strip()
        env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
        env["ATPU_TEST_NB_PORT"] = str(port)
        procs = []
        for rank in range(2):
            e = {**env, "ATPU_TEST_NB_RANK": str(rank)}
            procs.append(subprocess.Popen(
                [sys.executable, "-m",
                 "accelerate_tpu.test_utils.scripts.test_pod_shape", "--notebook"],
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
                cwd=str(REPO), env=e))
        outs = [p.communicate(timeout=600) for p in procs]
        for rank, (p, (out, err)) in enumerate(zip(procs, outs)):
            assert p.returncode == 0, f"rank {rank}: {out[-3000:]}{err[-3000:]}"
            assert "All pod-shape checks passed" in out


class TestReshardCheckpoint:
    def test_save_2_processes_restore_4(self, tmp_path):
        """Elastic resume: checkpoint written by a 2-process fsdp=4 world
        restores bit-compatibly into a 4-process dp=2 x fsdp=4 world."""
        workdir = tmp_path / "reshard"
        workdir.mkdir()
        module = "accelerate_tpu.test_utils.scripts.test_reshard_checkpoint"
        save = _launch([
            "--num_processes", "2", "--emulated_device_count", "2",
            "--dp", "1", "--fsdp", "4",
            "--module", module, str(workdir), "save",
        ], timeout=600)
        assert save.returncode == 0, save.stdout[-3000:] + save.stderr[-3000:]
        assert "saved under 2 processes" in save.stdout

        restore = _launch([
            "--num_processes", "4", "--emulated_device_count", "2",
            "--dp", "2", "--fsdp", "4",
            "--module", module, str(workdir), "restore",
        ], timeout=600)
        assert restore.returncode == 0, restore.stdout[-3000:] + restore.stderr[-3000:]
        assert "restored under 4 processes" in restore.stdout
        assert "checksums match" in restore.stdout
        assert "post-restore step ok" in restore.stdout


CRASH_ONCE = """
import os, sys
marker = sys.argv[1]
if not os.path.exists(marker):
    open(marker, "w").write("crashed")
    print("first attempt: crashing", flush=True)
    sys.exit(3)
print(f"recovered on restart {os.environ.get('ACCELERATE_TPU_RESTART_COUNT')}", flush=True)
"""


RESUME_TRAINER = """
import os, sys
import numpy as np
import jax

jax.config.update("jax_platforms", "cpu")
import optax

from accelerate_tpu import Accelerator, Model, ProjectConfiguration
from accelerate_tpu.test_utils.training import RegressionData, init_mlp, mlp_apply, mse_loss

project_dir, crash_marker = sys.argv[1], sys.argv[2]
acc = Accelerator(project_config=ProjectConfiguration(
    project_dir=project_dir, automatic_checkpoint_naming=True, total_limit=3))

class StepCounter:
    step = 0
    def state_dict(self): return {"step": self.step}
    def load_state_dict(self, sd): self.step = sd["step"]

counter = StepCounter()
model = Model(mlp_apply, init_mlp())
model, opt = acc.prepare(model, optax.sgd(0.05))
acc.register_for_checkpointing(counter)
try:
    acc.load_state()
    print(f"resumed at step {counter.step}", flush=True)
except FileNotFoundError:
    print("fresh start", flush=True)

data = RegressionData(32)
batch = {k: np.stack([s[k] for s in data[:16]]) for k in data[0]}
while counter.step < 10:
    acc.backward(mse_loss, batch)
    opt.step()
    opt.zero_grad()
    counter.step += 1
    if counter.step % 2 == 0:
        acc.save_state()
    if counter.step == 5 and not os.path.exists(crash_marker):
        open(crash_marker, "w").write("crashed")
        print("simulated preemption at step 5", flush=True)
        os._exit(7)  # hard kill: no cleanup, like a real preemption
print(f"finished at step {counter.step}", flush=True)
"""


PREEMPT_TRAINER = """
import os, signal, sys
import numpy as np
import jax

jax.config.update("jax_platforms", "cpu")
import optax

from accelerate_tpu import Accelerator, Model, ProjectConfiguration
from accelerate_tpu.test_utils.training import RegressionData, init_mlp, mlp_apply, mse_loss

project_dir, marker = sys.argv[1], sys.argv[2]
acc = Accelerator(project_config=ProjectConfiguration(
    project_dir=project_dir, automatic_checkpoint_naming=True))
acc.install_preemption_handler()

class StepCounter:
    step = 0
    def state_dict(self): return {"step": self.step}
    def load_state_dict(self, sd): self.step = sd["step"]

counter = StepCounter()
model = Model(mlp_apply, init_mlp())
model, opt = acc.prepare(model, optax.sgd(0.05))
acc.register_for_checkpointing(counter)
try:
    acc.load_state()
    print(f"resumed at step {counter.step}", flush=True)
except FileNotFoundError:
    print("fresh start", flush=True)

data = RegressionData(32)
batch = {k: np.stack([s[k] for s in data[:16]]) for k in data[0]}
while counter.step < 8:
    if acc.preemption_requested:
        acc.save_state()
        print(f"preempted: saved at step {counter.step}", flush=True)
        sys.exit(acc.PREEMPTED_EXIT_CODE)
    acc.backward(mse_loss, batch)
    opt.step()
    opt.zero_grad()
    counter.step += 1
    if counter.step == 4 and not os.path.exists(marker):
        open(marker, "w").write("preempting")
        # The pod scheduler's preemption notice: SIGTERM to this process.
        os.kill(os.getpid(), signal.SIGTERM)
print(f"finished at step {counter.step}", flush=True)
"""


class TestElasticLaunch:
    def test_sigterm_saves_and_resumes(self, tmp_path):
        """Graceful preemption: SIGTERM -> flag -> save_state -> exit(75);
        --max_restarts relaunches and load_state resumes exactly where the
        signal landed."""
        script = tmp_path / "preempt_trainer.py"
        script.write_text(PREEMPT_TRAINER)
        project = tmp_path / "project"
        marker = tmp_path / "marker"
        res = _launch([
            "--max_restarts", "1", "--restart_backoff", "0.1",
            "--use_cpu_emulation",
            str(script), str(project), str(marker),
        ], timeout=600)
        assert res.returncode == 0, res.stdout[-3000:] + res.stderr[-3000:]
        assert "preempted: saved at step 4" in res.stdout
        assert "resumed at step 4" in res.stdout
        assert "finished at step 8" in res.stdout

    def test_max_restarts_recovers(self, tmp_path):
        script = tmp_path / "crash_once.py"
        script.write_text(CRASH_ONCE)
        marker = tmp_path / "marker"
        res = _launch([
            "--max_restarts", "2", "--restart_backoff", "0.1",
            "--use_cpu_emulation", str(script), str(marker),
        ])
        assert res.returncode == 0, res.stdout + res.stderr
        assert "recovered on restart 1" in res.stdout
        assert "restart 1/2" in res.stderr

    def test_restarts_exhausted_propagates_failure(self, tmp_path):
        script = tmp_path / "always_crash.py"
        script.write_text("import sys; sys.exit(9)\n")
        res = _launch([
            "--max_restarts", "1", "--restart_backoff", "0.1",
            "--use_cpu_emulation", str(script),
        ])
        assert res.returncode == 9

    def test_auto_resume_from_checkpoint(self, tmp_path):
        script = tmp_path / "trainer.py"
        script.write_text(RESUME_TRAINER)
        project = tmp_path / "project"
        marker = tmp_path / "crash_marker"
        res = _launch([
            "--max_restarts", "1", "--restart_backoff", "0.1",
            "--use_cpu_emulation",
            str(script), str(project), str(marker),
        ], timeout=600)
        assert res.returncode == 0, res.stdout[-3000:] + res.stderr[-3000:]
        assert "simulated preemption at step 5" in res.stdout
        # The relaunch resumed from the step-4 checkpoint, not from scratch.
        assert "resumed at step 4" in res.stdout
        assert "finished at step 10" in res.stdout
        # Rotation kept at most 3 checkpoint dirs; resume continued the
        # numbering past the loaded one instead of overwriting checkpoint_0.
        ckpts = sorted((project / "checkpoints").glob("checkpoint_*"))
        assert len(ckpts) <= 3
        indices = sorted(int(p.name.split("_")[-1]) for p in ckpts)
        assert indices[-1] >= 4, indices
