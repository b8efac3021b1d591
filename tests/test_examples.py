"""Example drift guard (reference: tests/test_examples.py:42-45 —
compare_against_test + run-one-epoch execution).

The reference diffs every by_feature script against the canonical example
source; here drift is prevented structurally (all scripts import the shared
canonical pieces from examples/example_lib.py) and each script RUNS
end-to-end on the CPU mesh, which is the stronger guarantee.
"""

import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
EXAMPLES = REPO / "examples"
BY_FEATURE = EXAMPLES / "by_feature"

FAST_ARGS = ["--epochs", "1", "--batch_size", "16"]

# script -> extra args keeping the run small
SCRIPTS = {
    "gradient_accumulation.py": [],
    "automatic_gradient_accumulation.py": [],
    "checkpointing.py": [],       # project_dir injected per-test
    "early_stopping.py": ["--epochs", "2", "--patience", "1", "--min_delta", "10.0"],
    "local_sgd.py": [],
    "memory.py": [],
    "multi_process_metrics.py": [],
    "profiler.py": [],            # trace_dir injected per-test
    "tracking.py": [],            # project_dir injected per-test
    "fsdp_with_peak_mem_tracking.py": ["--cpu_offload", "--activation_checkpointing"],
    "cross_validation.py": ["--num_folds", "2"],
    "ddp_comm_hook.py": [],
    "schedule_free.py": [],
    "deepspeed_with_config_support.py": [],
    "megatron_lm_gpt_pretraining.py": ["--tp", "2", "--pp", "2", "--steps", "4"],
    "moe_context_parallel.py": ["--steps", "4"],
    "native_data_pipeline.py": ["--seq_len", "64"],
    "hf_checkpoint_finetune.py": [],
    "sequence_packing.py": ["--seq_len", "32"],
}


def _run_example(path: Path, extra, timeout=600):
    env = {**os.environ}
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"
    res = subprocess.run(
        [sys.executable, str(path), *FAST_ARGS, *extra],
        capture_output=True, text=True, timeout=timeout, cwd=str(REPO), env=env,
    )
    assert res.returncode == 0, f"{path.name} failed:\n{res.stdout[-2500:]}\n{res.stderr[-2500:]}"
    return res


class TestExampleInventory:
    def test_all_by_feature_scripts_covered(self):
        on_disk = {p.name for p in BY_FEATURE.glob("*.py")}
        assert on_disk == set(SCRIPTS), (
            f"untested scripts: {on_disk - set(SCRIPTS)}; missing: {set(SCRIPTS) - on_disk}"
        )

    def test_scripts_share_the_canonical_skeleton(self):
        # The structural drift guard: every script must build on the shared
        # canonical pieces and expose the standard entrypoints.
        for p in sorted(BY_FEATURE.glob("*.py")):
            src = p.read_text()
            assert "def training_function(args)" in src, p.name
            assert "def main()" in src, p.name
            assert "example_lib" in src or "common_parser" in src, p.name
            assert "Accelerator(" in src, p.name


class TestCanonicalExamples:
    def test_nlp_example_learns(self):
        """The reference's test_performance pattern: the printed metric must
        clear a threshold, not just appear. At the defaults the synthetic
        paraphrase task reaches eval_acc 1.00 by epoch 3 (seeds 42/7
        measured); 0.8 leaves seed headroom while still proving the full
        loop (optimizer, schedule, masking, gather_for_metrics) learns."""
        import re

        # extra args come after FAST_ARGS, so this --epochs wins (argparse
        # keeps the last occurrence).
        res = _run_example(EXAMPLES / "nlp_example.py", ["--epochs", "5"])
        accs = [float(a) for a in re.findall(r"eval_acc (\d\.\d+)", res.stdout)]
        assert accs, res.stdout[-2000:]
        assert max(accs) >= 0.8, f"eval accuracy never reached 0.8: {accs}"

    def test_cv_example_learns(self):
        """Dominant-channel classification hits 1.00 in one epoch; 0.9
        leaves shuffle-order headroom (test_performance pattern)."""
        import re

        res = _run_example(EXAMPLES / "cv_example.py", ["--epochs", "1"])
        accs = [float(a) for a in re.findall(r"acc (\d\.\d+)", res.stdout)]
        assert accs and max(accs) >= 0.9, res.stdout[-1500:]


class TestInferenceExamples:
    """examples/inference/ — the reference's examples/inference/{pippy,
    distributed} counterparts."""

    def test_pipeline_inference_over_pp_mesh(self):
        env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
        res = subprocess.run(
            [sys.executable, "-m", "accelerate_tpu.commands.accelerate_cli", "launch",
             "--use_cpu_emulation", "--emulated_device_count", "8",
             "--pp", "2", "--tp", "2",
             str(EXAMPLES / "inference" / "pipeline_inference.py")],
            capture_output=True, text=True, timeout=600, cwd=str(REPO), env=env)
        assert res.returncode == 0, res.stdout[-2500:] + res.stderr[-2500:]
        assert "'pp': 2" in res.stdout and "'tp': 2" in res.stdout
        assert "pipeline inference example: OK" in res.stdout

    def test_distributed_inference_two_processes(self):
        env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
        res = subprocess.run(
            [sys.executable, "-m", "accelerate_tpu.commands.accelerate_cli", "launch",
             "--num_processes", "2", "--emulated_device_count", "1",
             str(EXAMPLES / "inference" / "distributed_inference.py")],
            capture_output=True, text=True, timeout=600, cwd=str(REPO), env=env)
        assert res.returncode == 0, res.stdout[-2500:] + res.stderr[-2500:]
        assert "distributed inference example: OK" in res.stdout

    def test_speculative_decoding(self):
        env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
        res = subprocess.run(
            [sys.executable, str(EXAMPLES / "inference" / "speculative_decoding.py")],
            capture_output=True, text=True, timeout=420, cwd=str(REPO), env=env)
        assert res.returncode == 0, res.stdout[-2500:] + res.stderr[-2500:]
        assert "speculative decoding example: OK" in res.stdout


class TestConfigTemplates:
    @pytest.mark.nightly  # every-template sweep; CLI config tests cover default
    def test_every_template_resolves(self):
        """Each shipped YAML template must launch run_me.py cleanly (the
        reference's config_yaml_templates/run_me.py drill)."""
        templates = sorted((EXAMPLES / "config_yaml_templates").glob("*.yaml"))
        assert len(templates) >= 5
        for tpl in templates:
            env = {**os.environ, "JAX_PLATFORMS": "cpu"}
            env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
            flags = env.get("XLA_FLAGS", "")
            if "--xla_force_host_platform_device_count" not in flags:
                env["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"
            # Topology-bound templates are scaled down to the 8-device
            # emulation via CLI flags (which must take priority over the file).
            overrides = {
                "multi_node.yaml": ["--num_machines", "1"],
                "composed_3d.yaml": ["--dp", "1", "--fsdp", "4", "--tp", "2"],
            }
            args = overrides.get(tpl.name, [])
            res = subprocess.run(
                [sys.executable, "-m", "accelerate_tpu.commands.accelerate_cli",
                 "launch", "--config_file", str(tpl), *args,
                 str(EXAMPLES / "config_yaml_templates" / "run_me.py")],
                capture_output=True, text=True, timeout=300, cwd=str(REPO), env=env)
            assert res.returncode == 0, (
                f"{tpl.name}:\n{res.stdout[-1500:]}\n{res.stderr[-1500:]}")
            assert "config resolved OK" in res.stdout, tpl.name


#: One-epoch runs that stay in the DEFAULT suite; every other script is
#: exercised nightly (each is a fresh-interpreter subprocess costing
#: ~15-35 s on this 1-core box, and the inventory guard above still pins
#: that all scripts exist and share the skeleton).
DEFAULT_SCRIPTS = {
    # tp+pp composed through the launcher-style flags — the one script
    # whose mesh shape nothing else in the default suite reproduces.
    # checkpointing.py runs TWICE in test_checkpointing_resumes (default);
    # accumulation/MoE/cp have dedicated in-process default tests
    # (test_accelerator, test_moe, test_ring_attention).
    "megatron_lm_gpt_pretraining.py",
}


class TestByFeatureExamples:
    @pytest.mark.parametrize("script", [
        s if s in DEFAULT_SCRIPTS else pytest.param(s, marks=pytest.mark.nightly)
        for s in sorted(SCRIPTS)
    ])
    def test_runs_one_epoch(self, script, tmp_path):
        extra = list(SCRIPTS[script])
        if script == "checkpointing.py":
            extra += ["--project_dir", str(tmp_path / "proj")]
        elif script == "profiler.py":
            extra += ["--trace_dir", str(tmp_path / "trace")]
        elif script == "tracking.py":
            extra += ["--project_dir", str(tmp_path / "track")]
        res = _run_example(BY_FEATURE / script, extra)
        assert res.stdout.strip(), f"{script} produced no output"

    def test_checkpointing_resumes(self, tmp_path):
        proj = tmp_path / "proj"
        _run_example(BY_FEATURE / "checkpointing.py",
                     ["--project_dir", str(proj), "--epochs", "1"])
        res = _run_example(
            BY_FEATURE / "checkpointing.py",
            ["--project_dir", str(proj), "--epochs", "2",
             "--resume_from_checkpoint", "latest"],
        )
        assert "resumed from epoch 1" in res.stdout

    def test_tracking_writes_jsonl(self, tmp_path):
        proj = tmp_path / "track"
        _run_example(BY_FEATURE / "tracking.py",
                     ["--project_dir", str(proj), "--epochs", "1"])
        metrics = list(proj.rglob("*.jsonl"))
        assert metrics, f"no jsonl metrics under {proj}"
        assert "train_loss" in metrics[0].read_text()
