"""CLI + config + launcher tests (reference: tests/test_cli.py,
tests/test_configs/*, test_sagemaker arg-construction pattern)."""

import os
import subprocess
import sys

import numpy as np
import pytest
import yaml

from accelerate_tpu.commands.config.config_args import ClusterConfig, load_config_from_file
from accelerate_tpu.commands.config.default import write_basic_config
from accelerate_tpu.commands.launch import _resolve_config, launch_command_parser
from accelerate_tpu.utils.environment import env_var


class TestClusterConfig:
    def test_roundtrip_yaml(self, tmp_path):
        cfg = ClusterConfig(mixed_precision="bf16", mesh_tp=4, num_machines=2,
                            main_process_ip="10.0.0.1")
        path = cfg.save(str(tmp_path / "c.yaml"))
        loaded = load_config_from_file(str(path))
        assert loaded.mixed_precision == "bf16"
        assert loaded.mesh_tp == 4
        assert loaded.num_machines == 2

    def test_roundtrip_json(self, tmp_path):
        cfg = ClusterConfig(mesh_fsdp=8)
        path = cfg.save(str(tmp_path / "c.json"))
        assert load_config_from_file(str(path)).mesh_fsdp == 8

    def test_unknown_keys_preserved_not_fatal(self, tmp_path):
        p = tmp_path / "c.yaml"
        p.write_text(yaml.safe_dump({"mixed_precision": "fp16", "future_knob": 1}))
        cfg = load_config_from_file(str(p))
        assert cfg.mixed_precision == "fp16"
        assert cfg.extra == {"future_knob": 1}

    def test_missing_explicit_file_raises(self):
        with pytest.raises(FileNotFoundError):
            load_config_from_file("/nonexistent/cfg.yaml")

    def test_launch_env_mesh_and_precision(self):
        cfg = ClusterConfig(mixed_precision="bf16", mesh_tp=2, mesh_fsdp=4)
        env = cfg.launch_env()
        assert env[env_var("MESH_TP")] == "2"
        assert env[env_var("MESH_FSDP")] == "4"
        assert env[env_var("MIXED_PRECISION")] == "bf16"

    def test_launch_env_multihost(self):
        cfg = ClusterConfig(num_machines=4, machine_rank=2, main_process_ip="10.0.0.1")
        env = cfg.launch_env()
        assert env[env_var("COORDINATOR_ADDRESS")] == "10.0.0.1:8476"
        assert env[env_var("NUM_PROCESSES")] == "4"
        assert env[env_var("PROCESS_ID")] == "2"

    def test_write_basic_config(self, tmp_path):
        path = write_basic_config(config_file=str(tmp_path / "d.yaml"))
        assert load_config_from_file(str(path)).mixed_precision == "bf16"


class TestLaunchResolution:
    def test_cli_overrides_config(self, tmp_path):
        cfg_path = tmp_path / "c.yaml"
        ClusterConfig(mixed_precision="no", mesh_tp=1).save(str(cfg_path))
        parser = launch_command_parser()
        args = parser.parse_args(["--config_file", str(cfg_path), "--mixed_precision", "bf16",
                                  "--tp", "2", "script.py"])
        cfg = _resolve_config(args)
        assert cfg.mixed_precision == "bf16"
        assert cfg.mesh_tp == 2

    def test_script_args_passthrough(self):
        parser = launch_command_parser()
        args = parser.parse_args(["train.py", "--lr", "3", "--epochs", "2"])
        assert args.training_script == "train.py"
        assert args.training_script_args == ["--lr", "3", "--epochs", "2"]


def _run_cli(*argv, env_extra=None, cwd=None):
    # JAX_PLATFORMS=cpu is inherited from conftest; accelerate_tpu/__init__
    # mirrors it into jax.config in the child so the pin actually holds.
    # timeout kills the child on expiry — a hung CLI must fail, not wedge CI.
    env = {**os.environ, **(env_extra or {})}
    return subprocess.run(
        [sys.executable, "-m", "accelerate_tpu.commands.accelerate_cli", *argv],
        capture_output=True, text=True, env=env, timeout=120,
        cwd=cwd or os.path.dirname(os.path.dirname(__file__)))


FIXTURES = os.path.join(os.path.dirname(__file__), "test_configs")


class TestConfigBackcompat:
    """Pinned old-schema config files must load and upgrade forever
    (reference pins its generations the same way: tests/test_configs/)."""

    def _upgrade(self, tmp_path, name):
        import shutil

        path = tmp_path / name
        shutil.copy(os.path.join(FIXTURES, name), path)
        out = _run_cli("config", "update", "--config_file", str(path))
        assert out.returncode == 0, out.stderr
        return out, load_config_from_file(str(path))

    def test_hf_legacy_fp16_schema(self, tmp_path):
        cfg = load_config_from_file(os.path.join(FIXTURES, "hf_0_11_legacy.yaml"))
        assert cfg.mixed_precision == "fp16"  # pre-0.12 'fp16: true' key
        assert any("fp16" in n for n in cfg.migration_notes)
        out, upgraded = self._upgrade(tmp_path, "hf_0_11_legacy.yaml")
        assert upgraded.mixed_precision == "fp16"
        assert upgraded.extra == {}  # rewritten in the current schema
        assert not upgraded.migration_notes  # no longer a reference file

    def test_hf_fsdp_multinode_schema(self, tmp_path):
        cfg = load_config_from_file(os.path.join(FIXTURES, "hf_0_34_fsdp.yaml"))
        assert cfg.mesh_fsdp == -1 and cfg.mesh_dp == 1  # FSDP -> fsdp axis
        assert cfg.num_machines == 2 and cfg.machine_rank == 1
        assert cfg.main_process_ip == "10.0.0.7" and cfg.main_process_port == 29500
        assert cfg.mixed_precision == "bf16" and cfg.debug is True
        assert "rdzv_backend" in cfg.extra  # untranslatable, kept for report
        out, upgraded = self._upgrade(tmp_path, "hf_0_34_fsdp.yaml")
        assert "Dropping unknown keys" in out.stdout
        assert upgraded.mesh_fsdp == -1 and upgraded.num_machines == 2
        assert upgraded.extra == {}

    def test_hf_fp8_dynamo_schema(self, tmp_path):
        cfg = load_config_from_file(os.path.join(FIXTURES, "hf_0_34_fp8.yaml"))
        assert cfg.mixed_precision == "bf16"  # fp8 -> bf16 autocast
        assert any("fp8" in n for n in cfg.migration_notes)
        out, upgraded = self._upgrade(tmp_path, "hf_0_34_fp8.yaml")
        assert "note:" in out.stdout
        assert upgraded.mixed_precision == "bf16"

    def test_own_minimal_v1_schema(self, tmp_path):
        cfg = load_config_from_file(os.path.join(FIXTURES, "v1_minimal.yaml"))
        assert cfg.mesh_fsdp == 2 and cfg.mixed_precision == "bf16"
        assert cfg.mesh_cp == 1 and cfg.mesh_ep == 1  # later fields default
        out, upgraded = self._upgrade(tmp_path, "v1_minimal.yaml")
        assert upgraded.mesh_fsdp == 2

    def test_invalid_keys_reported_and_dropped(self, tmp_path):
        cfg = load_config_from_file(os.path.join(FIXTURES, "invalid_keys.yaml"))
        assert set(cfg.extra) == {"another_invalid_key", "invalid_key"}
        out, upgraded = self._upgrade(tmp_path, "invalid_keys.yaml")
        assert "another_invalid_key" in out.stdout and "invalid_key" in out.stdout
        assert upgraded.extra == {} and upgraded.mesh_tp == 2

    def test_sagemaker_config_rejected(self, tmp_path):
        p = tmp_path / "sm.yaml"
        p.write_text(yaml.safe_dump({
            "compute_environment": "AMAZON_SAGEMAKER", "distributed_type": "NO",
            "ec2_instance_type": "ml.p3.2xlarge"}))
        with pytest.raises(ValueError, match="SageMaker"):
            load_config_from_file(str(p))


class TestCLISubprocess:
    def test_help_lists_all_subcommands(self):
        out = _run_cli("--help")
        for cmd in ["config", "env", "estimate-memory", "launch", "merge-weights", "serve",
                    "test", "tpu-config"]:
            assert cmd in out.stdout

    def test_config_default_and_env(self, tmp_path):
        env = {"ACCELERATE_TPU_CONFIG_DIR": str(tmp_path)}
        out = _run_cli("config", "--default", env_extra=env)
        assert out.returncode == 0, out.stderr
        assert (tmp_path / "default_config.yaml").exists()
        out = _run_cli("env", env_extra=env)
        assert out.returncode == 0, out.stderr
        assert "accelerate_tpu version" in out.stdout
        assert "mixed_precision" in out.stdout

    def test_estimate_memory_tiny(self):
        out = _run_cli("estimate-memory", "llama-tiny", "--dtypes", "float32", "bfloat16")
        assert out.returncode == 0, out.stderr
        assert "float32" in out.stdout and "bfloat16" in out.stdout

    def test_estimate_memory_counts_held_experts(self):
        """One chip's share: 16 of 128 routed experts a layer. A layer is
        344.5 M outside the routed experts + 50.3 M an expert; 32 layers + the
        1.07 B tied embedding: 218.3 B whole, 37.9 B with 16 held."""
        whole = _run_cli("estimate-memory", "command-a-plus", "--dtypes", "bfloat16")
        share = _run_cli("estimate-memory", "command-a-plus", "--dtypes", "bfloat16",
                         "--held-experts", "16")
        assert whole.returncode == 0 and share.returncode == 0, share.stderr
        assert "(218.25 B params)" in whole.stdout
        assert "(37.87 B params)" in share.stdout
        assert "holding 16 of 128 routed experts" in share.stdout
        refused = _run_cli("estimate-memory", "llama-tiny", "--held-experts", "2")
        assert refused.returncode == 2 and "no expert layer" in refused.stdout

    def test_estimate_memory_lora_rank(self):
        out = _run_cli("estimate-memory", "llama-tiny",
                       "--dtypes", "float32", "--lora-rank", "8")
        assert out.returncode == 0, out.stderr
        assert "trainable params" in out.stdout
        assert "% of base" in out.stdout
        assert "adapter checkpoint" in out.stdout

    def test_estimate_memory_tp(self):
        out = _run_cli("estimate-memory", "llama-tiny",
                       "--dtypes", "bfloat16", "--tp", "2", "--lora-rank", "8")
        assert out.returncode == 0, out.stderr
        assert "Tensor-parallel slice (tp=2" in out.stdout
        assert "params per chip" in out.stdout
        assert "KV cache per chip" in out.stdout
        assert "adapter bank row per chip" in out.stdout
        # tiny llama: 2 kv-heads x 16 head-dim x 2 layers, k+v in bf16 is
        # 256 B/token unsharded; tp=2 splits the kv-heads axis -> 128 B.
        assert "128 B/token/slot" in out.stdout

    def test_estimate_memory_tp_not_divisible_replicates(self):
        out = _run_cli("estimate-memory", "llama-tiny",
                       "--dtypes", "bfloat16", "--tp", "3")
        assert out.returncode == 0, out.stderr
        # Nothing in the tiny model divides by 3: every weight stays
        # replicated and the KV line flags it rather than lying.
        assert "0.0% of weights sharded" in out.stdout
        assert "REPLICATED" in out.stdout

    def test_estimate_memory_zero(self):
        out = _run_cli("estimate-memory", "llama-tiny",
                       "--dtypes", "float32", "--zero", "8")
        assert out.returncode == 0, out.stderr
        assert "opt state/chip (zero=8)" in out.stdout
        # tiny llama: 834.50 KiB of fp32 Adam moments; everything but the
        # norm scales (99.7% of elements) has a dim divisible by 8.
        assert "ZeRO-8 optimizer state" in out.stdout
        assert "106.50 KiB/replica" in out.stdout
        assert "99.7% of elements sharded" in out.stdout

    def test_estimate_memory_zero_defaults_to_world_size(self):
        # bare --zero resolves the replica count from the (8-device
        # virtual) world instead of making the user repeat it.
        out = _run_cli("estimate-memory", "llama-tiny",
                       "--dtypes", "float32", "--zero")
        assert out.returncode == 0, out.stderr
        assert "opt state/chip (zero=8)" in out.stdout

    def test_estimate_memory_zero_not_divisible_replicates(self):
        out = _run_cli("estimate-memory", "llama-tiny",
                       "--dtypes", "float32", "--zero", "7")
        assert out.returncode == 0, out.stderr
        # No tensor in the tiny model has a dim divisible by 7: the
        # estimate must say so and charge every chip the full state.
        assert "0.0% of elements sharded" in out.stdout
        assert "no dimension divisible by 7: REPLICATED" in out.stdout
        assert "834.50 KiB/replica" in out.stdout

    def test_estimate_memory_page_sizing(self):
        out = _run_cli("estimate-memory", "llama-tiny", "--dtypes", "bfloat16",
                       "--page-size", "16", "--max-pages", "256",
                       "--seq-lens", "32", "128")
        assert out.returncode == 0, out.stderr
        assert "Paged KV pool (page_size=16" in out.stdout
        # tiny llama is 256 B/token (see test_estimate_memory_tp), so a
        # 16-token page is 4 KiB and 256 pages are 1 MiB.
        assert "bytes per page  : 4.00 KiB" in out.stdout
        assert "pool (256 pages): 1.00 MiB" in out.stdout
        # 32 tokens need ceil(32/16) = 2 pages; the pool fits 128 such.
        assert "2 pages" in out.stdout
        assert "32tok x 128" in out.stdout

    def test_estimate_memory_spec_tokens(self):
        out = _run_cli("estimate-memory", "llama-tiny", "--dtypes", "bfloat16",
                       "--page-size", "16", "--max-pages", "256",
                       "--seq-lens", "32", "128", "--spec-tokens", "4")
        assert out.returncode == 0, out.stderr
        assert "Speculative decoding (--spec-tokens 4):" in out.stdout
        # Draft KV rides the same pool through a second page-table column
        # (ServingEngine._spec_page_factor == 2): a 32-token request
        # covers 4 pages instead of 2, so the 256-page pool fits 64
        # concurrent requests instead of 128.
        assert "2x pages per request" in out.stdout
        assert "32 tokens:      4 pages  (pool fits 64 concurrent)" \
            in out.stdout
        # Verify forward widens [1, 1] -> [1, K+1]: the bf16 logits row
        # grows from vocab*2 = 512 B to (K+1)*vocab*2 = 2.5 KiB per slot
        # (tiny llama vocab = 256).
        assert "[1, 1] -> [1, 5]: logits 512 B -> 2.50 KiB/slot" \
            in out.stdout

    def test_estimate_memory_spec_draft_rank(self):
        out = _run_cli("estimate-memory", "llama-tiny", "--dtypes", "bfloat16",
                       "--page-size", "16", "--max-pages", "256",
                       "--spec-tokens", "4", "--draft-rank", "8")
        assert out.returncode == 0, out.stderr
        # Rank-8 draft proxy: 2 (k+v) x 2 layers x 8 x 2 bytes =
        # 64 B/token -> 1 KiB per 16-token page, +256 KiB over the pool.
        assert ("draft KV (rank-8 proxy, 2 x 2 layers x 8 x bf16): "
                "64 B/token, 1.00 KiB/page, pool +256.00 KiB") in out.stdout

    def test_estimate_memory_spec_tokens_needs_page_size(self):
        out = _run_cli("estimate-memory", "llama-tiny", "--dtypes", "bfloat16",
                       "--spec-tokens", "4")
        assert out.returncode == 2
        assert "--spec-tokens needs --page-size" in out.stdout

    def test_estimate_memory_page_sizing_tp(self):
        out = _run_cli("estimate-memory", "llama-tiny", "--dtypes", "bfloat16",
                       "--page-size", "16", "--tp", "2")
        assert out.returncode == 0, out.stderr
        # Pool pages shard on kv-heads exactly like the dense cache:
        # half the page bytes land on each of the two chips.
        assert "(2.00 KiB/chip at tp=2)" in out.stdout

    def test_estimate_memory_unknown_model(self):
        out = _run_cli("estimate-memory", "not-a-model")
        assert out.returncode == 2
        assert "built-in name" in out.stdout

    def test_estimate_memory_from_config_json(self, tmp_path):
        import json

        cfg = tmp_path / "config.json"
        cfg.write_text(json.dumps({
            "model_type": "llama", "vocab_size": 256, "hidden_size": 64,
            "intermediate_size": 128, "num_hidden_layers": 2,
            "num_attention_heads": 4, "num_key_value_heads": 2,
        }))
        out = _run_cli("estimate-memory", str(cfg), "--dtypes", "bfloat16")
        assert out.returncode == 0, out.stderr
        assert "bfloat16" in out.stdout

    def test_estimate_memory_from_safetensors_dir(self, tmp_path):
        import numpy as np
        from safetensors.numpy import save_file

        save_file({"model.layers.0.w": np.zeros((8, 8), np.float32),
                   "model.layers.1.w": np.zeros((8, 8), np.float32)},
                  str(tmp_path / "model.safetensors"))
        out = _run_cli("estimate-memory", str(tmp_path), "--dtypes", "float32")
        assert out.returncode == 0, out.stderr
        assert "float32" in out.stdout

    def test_tpu_config_debug_prints_gcloud(self):
        out = _run_cli("tpu-config", "--tpu_name", "pod1", "--tpu_zone", "us-central2-b",
                       "--command", "echo hi", "--install_accelerate", "--debug")
        assert out.returncode == 0, out.stderr
        assert "gcloud compute tpus tpu-vm ssh pod1 --zone us-central2-b" in out.stdout
        assert "pip install" in out.stdout and "echo hi" in out.stdout
        assert "--worker all" in out.stdout

    def test_tpu_config_sudo_and_env(self):
        """launch --tpu_use_sudo / --env parity: sudo prefixes every remote
        command, --env exports land before them (reference:
        commands/launch.py --tpu_use_sudo/--env). With --env present the
        vars must be inlined per command (`sudo env K=V cmd`): sudo's
        default env_reset strips shell-exported vars, and `sudo -E` would
        both need the SETENV sudoers tag and leak the whole invoking
        environment."""
        out = _run_cli("tpu-config", "--tpu_name", "pod1",
                       "--command", "echo hi", "--use_sudo",
                       "--env", "FOO=bar baz", "--env", "N=1", "--debug")
        assert out.returncode == 0, out.stderr
        assert "export FOO='bar baz'; export N=1; sudo env FOO='bar baz' N=1 echo hi" in out.stdout
        out = _run_cli("tpu-config", "--tpu_name", "pod1",
                       "--command", "echo hi", "--use_sudo", "--debug")
        assert "sudo echo hi" in out.stdout and "sudo env" not in out.stdout
        out = _run_cli("tpu-config", "--tpu_name", "pod1",
                       "--command", "echo hi", "--env", "MALFORMED")
        assert out.returncode == 2

    def test_tpu_config_requires_name_and_commands(self, tmp_path):
        # Isolate the config dir: a developer's real default config could
        # name a live pod, and this test must never reach gcloud.
        env = {"ACCELERATE_TPU_CONFIG_DIR": str(tmp_path)}
        out = _run_cli("tpu-config", "--command", "echo hi", env_extra=env)
        assert out.returncode == 2
        out = _run_cli("tpu-config", "--tpu_name", "pod1", env_extra=env)
        assert out.returncode == 2

    def test_config_update_migrates_schema(self, tmp_path):
        import yaml

        cfg_file = tmp_path / "cfg.yaml"
        out = _run_cli("config", "--default", "--config_file", str(cfg_file))
        assert out.returncode == 0, out.stderr
        data = yaml.safe_load(cfg_file.read_text())
        data.pop("mesh_tp")
        data["mixed_precision"] = "fp16"  # a kept user value
        data["obsolete_key"] = 1
        cfg_file.write_text(yaml.safe_dump(data))
        out = _run_cli("config", "update", "--config_file", str(cfg_file))
        assert out.returncode == 0, out.stderr
        updated = yaml.safe_load(cfg_file.read_text())
        assert updated["mesh_tp"] == 1          # new field gains its default
        assert updated["mixed_precision"] == "fp16"  # old value preserved
        assert "obsolete_key" not in updated

    def test_estimate_from_hf_configs(self, tmp_path):
        import json

        for name, cfg in {
            "t5": {"model_type": "t5", "vocab_size": 128, "d_model": 16,
                   "d_ff": 32, "d_kv": 4, "num_layers": 1, "num_heads": 4},
            "gpt2": {"model_type": "gpt2", "vocab_size": 128, "n_embd": 16,
                     "n_layer": 1, "n_head": 4, "n_positions": 32},
        }.items():
            p = tmp_path / f"{name}.json"
            p.write_text(json.dumps(cfg))
            out = _run_cli("estimate-memory", str(p), "--dtypes", "bfloat16")
            assert out.returncode == 0, out.stderr
            assert "training (Adam)" in out.stdout

    def test_launch_simple_passes_env(self, tmp_path):
        probe = tmp_path / "probe.py"
        probe.write_text("import os\nprint(os.environ['" + env_var("MESH_TP") + "'])\n"
                         "print(os.environ['" + env_var("MIXED_PRECISION") + "'])\n")
        out = _run_cli("launch", "--tp", "2", "--mixed_precision", "bf16", str(probe))
        assert out.returncode == 0, out.stderr
        assert out.stdout.splitlines()[:2] == ["2", "bf16"]

    def test_merge_weights_sharded_safetensors(self, tmp_path):
        import json

        from safetensors.numpy import load_file, save_file

        d = tmp_path / "src"
        d.mkdir()
        save_file({"a.w": np.ones((2, 2), np.float32)}, str(d / "model-00001-of-00002.safetensors"))
        save_file({"b.w": np.zeros((3,), np.float32)}, str(d / "model-00002-of-00002.safetensors"))
        (d / "model.safetensors.index.json").write_text(json.dumps({
            "weight_map": {"a.w": "model-00001-of-00002.safetensors",
                           "b.w": "model-00002-of-00002.safetensors"}}))
        out_path = tmp_path / "merged.safetensors"
        out = _run_cli("merge-weights", str(d), str(out_path))
        assert out.returncode == 0, out.stderr
        merged = load_file(str(out_path))
        assert set(merged) == {"a.w", "b.w"}

    def test_serve_help(self):
        out = _run_cli("serve", "--help")
        assert out.returncode == 0, out.stderr
        for flag in ["--model", "--replicas", "--port", "--max-slots", "--tp",
                     "--page-size", "--max-pages",
                     "--priority-preemption", "--no-priority-preemption",
                     "--rate-limit", "--fair-share",
                     "--autoscale-min", "--autoscale-max"]:
            assert flag in out.stdout
        assert "--no-paged" not in out.stdout

    def test_serve_tenant_float_specs(self):
        """--rate-limit/--fair-share NAME=FLOAT parsing: valid pairs (incl.
        the '*' wildcard) build a dict, malformed or non-positive values
        exit with a usage error, and no pairs means None (feature off)."""
        from accelerate_tpu.commands.serve import _parse_tenant_floats

        got = _parse_tenant_floats(["alice=5", "*=1.5"], "--rate-limit",
                                   "RPS")
        assert got == {"alice": 5.0, "*": 1.5}
        assert _parse_tenant_floats([], "--rate-limit", "RPS") is None
        assert _parse_tenant_floats(None, "--fair-share", "WEIGHT") is None
        for bad in ["alice", "=3", "alice=", "alice=zero", "alice=0",
                    "alice=-1"]:
            with pytest.raises(SystemExit):
                _parse_tenant_floats([bad], "--rate-limit", "RPS")

    def test_serve_autoscale_bounds_validated(self):
        """Bad --autoscale-min/--autoscale-max combos die before any
        model warmup (fast usage errors, not a traceback mid-build)."""
        for argv in (["serve", "--model", "tiny", "--autoscale-max", "2",
                      "--autoscale-min", "0"],
                     ["serve", "--model", "tiny", "--autoscale-max", "1",
                      "--autoscale-min", "3"]):
            out = _run_cli(*argv)
            assert out.returncode != 0
            assert "--autoscale" in out.stderr

    @pytest.mark.slow
    def test_serve_tiny_end_to_end(self):
        """`accelerate-tpu serve --model tiny --port 0`: the process must
        announce its OS-assigned URL, answer a real completion + /readyz
        over HTTP, then drain cleanly on SIGTERM (exit 0, 'bye' printed)."""
        import json as _json
        import re
        import signal
        import urllib.request

        proc = subprocess.Popen(
            [sys.executable, "-m", "accelerate_tpu.commands.accelerate_cli",
             "serve", "--model", "tiny", "--replicas", "1", "--port", "0",
             "--max-slots", "2", "--max-len", "64", "--prefill-chunk", "32",
             "--eos-token-id", "7"],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
            cwd=os.path.dirname(os.path.dirname(__file__)))
        try:
            url = None
            for line in proc.stdout:  # warmup chatter, then the URL line
                m = re.search(r"serving on (http://\S+)", line)
                if m:
                    url = m.group(1)
                    break
            assert url, "serve never announced its URL"
            req = urllib.request.Request(
                url + "/v1/completions",
                data=_json.dumps({"prompt": [3, 5, 7, 11],
                                  "max_new_tokens": 4}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=60) as resp:
                assert resp.status == 200
                body = _json.loads(resp.read())
            assert body["status"] == "completed"
            assert 1 <= len(body["tokens"]) <= 4
            with urllib.request.urlopen(url + "/readyz", timeout=10) as resp:
                assert resp.status == 200
            proc.send_signal(signal.SIGTERM)
            out, err = proc.communicate(timeout=60)
            assert proc.returncode == 0, err
            assert "gateway drained; bye" in out
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.communicate(timeout=30)

    @pytest.mark.slow
    def test_serve_slo_flags_end_to_end(self):
        """`serve --rate-limit '*=0.5' --autoscale-max 2`: the elastic
        fleet announces autoscale supervision, /metrics exports the
        parked-replica gauge, and a second immediate request trips the
        token bucket into a structured 429 with a bounded Retry-After."""
        import json as _json
        import re
        import signal
        import urllib.error
        import urllib.request

        proc = subprocess.Popen(
            [sys.executable, "-m", "accelerate_tpu.commands.accelerate_cli",
             "serve", "--model", "tiny", "--port", "0",
             "--max-slots", "2", "--max-len", "64", "--prefill-chunk", "32",
             "--eos-token-id", "7", "--rate-limit", "*=0.5",
             "--fair-share", "*=1", "--autoscale-min", "1",
             "--autoscale-max", "2"],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
            cwd=os.path.dirname(os.path.dirname(__file__)))
        try:
            url = None
            saw_autoscale = False
            for line in proc.stdout:
                saw_autoscale |= "autoscale 1..2" in line
                m = re.search(r"serving on (http://\S+)", line)
                if m:
                    url = m.group(1)
                    break
            assert url, "serve never announced its URL"
            assert saw_autoscale, "autoscale supervision never announced"

            def post():
                req = urllib.request.Request(
                    url + "/v1/completions",
                    data=_json.dumps({"prompt": [3, 5, 7, 11],
                                      "max_new_tokens": 4}).encode(),
                    headers={"Content-Type": "application/json"})
                return urllib.request.urlopen(req, timeout=60)

            with post() as resp:  # burst of 1 token at 0.5 rps
                assert resp.status == 200
            with pytest.raises(urllib.error.HTTPError) as ei:
                post().close()
            assert ei.value.code == 429
            retry_after = float(ei.value.headers["Retry-After"])
            assert 0 < retry_after <= 60.0
            assert _json.loads(ei.value.read())["error"] == "rate_limited"
            with urllib.request.urlopen(url + "/metrics",
                                        timeout=10) as resp:
                metrics = resp.read().decode()
            assert "accelerate_tpu_serving_replicas_parked 1" in metrics
            assert ("accelerate_tpu_gateway_rate_limit_sheds 1"
                    in metrics)
            proc.send_signal(signal.SIGTERM)
            out, err = proc.communicate(timeout=60)
            assert proc.returncode == 0, err
            assert "gateway drained; bye" in out
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.communicate(timeout=30)

    @pytest.mark.slow
    def test_serve_tp_end_to_end(self):
        """`serve --tp 2 --replicas 2` carves the 8 emulated devices into
        two 2-chip mesh slices and serves a completion through them."""
        import json as _json
        import re
        import signal
        import urllib.request

        proc = subprocess.Popen(
            [sys.executable, "-m", "accelerate_tpu.commands.accelerate_cli",
             "serve", "--model", "tiny", "--replicas", "2", "--tp", "2",
             "--port", "0", "--max-slots", "2", "--max-len", "64",
             "--prefill-chunk", "32", "--eos-token-id", "7"],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
            cwd=os.path.dirname(os.path.dirname(__file__)))
        try:
            url = None
            for line in proc.stdout:
                m = re.search(r"serving on (http://\S+)", line)
                if m:
                    url = m.group(1)
                    break
            assert url, "serve --tp never announced its URL"
            req = urllib.request.Request(
                url + "/v1/completions",
                data=_json.dumps({"prompt": [3, 5, 7, 11],
                                  "max_new_tokens": 4}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=60) as resp:
                assert resp.status == 200
                body = _json.loads(resp.read())
            assert body["status"] == "completed"
            assert 1 <= len(body["tokens"]) <= 4
            proc.send_signal(signal.SIGTERM)
            out, err = proc.communicate(timeout=60)
            assert proc.returncode == 0, err
            assert "gateway drained; bye" in out
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.communicate(timeout=30)


class TestLaunchValidation:
    """validate_launch is pure over (args, cfg) — no subprocess needed
    (reference: _validate_launch_command :972)."""

    def _args(self, tmp_path, **over):
        from accelerate_tpu.commands.launch import launch_command_parser

        script = tmp_path / "train.py"
        script.write_text("pass\n")
        parser = launch_command_parser()
        args = parser.parse_args([str(script)])
        for k, v in over.items():
            setattr(args, k, v)
        return args

    def _problems(self, tmp_path, cfg_over=None, **arg_over):
        from accelerate_tpu.commands.config.config_args import ClusterConfig
        from accelerate_tpu.commands.launch import validate_launch

        cfg = ClusterConfig()
        for k, v in (cfg_over or {}).items():
            setattr(cfg, k, v)
        return validate_launch(self._args(tmp_path, **arg_over), cfg)

    def test_clean_launch_has_no_problems(self, tmp_path):
        assert self._problems(tmp_path) == []

    def test_missing_script(self, tmp_path):
        problems = self._problems(tmp_path, training_script=str(tmp_path / "nope.py"))
        assert any("not found" in p for p in problems)

    def test_bad_mesh_axis(self, tmp_path):
        problems = self._problems(tmp_path, cfg_over={"mesh_tp": 0})
        assert any("mesh_tp" in p for p in problems)

    def test_dp_minus_one_ok_zero_rejected(self, tmp_path):
        assert self._problems(tmp_path, cfg_over={"mesh_dp": -1}) == []
        assert any("mesh_dp" in p for p in self._problems(tmp_path, cfg_over={"mesh_dp": 0}))

    def test_machine_rank_range(self, tmp_path):
        problems = self._problems(
            tmp_path, cfg_over={"num_machines": 2, "machine_rank": 5, "main_process_ip": "10.0.0.1"})
        assert any("machine_rank" in p for p in problems)

    def test_multihost_needs_rendezvous(self, tmp_path):
        problems = self._problems(tmp_path, cfg_over={"num_machines": 2})
        assert any("rendezvous" in p for p in problems)

    def test_num_processes_conflicts_with_multihost(self, tmp_path):
        problems = self._problems(
            tmp_path, num_processes=2,
            cfg_over={"num_machines": 2, "main_process_ip": "10.0.0.1"})
        assert any("mutually exclusive" in p for p in problems)

    def test_launch_command_rejects_invalid(self, tmp_path, capsys):
        from accelerate_tpu.commands.launch import launch_command

        args = self._args(tmp_path, training_script=str(tmp_path / "nope.py"))
        assert launch_command(args) == 2


class TestLaunchers:
    def test_notebook_launcher_sets_mesh_env(self):
        from accelerate_tpu.launchers import notebook_launcher

        captured = {}

        def fn():
            captured["tp"] = os.environ.get(env_var("MESH_TP"))
            return 7

        result = notebook_launcher(fn, tp=2)
        assert result == 7
        assert captured["tp"] == "2"
