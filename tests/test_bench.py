"""bench.py's contract: one JSON line from a run on the device it names, and
a non-zero exit — never a fallback — when the run it was asked for did not
happen on the device it was asked for."""

import json
import os
import sys
import types

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench  # noqa: E402


def _emitted(capsys):
    lines = [l for l in capsys.readouterr().out.splitlines() if l.strip().startswith("{")]
    assert lines, "bench must emit a JSON line"
    return json.loads(lines[-1])


def _stub_result():
    return {"metric": bench.METRIC, "value": 1.0, "unit": "tokens/s/chip",
            "vs_baseline": None, "extra": {}}


class TestPeakTable:
    @pytest.mark.parametrize("kind,peak", [
        ("TPU v4", 275.0),
        ("TPU v5 lite", 197.0),
        ("TPU v5p", 459.0),
        ("TPU v6 lite", 918.0),
    ])
    def test_known_device_kinds(self, kind, peak):
        assert bench.detect_peak_tflops(types.SimpleNamespace(device_kind=kind)) == peak

    @pytest.mark.parametrize("kind", ["cpu", "TPU v9", ""])
    def test_unknown_device_kind_raises(self, kind):
        """A device that is not in the table is an error, not 197.0."""
        with pytest.raises(ValueError, match="no published peak"):
            bench.detect_peak_tflops(types.SimpleNamespace(device_kind=kind))

    def test_cpu_run_has_no_mfu(self):
        from accelerate_tpu.models.llama import LlamaConfig

        cfg = LlamaConfig.tiny()
        fields = bench.mfu_fields(1000.0, cfg, 32, 10 * cfg.vocab_size * cfg.hidden_size)
        assert fields["mfu"] is None and fields["peak_tflops"] is None
        assert fields["achieved_tflops"] > 0


class TestNoFallback:
    """The test process sits on the CPU backend, so bench.py sees "no TPU";
    the only thing that varies is whether the caller asked for the CPU."""

    def test_main_exits_nonzero_without_a_chip(self, monkeypatch, capsys):
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        monkeypatch.setattr(bench, "run_bench", lambda on_tpu: pytest.fail("ran anyway"))
        with pytest.raises(SystemExit) as exc:
            bench.main()
        assert exc.value.code not in (0, None)
        assert "needs 1 TPU device" in str(exc.value.code)
        assert not capsys.readouterr().out.strip(), "no result line without a run"

    def test_main_mesh_exits_nonzero_without_enough_chips(self, monkeypatch, capsys):
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        monkeypatch.setattr(bench, "run_mesh_bench",
                            lambda *a, **k: pytest.fail("emulated anyway"))
        with pytest.raises(SystemExit) as exc:
            bench.main_mesh("fsdp=2,tp=2")
        assert "needs 4 TPU device" in str(exc.value.code)
        assert not capsys.readouterr().out.strip()

    def test_cpu_run_only_when_asked_and_labelled(self, monkeypatch, capsys):
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        seen = {}

        def fake_run(on_tpu):
            seen["on_tpu"] = on_tpu
            return _stub_result()

        monkeypatch.setattr(bench, "run_bench", fake_run)
        assert bench.main() == 0
        assert seen == {"on_tpu": False}
        assert _emitted(capsys)["extra"]["cpu_smoke"] is True

    def test_cpu_mesh_only_when_asked_and_labelled(self, monkeypatch, capsys):
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        monkeypatch.setattr(bench, "run_mesh_bench",
                            lambda spec, on_tpu: {**_stub_result(), "extra": {"spec": spec}})
        assert bench.main_mesh("dp=2") == 0
        out = _emitted(capsys)
        assert out["extra"] == {"spec": {"dp": 2}, "emulated": True}

    def test_mesh_flag_needs_a_spec(self, monkeypatch):
        monkeypatch.setattr(sys, "argv", ["bench.py", "--mesh"])
        with pytest.raises(SystemExit, match="needs a spec"):
            bench._cli()

    def test_flash_is_not_switchable_from_the_environment(self, monkeypatch):
        """The tier-1 config runs the flash kernel; no variable swaps it for
        einsum behind the result's back."""
        monkeypatch.setenv("ACCELERATE_TPU_BENCH_NO_FLASH", "1")
        assert bench.tier1_llama_config(on_tpu=True).use_flash_attention is True


class TestMeshBench:
    """The multi-chip perf harness (bench.py --mesh): per-chip throughput,
    MFU, and scaling efficiency over an explicit mesh (reference equivalent:
    its multi-GPU benchmark configs,
    benchmarks/fp8/{ddp,fsdp,distrib_deepspeed}.py)."""

    def test_parse_mesh_spec(self):
        assert bench.parse_mesh_spec("dp=8") == {"dp": 8}
        assert bench.parse_mesh_spec("fsdp=4,tp=2") == {"fsdp": 4, "tp": 2}
        with pytest.raises(ValueError, match="unknown mesh axis"):
            bench.parse_mesh_spec("pp=2")
        with pytest.raises(ValueError, match="positive size"):
            bench.parse_mesh_spec("dp=0")
        with pytest.raises(ValueError, match="empty"):
            bench.parse_mesh_spec("")

    @pytest.mark.nightly  # the driver's dryrun_multichip perf stage runs
    # this harness every round; the default suite keeps the parse test.
    def test_emulated_mesh_run_schema_and_scaling(self):
        """The dp x fsdp composed run must emit the driver JSON schema with
        real scaling fields; numbers are meaningless on CPU but every
        sharding in the step is live."""
        from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

        try:
            r = bench.run_mesh_bench({"dp": 4, "fsdp": 2}, on_tpu=False, quick=True)
        finally:
            for cls in (AcceleratorState, GradientState, PartialState):
                cls._reset_state()
        assert r["metric"] == bench.METRIC and r["unit"] == "tokens/s/chip"
        assert r["vs_baseline"] is None  # honest: no MFU target off-TPU
        e = r["extra"]
        assert e["mesh"] == {"dp": 4, "fsdp": 2} and e["n_chips"] == 8
        assert e["baseline_target_mfu"] == bench.TARGET_MFU
        assert r["value"] > 0 and e["step_ms"] > 0 and e["single_chip_step_ms"] > 0
        assert e["scaling_efficiency"] > 0
        assert e["mfu"] is None and e["peak_tflops"] is None
        assert e["config"]["backend"] == "cpu"
