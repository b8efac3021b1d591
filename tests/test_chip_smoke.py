"""chip_smoke.py, rehearsed on the CPU at toy size: every phase of the
one-chip run and of ``--four-chips`` (on four virtual devices), the last-line
format the driver reads, and the two ways it must fail — a failing phase, and
a platform that is not a TPU outside the rehearsal.

Each case is a real ``python chip_smoke.py ...`` child: the script owns its
process (it pins platforms and counts compiles), exactly as on the chip.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def _run(*argv, timeout=600):
    env = dict(os.environ)
    # The child sizes its own virtual-device world; drop the suite's 8.
    env["XLA_FLAGS"] = " ".join(f for f in env.get("XLA_FLAGS", "").split()
                                if "xla_force_host_platform_device_count" not in f)
    return subprocess.run([sys.executable, SCRIPT, *argv], capture_output=True, text=True,
                          env=env, timeout=timeout, cwd=REPO)


def _phases(stdout):
    """stdout is one JSON object per line; returns ({phase: fields}, last line)."""
    lines = [json.loads(l) for l in stdout.splitlines() if l.strip()]
    return {l["phase"]: l for l in lines[:-1]}, lines[-1]


@pytest.fixture(scope="module")
def one_chip_rehearsal():
    r = _run("--rehearse-cpu")
    assert r.returncode == 0, r.stderr[-3000:]
    return _phases(r.stdout)


@pytest.fixture(scope="module")
def four_chip_rehearsal():
    r = _run("--rehearse-cpu", "--four-chips")
    assert r.returncode == 0, r.stderr[-3000:]
    return _phases(r.stdout)


class TestOneChipRehearsal:
    def test_last_line_is_the_result_object_and_nothing_else(self, one_chip_rehearsal):
        phases, last = one_chip_rehearsal
        assert last == {"ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
        assert list(phases) == ["start", "trainer", "kernels", "server", "served_form"]

    def test_start_names_versions_cache_and_native_library(self, one_chip_rehearsal):
        start = one_chip_rehearsal[0]["start"]
        assert start["rehearsal"] is True and start["mode"] == "one-chip"
        assert start["jax"] and start["jaxlib"]
        assert start["compilation_cache"] == os.environ["JAX_COMPILATION_CACHE_DIR"]
        assert isinstance(start["native_host_library_loaded"], bool)

    def test_trainer_loss_falls_with_one_compile(self, one_chip_rehearsal):
        t = one_chip_rehearsal[0]["trainer"]
        assert len(t["losses"]) == 4 and t["losses"][-1] < t["losses"][0]
        assert t["recompiles_after_step_1"] == 0
        assert t["first_step"]["compiles"] + t["first_step"]["cache_hits"] >= 1
        # The CPU takes the einsum path by design; the chip run asserts True.
        assert t["pallas_call_in_step_hlo"] is False
        assert t["times"] == "smoke, not a measurement"

    def test_served_form_compiles_both_programs_and_reads_their_text(self, one_chip_rehearsal):
        f = one_chip_rehearsal[0]["served_form"]
        assert f["weights_served_form_leaves"] == 7 and f["weights_served_form_bytes"] > 0
        assert f["q_kernel_relayouts"] == {"tick": [], "chunk": []}

    def test_a_whole_kernel_copy_or_reshape_is_found_in_a_compiled_text(self):
        """The reader itself, on the parent's instructions (my chip run, PR 34)."""
        import chip_smoke

        text = "\n".join([
            "%copy.136 = bf16[16384,4096]{1,0:T(8,128)(2,1)} copy(%bitcast.175)",
            "%reshape.805 = bf16[128,64,2,4096]{3,2,1,0:T(2,128)(2,1)} reshape(%copy.136)",
            "%fusion.9 = bf16[8,16,128,4096]{3,2,1,0:T(8,128)(2,1)} fusion(%p), kind=kLoop, calls=%bitcast_fusion.2",
            "%copy.95 = f32[1,8,16,256,128]{3,4,2,1,0:T(8,128)S(1)} copy(%while.55)",
            "%copy.84 = bf16[1024,4096]{1,0:T(8,128)(2,1)S(1)} copy(%bitcast.780)"])
        found = chip_smoke.kernel_relayouts(text, 16384 * 4096)
        assert [f.split(" ")[0] for f in found] == ["%copy.136", "%reshape.805"]

    def test_kernels_agree_in_the_interpreter(self, one_chip_rehearsal):
        k = one_chip_rehearsal[0]["kernels"]
        assert k["interpreted"] is True
        assert k["max_rel_err"]["out"] <= k["tolerance"]["fwd"]
        assert max(k["max_rel_err"][g] for g in ("dq", "dk", "dv")) <= k["tolerance"]["bwd"]

    def test_server_answers_json_and_sse_without_recompiling(self, one_chip_rehearsal):
        s = one_chip_rehearsal[0]["server"]
        assert [r["mode"] for r in s["requests"]] == ["json", "sse", "json", "sse"]
        assert all(r["new_tokens"] == 8 for r in s["requests"])
        # Prompts cross a prefill chunk (16 here).
        assert all(r["prompt_len"] > s["config"]["prefill_chunk"] for r in s["requests"])
        assert s["compile_events_after_warmup"] == 0 and s["clean_shutdown"] is True
        assert s["logit_gap_of_served_tokens"]["share_within_tolerance"] >= 0.9
        assert max(s["logit_gap_of_first_tokens"]) <= s["near_tie"]
        assert s["config"]["num_experts"] == 4  # the MoE path, not a dense stand-in


class TestFourChipRehearsal:
    def test_only_the_cross_chip_phases_run_and_count_is_four(self, four_chip_rehearsal):
        phases, last = four_chip_rehearsal
        assert list(phases) == ["start", "trainer", "serving"]
        assert last == {"ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 4}}

    def test_sharded_trainer_tracks_one_device(self, four_chip_rehearsal):
        t = four_chip_rehearsal[0]["trainer"]
        assert t["one_device"]["devices"] == [0]
        assert t["fsdp2_tp2"]["mesh"] == {"fsdp": 2, "tp": 2}
        assert t["fsdp2_tp2"]["devices"] == [0, 1, 2, 3]
        assert t["max_loss_diff"] <= t["tolerance"]
        assert t["fsdp2_tp2"]["recompiles_after_step_1"] == 0
        assert t["param_spread"]["devices"] == [0, 1, 2, 3]

    def test_replicas_and_slices_sit_on_their_own_devices(self, four_chip_rehearsal):
        s = four_chip_rehearsal[0]["serving"]
        assert s["replicas_4"]["devices"] == [{"params": [i], "kv": [i]} for i in range(4)]
        assert s["replicas_4"]["prompt0_tokens_equal_across_replicas"] is True
        assert s["tp2_slices_2"]["devices"] == [{"params": [0, 1], "kv": [0, 1]},
                                                {"params": [2, 3], "kv": [2, 3]}]
        for fleet in ("replicas_4", "tp2_slices_2"):
            assert s[fleet]["compile_events_after_warmup"] == 0
        check = s["tp2_slices_2"]["slices_vs_reference"]
        assert check["logit_gap_of_served_tokens"]["share_within_tolerance"] >= 0.9


class TestFailures:
    def test_not_a_tpu_outside_the_rehearsal_exits_nonzero_with_no_result(self):
        """The suite's environment pins the CPU, as the sandbox does: with no
        ``--rehearse-cpu`` that is "no accelerator", whatever JAX_PLATFORMS says."""
        r = _run()
        assert r.returncode != 0
        assert "not a TPU" in r.stderr
        assert r.stdout.strip() == "", "printed a result without a chip"

    def test_four_chips_without_four_devices_exits_nonzero(self):
        r = _run("--four-chips")
        assert r.returncode != 0 and r.stdout.strip() == ""

    def test_failing_phase_exits_nonzero_without_the_ok_line(self, tmp_path):
        """A phase's exception is not caught: a tiny driver imports the
        script, makes the kernel tolerance impossible, and calls its main()."""
        driver = tmp_path / "drive.py"
        driver.write_text(
            "import sys, chip_smoke\n"
            "chip_smoke.KERNEL_TOL = {'fwd': 0.0, 'bwd': 0.0}\n"
            "sys.argv = ['chip_smoke.py', '--rehearse-cpu', '--phases', 'kernels']\n"
            "sys.exit(chip_smoke.main())\n")
        env = dict(os.environ, PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
        r = subprocess.run([sys.executable, str(driver)], capture_output=True, text=True,
                           env=env, timeout=600, cwd=str(tmp_path))
        assert r.returncode != 0
        assert "flash forward disagrees" in r.stderr
        assert '"ok"' not in r.stdout

    def test_unknown_phase_is_a_usage_error(self):
        r = _run("--rehearse-cpu", "--phases", "nonsense")
        assert r.returncode == 2 and '"ok"' not in r.stdout
