"""Both cells end to end at toy size on the CPU — the whole of a run but the
harness's look for a chip — sound, and with the timed path broken underneath;
the control (the reference in the nearest lower precision, put in the
program's place) at toy size; and the refusals."""

import contextlib
import io
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from chipbench import harness, run, traffic
from chipbench import reference_ops as ops
from chipbench.drivers import train_stream

TOY = harness.PACKAGE / "tests" / "toy" / "BENCHMARK.json"


def run_cell(workload, seed=3, seconds=1.5, control=0):
    args = run.parse(["--workload", workload, "--seed", str(seed), "--seconds", str(seconds),
                      "--trace", "0", "--control", str(control), "--manifest", str(TOY)])
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = run.run(args, require_chip=False)
    assert rc == 0
    last = json.loads(out.getvalue().strip().splitlines()[-1])
    return last, err.getvalue()


def assert_counts_only(last):
    assert list(last)[-1] == "compared"            # the numbers compared come last
    assert last["device"]["platform"] == "cpu" and last["metrics"] == {}
    text = json.dumps({k: last[k] for k in ("metrics", "counts")})
    assert not any(word in text for word in ("_ms", "_s\"", "tok_s", "mfu", "roofline"))


def test_train_cell_end_to_end_on_the_cpu():
    last, err = run_cell("toy-train", seed=2 ** 31 + 5, control=1)
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] >= 5
    # --control 1: the control and the half-batch fault, judged as a run is, both fail
    assert last["observed"]["control_correct"] is False
    assert last["observed"]["fault_half_batch_correct"] is False
    assert "control_correct: False" in err and "fault_half_batch_correct: False" in err
    assert_counts_only(last)
    assert set(last["compared"]) == {"loss_gap", "gnorm_gap", "grad_leaf_gap", "delta_leaf_gap"}
    for name, entry in last["compared"].items():   # each number beside its limit, on stderr too
        assert f"compared {name}: {entry['value']} (limit {entry['limit']})" in err
    assert err.rstrip().endswith("correct: True")


def break_trainer(monkeypatch, make_step):
    family = harness.load_module("models", "mistral_dense")
    build = family.build_trainer

    def broken(*args, **kwargs):
        trainer = build(*args, **kwargs)
        trainer.step = make_step(trainer, trainer.step)
        return trainer

    monkeypatch.setattr(family, "build_trainer", broken)


def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    import jax
    import jax.numpy as jnp

    def make_step(trainer, step):
        def unchanged(batch):
            saved = jax.tree.map(jnp.copy, (trainer.model.params, trainer.optimizer.opt_state))
            metrics = step(batch)                  # the real step (it donates its state) ...
            trainer.model.params, trainer.optimizer.opt_state = saved   # ... thrown away
            return metrics
        return unchanged

    break_trainer(monkeypatch, make_step)
    last, _ = run_cell("toy-train")
    assert last["correct"] is False
    # Adam's moment stays nought and no parameter moves: both read 1 by the measure
    assert last["compared"]["grad_leaf_gap"]["value"] == pytest.approx(1.0, abs=1e-3)
    assert last["compared"]["delta_leaf_gap"]["value"] == pytest.approx(1.0, abs=1e-3)


def test_half_of_the_batch_left_out_is_not_correct(monkeypatch):
    def make_step(trainer, step):
        def halved(batch):
            half = {k: np.concatenate([np.asarray(v)[: len(v) // 2]] * 2) for k, v in batch.items()}
            return step(half)                      # the mean over the first half alone
        return halved

    break_trainer(monkeypatch, make_step)
    last, _ = run_cell("toy-train")
    assert last["correct"] is False
    gnorm = last["compared"]["gnorm_gap"]
    assert gnorm["value"] > gnorm["limit"]         # ~0.4: rows' gradients barely correlate


def test_the_train_control_is_not_correct():
    """The reference itself in fp8, put in the program's place, at toy size."""
    cell = harness.load_cell(TOY, "toy-train")
    family = harness.load_module("models", cell.config["family"])
    rows = traffic.TokenRows(cell.traffic, cell.config["vocab_size"], seed=11)
    batches = [rows.batch(i, cell.traffic["batch"]) for i in range(cell.traffic["reference_steps"])]
    reference = family.reference_train(cell.config, 11, batches)
    lower = ops.CONTROL_OF[cell.config["torch_dtype"]]
    control = family.reference_train(cell.config, 11, batches, precision=lower)
    ok, compared = harness.judge(train_stream.compare(control, reference)[0], cell.limits)
    assert not ok, compared
    same = family.reference_train(cell.config, 11, batches)
    ok, compared = harness.judge(train_stream.compare(same, reference)[0], cell.limits)
    assert ok and all(c["value"] == 0 for c in compared.values())


def test_serve_cell_end_to_end_on_the_cpu_and_its_control():
    last, err = run_cell("toy-serve", seed=2 ** 31 + 6, seconds=2.0, control=1)
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] >= 8
    assert_counts_only(last)
    assert last["counts"]["requests_sent"] == last["attempted"]
    assert len(last["observed"]["sampled_requests"]) == 4
    assert "load_generator_lateness_s" in err
    assert last["observed"]["control_correct"] is False    # the fp8 reference's own tokens
    assert "control_correct: False" in err


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from accelerate_tpu.serving.engine import ServingEngine

    commit = ServingEngine._commit_token

    def altered(self, req, token):
        if len(req.tokens) % 3 == 2:               # every third token of every stream
            token = (int(token) + 1) % 256
        return commit(self, req, token)

    monkeypatch.setattr(ServingEngine, "_commit_token", altered)
    last, _ = run_cell("toy-serve", seconds=2.0)
    assert last["correct"] is False and last["failed"] == 0
    assert last["compared"]["gap_mean"]["value"] > 10 * last["compared"]["gap_mean"]["limit"]


def test_without_a_tpu_the_command_exits_non_zero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(harness.ROOT))
    for workload in ("toy-train", "toy-serve"):
        done = subprocess.run(
            [sys.executable, "-m", "chipbench.run", "--workload", workload, "--seed", "1",
             "--seconds", "1", "--trace", "0", "--manifest", str(TOY)],
            capture_output=True, text=True, env=env, cwd=str(harness.ROOT), timeout=120)
        assert done.returncode != 0 and done.stdout == ""
        assert "not a TPU" in done.stderr


def test_alone_in_a_directory_the_command_exits_non_zero(tmp_path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.PACKAGE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "*.pb"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    manifest = json.loads((tmp_path / "BENCHMARK.json").read_text())
    for cell in manifest["workloads"]:
        done = subprocess.run(
            manifest["command"] + ["--workload", cell["name"], "--seed", "1", "--seconds", "1",
                                   "--trace", "0"],
            capture_output=True, text=True, env=dict(env, JAX_PLATFORMS="cpu"),
            cwd=str(tmp_path), timeout=120)
        assert done.returncode != 0 and done.stdout == ""
