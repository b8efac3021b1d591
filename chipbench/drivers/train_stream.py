"""Driver ``train_stream``: a training job fed fresh batches from the seed.

Set-up builds ONE object — the compiled step with its state — and drives it
through its first ``reference_steps`` steps by the window's own call and
feed; after step 1 it reads the step's loss, its gradient norm and the norms
of Adam's first moment, after the last of them the norms of the parameters'
change. The same object then runs the window: steps until ``--seconds`` have
passed, the loss read back every ``loss_readback_every`` steps as a training
script logs it. Once the window has closed and the peak memory is read, the
state is freed and the plain reference follows the same first steps.
"""

from __future__ import annotations

import statistics
import sys
import time

import numpy as np

from chipbench import harness, traffic
from chipbench.drivers import RunResult


def worst_leaf_gap(got, want) -> tuple[float, int]:
    """Largest ``|got - want|`` over the leaves, against the reference's norm
    of that leaf or of the median leaf, whichever is larger."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = np.maximum(want, statistics.median(want.tolist()))
    gaps = np.abs(got - want) / scale
    return float(gaps.max()), int(gaps.argmax())


def compare(program: dict, reference: dict) -> tuple[dict, dict]:
    """The numbers compared (``readings``) and what else was seen."""
    ref_losses = np.asarray(reference["losses"])
    loss_gaps = np.abs(np.asarray(program["losses"]) - ref_losses) / ref_losses
    grad_gap, grad_leaf = worst_leaf_gap(program["grad_norms"], reference["grad_norms"])
    # Leaves whose gradient is nought to rounding in the reference move under
    # Adam by round-off alone: left out of the change by a rule on the
    # reference's gradient (under a thousandth of the median leaf's).
    ref_grads = np.asarray(reference["grad_norms"])
    moved = ref_grads >= 1e-3 * statistics.median(ref_grads.tolist())
    delta_gap, delta_leaf = worst_leaf_gap(np.asarray(program["delta_norms"])[moved],
                                           np.asarray(reference["delta_norms"])[moved])
    leaves = reference["leaves"]
    readings = {
        "loss_gap": float(loss_gaps.max()),
        "gnorm_gap": abs(program["gnorm"] - reference["gnorm"]) / reference["gnorm"],
        "grad_leaf_gap": grad_gap,
        "delta_leaf_gap": delta_gap,
    }
    observed = {
        "program_losses": program["losses"], "reference_losses": reference["losses"],
        "loss_gaps": loss_gaps.tolist(),
        "program_gnorm": program["gnorm"], "reference_gnorm": reference["gnorm"],
        "grad_worst_leaf": leaves[grad_leaf],
        "delta_worst_leaf": [l for l, m in zip(leaves, moved) if m][delta_leaf],
        "leaves_left_out_of_delta": [l for l, m in zip(leaves, moved) if not m],
    }
    return readings, observed


class Driver:
    def __init__(self, cell: harness.Cell, args, process_start: float):
        self.cell, self.args, self.process_start = cell, args, process_start

    def before_device(self) -> None:
        pass

    def close(self) -> None:
        pass

    def run(self, family, tracer, marks: dict) -> RunResult:
        import jax

        from accelerate_tpu.utils.profiling import CompileWatcher
        from chipbench import reference_ops as ops

        cfg, mix, seed = self.cell.config, self.cell.traffic, self.args.seed
        batch_size, seq = mix["batch"], mix["seq_len"]
        n_ref = mix["reference_steps"]
        rows = traffic.TokenRows(mix, cfg["vocab_size"], seed)
        table = family.leaf_table(cfg)
        key = ops.seed_key(seed)

        since_start = lambda: time.monotonic() - self.process_start      # noqa: E731
        phases = {name: t - self.process_start for name, t in marks.items()}
        trainer = family.build_trainer(cfg, family.make_params(cfg, seed), rows, batch_size)
        feed = iter(trainer.loader)
        phases["built"] = since_start()            # weights, prepare, the step's closure
        program = {"losses": []}
        for i in range(n_ref):                     # the window's own call and feed
            metrics = trainer.step(next(feed))
            program["losses"].append(float(metrics["loss"]))
            if i == 0:
                program["gnorm"] = float(metrics["grad_norm"])
                mu = np.asarray(jax.jit(ops.leaf_norms)(trainer.adam_mu()))
                # mu_1 = (1 - b1) * g: the gradient as the optimizer got it
                program["grad_norms"] = (mu / (1.0 - cfg["assumed"]["adam_b1"])).tolist()
        program["delta_norms"] = ops.delta_norms(trainer.params, table, key, "float32")
        trainer.step(next(feed))                   # one more, so nothing is first in the window
        steps_before = n_ref + 1

        every = mix["loss_readback_every"]
        trace_s = mix["trace_seconds"]
        jax.block_until_ready(trainer.params)
        phases["warmed"] = since_start()           # compile or cache load, the first steps
        with CompileWatcher() as watcher:
            t0 = time.monotonic()
            setup_s = t0 - self.process_start
            steps, trace, logged = 0, None, []
            while True:
                now = time.monotonic() - t0
                if now >= self.args.seconds:
                    break
                if tracer is not None and trace is None:
                    if not tracer.active and now >= 1.0:
                        tracer.start()
                        trace_t0 = now
                    elif tracer.active and now - trace_t0 >= trace_s:
                        jax.block_until_ready(trainer.params)
                        trace = tracer.stop()
                metrics = trainer.step(next(feed))
                steps += 1
                if steps % every == 0:
                    logged.append(float(metrics["loss"]))
            jax.block_until_ready(trainer.params)
            window_s = time.monotonic() - t0
            if tracer is not None and tracer.active:
                trace = tracer.stop()
            compiles = harness.backend_compiles(watcher)
            jit_events = {"traces_and_compiles": watcher.total, "cache_hits": watcher.cache_hits}
        if compiles:
            raise harness.Refused(f"{len(compiles)} backend compile(s) inside the window: "
                                  f"{compiles[:3]}; not steady state")
        last_loss = float(metrics["loss"])
        peak = harness.memory_peak_bytes()
        trainer.free()
        del trainer, feed, metrics

        batches = [rows.batch(i, batch_size) for i in range(n_ref)]
        t_ref = time.monotonic()
        reference = family.reference_train(cfg, seed, batches)
        reference_s = time.monotonic() - t_ref
        readings, observed = compare(program, reference)
        if getattr(self.args, "control", 0):
            lower = ops.CONTROL_OF[cfg["torch_dtype"]]
            control = family.reference_train(cfg, seed, batches, precision=lower)
            halved = family.reference_train(cfg, seed, batches, fault="half_batch")
            for name, stand_in in (("control", control), ("fault_half_batch", halved)):
                observed[name] = compare(stand_in, reference)[0]
                # judged as a run would be, against the cell's limits: has to fail
                observed[f"{name}_correct"] = harness.judge(observed[name], self.cell.limits)[0]
                print(f"{name}_correct: {observed[name + '_correct']}", file=sys.stderr)
        observed.update(reference_s=reference_s, window_last_loss=last_loss,
                        window_logged_losses=len(logged))
        finite = bool(np.isfinite(logged + [last_loss]).all())
        tokens = steps * batch_size * seq
        return RunResult(
            metrics={"train_tok_s": tokens / window_s, "setup_s": setup_s},
            attempted=steps, failed=0 if finite else steps,
            window_s=window_s,
            counts={"steps": steps, "tokens": tokens, "batch": batch_size, "seq_len": seq,
                    "steps_before_window": steps_before, "setup_reached_s": phases,
                    "window_jit_events": jit_events},
            stats={}, readings=readings, observed=observed,
            memory_peak_bytes=peak, trace=trace)
