"""Driver ``closed_loop``: a served model under a fixed number of clients,
each of which sends its next request when the last one's ``done`` arrives.

The load generator is a child process started before this one touches jax
(closed_loop_client.py). After a ramp (each client has completed its ramp
requests) the window opens for ``--seconds``:

* the rate counts every output token that reached a client inside it;
* ITL samples are all gaps between consecutive tokens of one stream whose
  later token fell inside it;
* TTFT samples are all requests whose first token fell inside it, timed from
  the send;
* ``attempted`` is the requests sent inside it, ``failed`` those of them that
  ended in anything but a complete stream of the asked length.

Then the clients stop and in-flight requests drain outside the window. Once
it has closed and the peak memory is read, the engines are freed and the
plain reference runs once over a sample of the finished requests (drawn from
the seed, the longest in it): prompt + served tokens in one full forward
pass, and for every served token how far its logit lies below the
reference's best at that position.
"""

from __future__ import annotations

import gc
import json
import os
import subprocess
import sys
import time

import numpy as np

from chipbench import harness, traffic
from chipbench.drivers import RunResult


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile over ALL the samples."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def reduce_records(records: list, open_t: float, close_t: float) -> tuple[dict, dict]:
    """All the window's numbers from the clients' records: ``(metrics,
    counts)``. No chunking and no medians of parts: rates are over the whole
    window, tails over every sample in it."""
    inside = lambda t: open_t <= t < close_t                      # noqa: E731
    tokens = 0
    ttft, itl = [], []
    attempted = failed = 0
    work = []            # (prompt_len, first token inside?, [contexts of later tokens inside])
    for r in records:
        times = r["token_t"]
        if inside(r["send_t"]):
            attempted += 1
            failed += r["error"] is not None
        first_in = bool(times) and inside(times[0])
        if first_in:
            ttft.append((times[0] - r["send_t"]) * 1e3)
        later = []
        for j, t in enumerate(times):
            if not inside(t):
                continue
            tokens += 1
            if j > 0:
                itl.append((t - times[j - 1]) * 1e3)
                later.append(r["prompt_len"] + j)            # keys seen by token j's step
        if first_in or later:
            work.append((r["prompt_len"], first_in, later))
    window = close_t - open_t
    metrics = {"serve_tok_s": tokens / window}
    if ttft:
        metrics["ttft_p95_ms"] = percentile(ttft, 95)
    if itl:
        metrics["itl_p95_ms"] = percentile(itl, 95)
    later_all = [c for _, _, later in work for c in later]
    by_second = [0] * (int(window) + 1)            # where in the window the tokens fell
    for r in records:
        for t in r["token_t"]:
            if inside(t):
                by_second[int(t - open_t)] += 1
    counts = {
        "requests_sent": attempted, "requests_failed": failed, "output_tokens": tokens,
        "ttft_samples": len(ttft), "itl_samples": len(itl),
        "ttft_p50_ms": percentile(ttft, 50) if ttft else None,
        "itl_p50_ms": percentile(itl, 50) if itl else None,
        "itl_max_ms": max(itl) if itl else None,
        "tokens_by_second": by_second,
        "prompt_tokens_prefilled": sum(p for p, first, _ in work if first),
        "decode_tokens": len(later_all),
        "mean_decode_context": float(np.mean(later_all)) if later_all else None,
        "_work": work,
    }
    return metrics, counts


def pick_sample(records: list, per_client: int, seed: int) -> list:
    """Finished requests drawn from the seed: ``per_client`` of every client's,
    so that no client (and no slot that served it) goes unread, and the longest
    of all among them."""
    done = sorted((r for r in records if r["error"] is None and r["tokens"]),
                  key=lambda r: r["index"])
    if not done:
        return []
    longest = max(done, key=lambda r: r["prompt_len"] + len(r["tokens"]))
    rng = np.random.default_rng([int(seed), 4])
    sample = [longest]
    for client in sorted({r["client"] for r in done}):
        rest = [r for r in done if r["client"] == client and r is not longest]
        want = per_client - (longest["client"] == client)
        sample += [rest[i] for i in rng.permutation(len(rest))[:want]]
    return sample


def summarize(gaps: np.ndarray) -> dict:
    return {"gap_max": float(gaps.max()), "gap_mean": float(gaps.mean()),
            "gap_p90": float(np.percentile(gaps, 90)), "gap_p99": float(np.percentile(gaps, 99)),
            "share_exact": float((gaps == 0).mean()),
            "share_over_quarter": float((gaps > 0.25).mean()), "tokens": int(gaps.size)}


def check_against_reference(family, cfg: dict, seed: int, plan, sample: list, pad_to: int,
                            control: bool) -> dict:
    """The reference once over each sampled request (prompt + served tokens,
    padded to one static length so that nothing compiles per request): for
    every served token, the reference's best logit at its position minus the
    reference's logit of that token. With ``control`` also the reference in
    the nearest lower precision, read at the same positions: the gap of the
    token IT puts first."""
    import jax
    import jax.numpy as jnp

    from chipbench import reference_ops as ops

    params = family.make_params(cfg, seed)
    exact = ops.matmul("float32")
    lower = ops.matmul(ops.CONTROL_OF[cfg["torch_dtype"]])

    def gap_of(logits, tokens):
        """Per position t: best logit minus the logit of ``tokens[t]``."""
        return logits.max(-1) - jnp.take_along_axis(logits, tokens[:, None], axis=-1)[:, 0]

    @jax.jit
    def score(params, ids):                  # position t scores the token at t + 1
        logits = family.reference_logits(params, ids, cfg, exact)[:-1]
        return gap_of(logits, ids[1:])

    @jax.jit
    def score_control(params, ids):
        logits = family.reference_logits(params, ids, cfg, exact)[:-1]
        picks = jnp.argmax(family.reference_logits(params, ids, cfg, lower)[:-1], axis=-1)
        return gap_of(logits, picks)

    gaps, ctl_gaps = [], []
    for r in sample:
        ids = plan.prompt(r["index"]) + r["tokens"]
        padded = jnp.asarray(ids + [0] * (pad_to - len(ids)), jnp.int32)
        served = slice(r["prompt_len"] - 1, r["prompt_len"] - 1 + len(r["tokens"]))
        gaps.append(np.asarray(score(params, padded))[served])
        if control:
            ctl_gaps.append(np.asarray(score_control(params, padded))[served])
    out = {"program": summarize(np.concatenate(gaps))}
    if ctl_gaps:
        out["control"] = summarize(np.concatenate(ctl_gaps))
    return out


class GcWatch:
    """How long the collector stopped this process, and with it the engine's
    host loop, while the watch was on."""

    def __init__(self):
        self.pauses_ms, self._t0 = [], None

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.monotonic()
        elif self._t0 is not None:
            self.pauses_ms.append((time.monotonic() - self._t0) * 1e3)

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc) -> None:
        gc.callbacks.remove(self)


class Driver:
    def __init__(self, cell: harness.Cell, args, process_start: float):
        self.cell, self.args, self.process_start = cell, args, process_start
        self.child = None

    def before_device(self) -> None:
        env = {k: v for k, v in os.environ.items() if not k.startswith(("JAX_", "XLA_", "TPU_"))}
        env["PYTHONPATH"] = os.pathsep.join([str(harness.ROOT), env.get("PYTHONPATH", "")])
        self.child = subprocess.Popen(
            [sys.executable, "-m", "chipbench.drivers.closed_loop_client"],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, env=env,
            cwd=str(harness.ROOT))

    def close(self) -> None:
        if self.child is not None:
            if self.child.poll() is None:
                self.child.kill()
            self.child.wait()
            for pipe in (self.child.stdin, self.child.stdout):
                if pipe is not None:
                    pipe.close()
            self.child = None

    def _event(self, want: str) -> dict:
        line = self.child.stdout.readline()
        if not line:
            raise harness.Refused(f"the load generator ended before {want!r}")
        event = json.loads(line)
        if event["event"] != want:
            raise harness.Refused(f"the load generator said {str(event)[:500]} before {want!r}")
        return event

    def run(self, family, tracer, marks: dict) -> RunResult:
        from accelerate_tpu.utils.profiling import CompileWatcher

        cfg, mix, seed = self.cell.config, self.cell.traffic, self.args.seed
        phases = {name: t - self.process_start for name, t in marks.items()}
        server = family.build_server(cfg, family.make_params(cfg, seed))
        phases["built"] = time.monotonic() - self.process_start     # weights, fleet, its warm-up
        trace = None
        try:
            slots = server.slots()
            with CompileWatcher() as watcher:
                self.child.stdin.write(json.dumps({
                    "url": server.url, "traffic": mix, "vocab_size": cfg["vocab_size"],
                    "seed": seed, "seconds": self.args.seconds}) + "\n")
                self.child.stdin.flush()
                open_t = self._event("window_open")["t"]
                server.reset_stats()
                watcher.reset()
                with GcWatch() as collector:
                    if tracer is not None:
                        time.sleep(min(1.0, self.args.seconds / 4))
                        tracer.start()
                        time.sleep(min(mix["trace_seconds"], self.args.seconds / 2))
                        trace = tracer.stop()
                    self._event("window_close")
                stats = server.stats()
                pauses = collector.pauses_ms
                compiles = harness.backend_compiles(watcher)
                jit_events = {"traces_and_compiles": watcher.total, "cache_hits": watcher.cache_hits}
                result = self._event("records")
        finally:
            server.shutdown()
        if compiles:
            raise harness.Refused(f"{len(compiles)} backend compile(s) inside the window: "
                                  f"{compiles[:3]}; not steady state")
        print(json.dumps({"load_generator_lateness_s": result["lateness_s"]}), file=sys.stderr)
        peak = harness.memory_peak_bytes()
        server.free()
        del server

        records = result["records"]
        open_t, close_t = result["open_t"], result["close_t"]
        metrics, counts = reduce_records(records, open_t, close_t)
        metrics["setup_s"] = open_t - self.process_start
        counts.update(slots=slots, lateness_s=result["lateness_s"], setup_reached_s=phases,
                      window_jit_events=jit_events,   # a program first used inside the window
                      collector_pauses=len(pauses), collector_pause_max_ms=max(pauses, default=0.0),
                      requests_finished=sum(r["error"] is None for r in records),
                      errors=sorted({r["error"] for r in records if r["error"]})[:5])

        plan = traffic.RequestPlan(mix, cfg["vocab_size"], seed)
        sample = pick_sample(records, mix["check_requests_per_client"], seed)
        observed, readings = {"sampled_requests": [r["index"] for r in sample]}, {}
        if sample:
            t_ref = time.monotonic()
            checked = check_against_reference(
                family, cfg, seed, plan, sample, cfg["assumed"]["max_len"],
                control=bool(getattr(self.args, "control", 0)))
            observed.update(checked, reference_s=time.monotonic() - t_ref)
            readings = dict(checked["program"])
            if "control" in checked:               # judged as a run would be: has to fail
                observed["control_correct"] = harness.judge(checked["control"], self.cell.limits)[0]
                print(f"control_correct: {observed['control_correct']}", file=sys.stderr)
        return RunResult(
            metrics=metrics, attempted=counts["requests_sent"], failed=counts["requests_failed"],
            window_s=close_t - open_t, counts=counts, stats=stats, readings=readings,
            observed=observed, memory_peak_bytes=peak, trace=trace)
