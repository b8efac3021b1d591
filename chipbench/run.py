"""One process, one cell, one run.

    python -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Refuses to start unless jax's first device is a TPU whose ``device_kind`` is
a key of peaks.json. Sets up, warms up that cell's shapes, measures for
``--seconds``, compares the outputs with the plain reference and prints the
result as the last line of standard output. Everything that belongs to one
cell is found by name (see harness.py); nothing here names one.
"""

from __future__ import annotations

import time

PROCESS_START = time.monotonic()      # setup_s counts from here

import argparse                       # noqa: E402
import sys                            # noqa: E402
from pathlib import Path              # noqa: E402

from chipbench import harness         # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest", default=str(harness.ROOT / "BENCHMARK.json"))
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also read the control (the reference in the nearest lower "
                         "precision) and the plantable faults; the benchmark's own runs do not")
    return ap.parse_args(argv)


def run(args, require_chip: bool = True, process_start: float | None = None) -> int:
    """The whole run. ``require_chip=False`` is the CPU rehearsal (tests):
    the same control flow, and a last line with counts only."""
    start = PROCESS_START if process_start is None else process_start
    cell = harness.load_cell(Path(args.manifest), args.workload)
    driver = harness.load_module("drivers", cell.traffic["driver"]).Driver(cell, args, start)
    try:
        driver.before_device()                    # children start before jax is touched
        device, peaks, marks = harness.claim_device(cell.workload["chips"], require_chip)
        family = harness.load_module("models", cell.config["family"])
        result = driver.run(family, harness.Tracer(cell.name) if args.trace else None, marks)
    finally:
        driver.close()

    device["memory_peak_bytes"] = result.memory_peak_bytes
    correct, compared = harness.judge(result.readings, cell.limits)
    correct &= result.failed == 0
    counts = {k: v for k, v in result.counts.items() if not k.startswith("_")}
    metrics, breakdown, extra = {}, None, {"counts": counts, "observed": result.observed}
    if peaks is None:                             # the CPU rehearsal: counts only
        result.observed.pop("reference_s", None)
        for name in [k for k in counts if k.endswith(("_ms", "_s"))]:
            del counts[name]
    else:                                         # a chip: times may be reported
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        extra["stats"] = result.stats
        rates = {k: v for k, v in result.metrics.items() if k in units}
        if args.trace:
            from chipbench import trace_reduce

            ctx = harness.LayerContext(result.trace, result.stats, result.counts,
                                       result.window_s, cell.config, cell.traffic, peaks, rates)
            metrics = harness.layer_metrics(cell, ctx)
            extra["end_to_end_of_this_traced_run"] = rates
            device["busy_s"] = result.trace.busy_s()
            device["window_s"] = result.trace.window_s()
            breakdown = {"device_ops": trace_reduce.top_ops(result.trace),
                         "idle_gaps": trace_reduce.idle_gaps(result.trace)}
        else:
            metrics = {k: {"value": float(v), "unit": units[k]} for k, v in rates.items()}
    harness.print_result(correct=correct, attempted=result.attempted, failed=result.failed,
                         metrics=metrics, device=device, compared=compared,
                         breakdown=breakdown, extra=extra)
    return 0


def main(argv=None) -> int:
    try:
        return run(parse(argv))
    except harness.Refused as e:
        print(f"chipbench.run: {e}", file=sys.stderr)
        return 1


if __name__ == "__main__":
    sys.exit(main())
