"""What every run shares: reading the manifest, claiming the chip, tracing,
the per-layer metric readers, the comparison's bookkeeping and the last line.

Nothing here names a cell, a configuration, a traffic mix or a metric: they
are found by the names in the manifest (``BENCHMARK.json``):

* configuration  -> the manifest entry's ``file``; its ``family`` names
  ``chipbench/models/<family>.py``;
* traffic mix    -> ``<paths[0]>/traffic/<traffic>.json``; its ``driver``
  names ``chipbench/drivers/<driver>.py``;
* limits of the comparison -> ``<paths[0]>/limits/<workload>.json``;
* per-layer metric -> ``chipbench/layer_metrics/<name>.py`` with one
  ``compute(ctx)``.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import shutil
import sys
from pathlib import Path

PACKAGE = Path(__file__).resolve().parent
ROOT = PACKAGE.parent
TRACE_DIR = ROOT / ".chipbench_trace"        # fixed, inside the checkout, git-ignored


class Refused(Exception):
    """The run cannot be a measurement (no chip, unknown chip, a compile in
    the window, ...): non-zero exit, no result line."""


@dataclasses.dataclass
class Cell:
    workload: dict
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list

    @property
    def name(self) -> str:
        return self.workload["name"]


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(manifest_path: Path, workload: str) -> Cell:
    manifest = json.loads(Path(manifest_path).read_text())
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r} in {manifest_path} (has {sorted(cells)})")
    entry = cells[workload]
    data_dir = ROOT / manifest["paths"][0]
    config_entry = next(c for c in manifest["configs"] if c["name"] == entry["config"])
    config = json.loads((ROOT / config_entry["file"]).read_text())
    traffic = json.loads((data_dir / "traffic" / f"{entry['traffic']}.json").read_text())
    limits = json.loads((data_dir / "limits" / f"{workload}.json").read_text())
    e2e = [m for m in manifest["end_to_end"] if _applies(m, workload)]
    reported = {m["name"] for m in e2e}
    layer = [m for m in manifest["per_layer"]
             if _applies(m, workload) and m["moves"] in reported]
    return Cell(entry, config, traffic, limits, e2e, layer)


def load_module(kind: str, name: str):
    """``chipbench/<kind>/<name>.py`` (``name`` may hold dots and dashes)."""
    path = PACKAGE / kind / f"{name}.py"
    if not path.is_file():
        raise Refused(f"{path.relative_to(ROOT)} does not exist")
    mod_name = f"chipbench.{kind}." + name.replace(".", "_").replace("-", "_")
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = module
    spec.loader.exec_module(module)
    return module


# ---------------------------------------------------------------------------
# The device
# ---------------------------------------------------------------------------

def claim_device(chips: int, require_chip: bool = True) -> tuple[dict, dict | None, dict]:
    """Touch jax. Returns ``(device, peaks, marks)``; without ``require_chip``
    (the CPU rehearsal) peaks are None and no time may be reported. ``marks``
    are the instants at which the imports were done and jax had its devices."""
    import time

    import jax

    from accelerate_tpu.utils.platforms import enable_compilation_cache

    marks = {"imported": time.monotonic()}           # jax and the program are in
    devices = jax.devices()
    marks["devices"] = time.monotonic()              # the runtime has the chip
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    if not require_chip:
        return device, None, marks
    if device["platform"] != "tpu":
        raise Refused(f"jax's first device is {device}, not a TPU; nothing ran")
    if len(devices) < chips:
        raise Refused(f"the cell needs {chips} chip(s), jax gives {len(devices)}")
    from chipbench.flops import load_peaks

    try:
        peaks = load_peaks(device["kind"])
    except KeyError as e:
        raise Refused(str(e)) from None
    enable_compilation_cache()
    # Every program, however quick its compile, is found again by the next run:
    # set-up is then the same work each time.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return device, peaks, marks


def memory_peak_bytes() -> int | None:
    """Peak bytes in use on the fullest device since the process started."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def release_program_state() -> None:
    """After a driver has dropped its references: reset the Accelerator's
    process-wide state (mesh, precision policy), collect, and drop jax's
    caches, so that the reference has the chip to itself."""
    import gc

    import jax

    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

    for cls in (AcceleratorState, GradientState, PartialState):
        cls._reset_state()
    gc.collect()
    jax.clear_caches()
    gc.collect()


def backend_compiles(watcher) -> list:
    """XLA backend compiles a CompileWatcher saw (not jaxpr traces)."""
    return [(n, d) for n, d in watcher.durations
            if n == "/jax/core/compile/backend_compile_duration"]


# ---------------------------------------------------------------------------
# Tracing
# ---------------------------------------------------------------------------

class Tracer:
    """A jax profiler trace of a few seconds of the window, written to the
    fixed ``.chipbench_trace/<workload>/`` and reduced by trace_reduce."""

    def __init__(self, workload: str):
        self.dir = TRACE_DIR / workload
        self.active = False

    def start(self) -> None:
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        self.dir.mkdir(parents=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0          # host python frames: huge, unused
        options.host_tracer_level = 1
        jax.profiler.start_trace(str(self.dir), profiler_options=options)
        self.active = True

    def stop(self):
        """Stops the trace; returns the reduced trace (trace_reduce.Trace)."""
        import jax

        from chipbench import trace_reduce

        jax.profiler.stop_trace()
        self.active = False
        files = sorted(self.dir.rglob("*.xplane.pb"))
        if not files:
            raise Refused(f"the profiler wrote no .xplane.pb under {self.dir}")
        return trace_reduce.load(files[-1])


# ---------------------------------------------------------------------------
# Per-layer metrics
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class LayerContext:
    """What a per-layer metric's ``compute(ctx)`` may read."""
    trace: object            # trace_reduce.Trace (device events of the traced span)
    stats: dict              # the program's counters over the window
    counts: dict             # the driver's own counts over the window
    window_s: float
    config: dict
    traffic: dict
    peaks: dict
    rates: dict              # this run's end-to-end metrics, by name


def layer_metrics(cell: Cell, ctx: LayerContext) -> dict:
    out = {}
    for metric in cell.per_layer:
        value = load_module("layer_metrics", metric["name"]).compute(ctx)
        if value is not None:                     # nothing to read: left out
            out[metric["name"]] = {"value": float(value), "unit": metric["unit"]}
    return out


# ---------------------------------------------------------------------------
# The comparison and the last line
# ---------------------------------------------------------------------------

def judge(readings: dict, limits: dict) -> tuple[bool, dict]:
    """Every number compared beside its limit. A limit the file lacks, or a
    reading that is missing or not finite, is a failure."""
    compared, ok = {}, True
    for name, limit in limits["limits"].items():
        value = readings.get(name)
        good = value is not None and value == value and value <= limit
        ok &= bool(good)
        compared[name] = {"value": value, "limit": limit}
    return ok, compared


def print_result(*, correct, attempted, failed, metrics, device, compared,
                 breakdown=None, extra=None) -> None:
    lines = [f"compared {k}: {v['value']} (limit {v['limit']})" for k, v in compared.items()]
    print("\n".join(lines) + f"\ncorrect: {correct}", file=sys.stderr, flush=True)
    result = {"correct": bool(correct), "attempted": int(attempted), "failed": int(failed),
              "metrics": metrics, "device": device}
    if breakdown:
        result["breakdown"] = breakdown
    result.update(extra or {})
    result["compared"] = compared
    print(json.dumps(result), flush=True)
