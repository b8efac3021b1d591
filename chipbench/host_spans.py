"""The program's own host spans, on the clock of the device trace.

``accelerate_tpu.observability.Tracer.span`` regions are also
``jax.profiler.TraceAnnotation``s named ``atpu:<span>``: in a profiler trace
they lie on the plane ``/host:CPU``, one line per thread, in the same
``.xplane.pb`` as the device's ``XLA Modules`` and ``XLA Ops`` lines. This
module reads them and puts the two side by side:

* ``idle_by_span``      the device's idle time, booked to the engine-thread
                        span that was open while it idled;
* ``thread_busy_share`` how much of a window one thread spent inside spans;
* ``launch_lag``        from the end of a ``tick_launch`` span to the start of
                        the decode program it launched.

**The two planes' clocks are not quite one.** On the v5e the device plane's
timestamps lay 1.6-1.9 ms early against the host plane's (my chip run, PR 26):
decode programs "started" 1.6 ms before the runtime's own ``DoEnqueueProgram``
event of the same ``run_id``, and "ended" 1.85-2.0 ms before its
``Execute=>Done``. So ``load`` also measures the skew from the runtime's
events — the least shift of the device plane that lets every program start
after it was enqueued — and every comparison here adds it to the device's
timestamps. Without such events the skew is taken as 0.

A thread is known by the spans on its line, not by a name: the line that
holds ``atpu:tick_launch`` is the engine's, the one with ``atpu:emit`` the
emitter's, the one with ``atpu:gw.sse_write`` the gateway loop's. A program
that has no such spans (the parent of the PR that added them) gives empty
tables and ``None`` metrics, never an error.

``python -m chipbench.host_spans FILE`` prints the three tables of a trace.
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
import statistics
import sys
from collections import defaultdict
from pathlib import Path

from chipbench import harness, trace_reduce

PREFIX = "atpu:"
HOST_PLANE = "/host:CPU"
ENGINE_MARK = "tick_launch"
EMITTER_MARK = "emit"
GATEWAY_MARK = "gw.sse_write"
#: Spans in which the host itself waits: for the device (its idle time under
#: them is wake-up latency) or for a request.
WAITS = ("prefill_wait", "tick_wait", "idle")
UNNAMED = "unnamed"
DECODE = r"^jit__paged_decode_fn"
#: The TPU runtime's own events on the host plane: a program handed to the
#: device (stat ``run_id``, as on the program's ``XLA Modules`` event), and the
#: host hearing that one finished.
ENQUEUE = "DoEnqueueProgram"
DONE = "tpu::System::Execute=>Done"


@dataclasses.dataclass(frozen=True)
class HostEvent:
    thread: int                       # index of the line on the host plane
    line: str                         # the line's name (a thread name; not unique)
    name: str                         # the span's name, prefix taken off
    start_ns: float
    dur_ns: float
    stats: tuple = ()                 # ((key, value), ...), e.g. trace_id

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def newest_trace(root: Path | None = None) -> Path | None:
    """The newest ``*.xplane.pb`` under the harness's trace directory (which
    ``harness.Tracer.start`` empties per cell before each trace)."""
    files = sorted((root or harness.TRACE_DIR).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime_ns)
    return files[-1] if files else None


class HostEvents(list):
    """The ``atpu:*`` events of one trace, and how far the device plane's
    clock lies behind the host plane's: add ``skew_ns`` to a device timestamp
    to place it among these events. ``skew_ceiling_ns`` is the most the
    runtime's ``Done`` events allow (None where there are none)."""

    skew_ns = 0.0
    skew_ceiling_ns = None


def clock_skew_ns(enqueued: dict, done: list, modules: list) -> tuple:
    """``(skew, ceiling)`` from the runtime's own host events. ``enqueued``:
    run_id -> start of its ``DoEnqueueProgram``; ``done``: starts of
    ``Execute=>Done``; ``modules``: ``(start, end, run_id)`` of the first
    device plane's programs. A program cannot start before it is enqueued, so
    the skew is at least ``enqueue - start`` for every program (and 0); it
    cannot end after the host heard of it, so at most ``done - end``."""
    floors = [enqueued[r] - start for start, _, r in modules if r in enqueued]
    skew = max(floors + [0.0])
    done = sorted(done)
    ceilings = []
    for _, end, _ in modules:
        i = bisect.bisect_left(done, end + skew)
        if i < len(done):
            ceilings.append(done[i] - end)
    return skew, (min(ceilings) if ceilings else None)


@functools.lru_cache(maxsize=1)
def _load(path: str, mtime_ns: int) -> tuple:
    from jax.profiler import ProfileData

    events, enqueued, done, modules = [], {}, [], []
    device = None
    for plane in ProfileData.from_file(path).planes:
        if plane.name == HOST_PLANE:
            for index, line in enumerate(plane.lines):
                for ev in line.events:
                    if ev.name.startswith(PREFIX):
                        events.append(HostEvent(index, line.name, ev.name[len(PREFIX):],
                                                float(ev.start_ns), float(ev.duration_ns),
                                                tuple(ev.stats)))
                    elif ev.name == ENQUEUE:
                        enqueued[dict(ev.stats).get("run_id")] = float(ev.start_ns)
                    elif ev.name == DONE:
                        done.append(float(ev.start_ns))
        elif trace_reduce.is_device_plane(plane.name) and (device is None or plane.name < device):
            device = plane.name
            modules = [(float(ev.start_ns), float(ev.start_ns + ev.duration_ns),
                        dict(ev.stats).get("run_id"))
                       for line in plane.lines if line.name == trace_reduce.MODULES_LINE
                       for ev in line.events]
    return (tuple(events),) + clock_skew_ns(enqueued, done, modules)


def load(path=None) -> HostEvents:
    """The ``atpu:*`` events of a trace's host plane and the clocks' skew; of
    this run's trace when no path is given, and empty where there is no
    trace."""
    path = Path(path) if path is not None else newest_trace()
    out = HostEvents()
    if path is not None and path.is_file():
        events, out.skew_ns, out.skew_ceiling_ns = _load(str(path), path.stat().st_mtime_ns)
        out.extend(events)
    return out


def skew_of(host_events) -> float:
    return getattr(host_events, "skew_ns", 0.0)


def thread_of(host_events, mark: str) -> int | None:
    """The thread whose line holds a span named ``mark``."""
    return next((e.thread for e in host_events if e.name == mark), None)


def on_thread(host_events, thread: int | None, prefix: str = "") -> list:
    return [e for e in host_events if e.thread == thread and e.name.startswith(prefix)]


def device_gaps(trace, skew_ns: float = 0.0) -> list:
    """``[(start_ns, end_ns)]``, on the host plane's clock: the gaps in the
    union of the first device plane's ``XLA Ops`` — what
    ``trace_reduce.idle_gaps`` sums by neighbours."""
    plane = trace.planes()[0] if trace.planes() else None
    gaps, end = [], None
    for e in sorted(trace.select(trace_reduce.OPS_LINE, plane=plane), key=lambda e: e.start_ns):
        if end is not None and e.start_ns > end:
            gaps.append((end + skew_ns, e.start_ns + skew_ns))
        end = e.end_ns if end is None else max(end, e.end_ns)
    return gaps


def innermost_segments(spans) -> list:
    """One thread's spans flattened to ``[(start_ns, end_ns, name)]`` without
    overlap: where spans nest, a stretch belongs to the innermost one."""
    segments, stack = [], []
    cursor = float("-inf")

    def close_until(t):
        nonlocal cursor
        while stack and stack[-1].end_ns <= t:
            top = stack.pop()
            if top.end_ns > cursor:
                segments.append((cursor, top.end_ns, top.name))
                cursor = top.end_ns

    for s in sorted(spans, key=lambda s: (s.start_ns, -s.dur_ns)):
        close_until(s.start_ns)
        if stack and s.start_ns > cursor:
            segments.append((cursor, s.start_ns, stack[-1].name))
        cursor = max(cursor, s.start_ns)
        stack.append(s)
    close_until(float("inf"))
    return segments


def idle_by_span(trace, host_events) -> dict:
    """``{span: seconds}``: each idle gap of the device booked to the
    engine-thread span open during it (the innermost; a gap that crosses spans
    is split), and to ``unnamed`` where none was. Only the stretch from the
    engine thread's first recorded span to its last is judged: a span already
    open when the profiler starts is not recorded, so the device's idle time
    during the profiler's own start and stop has nothing it could be booked
    to. Empty without an engine thread."""
    engine = thread_of(host_events, ENGINE_MARK)
    if engine is None or trace is None:
        return {}
    spans = on_thread(host_events, engine)
    first, last = min(e.start_ns for e in spans), max(e.end_ns for e in spans)
    segments = innermost_segments(spans)
    starts = [s[0] for s in segments]
    out: dict = defaultdict(float)
    for lo, hi in device_gaps(trace, skew_of(host_events)):
        lo, hi = max(lo, first), min(hi, last)
        if hi <= lo:
            continue
        named = 0.0
        i = max(0, bisect.bisect_right(starts, lo) - 1)
        while i < len(segments) and segments[i][0] < hi:
            start, end, name = segments[i]
            overlap = min(hi, end) - max(lo, start)
            if overlap > 0:
                out[name] += overlap * 1e-9
                named += overlap
            i += 1
        out[UNNAMED] += (hi - lo - named) * 1e-9
    return dict(out)


def thread_busy_share(host_events, thread, window_ns=None, prefix: str = "") -> float | None:
    """Share (0-1) of ``window_ns`` (``(lo, hi)``; by default from the first
    host span's start to the last one's end) that ``thread`` spent inside
    spans whose name starts with ``prefix``. ``None`` without such spans."""
    spans = on_thread(host_events, thread, prefix)
    if thread is None or not spans:
        return None
    lo, hi = window_ns or (min(e.start_ns for e in host_events),
                           max(e.end_ns for e in host_events))
    clipped = [dataclasses.replace(e, start_ns=max(e.start_ns, lo),
                                   dur_ns=min(e.end_ns, hi) - max(e.start_ns, lo))
               for e in spans if e.end_ns > lo and e.start_ns < hi]
    return trace_reduce.union_ns(clipped) / (hi - lo) if hi > lo else None


def launch_lag(trace, host_events, pattern: str = DECODE) -> list:
    """``[(span, module, lag_ns)]``: each execution of the decode program on
    ``XLA Modules`` beside the ``tick_launch`` span that launched it, and the
    program's start (on the host plane's clock) minus the span's end; negative
    where the device took the program up before the host call returned.
    Paired in order where the trace holds as many spans as programs — then
    "every program starts after its span began" is a check of the clocks, not
    of the pairing — and otherwise (a trace that begins or ends between a
    launch and its program) with the last span that began before the program."""
    engine = thread_of(host_events, ENGINE_MARK)
    if engine is None or trace is None:
        return []
    skew = skew_of(host_events)
    spans = sorted((e for e in on_thread(host_events, engine) if e.name == ENGINE_MARK),
                   key=lambda e: e.start_ns)
    plane = trace.planes()[0] if trace.planes() else None
    modules = sorted(trace.select(trace_reduce.MODULES_LINE, pattern, plane=plane),
                     key=lambda e: e.start_ns)
    if len(spans) == len(modules):
        pairs = list(zip(spans, modules))
    else:
        starts = [s.start_ns for s in spans]
        found = ((bisect.bisect_right(starts, m.start_ns + skew) - 1, m) for m in modules)
        pairs = [(spans[i], m) for i, m in found if i >= 0]
    return [(s, m, m.start_ns + skew - s.end_ns) for s, m in pairs]


def idle_table(idle: dict) -> str:
    """The table ``idle_named.serve`` prints: seconds of device idle time by
    span, the waits in rows of their own."""
    total = sum(idle.values()) or 1.0
    rows = sorted(idle.items(), key=lambda kv: -kv[1])
    work = [(k, v) for k, v in rows if k not in WAITS and k != UNNAMED]
    waits = [(k, v) for k, v in rows if k in WAITS]
    lines = [f"device idle by engine-thread span: {sum(idle.values()):.6f} s"]
    for title, part in (("host at work", work),
                        ("host waiting (wake-up latency)", waits),
                        ("no span", [(k, v) for k, v in rows if k == UNNAMED])):
        lines.append(f"  {title}:")
        lines += [f"    {v:10.6f} s  {100 * v / total:5.1f} %  {k}" for k, v in part]
    return "\n".join(lines)


def describe(trace, host_events) -> str:
    skew = skew_of(host_events)
    ceiling = getattr(host_events, "skew_ceiling_ns", None)
    out = [f"device clock behind the host plane's by {skew * 1e-3:.1f} us (the least shift that "
           f"lets every program start after the runtime enqueued it; its Done events allow "
           f"{'?' if ceiling is None else f'{ceiling * 1e-3:.1f}'} us at most); applied below", "",
           idle_table(idle_by_span(trace, host_events)), "", "threads (spans on the line):"]
    window = None
    if trace is not None and trace.events:
        lo, hi = trace.span_ns()
        window = (lo + skew, hi + skew)
    for thread in sorted({e.thread for e in host_events}):
        spans = on_thread(host_events, thread)
        by: dict = defaultdict(lambda: [0, 0.0])
        for e in spans:
            by[e.name][0] += 1
            by[e.name][1] += e.dur_ns * 1e-9
        share = thread_busy_share(host_events, thread, window)
        out.append(f"  line {thread} {spans[0].line!r}: busy {100 * (share or 0):.2f} % of the traced span")
        out += [f"    {s:10.6f} s x{n:<6d} {name}" for name, (n, s) in
                sorted(by.items(), key=lambda kv: -kv[1][1])]
    lags = launch_lag(trace, host_events)
    launches = sum(1 for e in host_events if e.name == ENGINE_MARK)
    modules = len(trace.select(trace_reduce.MODULES_LINE, DECODE)) if trace is not None else 0
    out += ["", f"launch lag: {launches} tick_launch spans, {modules} decode programs, "
                f"{len(lags)} paired{' in order' if launches == modules else ' by time'}"]
    if lags:
        values = sorted(lag * 1e-3 for _, _, lag in lags)
        after = sum(1 for s, m, _ in lags if m.start_ns + skew >= s.start_ns)
        raw = sum(1 for s, m, _ in lags if m.start_ns >= s.start_ns)
        inside = [lag * 1e-3 for s, m, lag in lags if lag < 0]
        out.append(f"  us: median {statistics.median(values):.1f}, min {values[0]:.1f}, "
                   f"p95 {values[int(0.95 * (len(values) - 1))]:.1f}, max {values[-1]:.1f}")
        out.append(f"  {after} of {len(lags)} programs start after their span began "
                   f"({raw} by the planes' raw clocks)")
        if inside:
            out.append(f"  {len(inside)} started inside their span (the device was idle): "
                       f"median {-statistics.median(inside):.1f} us before the call returned")
    return "\n".join(out)


if __name__ == "__main__":
    print(describe(trace_reduce.load(sys.argv[1]), load(sys.argv[1])))
