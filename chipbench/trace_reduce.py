"""From a profiler trace (``.xplane.pb``) to numbers. Needs nothing but jax's
own reader. ``python -m chipbench.trace_reduce FILE`` prints what a trace
holds (planes, lines, heaviest names): look before writing a name pattern.

A device plane of a TPU trace (``/device:TPU:n``) has, among others, the line
``XLA Modules`` (one event per execution of a jitted program, named
``jit_<function>(<fingerprint>)``) and the line ``XLA Ops`` (one event per
HLO operation executed, nested operations included, e.g. the body of a
``while``). Busy time is the UNION of the op intervals, so nested or
overlapping events count once.
"""

from __future__ import annotations

import dataclasses
import re
import sys
from collections import defaultdict

MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Trace:
    events: list                      # every event of every device plane

    def planes(self) -> list:
        return sorted({e.plane for e in self.events})

    def select(self, line: str, pattern: str | None = None, plane: str | None = None) -> list:
        """Events of ``line`` whose name matches the regex ``pattern``."""
        rx = re.compile(pattern) if pattern else None
        return [e for e in self.events if e.line == line
                and (plane is None or e.plane == plane)
                and (rx is None or rx.search(e.name))]

    def span_ns(self) -> tuple[float, float]:
        """First start and last end over the op events of all device planes."""
        ops = self.select(OPS_LINE) or self.events
        return min(e.start_ns for e in ops), max(e.end_ns for e in ops)

    def window_s(self) -> float:
        lo, hi = self.span_ns()
        return (hi - lo) * 1e-9

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the device planes."""
        planes = self.planes()
        return sum(union_ns(self.select(OPS_LINE, plane=p)) for p in planes) * 1e-9 / len(planes)


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CUSTOM" not in name.upper()


def load(path) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    events = []
    for plane in data.planes:
        if not is_device_plane(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                events.append(Event(plane.name, line.name, ev.name,
                                    float(ev.start_ns), float(ev.duration_ns)))
    return Trace(events)


def union_ns(events) -> float:
    """Total length of the union of the events' intervals."""
    total, end = 0.0, float("-inf")
    for e in sorted(events, key=lambda e: e.start_ns):
        if e.start_ns > end:
            total += e.dur_ns
            end = e.end_ns
        elif e.end_ns > end:
            total += e.end_ns - end
            end = e.end_ns
    return total


def idle_percent(trace: Trace) -> float:
    """100 x (1 - busy / traced span)."""
    return 100.0 * (1.0 - trace.busy_s() / trace.window_s())


def sum_by_name(events) -> dict:
    out: dict = defaultdict(float)
    for e in events:
        out[e.name] += e.dur_ns * 1e-9
    return dict(out)


def mean_duration_s(events) -> float | None:
    return sum(e.dur_ns for e in events) * 1e-9 / len(events) if events else None


def mean_module_ms(trace: Trace, pattern: str) -> float | None:
    """Mean device time, in ms, of one execution of the programs whose name on
    the ``XLA Modules`` line matches ``pattern``; None where none ran."""
    mean = mean_duration_s(trace.select(MODULES_LINE, pattern))
    return None if mean is None else mean * 1e3


def leaf_events(events) -> list:
    """Drop events that contain another event of the list (a ``while`` around
    its body): what is left tiles the busy time without double counting."""
    ordered = sorted(events, key=lambda e: (e.start_ns, -e.dur_ns))
    keep = []
    for i, e in enumerate(ordered):
        nxt = ordered[i + 1] if i + 1 < len(ordered) else None
        if nxt is not None and nxt.start_ns < e.end_ns and nxt.end_ns <= e.end_ns \
                and nxt.dur_ns < e.dur_ns:
            continue
        keep.append(e)
    return keep


def short_name(name: str) -> str:
    """An XLA Ops event is named by its whole HLO instruction: keep the
    result's name, the opcode and, for a custom call, its target."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:120]
    opcode = re.search(r"\s([a-z][a-z0-9\-]*)\(", " " + rest)
    target = re.search(r'custom_call_target="([^"]+)"', rest)
    parts = [head, opcode.group(1) if opcode else "?"] + ([target.group(1)] if target else [])
    return " ".join(parts)[:120]


def top_ops(trace: Trace, n: int = 10) -> list:
    """[[name, seconds]] of the device operations that took most time."""
    plane = trace.planes()[0] if trace.planes() else None
    sums: dict = defaultdict(float)
    for name, seconds in sum_by_name(leaf_events(trace.select(OPS_LINE, plane=plane))).items():
        sums[short_name(name)] += seconds
    return [[k, v] for k, v in sorted(sums.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: Trace, n: int = 10) -> list:
    """[[what, seconds]]: idle time of the first device summed by the pair of
    programs it lay between (host spans inside the program do not exist yet,
    so a gap is named by its neighbours)."""
    plane = trace.planes()[0] if trace.planes() else None
    ops = sorted(trace.select(OPS_LINE, plane=plane), key=lambda e: e.start_ns)
    mods = sorted(trace.select(MODULES_LINE, plane=plane), key=lambda e: e.start_ns)

    def module_at(t):
        name = "?"
        for m in mods:
            if m.start_ns <= t:
                name = re.sub(r"\(.*", "", m.name)
            else:
                break
        return name

    gaps: dict = defaultdict(float)
    end = None
    for e in ops:
        if end is not None and e.start_ns > end:
            gaps[f"after {module_at(end - 1)} before {module_at(e.start_ns)}"] += \
                (e.start_ns - end) * 1e-9
        end = e.end_ns if end is None else max(end, e.end_ns)
    return [[k, v] for k, v in sorted(gaps.items(), key=lambda kv: -kv[1])[:n]]


def describe(trace: Trace, n: int = 25) -> str:
    out = []
    by = defaultdict(list)
    for e in trace.events:
        by[(e.plane, e.line)].append(e)
    for (plane, line), evs in sorted(by.items()):
        out.append(f"{plane} | {line}: {len(evs)} events, "
                   f"sum {sum(e.dur_ns for e in evs) * 1e-9:.4f}s, union {union_ns(evs) * 1e-9:.4f}s")
        sums = sum_by_name(evs)
        counts = defaultdict(int)
        for e in evs:
            counts[e.name] += 1
        for name, s in sorted(sums.items(), key=lambda kv: -kv[1])[:n]:
            out.append(f"    {s:10.5f}s x{counts[name]:<6d} {name[:140]}")
    out.append(f"window {trace.window_s():.4f}s busy {trace.busy_s():.4f}s")
    return "\n".join(out)


if __name__ == "__main__":
    print(describe(load(sys.argv[1])))
