"""Device time by the program's own parts, read from a trace's ``tf_op`` metadata.

The programs name their parts (``accelerate_tpu/observability/program_parts.py``:
``jax.named_scope`` through ``program_part``), and every HLO instruction keeps
the scopes it was made under in its ``op_name``. A profiler trace keeps that
path too, but not where ``jax.profiler.ProfileData`` looks: an ``XLA Ops``
event holds only its own stats (``device_offset_ps``, ``device_duration_ps``);
the path is a stat of the event's METADATA — the device plane's
``event_metadata`` map, one entry an instruction, with ``tf_op`` (the
``op_name``, then ``:`` and an op type), ``hlo_category``, ``program_id``,
``flops``, ``bytes_accessed``, ``source``. This module reads that map from the
file's bytes with a small protobuf wire reader that SKIPS the plane's ``lines``
(the events, up to a million of them, stay with jax's C++ reader:
``trace_reduce.load``), joins it to the events by name, and books device time
to parts:

* **the map** (``read_op_map``): instruction text -> the parts on its path
  (the segments of ``tf_op`` that are in the vocabulary; a transformation wraps
  the scope next to it, ``vmap(sample)``, and is taken off), ``hlo_category``,
  ``program_id``, ``bytes_accessed``. Where XLA merged instructions the paths
  are joined with ``;``: the first path that holds a part counts.
* **the join**: an event's name is its metadata's name, the whole instruction
  text. A program's name on ``XLA Modules`` ends in its ``program_id``, so an
  event inside an execution finds its own program's instruction; where that
  fails and several programs hold the text under different parts, the time
  goes to ``ambiguous``, never to a guess.
* **inheritance**: an event with no part of its own — a leaf of a ``while``
  body that the compiler made, a ``copy`` it put in — takes the parts of the
  innermost event that encloses it in time; with none around it (the TPU
  compiler rebuilds a ``while`` without its metadata, and only the body's
  instructions keep their paths) it takes the parts that the events nested
  in it share.
* **per execution**: an execution's busy time is cut into stretches that each
  belong to the innermost event running then, so nested events (a ``while``
  and its body) count once, as in ``trace_reduce.union_ns``. A part's time is
  the stretches whose path holds the part; its OWN time those whose path ends
  in it, so one program's own times + ``unscoped`` + ``ambiguous`` are its
  busy time exactly. Only executions that lie wholly inside the traced span
  count (``decode_tick_device_ms`` averages the cut-off last one in).
* **relayout**: the stretches of leaf events whose ``hlo_category`` is ``data
  formatting`` or that carry no ``tf_op`` of their own (``copy.N``,
  ``copy-done.N``: the compiler's), under whatever part. A re-layout the
  compiler fused with arithmetic (``slice_bitcast_fusion``,
  ``convert_bitcast_fusion``) is not seen.

A trace of a program without the vocabulary (the parent of the PR that added
it) gives empty tables and ``None`` metrics, never an error.

``python -m chipbench.op_scopes FILE`` prints the tables of a trace: per
program one row a part — ms an execution, share of the program's busy time,
own ms, the re-layouts under it, and XLA's own ``bytes_accessed`` over the
time in GB/s (for reading only: no roofline is built on it).
"""

from __future__ import annotations

import bisect
import dataclasses
import re
import sys
from collections import defaultdict
from pathlib import Path

from chipbench import host_spans, trace_reduce

try:
    from accelerate_tpu.observability.program_parts import PROGRAM_PARTS as PARTS
except ImportError:                    # a program from before the vocabulary
    PARTS = ()

DECODE = r"^jit__paged_decode_fn"
CHUNK = r"^jit__paged_prefill_chunk_fn"
TRAIN = r"^jit_train_step"
UNSCOPED = "unscoped"
AMBIGUOUS = "ambiguous"
RELAYOUT_CATEGORY = "data formatting"
EXPERT_PARTS = ("moe_router", "moe_experts", "moe_shared")
SSM_PARTS = ("ssm_in", "ssm_conv", "ssm_scan", "ssm_out")


# ---------------------------------------------------------------------------
# The wire reader: XSpace -> the first device plane's two metadata maps
# ---------------------------------------------------------------------------

def _varint(buf, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, wire type, value)`` of one message: an int for a
    varint or a fixed-width field, a memoryview for a length-delimited one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            width = 8 if wire == 1 else 4
            value, i = int.from_bytes(buf[i:i + width], "little"), i + width
        else:
            raise ValueError(f"wire type {wire} at byte {i}: not an XSpace")
        yield number, wire, value


def _signed(value: int) -> int:
    """A varint read as an ``int64`` field (ids, map keys, ``int64_value``)."""
    return value - (1 << 64) if value >= 1 << 63 else value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_entry(buf) -> tuple[int, memoryview]:
    key, value = 0, memoryview(b"")
    for number, _, v in _fields(buf):
        if number == 1:
            key = _signed(v)
        elif number == 2:
            value = v
    return key, value


#: XStat: which field holds the value, by field number
_STAT_UINT, _STAT_INT, _STAT_STR, _STAT_REF = 3, 4, (5, 6), 7


def _stats(buf, stat_names: dict) -> dict:
    """One XEventMetadata's stats as ``{stat name: int or str}`` (a
    ``ref_value`` is a string kept as a stat metadata's name)."""
    out = {}
    for number, _, stat in _fields(buf):
        if number != 5:                              # XEventMetadata.stats
            continue
        key = value = None
        for n, _, v in _fields(stat):
            if n == 1:
                key = stat_names.get(_signed(v))
            elif n == _STAT_UINT:
                value = v
            elif n == _STAT_INT:
                value = _signed(v)
            elif n in _STAT_STR:
                value = _text(v)
            elif n == _STAT_REF:
                value = stat_names.get(v, "")
        if key is not None and value is not None:
            out[key] = value
    return out


def read_planes_metadata(path) -> dict:
    """``{plane name: (event_metadata, stat_names)}`` of an ``.xplane.pb``,
    the planes' ``lines`` skipped: ``event_metadata`` is ``{id: (name, raw
    XEventMetadata bytes)}``, ``stat_names`` ``{id: name}``."""
    data = memoryview(Path(path).read_bytes())
    planes = {}
    for number, _, plane in _fields(data):
        if number != 1:                              # XSpace.planes
            continue
        name, events, stat_names = "", {}, {}
        for n, _, v in _fields(plane):
            if n == 2:
                name = _text(v)
            elif n == 4:                             # event_metadata: map<int64, XEventMetadata>
                key, meta = _map_entry(v)
                events[key] = meta
            elif n == 5:                             # stat_metadata: map<int64, XStatMetadata>
                key, meta = _map_entry(v)
                stat_names[key] = next((_text(x) for m, _, x in _fields(meta) if m == 2), "")
            # n == 3, the lines: skipped whole, a length and a jump each
        planes[name] = (events, stat_names)
    return planes


# ---------------------------------------------------------------------------
# The map: instruction -> parts, category, program, bytes
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class OpInfo:
    parts: tuple                 # the vocabulary's names on the path, outermost first
    path: str                    # tf_op's path ("" where the instruction has none)
    category: str
    program_id: int
    bytes_accessed: int


_WRAPPED = re.compile(r"^[\w.\-]+\((.*)\)$")


def path_parts(path: str) -> tuple:
    """The parts on an ``op_name`` path: its ``/`` segments, each with the
    transformations around it taken off (``vmap(jvp(loss))`` is ``loss``),
    that are in the vocabulary."""
    parts = []
    for segment in path.split("/"):
        while (m := _WRAPPED.match(segment)):
            segment = m.group(1)
        if segment in PARTS:
            parts.append(segment)
    return tuple(parts)


def op_path(tf_op: str) -> str:
    """``tf_op`` is ``<op_name>:<op type>``, and an ``op_name`` that XLA
    merged from several instructions joins their paths with ``;``: the first
    of them that holds a part (else the first)."""
    name = tf_op.rsplit(":", 1)[0] if ":" in tf_op else tf_op
    paths = [p.strip() for p in name.split(";") if p.strip()]
    return next((p for p in paths if path_parts(p)), paths[0] if paths else "")


class OpMap(dict):
    """``{instruction text: [OpInfo, ...]}`` of one device plane (several
    programs may hold the same text)."""

    def find(self, name: str, program_id: int | None):
        """The instruction's OpInfo in program ``program_id``; without one, the
        only OpInfo of that text or any of several that agree on the parts;
        ``AMBIGUOUS`` where they do not; None for a text the map lacks."""
        infos = self.get(name)
        if not infos:
            return None
        for info in infos:
            if info.program_id == program_id:
                return info
        if len({info.parts for info in infos}) == 1:
            return infos[0]
        return AMBIGUOUS


def read_op_map(path) -> OpMap:
    """The first device plane's instructions (``trace_reduce.Trace.planes``'
    first: the plane the other readers judge)."""
    planes = read_planes_metadata(path)
    device = sorted(p for p in planes if trace_reduce.is_device_plane(p))
    out = OpMap()
    if not device:
        return out
    events, stat_names = planes[device[0]]
    for raw in events.values():
        name = next((_text(v) for n, _, v in _fields(raw) if n == 2), "")
        stats = _stats(raw, stat_names)
        if "hlo_category" not in stats and "tf_op" not in stats:
            continue                                 # a step or a program, not an instruction
        tf_path = op_path(str(stats.get("tf_op", "")))
        out.setdefault(name, []).append(OpInfo(
            path_parts(tf_path), tf_path, str(stats.get("hlo_category", "")),
            int(stats.get("program_id", 0)), int(stats.get("bytes_accessed", 0) or 0)))
    return out


# ---------------------------------------------------------------------------
# Device time by part
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class PartTimes:
    """One program's whole executions in a trace, summed."""
    executions: int = 0
    busy_ns: float = 0.0
    by_path: dict = dataclasses.field(default_factory=lambda: defaultdict(float))   # parts path -> ns
    bytes_by_path: dict = dataclasses.field(default_factory=lambda: defaultdict(int))
    relayout_by_path: dict = dataclasses.field(default_factory=lambda: defaultdict(float))

    def ns(self, *parts: str) -> float:
        """Time under any of ``parts`` (counted once where they nest)."""
        return sum(t for path, t in self.by_path.items() if set(path) & set(parts))

    def own_ns(self, part: str) -> float:
        return sum(t for path, t in self.by_path.items() if path and path[-1] == part)

    @property
    def relayout_ns(self) -> float:
        return sum(self.relayout_by_path.values())

    def relayout_under(self, part: str) -> float:
        return sum(t for path, t in self.relayout_by_path.items() if part in path)

    def bytes(self, part: str) -> int:
        return sum(b for path, b in self.bytes_by_path.items() if part in path)

    def parts(self) -> list:
        seen = {p for path in self.by_path for p in path}
        return [p for p in PARTS if p in seen] + sorted(seen - set(PARTS) - {UNSCOPED, AMBIGUOUS})

    def named_ns(self) -> float:
        return self.busy_ns - self.by_path.get((UNSCOPED,), 0.0) - self.by_path.get((AMBIGUOUS,), 0.0)

    def ms_per_execution(self, ns: float) -> float | None:
        return ns * 1e-6 / self.executions if self.executions else None


def program_id_of(module_name: str) -> int | None:
    """``jit_train_step(11836925205853754573)`` -> the program's id."""
    m = re.search(r"\((\d+)\)$", module_name)
    return int(m.group(1)) if m else None


@dataclasses.dataclass
class DeviceLines:
    """The first device plane's programs and operations, each sorted by start
    (an operation that encloses another first), and the span they cover."""
    modules: list
    ops: list
    starts: list                  # the operations' starts, for bisecting
    span: tuple

    @classmethod
    def of(cls, trace) -> "DeviceLines":
        plane = trace.planes()[0] if trace.planes() else None
        order = lambda e: (e.start_ns, -e.dur_ns)                             # noqa: E731
        modules = sorted(trace.select(trace_reduce.MODULES_LINE, plane=plane), key=order)
        ops = sorted(trace.select(trace_reduce.OPS_LINE, plane=plane), key=order)
        both = modules + ops
        span = (min(e.start_ns for e in both), max(e.end_ns for e in both)) if both else (0.0, 0.0)
        return cls(modules, ops, [e.start_ns for e in ops], span)

    def whole_executions(self, pattern: str) -> list:
        """The executions of the programs matching ``pattern`` that lie
        strictly inside the span: the first and the last program of a trace
        may be cut off."""
        rx, (lo, hi) = re.compile(pattern), self.span
        return [e for e in self.modules if rx.search(e.name) and e.start_ns > lo and e.end_ns < hi]


def _shared_prefix(a: tuple, b: tuple) -> tuple:
    n = 0
    while n < min(len(a), len(b)) and a[n] == b[n]:
        n += 1
    return a[:n]


def _nesting(events, start_ns: float) -> tuple:
    """``(parent, own_ns, leaf)`` of one execution's events, sorted by start
    (the longer first): the index of the innermost event that encloses each
    (-1: none), the time in which each is the innermost event running, and
    whether nothing is nested in it."""
    n = len(events)
    parent, own_ns, leaf = [-1] * n, [0.0] * n, [True] * n
    stack, cursor = [], start_ns                   # the events running; booked up to here

    def close(until):
        nonlocal cursor
        while stack and events[stack[-1]].end_ns <= until:
            j = stack.pop()
            end = events[j].end_ns
            if end > cursor:
                own_ns[j] += end - cursor
                cursor = end

    for i, e in enumerate(events):
        close(e.start_ns)
        if stack:
            j = stack[-1]
            if e.start_ns > cursor:
                own_ns[j] += e.start_ns - cursor
            parent[i], leaf[j] = j, False
        cursor = max(cursor, e.start_ns)
        stack.append(i)
    close(float("inf"))
    return parent, own_ns, leaf


def part_times(lines: DeviceLines, op_map: OpMap, pattern: str) -> PartTimes:
    """Device time of the whole executions of ``pattern``'s programs, by the
    parts path of the innermost event running (see the module docstring)."""
    out = PartTimes()
    ops, starts = lines.ops, lines.starts
    found: dict = {}                       # (program, instruction) -> (path, relayout, bytes)

    def lookup(key):
        info = op_map.find(key[1], key[0])
        if info is None:
            return (), False, 0
        if info == AMBIGUOUS:
            return (AMBIGUOUS,), False, 0
        return info.parts, info.category == RELAYOUT_CATEGORY or not info.path, info.bytes_accessed

    for run in lines.whole_executions(pattern):
        program = program_id_of(run.name)
        out.executions += 1
        events = ops[bisect.bisect_left(starts, run.start_ns):bisect.bisect_left(starts, run.end_ns)]
        parent, own_ns, leaf = _nesting(events, run.start_ns)
        own = []
        for e in events:
            key = (program, e.name)
            if key not in found:
                found[key] = lookup(key)
            own.append(found[key])
        # upwards: an event with no part of its own and none around it (a
        # ``while`` the compiler rebuilt without its metadata) takes the parts
        # that the events nested in it share
        shared: dict = {}
        for i in reversed(range(len(events))):
            path = own[i][0] or shared.get(i, ())
            j = parent[i]
            if j >= 0 and not own[j][0] and path and path != (AMBIGUOUS,):
                shared[j] = _shared_prefix(shared[j], path) if j in shared else path
        # downwards: no part of its own -> the parts of the event around it
        paths = []
        for i in range(len(events)):
            path = own[i][0] or (paths[parent[i]] if parent[i] >= 0 else ()) or shared.get(i, ())
            paths.append(path)
            key = path or (UNSCOPED,)
            out.by_path[key] += own_ns[i]
            out.busy_ns += own_ns[i]
            if leaf[i]:
                out.bytes_by_path[key] += own[i][2]
                if own[i][1]:
                    out.relayout_by_path[key] += own_ns[i]
    return out


def tables(trace, path=None) -> dict:
    """``{"decode" | "chunk" | "train": PartTimes}`` of a trace (those of its
    programs that ran), computed once a trace object; ``path`` is the trace's
    file (this run's newest by default). Empty without a trace, a file or a
    vocabulary."""
    if trace is None or not PARTS:
        return {}
    cached = getattr(trace, "_op_scopes_tables", None)
    if cached is not None:
        return cached
    path = Path(path) if path is not None else host_spans.newest_trace()
    out = {}
    if path is not None and path.is_file():
        op_map, lines = read_op_map(path), DeviceLines.of(trace)
        for name, pattern in (("decode", DECODE), ("chunk", CHUNK), ("train", TRAIN)):
            times = part_times(lines, op_map, pattern)
            if times.executions:
                out[name] = times
    trace._op_scopes_tables = out
    return out


def part_ms(trace, program: str, *parts: str) -> float | None:
    """Mean device ms of one execution of ``program`` under any of ``parts``;
    None where the program did not run or holds none of them."""
    times = tables(trace).get(program)
    if times is None:
        return None
    ns = times.ns(*parts)
    return times.ms_per_execution(ns) if ns else None


def relayout_ms(trace, program: str) -> float | None:
    """Mean device ms of one execution of ``program`` in leaf events that
    only move data (see the module docstring); None where the program did
    not run or no path of it holds a part (a program without the vocabulary)."""
    times = tables(trace).get(program)
    if times is None or not times.named_ns():
        return None
    return times.ms_per_execution(times.relayout_ns)


def table(name: str, times: PartTimes) -> str:
    """One program's table, as the metrics print it on standard error."""
    busy = times.busy_ns or 1.0
    per = lambda ns: ns * 1e-6 / times.executions                             # noqa: E731
    lines = [f"device time by part: {name}, {times.executions} whole executions, "
             f"busy {per(times.busy_ns):.4f} ms each",
             f"  {'part':<18} {'ms':>9} {'share':>7} {'own ms':>9} {'relayout':>9} {'GB/s':>8}"]
    for part in times.parts():
        ns = times.ns(part)
        rate = times.bytes(part) / ns if ns else 0.0                          # bytes / ns = GB/s
        lines.append(f"  {part:<18} {per(ns):9.4f} {100 * ns / busy:6.2f}% {per(times.own_ns(part)):9.4f} "
                     f"{per(times.relayout_under(part)):9.4f} {rate:8.1f}")
    for row in (UNSCOPED, AMBIGUOUS):
        ns = times.by_path.get((row,), 0.0)
        lines.append(f"  {row:<18} {per(ns):9.4f} {100 * ns / busy:6.2f}% {per(ns):9.4f} "
                     f"{per(times.relayout_under(row)):9.4f}")
    lines.append(f"  {'relayout':<18} {per(times.relayout_ns):9.4f} {100 * times.relayout_ns / busy:6.2f}%"
                 f"   (leaf data-formatting ops and the compiler's copies, under whatever part: "
                 f"the column)")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# What a trace's metadata holds (``python -m chipbench.op_scopes FILE``)
# ---------------------------------------------------------------------------

def shares_by_category(trace, op_map: OpMap) -> dict:
    """``{hlo_category: share}`` of the first device plane's ``XLA Ops``
    time, as sums of the events' durations (nested events count again)."""
    plane = trace.planes()[0] if trace.planes() else None
    sums: dict = defaultdict(float)
    for e in trace.select(trace_reduce.OPS_LINE, plane=plane):
        info = op_map.find(e.name, None)
        sums[info.category if isinstance(info, OpInfo) else "?"] += e.dur_ns
    total = sum(sums.values()) or 1.0
    return {k: v / total for k, v in sums.items()}


def share_with_path(trace, op_map: OpMap) -> float:
    """Share of the ``XLA Ops`` time (sums of durations) in events whose
    instruction carries a ``tf_op`` path."""
    plane = trace.planes()[0] if trace.planes() else None
    with_path = total = 0.0
    for e in trace.select(trace_reduce.OPS_LINE, plane=plane):
        info = op_map.find(e.name, None)
        total += e.dur_ns
        if info == AMBIGUOUS or (info is not None and info.path):
            with_path += e.dur_ns
    return with_path / total if total else 0.0


def describe(trace, path) -> str:
    op_map = read_op_map(path)
    out = [f"{sum(map(len, op_map.values()))} instructions in the first device plane's metadata; "
           f"{100 * share_with_path(trace, op_map):.2f} % of the XLA Ops time carries a tf_op path",
           "by hlo_category (sums of durations): " + ", ".join(
               f"{k} {100 * v:.2f} %" for k, v in
               sorted(shares_by_category(trace, op_map).items(), key=lambda kv: -kv[1]))]
    for name, times in tables(trace, path).items():
        out += ["", table(name, times)]
    return "\n".join(out)


if __name__ == "__main__":
    print(describe(trace_reduce.load(sys.argv[1]), sys.argv[1]))
