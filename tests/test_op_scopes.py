"""chipbench/op_scopes.py: a trace's ``tf_op`` metadata read from the file's
bytes, joined to the events and booked to the program's parts — on the
recorded TPU trace of the trainer (a tree from before the vocabulary: its only
part is the head's flax module name) and on a small ``XSpace`` built here."""

import json
from pathlib import Path

import pytest

from chipbench import harness, op_scopes, trace_reduce

RECORDED = Path(op_scopes.__file__).parent / "tests" / "recorded_train.xplane.pb"


@pytest.fixture(scope="module")
def recorded():
    return trace_reduce.load(RECORDED), op_scopes.read_op_map(RECORDED)


def test_nearly_all_of_the_recorded_device_time_carries_a_path(recorded):
    trace, op_map = recorded
    assert op_scopes.share_with_path(trace, op_map) >= 0.99
    paths = {info.path for infos in op_map.values() for info in infos}
    assert "jit(train_step)/jvp(LlamaForCausalLM)/model/layers_0/mlp/up_proj/dot_general" in paths


@pytest.mark.parametrize("category,percent", [
    ("custom-call", 49.45), ("convolution fusion", 38.65), ("loop fusion", 8.78),
    ("non-fusion elementwise", 1.17), ("data formatting", 0.70)])
def test_the_recorded_shares_by_hlo_category(recorded, category, percent):
    assert 100 * op_scopes.shares_by_category(*recorded)[category] == pytest.approx(percent, abs=0.1)


def test_the_wire_reader_reads_what_the_generated_reader_reads():
    xplane_pb2 = pytest.importorskip("tensorflow.tsl.profiler.protobuf.xplane_pb2")
    space = xplane_pb2.XSpace()
    space.ParseFromString(RECORDED.read_bytes())
    mine = op_scopes.read_planes_metadata(RECORDED)
    assert sorted(mine) == sorted(p.name for p in space.planes)
    checked = 0
    for plane in space.planes:
        events, stat_names = mine[plane.name]
        assert stat_names == {k: v.name for k, v in plane.stat_metadata.items()}
        assert sorted(events) == sorted(plane.event_metadata)
        for key, meta in plane.event_metadata.items():
            want = {}
            for stat in meta.stats:
                kind = stat.WhichOneof("value")
                if kind == "double_value":
                    continue
                value = getattr(stat, kind)
                if kind == "ref_value":
                    value = plane.stat_metadata[value].name
                elif kind == "bytes_value":
                    value = value.decode("utf-8", "replace")
                want[plane.stat_metadata[stat.metadata_id].name] = value
            assert op_scopes._stats(events[key], stat_names) == want
            checked += len(want)
    assert checked > 5000


def test_the_recorded_steps_own_times_add_up_and_the_cut_off_step_is_left_out(recorded):
    trace, op_map = recorded
    times = op_scopes.part_times(op_scopes.DeviceLines.of(trace), op_map, op_scopes.TRAIN)
    steps = trace.select(trace_reduce.MODULES_LINE, op_scopes.TRAIN)
    assert times.executions == len(steps) - 1 == 9           # the last step ends the trace
    own = sum(times.own_ns(p) for p in times.parts())
    rest = times.by_path[(op_scopes.UNSCOPED,)] + times.by_path.get((op_scopes.AMBIGUOUS,), 0.0)
    assert own + rest == pytest.approx(times.busy_ns, rel=1e-9)
    whole = [e for e in steps[:-1]]
    busy = sum(trace_reduce.union_ns([o for o in trace.select(trace_reduce.OPS_LINE)
                                      if s.start_ns <= o.start_ns < s.end_ns]) for s in whole)
    assert times.busy_ns == pytest.approx(busy, rel=1e-6)     # nested events count once
    assert times.parts() == ["lm_head"] and times.ns("lm_head") > 0


@pytest.mark.parametrize("path,parts", [
    ("jit(_paged_decode_fn)/vmap(M)/layers_1/self_attn/attn_mla/kv_attn/while/body/dot_general",
     ("attn_mla", "kv_attn")),
    ("jit(_paged_decode_fn)/vmap(sample)/vmap(jit(_threefry_split))/while", ("sample",)),
    ("jit(train_step)/loss/transpose(jvp(LlamaForCausalLM))/model/layers_0/mlp/mlp_dense/mul",
     ("loss", "mlp_dense")),
    ("jit(train_step)/transpose(jvp(loss))/add", ("loss",)),
    ("jit(f)/model/layers_0/mlp/up_proj/dot_general", ()),
    ("", ()),
])
def test_the_parts_on_a_path(path, parts):
    assert op_scopes.path_parts(path) == parts


def test_a_tf_op_is_a_path_then_a_type_and_merged_paths_are_joined():
    assert op_scopes.op_path("jit(f)/lm_head/dot_general:") == "jit(f)/lm_head/dot_general"
    assert op_scopes.op_path("jit(f)/a/mul:Mul") == "jit(f)/a/mul"
    merged = "transpose;jit(f)/x/add;jit(f)/kv_attn/while/body/dot_general;jit(f)/lm_head/mul:"
    assert op_scopes.op_path(merged) == "jit(f)/kv_attn/while/body/dot_general"
    assert op_scopes.op_path("") == ""


# ---------------------------------------------------------------------------
# A small XSpace, written field by field
# ---------------------------------------------------------------------------

def varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def field(number: int, value) -> bytes:
    if isinstance(value, int):
        return varint(number << 3) + varint(value)
    value = value.encode() if isinstance(value, str) else value
    return varint(number << 3 | 2) + varint(len(value)) + value


STATS = {"tf_op": 1, "hlo_category": 2, "program_id": 3, "bytes_accessed": 4}
DECODE_ID, OTHER_ID = 77, 5
TICK = "jit(_paged_decode_fn)/vmap(M)/layers_0/self_attn/attn_mla"
#: metadata id -> (name, tf_op, hlo_category, program_id, bytes_accessed)
INSTRUCTIONS = {
    1: ("%while.1 = while(...)", TICK + "/kv_attn/while:", "while", DECODE_ID, 0),
    2: ("%fusion.2 = fusion(...)", TICK + "/kv_attn/while/body/dot_general:", "convolution fusion",
        DECODE_ID, 3000),
    3: ("%copy.3 = copy(...)", "", "data formatting", DECODE_ID, 900),
    4: ("%fusion.4 = fusion(...)", TICK + "/mla_q/q_b_proj/dot_general:", "convolution fusion",
        DECODE_ID, 1500),
    5: ("%fusion.5 = fusion(...)", TICK + "/reshape:", "loop fusion", DECODE_ID, 100),
    6: ("%reduce.6 = reduce(...)", "jit(_paged_decode_fn)/vmap(sample)/reduce:", "loop fusion",
        DECODE_ID, 50),
    7: ("%add.7 = add(...)", "jit(_paged_decode_fn)/add:", "non-fusion elementwise", DECODE_ID, 10),
    # one text, two other programs, different parts: nobody can say which ran
    8: ("%fusion.9 = fusion(...)", "jit(a)/kv_attn/mul:", "loop fusion", 88, 0),
    9: ("%fusion.9 = fusion(...)", "jit(b)/lm_head/mul:", "loop fusion", 99, 0),
    # a loop the compiler rebuilt: the while has lost its path, its body has not
    10: ("%while.10 = while(...)", "", "while", DECODE_ID, 0),
}
MODULES = {20: f"jit__paged_decode_fn({DECODE_ID})", 21: f"jit_other({OTHER_ID})"}
#: one tick's events: (metadata id, start, end), ns from the tick's start
TICK_OPS = [(5, 0, 100), (4, 100, 250), (1, 300, 700), (3, 310, 400), (2, 450, 600),
            (10, 700, 750), (2, 710, 740), (6, 750, 800), (7, 800, 900), (8, 900, 950)]


def event(meta: int, start_ns: int, end_ns: int) -> bytes:
    return field(1, meta) + field(2, start_ns * 1000) + field(3, (end_ns - start_ns) * 1000)


def small_xspace() -> bytes:
    metadata = b""
    for key, (name, tf_op, category, program, nbytes) in INSTRUCTIONS.items():
        stats = field(5, field(1, STATS["hlo_category"]) + field(5, category))
        stats += field(5, field(1, STATS["program_id"]) + field(3, program))
        stats += field(5, field(1, STATS["bytes_accessed"]) + field(4, nbytes))
        if tf_op:
            stats += field(5, field(1, STATS["tf_op"]) + field(5, tf_op))
        metadata += field(4, field(1, key) + field(2, field(1, key) + field(2, name) + stats))
    for key, name in MODULES.items():
        metadata += field(4, field(1, key) + field(2, field(1, key) + field(2, name)))
    for name, key in STATS.items():
        metadata += field(5, field(1, key) + field(2, field(1, key) + field(2, name)))
    # an earlier program, two whole ticks, and a third the trace's end cuts off
    modules = [event(21, 0, 500), event(20, 1000, 2000), event(20, 3000, 4000),
               event(20, 5000, 5400)]
    ops = [event(7, 0, 500)]
    for t0 in (1000, 3000):
        ops += [event(meta, t0 + a, t0 + b) for meta, a, b in TICK_OPS]
    ops.append(event(2, 5000, 5400))
    lines = (field(3, field(1, 1) + field(2, "XLA Modules") + b"".join(field(4, e) for e in modules))
             + field(3, field(1, 2) + field(2, "XLA Ops") + b"".join(field(4, e) for e in ops)))
    plane = field(1, 1) + field(2, "/device:TPU:0") + lines + metadata
    return field(1, plane)


@pytest.fixture(scope="module")
def small(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "plugins" / "small.xplane.pb"
    path.parent.mkdir()
    path.write_bytes(small_xspace())
    trace = trace_reduce.load(path)
    times = op_scopes.part_times(op_scopes.DeviceLines.of(trace), op_scopes.read_op_map(path),
                                 op_scopes.DECODE)
    return trace, path, times


def test_the_cut_off_last_execution_is_left_out(small):
    trace, _, times = small
    assert len(trace.select(trace_reduce.MODULES_LINE, op_scopes.DECODE)) == 3
    assert times.executions == 2
    assert trace_reduce.mean_module_ms(trace, op_scopes.DECODE) * 1e6 == pytest.approx(800)   # averaged in there
    assert times.ms_per_execution(times.ns("kv_attn")) * 1e6 == pytest.approx(450)


def test_a_leaf_with_no_part_takes_the_enclosing_whiles(small):
    _, _, times = small
    # the copy the compiler put into the loop's body has no tf_op at all: its
    # 90 ns lie under the while's parts, and are the tick's only re-layout
    assert times.by_path[("attn_mla", "kv_attn")] == pytest.approx(2 * 450)
    assert times.relayout_ns == pytest.approx(2 * 90)
    assert times.bytes("kv_attn") == 2 * (2 * 3000 + 900)     # leaves only: a while's own count stays out


def test_a_while_that_lost_its_path_takes_what_its_body_shares(small):
    trace, path, times = small
    # %while.10 has no tf_op and nothing around it; the one event nested in it
    # lies under attn_mla / kv_attn, and so do the loop's own 20 ns a tick
    op_map = op_scopes.read_op_map(path)
    assert op_map.find("%while.10 = while(...)", DECODE_ID).parts == ()
    assert times.by_path[(op_scopes.UNSCOPED,)] == pytest.approx(2 * 100)      # the add alone
    assert times.busy_ns == pytest.approx(2 * 900)


def test_a_parts_own_time_leaves_its_nested_parts_out(small):
    _, _, times = small
    assert times.ns("attn_mla") == pytest.approx(2 * (100 + 150 + 450))
    assert times.own_ns("attn_mla") == pytest.approx(2 * 100)
    assert times.own_ns("mla_q") == times.ns("mla_q") == pytest.approx(2 * 150)
    assert times.ns("mla_q", "kv_attn") == pytest.approx(2 * 600)
    assert times.ns("sample") == pytest.approx(2 * 50)        # vmap(sample)
    assert times.parts() == ["attn_mla", "mla_q", "kv_attn", "sample"]


def test_an_instruction_two_programs_hold_under_different_parts_is_kept_apart(small):
    _, path, times = small
    assert times.by_path[(op_scopes.AMBIGUOUS,)] == pytest.approx(2 * 50)
    assert times.ns("lm_head") == 0
    op_map = op_scopes.read_op_map(path)
    assert op_map.find("%fusion.9 = fusion(...)", 88).parts == ("kv_attn",)     # its program known
    assert op_map.find("%fusion.9 = fusion(...)", DECODE_ID) == op_scopes.AMBIGUOUS
    assert op_map.find("%fusion.404 = fusion(...)", DECODE_ID) is None


def test_own_times_unscoped_and_ambiguous_are_the_busy_time(small):
    trace, _, times = small
    own = sum(times.own_ns(p) for p in times.parts())
    assert times.by_path[(op_scopes.UNSCOPED,)] == pytest.approx(2 * 100)
    assert own + 2 * 100 + 2 * 50 == pytest.approx(times.busy_ns) == pytest.approx(2 * 900)
    assert times.named_ns() == pytest.approx(2 * 750)
    text = op_scopes.table("decode", times)
    assert "2 whole executions" in text and "kv_attn" in text and "relayout" in text


def test_the_metrics_read_the_parts_and_give_none_where_there_are_none(small, monkeypatch, capsys):
    trace, path, _ = small
    monkeypatch.setattr(harness, "TRACE_DIR", path.parent.parent)
    manifest = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    new = [m for m in manifest["per_layer"] if (harness.PACKAGE / "layer_metrics" / f"{m['name']}.py")
           .read_text().count("op_scopes")]
    assert [m["name"] for m in new] == [m["name"] for m in manifest["per_layer"][-8:]]
    assert all(m["source"] == "device_trace" for m in new)

    def read(trace):
        ctx = harness.LayerContext(trace, {}, {}, 30.0, {}, {}, {}, {})
        return {m["name"]: harness.load_module("layer_metrics", m["name"]).compute(ctx) for m in new}

    got = read(trace)
    assert got["tick_kv_attn_ms"] * 1e6 == pytest.approx(450)
    assert got["tick_relayout_ms"] * 1e6 == pytest.approx(90)
    assert got["program_parts_named.serve"] == pytest.approx(100 * 750 / 900)
    assert "device time by part: decode" in capsys.readouterr().err
    # no chunk ran, and nothing here is an expert or a Mamba layer
    assert {k for k, v in got.items() if v is None} == {
        "chunk_kv_attn_ms", "tick_experts_ms", "chunk_experts_ms", "chunk_ssm_ms", "chunk_relayout_ms"}
    # a program from before the vocabulary, and a run without a trace
    monkeypatch.setattr(op_scopes, "PARTS", ())
    assert set(read(trace_reduce.load(path)).values()) == {None}
    assert set(read(None).values()) == {None}
