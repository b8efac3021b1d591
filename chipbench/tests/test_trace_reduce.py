"""trace_reduce.py on synthetic events and on a small recorded TPU trace
(``recorded_train.xplane.pb``: a few train steps of the trainer cell on a
v5e, my chip run, PR 25)."""

from pathlib import Path

import pytest

from chipbench import trace_reduce as tr

RECORDED = Path(__file__).with_name("recorded_train.xplane.pb")


def ev(name, start, dur, line=tr.OPS_LINE, plane="/device:TPU:0"):
    return tr.Event(plane, line, name, float(start), float(dur))


def test_overlapping_events_count_once():
    events = [ev("a", 0, 10), ev("b", 5, 10), ev("c", 30, 5), ev("inner", 31, 2)]
    assert tr.union_ns(events) == 20.0                  # [0,15) and [30,35)
    trace = tr.Trace(events)
    assert trace.window_s() == pytest.approx(35e-9)
    assert trace.busy_s() == pytest.approx(20e-9)
    assert tr.idle_percent(trace) == pytest.approx(100 * 15 / 35)


def test_a_while_around_its_body_is_not_counted_twice():
    events = [ev("while", 0, 100), ev("body.1", 0, 40), ev("body.2", 50, 50)]
    kept = {e.name for e in tr.leaf_events(events)}
    assert kept == {"body.1", "body.2"}
    assert tr.sum_by_name(events)["while"] == pytest.approx(100e-9)


def test_busy_is_averaged_over_the_device_planes():
    events = [ev("a", 0, 10), ev("b", 0, 4, plane="/device:TPU:1")]
    assert tr.Trace(events).busy_s() == pytest.approx(7e-9)


def test_gaps_are_named_by_the_programs_around_them():
    events = [ev("jit_f(1)", 0, 10, tr.MODULES_LINE), ev("x", 0, 10),
              ev("jit_g(2)", 15, 10, tr.MODULES_LINE), ev("y", 15, 10)]
    assert tr.idle_gaps(tr.Trace(events)) == [["after jit_f before jit_g", pytest.approx(5e-9)]]


def test_short_name_keeps_result_opcode_and_target():
    name = ('%self_attn.14 = (bf16[2,8,4096,128]{3,2,1,0:T(8,128)(2,1)S(1)}, bf16[2,8]) '
            'custom-call(bf16[2,32] %x), custom_call_target="tpu_custom_call", foo={}')
    assert tr.short_name(name) == "%self_attn.14 custom-call tpu_custom_call"
    assert tr.short_name("%fusion.3 = f32[8]{0:T(128)} fusion(f32[8] %p)") == "%fusion.3 fusion"


@pytest.mark.skipif(not RECORDED.is_file(), reason="no recorded trace in the directory")
def test_recorded_trace_reduces_to_what_was_seen_on_the_chip():
    trace = tr.load(RECORDED)
    assert trace.planes() == ["/device:TPU:0"]
    steps = trace.select(tr.MODULES_LINE, r"^jit_train_step")
    assert len(steps) >= 2
    assert 0.40 < tr.mean_duration_s(steps) < 0.45      # 426 ms a step
    assert trace.busy_s() <= trace.window_s()
    assert tr.idle_percent(trace) < 1.0
    kernels = trace.select(tr.OPS_LINE, r"(?i)^%[\w.\-]*(attn|flash)[\w.\-]* = .*tpu_custom_call")
    assert len(kernels) == 8 * len(steps)               # fwd x2 (remat), dq, dkdv per layer
    per_step = sum(e.dur_ns for e in kernels) * 1e-9 / len(steps)
    assert 0.19 < per_step < 0.23                       # half of the step
    top = tr.top_ops(trace)
    assert len(top) == 10 and top[0][0].endswith("tpu_custom_call")
