"""The phi4flash family in the benchmark, on the CPU: its toy cell end to end
(a toy cell in a manifest of its own beside the toy manifest —
``tests/toy/BENCHMARK.phi4flash.json`` — because a PR may add benchmark files
and not edit them; for the same reason the family's cases live here and not in
test_cells_cpu.py, test_flops.py and test_manifest.py), two faults planted in
the program and seen to fail on it (a slot's recurrent state not reset for its
next stream; one state shared by the lanes of a tick — a cross-attention that
misses the shared layer's row of its own token moves one key's weight in one
kind of layer, which bfloat16 hides at this size: tests/test_phi4flash.py
holds it in float32), the control, the manifest's new entries, the family's counts by hand,
its trace reader on a made-up trace, and the benchmark's reference against the
program's own."""

import contextlib
import dataclasses
import io
import json
import re

import numpy as np
import pytest

from chipbench import flops_phi4flash as flops
from chipbench import harness, run, ssm_trace, trace_reduce

TOY = harness.PACKAGE / "tests" / "toy" / "BENCHMARK.phi4flash.json"
CELL = "toy-serve-phi4flash"
REAL_CELL = "serve-phi4flash-closed48-reason"
REAL = json.loads((harness.PACKAGE / "configs" / "phi-4-mini-flash-reasoning.json").read_text())
NEW_METRICS = ("serve_step_mfu.phi4flash", "decode_tick_roofline.phi4flash", "ssm_scan_roofline",
               "shared_kv_decode_roofline")


def run_cell(seed=3, seconds=2.0, control=0):
    args = run.parse(["--workload", CELL, "--seed", str(seed), "--seconds", str(seconds),
                      "--trace", "0", "--control", str(control), "--manifest", str(TOY)])
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        assert run.run(args, require_chip=False) == 0
    return json.loads(out.getvalue().strip().splitlines()[-1]), err.getvalue()


def test_the_toy_cell_end_to_end_on_the_cpu_and_its_control():
    last, err = run_cell(seed=2 ** 31 + 6, control=1)
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] >= 8
    assert last["device"]["platform"] == "cpu" and last["metrics"] == {}
    assert len(last["observed"]["sampled_requests"]) == 4
    assert set(last["compared"]) == {"gap_mean", "share_over_quarter"}
    assert last["observed"]["control_correct"] is False     # the fp8 reference's own tokens
    assert "control_correct: False" in err


def test_a_recurrent_state_that_is_not_reset_is_not_correct(monkeypatch):
    import jax

    from accelerate_tpu.serving.engine import ServingEngine

    kept = lambda state, slot, offset: jax.tree.map(lambda a: a[slot], state["recurrent"])  # noqa: E731
    monkeypatch.setattr(ServingEngine, "_slot_recurrent_rows", staticmethod(kept))
    last, _ = run_cell()
    assert last["correct"] is False and last["failed"] == 0
    assert last["compared"]["gap_mean"]["value"] > 5 * last["compared"]["gap_mean"]["limit"]


def test_one_recurrent_state_shared_by_the_lanes_of_a_tick_is_not_correct(monkeypatch):
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.serving.engine import ServingEngine

    tick = ServingEngine._paged_decode_fn

    def shared(self, params, state, active, table, bank=None):   # every lane reads slot 0's rows
        one = jax.tree.map(lambda a: jnp.broadcast_to(a[:1], a.shape), state["recurrent"])
        return tick(self, params, dict(state, recurrent=one), active, table, bank)

    monkeypatch.setattr(ServingEngine, "_paged_decode_fn", shared)
    last, _ = run_cell()
    assert last["correct"] is False and last["failed"] == 0


def test_the_manifest_gained_the_configuration_the_cell_and_four_metrics():
    manifest = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    config = next(c for c in manifest["configs"] if c["name"] == REAL["name"])
    assert config["reduced"] == REAL["reduced"] == [] and config["source"] == REAL["source"] == \
        "https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/main/config.json"
    entry = next(w for w in manifest["workloads"] if w["name"] == REAL_CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "phi-4-mini-flash-reasoning", "closed48-reason2k", 1)
    cell = harness.load_cell(harness.ROOT / "BENCHMARK.json", REAL_CELL)
    assert {m["name"] for m in cell.end_to_end} == {"serve_tok_s", "ttft_p95_ms", "itl_p95_ms", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW_METRICS) <= names
    assert {"decode_tick_device_ms", "prefill_chunk_device_ms", "prefill_attn_rows_share",
            "decode_attn_rows_share", "kv_dead_rows_share", "queue_wait_ms", "idle_named.serve",
            "host_us_per_tick", "slot_occupancy", "device_idle.serve", "gateway_loop_busy"} <= names
    assert not {m for m in names if m.startswith(("moe_", "mla_")) or m.endswith(
        (".cohere2moe", ".pangumoe")) or m in ("serve_step_mfu", "decode_tick_roofline")}
    for name in names:
        assert (harness.PACKAGE / "layer_metrics" / f"{name}.py").is_file()
    for path in (harness.ROOT / "BENCHMARK.json", TOY):
        for w in json.loads(path.read_text())["workloads"]:
            assert 1 <= len(w["why"]) <= 200
            harness.load_cell(path, w["name"])


def test_the_configuration_keeps_every_published_number_and_cuts_nothing():
    published = {"embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
                 "intermediate_size": 10240, "layer_norm_eps": 1e-05,
                 "max_position_embeddings": 262144, "mb_per_layer": 2, "model_type": "phi4flash",
                 "num_attention_heads": 40, "num_hidden_layers": 32, "num_key_value_heads": 20,
                 "resid_pdrop": 0, "sliding_window": 512, "tie_word_embeddings": True,
                 "mlp_bias": False, "lm_head_bias": False, "vocab_size": 200064}
    assert {k: REAL[k] for k in published} == published and REAL["reduced"] == []
    a = REAL["assumed"]
    assert (a["mamba_d_state"], a["mamba_d_conv"], a["mamba_expand"], a["mamba_dt_rank"]) == (16, 4, 2, 160)
    assert (a["max_slots"], a["max_len"], a["prefill_chunk"], a["max_pages"], a["prefix_cache_mb"]) == \
        (48, 4096, 256, 448, 0)
    for reading in ("mamba", "layers", "differential_attention", "window", "head_layout", "cache"):
        assert reading in a
    family = harness.load_module("models", "phi4flash")
    table = family.leaf_table(REAL)
    assert sum(int(np.prod(shape)) for _, shape, _ in table) == 3_852_562_944    # 7.71 GB in bfloat16
    kinds = [family.mixer(REAL, i) for i in range(32)]
    assert [kinds.count(k) for k in ("mamba", "attn", "gmu", "cross")] == [9, 9, 7, 7]
    assert [family.window_for(REAL, i) for i in (1, 15, 17, 19)] == [512, 512, None, None]


def test_the_seeded_weights_follow_the_mamba_convention():
    import jax.numpy as jnp

    family = harness.load_module("models", "phi4flash")
    cfg = harness.load_cell(TOY, CELL).config
    mixer = family.make_params(cfg, 7, dtype="float32")["layers_0"]["mixer"]
    steps = np.asarray(jnp.logaddexp(mixer["dt_bias"], 0.0))                 # softplus
    assert 1e-3 * 0.999 <= steps.min() and steps.max() <= 1e-1 * 1.001
    assert np.allclose(np.exp(np.asarray(mixer["A_log"]))[:, 0], np.arange(1, 5))
    assert np.all(np.asarray(mixer["D"]) == 1.0)


def test_the_traffic_is_the_issues():
    from chipbench.traffic import lognormal_quantiles

    mix = json.loads((harness.PACKAGE / "traffic" / "closed48-reason2k.json").read_text())
    assert (mix["driver"], mix["clients"], mix["ramp_requests_per_client"], mix["pool"]) == \
        ("closed_loop", 48, 1, 64)
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 512, "sigma": 0.8, "min": 64, "max": 1792}
    assert mix["output_len"]["min"] == 256 and mix["output_len"]["max"] == 2048
    assert mix["output_len"]["sigma"] == 0.5 and mix["output_len"]["median"] in (1024, 768)
    assert (mix["ignore_eos"], mix["sampling"], mix["stream"], mix["check_requests_per_client"],
            mix["trace_seconds"]) == (True, "greedy", True, 1, 4.0)
    prompts = lognormal_quantiles(mix["prompt_len"], 64)
    outputs = lognormal_quantiles(mix["output_len"], 64)
    assert max(prompts) + max(outputs) == 3840 < REAL["assumed"]["max_len"]
    assert 600 < sum(prompts) / 64 < 700


def test_counts_by_hand():
    h, d, f = 2560, 5120, 10240
    p = flops.layer_params(REAL)
    assert p == {"mlp": 3 * h * f, "mamba": h * 2 * d + d * 192 + 160 * d + d * h,
                 "attn": h * 80 * 64 + h * h, "gmu": 2 * h * d, "cross": 2 * h * h}
    matmul = 32 * p["mlp"] + 9 * p["mamba"] + 9 * p["attn"] + 7 * p["gmu"] + 7 * p["cross"]
    assert flops.matmul_params(REAL) == matmul == 3_338_895_360
    assert flops.head_params(REAL) == h * 200064
    assert flops.kv_row_bytes(REAL) * 9 == 46080                  # a token, the 9 caching layers
    assert flops.recurrent_bytes_per_slot(REAL) == 9 * (d * 16 * 4 + d * 3 * 2) == 3_225_600
    assert flops.pair_flops(REAL) == 40 * (128 + 256)            # 40 heads: a 64-wide score, a 128-wide value
    # a token at context 3000: 8 windowed attentions see 512 keys, layer 17 and 7 readers all
    base = 2 * matmul + 9 * (9 * d * 16 + 2 * 4 * d)
    assert flops.token_flops(REAL, 3000) == base + 15360 * (8 * 512 + 8 * 3000) + 2 * h * 200064
    assert flops.token_flops(REAL, 100, False) == base + 15360 * 16 * 100
    # a prompt: every position at its own context
    assert flops.request_flops(REAL, 600, True, [601, 602]) == pytest.approx(
        sum(flops.token_flops(REAL, c, False) for c in range(1, 601)) + 2 * h * 200064
        + flops.token_flops(REAL, 601) + flops.token_flops(REAL, 602), rel=1e-12)
    assert flops.request_flops(REAL, 600, False, []) == 0
    # a tick at 48 streams of 1450 rows: weights 7.70 GB, layer 17's rows 8 times over 2.85 GB,
    # the 8 windows 0.94 GB, the state in and out 0.31 GB
    tick = flops.decode_tick_bytes(REAL, 48, [1450])
    assert tick == 2 * (matmul + h * 200064) + 8 * 48 * 1450 * 5120 + 8 * 48 * 512 * 5120 \
        + 2 * 48 * 3_225_600
    assert 11.8e9 < tick < 11.9e9
    assert flops.shared_kv_decode_bytes(REAL, 48, [1000, 1900]) == 8 * 48 * 1450 * 5120
    ops, nbytes = flops.chunk_scan_need(REAL, 256)
    assert ops == 9 * 9 * 256 * d * 16
    assert nbytes == 9 * 4 * (3 * 256 * d + 2 * 256 * 16 + 2 * d * 16)


def ev(line, name, start, dur):
    return trace_reduce.Event("/device:TPU:0", line, name, float(start), float(dur))


def test_the_trace_reader_finds_scan_ops_by_the_state_they_hold():
    state = "f32[1,32,16,5120]{3,2,1,0:T(8,128)}"
    events = [
        ev(trace_reduce.MODULES_LINE, "jit__paged_decode_fn(1)", 0, 1000),
        ev(trace_reduce.MODULES_LINE, "jit__paged_prefill_chunk_fn(2)", 2000, 4000),
        # the tick's one step holds a state too, but not inside a chunk's execution
        ev(trace_reduce.OPS_LINE, "%fusion.1 = f32[48,1,16,5120]{3,2,1,0} fusion(f32[48,1,16,5120] %h)", 100, 50),
        # the chunk's loop over blocks carries the state; its body's ops lie inside it
        ev(trace_reduce.OPS_LINE, f"%while.2 = (s32[], f32[1,16,5120]) while((s32[], f32[1,16,5120]) %t), body=%b", 2500, 1000),
        ev(trace_reduce.OPS_LINE, f"%fusion.3 = {state} fusion(f32[32,16] %b, f32[32,5120] %x)", 2600, 300),
        ev(trace_reduce.OPS_LINE, "%fusion.4 = bf16[256,2560] fusion(bf16[5120,2560] %w)", 3600, 500),
        ev(trace_reduce.OPS_LINE, f"%fusion.5 = f32[1,256,5120] fusion({state} %hs, f32[1,32,16] %c)", 4200, 200),
    ]
    trace = trace_reduce.Trace(events)
    assert re.search(ssm_trace.state_pattern(REAL), events[4].name)
    assert not re.search(ssm_trace.state_pattern(REAL), events[5].name)
    assert ssm_trace.scan_ms_per_execution(trace, REAL, r"^jit__paged_prefill_chunk_fn") == \
        pytest.approx((1000 + 200) * 1e-6)                      # the while once, and the read-out
    assert ssm_trace.scan_ms_per_execution(trace, REAL, r"^jit_other") is None
    other = trace_reduce.Trace(events[:2] + [events[5]])
    assert ssm_trace.scan_ms_per_execution(other, REAL, r"^jit__paged_prefill_chunk_fn") is None


def test_the_new_readers_return_nothing_for_another_family_or_an_older_program():
    mixtral = json.loads((harness.PACKAGE / "configs" / "mixtral-8x7b-v0.1-d3.json").read_text())
    ctx = harness.LayerContext(trace=trace_reduce.Trace([]), stats={"slot_occupancy": 0.9},
                               counts={"_work": [(10, True, [11, 12])], "slots": 8},
                               window_s=30.0, config=mixtral, traffic={}, peaks={}, rates={})
    for name in NEW_METRICS:
        assert harness.load_module("layer_metrics", name).compute(ctx) is None
    ctx = dataclasses.replace(ctx, config=REAL)                 # its own configuration, no such ops
    for name in ("decode_tick_roofline.phi4flash", "ssm_scan_roofline", "shared_kv_decode_roofline"):
        assert harness.load_module("layer_metrics", name).compute(ctx) is None


def test_the_readers_read_a_made_up_run():
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    trace = trace_reduce.Trace([
        ev(trace_reduce.MODULES_LINE, "jit__paged_decode_fn(1)", 0, 25e6),
        ev(trace_reduce.MODULES_LINE, "jit__paged_prefill_chunk_fn(2)", 30e6, 30e6),
        ev(trace_reduce.OPS_LINE, "%while.4 = (s32[], f32[1,16,5120]) while((s32[], f32[1,16,5120]) %t)", 31e6, 9e6),
    ])
    work = [(1024, True, [1025, 1026]), (512, False, [3000])]
    loop = "%while.{} = (s32[], bf16[449,1,256,1280]) while((s32[], bf16[449,1,256,1280]) %t), body=%b"
    trace.events += [ev(trace_reduce.OPS_LINE, loop.format(i), 1e6 * (1 + 5 * i), 2e6 if i < 2 else 4e6)
                     for i in range(4)]                        # 2 entries, 4 readers: the last 3 loops
    ctx = harness.LayerContext(trace=trace, stats={"slot_occupancy": 0.75, "kv_cache_layers": 2,
                                                   "kv_reader_layers": 4},
                               counts={"_work": work, "slots": 48}, window_s=30.0, config=REAL,
                               traffic={}, peaks=peaks, rates={})
    read = {name: harness.load_module("layer_metrics", name).compute(ctx) for name in NEW_METRICS}
    total = sum(flops.request_flops(REAL, p, first, later) for p, first, later in work)
    assert read["serve_step_mfu.phi4flash"] == pytest.approx(100 * total / 30 / 197e12)
    assert read["decode_tick_roofline.phi4flash"] == pytest.approx(
        100 * flops.decode_tick_bytes(REAL, 36, [1025, 1026, 3000]) / 819e9 / 25e-3)
    ops, nbytes = flops.chunk_scan_need(REAL, 256)
    assert read["ssm_scan_roofline"] == pytest.approx(100 * max(ops / 197e12, nbytes / 819e9) / 9e-3)
    kv_flops, kv_bytes = flops.shared_kv_decode_need(REAL, 36, [1025, 1026, 3000])
    assert read["shared_kv_decode_roofline"] == pytest.approx(
        100 * max(kv_flops / 197e12, kv_bytes / 819e9) / 10e-3)
    assert all(0 < v <= 100 for v in read.values())
    # an execution with another number of loops than the program counts is not read
    ctx.stats["kv_reader_layers"] = 5
    assert harness.load_module("layer_metrics", "shared_kv_decode_roofline").compute(ctx) is None


def test_the_benchmarks_reference_is_the_programs_reference():
    """Two plain references written apart (chipbench/models/phi4flash.py for
    the chip, accelerate_tpu/models/reference/phi4flash.py for tier-1) agree on
    the toy cell's seeded weights."""
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.models.phi4flash import Phi4FlashConfig
    from accelerate_tpu.models.reference import phi4flash as program_reference
    from chipbench import reference_ops as ops

    cfg = harness.load_cell(TOY, CELL).config
    family = harness.load_module("models", "phi4flash")
    params = family.make_params(cfg, 11, dtype="float32")
    ids = jnp.asarray(np.random.default_rng(5).integers(1, cfg["vocab_size"], 48), jnp.int32)
    ours = jax.jit(lambda p, i: family.reference_logits(p, i, cfg, ops.matmul("float32")))(params, ids)
    a = cfg["assumed"]
    theirs = program_reference.reference_logits(params, ids, Phi4FlashConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"], num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"], sliding_window=cfg["sliding_window"],
        layer_norm_eps=cfg["layer_norm_eps"], mamba_d_state=a["mamba_d_state"],
        mamba_d_conv=a["mamba_d_conv"], mamba_expand=a["mamba_expand"],
        mamba_dt_rank=a["mamba_dt_rank"]))
    assert ours.shape == (48, cfg["vocab_size"])
    assert float(jnp.abs(ours - theirs).max()) < 5e-5           # float32, sums ordered apart
