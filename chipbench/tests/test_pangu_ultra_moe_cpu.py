"""The pangu_ultra_moe family in the benchmark, on the CPU: its toy cell end
to end (a toy cell in a manifest of its own beside the toy manifest —
``tests/toy/BENCHMARK.pangu_ultra_moe.json`` — because a PR may add benchmark
files and not edit them; for the same reason the family's cases live here and
not in test_cells_cpu.py, test_flops.py and test_manifest.py), the
committed-token fault seen to fail on it, the manifest's new entries, the
family's counts by hand, its trace reader on a made-up trace, and the
benchmark's reference against the program's own."""

import contextlib
import dataclasses
import io
import json
import re

import numpy as np
import pytest

from chipbench import flops_pangu_ultra_moe as flops
from chipbench import harness, mla_trace, run, trace_reduce

TOY = harness.PACKAGE / "tests" / "toy" / "BENCHMARK.pangu_ultra_moe.json"
CELL = "toy-serve-pangu-ultra-moe"
REAL_CELL = "serve-pangu-closed32-longdoc"
REAL = json.loads((harness.PACKAGE / "configs" / "openpangu-ultra-moe-718b-l5e16.json").read_text())
NEW_METRICS = ("serve_step_mfu.pangumoe", "decode_tick_roofline.pangumoe", "mla_decode_roofline",
               "mla_prefill_roofline", "decode_attn_rows_share")


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
    assert set(last["compared"]) == {"gap_mean", "gap_p99"}
    assert last["observed"]["control_correct"] is False     # the fp8 reference's own tokens
    assert "control_correct: False" in err


def test_a_token_altered_where_it_is_committed_is_not_correct(monkeypatch):
    from accelerate_tpu.serving.engine import ServingEngine

    commit = ServingEngine._commit_token

    def altered(self, req, token):
        if len(req.tokens) % 3 == 2:               # every third token of every stream
            token = (int(token) + 1) % 256
        return commit(self, req, token)

    monkeypatch.setattr(ServingEngine, "_commit_token", altered)
    last, _ = run_cell()
    assert last["correct"] is False and last["failed"] == 0
    assert last["compared"]["gap_mean"]["value"] > 10 * last["compared"]["gap_mean"]["limit"]


def test_the_manifest_gained_the_configuration_the_cell_and_five_metrics():
    manifest = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    assert manifest["configs"][-1]["name"] == REAL["name"] == "openpangu-ultra-moe-718b-l5e16"
    assert manifest["configs"][-1]["reduced"] == REAL["reduced"]
    entry = manifest["workloads"][-1]
    assert (entry["name"], entry["traffic"], entry["chips"]) == (REAL_CELL, "closed32-longdoc8k", 1)
    assert tuple(m["name"] for m in manifest["per_layer"][-5:]) == NEW_METRICS
    cell = harness.load_cell(harness.ROOT / "BENCHMARK.json", REAL_CELL)
    assert {m["name"] for m in cell.end_to_end} == {"serve_tok_s", "ttft_p95_ms", "itl_p95_ms", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW_METRICS) <= names
    assert {"decode_tick_device_ms", "prefill_chunk_device_ms", "moe_tile_fill",
            "prefill_attn_rows_share", "queue_wait_ms", "idle_named.serve"} <= names
    assert not {m for m in names if "cohere2moe" in m or m.startswith("moe_") and "roofline" in m}
    assert "kv_dead_rows_share" not in names and len(names) == 20
    for name in names:
        assert (harness.PACKAGE / "layer_metrics" / f"{name}.py").is_file()
    for path in (harness.ROOT / "BENCHMARK.json", TOY):
        for w in json.loads(path.read_text())["workloads"]:
            assert 1 <= len(w["why"]) <= 200
            harness.load_cell(path, w["name"])


def test_the_configuration_keeps_every_published_number():
    assert REAL["reduced"] == ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
                               "vocab_size"]
    assert REAL["published"] == {"num_hidden_layers": 61, "first_k_dense_replace": 3,
                                 "n_routed_experts": 256, "vocab_size": 153600}
    assert (REAL["hidden_size"], REAL["intermediate_size"], REAL["moe_intermediate_size"],
            REAL["num_attention_heads"], REAL["q_lora_rank"], REAL["kv_lora_rank"],
            REAL["qk_nope_head_dim"], REAL["qk_rope_head_dim"], REAL["v_head_dim"],
            REAL["num_experts_per_tok"], REAL["n_shared_experts"], REAL["router_width"],
            REAL["routed_scaling_factor"], REAL["rope_theta"]) == \
        (7680, 18432, 2048, 128, 1536, 512, 128, 64, 128, 8, 1, 256, 2.5, 25600000)
    assert (REAL["num_hidden_layers"], REAL["first_k_dense_replace"], REAL["n_routed_experts"],
            REAL["vocab_size"], REAL["held_experts"]) == (5, 1, 16, 19200, [0, 16])
    for reading in ("router", "rotary", "sandwich_norm", "mtp", "cache"):
        assert reading in REAL["assumed"]
    family = harness.load_module("models", "pangu_ultra_moe")
    params = sum(int(np.prod(shape)) for _, shape, _ in family.leaf_table(REAL))
    assert params == 4_919_139_840                              # 9.84 GB in bfloat16


def test_the_traffic_is_the_issues():
    from chipbench.traffic import lognormal_quantiles

    mix = json.loads((harness.PACKAGE / "traffic" / "closed32-longdoc8k.json").read_text())
    assert (mix["driver"], mix["clients"], mix["ramp_requests_per_client"], mix["pool"]) == \
        ("closed_loop", 32, 2, 64)
    prompts = lognormal_quantiles(mix["prompt_len"], 64)
    outputs = lognormal_quantiles(mix["output_len"], 64)
    assert sum(p > 4096 for p in prompts) == 12 and sum(p == 7168 for p in prompts) == 4
    assert max(prompts) + max(outputs) == 7936 < REAL["assumed"]["max_len"]
    assert 2600 < sum(prompts) / 64 < 2700 and 280 < sum(outputs) / 64 < 300


def test_counts_by_hand():
    h = 7680
    proj = h * 1536 + 1536 * 128 * 192 + h * 576 + 128 * 128 * h
    kv_b = 512 * 128 * 256
    expert = 3 * h * 2048
    p = flops.layer_params(REAL)
    assert p == {"proj": proj, "kv_b": kv_b, "router": h * 256, "shared": expert,
                 "expert": expert, "dense": 3 * h * 18432}
    assert proj + kv_b == 196_575_232                           # MLA's matrices, a layer
    assert flops.held_picks_per_token(REAL) == 0.5              # 8 picks x 16 / 256
    matmul = 2 * (5 * proj + 3 * h * 18432 + 4 * (h * 256 + expert + 0.5 * expert))
    assert flops.token_matmul_flops(REAL) == matmul
    # a tick's one query at context 3000: absorbed, 2 (576 + 512) a row a head + the two folds
    one = 128 * (3000 * 2176 + 2 * 512 * 256)
    assert flops.attention_flops(REAL, 1, 3000, 3000) == one
    assert flops.token_flops(REAL, 3000) == matmul + 5 * one + 2 * h * 19200
    # a 256-token chunk at offset 1024: expanded (1280 rows made once, 640 a pair a head) is less
    pairs = 256 * 1024 + 256 * 257 / 2
    expanded = 128 * (1280 * 2 * 512 * 256 + pairs * 640)
    absorbed = 128 * (pairs * 2176 + 256 * 2 * 512 * 256)
    assert expanded < absorbed
    assert flops.chunk_attention_flops(REAL, 1024, 256) == expanded
    # ... and a 100-token tail chunk at offset 4096 is absorbed: few queries share many rows
    assert flops.chunk_attention_flops(REAL, 4096, 100) == \
        128 * ((100 * 4096 + 5050) * 2176 + 100 * 2 * 512 * 256)
    assert flops.prefill_attention_flops(REAL, 356) == 5 * (
        flops.chunk_attention_flops(REAL, 0, 256) + flops.chunk_attention_flops(REAL, 256, 100))
    assert flops.request_flops(REAL, 100, False, []) == 0
    assert flops.request_flops(REAL, 356, True, [357]) == (
        356 * matmul + flops.prefill_attention_flops(REAL, 356) + 2 * h * 19200
        + flops.token_flops(REAL, 357))
    # 32 slots touch 16 * (1 - (31/32)^32) = 10.2 held experts a layer
    assert flops.experts_touched(REAL, 32) == pytest.approx(10.2, abs=0.05)
    tick = flops.decode_tick_bytes(REAL, 32, [2000, 4000])
    weights = (5 * (proj + kv_b) + 3 * h * 18432
               + 4 * (h * 256 + expert + flops.experts_touched(REAL, 32) * expert) + h * 19200)
    assert tick == pytest.approx(2 * (weights + 5 * 32 * 3000 * 576))
    assert 7.5e9 < tick < 8.5e9                                 # 7.4 GB of weights + 0.55 GB of rows
    need_flops, need_bytes = flops.decode_attention_need(REAL, 32, [2000, 4000])
    assert need_flops == 5 * 32 * one and need_bytes == 2 * 5 * (32 * 3000 * 576 + kv_b)
    chunk_flops, chunk_bytes = flops.chunk_attention_need(REAL, 1024)
    assert chunk_flops == 5 * expanded and chunk_bytes == 2 * 5 * (1280 * 576 + kv_b)


def test_the_trace_reader_finds_attention_ops_by_the_view_they_read():
    def ev(line, name, start, dur):
        return trace_reduce.Event("/device:TPU:0", line, name, float(start), float(dur))

    view = "bf16[32,1,8192,512]{3,2,1,0:T(8,128)(2,1)}"
    one = "bf16[1,8192,512]{2,1,0:T(8,128)(2,1)}"
    events = [
        ev(trace_reduce.MODULES_LINE, "jit__paged_decode_fn(1)", 0, 1000),
        ev(trace_reduce.MODULES_LINE, "jit__paged_prefill_chunk_fn(2)", 2000, 4000),
        ev(trace_reduce.MODULES_LINE, "jit__paged_decode_fn(1)", 7000, 1000),
        # the gather BUILDS the view (its result): not counted
        ev(trace_reduce.OPS_LINE, f"%fusion.1 = {view} fusion(bf16[1025,1,256,512] %p.1)", 50, 40),
        ev(trace_reduce.OPS_LINE, f"%fusion.2 = f32[32,1,128,1,8192] fusion(bf16[32,128,512] %q, {view} %v)", 100, 300),
        ev(trace_reduce.OPS_LINE, "%fusion.3 = bf16[32,7680] fusion(bf16[16384,7680] %p.3)", 400, 100),
        # the chunk's loop over key blocks carries the view; its body ops lie inside it
        ev(trace_reduce.OPS_LINE, f"%while.4 = (s32[], {one}, f32[1,128,256,128]) while((s32[], {one}, f32[1,128,256,128]) %t), condition=%c, body=%b", 2500, 2000),
        ev(trace_reduce.OPS_LINE, "%fusion.5 = f32[1,128,256,512] fusion(bf16[1,512,512] %blk)", 2600, 500),
        ev(trace_reduce.OPS_LINE, f"%fusion.6 = f32[32,1,128,1,512] fusion({view} %v, f32[32,1,128,1,8192] %p)", 7100, 500),
    ]
    trace = trace_reduce.Trace(events)
    assert re.search(mla_trace.view_pattern(REAL), events[4].name)
    assert not re.search(mla_trace.view_pattern(REAL), events[3].name)
    assert mla_trace.attention_ms_per_execution(trace, REAL, r"^jit__paged_decode_fn") == \
        pytest.approx((300 + 500) * 1e-6 / 2)
    assert mla_trace.attention_ms_per_execution(trace, REAL, r"^jit__paged_prefill_chunk_fn") == \
        pytest.approx(2000 * 1e-6)                              # the while, once
    assert mla_trace.attention_ms_per_execution(trace, REAL, r"^jit_other") is None
    # a program of another family reads no such view: the reader returns nothing
    other = trace_reduce.Trace(events[:3] + [events[5]])
    assert mla_trace.attention_ms_per_execution(other, REAL, r"^jit__paged_decode_fn") is None


def test_the_new_readers_return_nothing_for_another_family_or_an_older_program():
    """Laid over a program that lacks the counter and a configuration that
    has no latent cache, each new reader returns None and does not raise."""
    mixtral = json.loads((harness.PACKAGE / "configs" / "mixtral-8x7b-v0.1-d3.json").read_text())
    ctx = harness.LayerContext(trace=trace_reduce.Trace([]), stats={"slot_occupancy": 0.9},
                               counts={"_work": [(10, True, [11, 12])], "slots": 8},
                               window_s=30.0, config=mixtral, traffic={}, peaks={}, rates={})
    for name in NEW_METRICS:
        assert harness.load_module("layer_metrics", name).compute(ctx) is None
    # the family's own configuration over a trace with no such ops, and stats without the counter
    ctx = dataclasses.replace(ctx, config=REAL)
    for name in ("mla_decode_roofline", "mla_prefill_roofline", "decode_tick_roofline.pangumoe",
                 "decode_attn_rows_share"):
        assert harness.load_module("layer_metrics", name).compute(ctx) is None


def test_the_readers_read_a_made_up_run():
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

    def ev(line, name, start, dur):
        return trace_reduce.Event("/device:TPU:0", line, name, float(start), float(dur))

    view = "bf16[32,1,8192,512]"
    trace = trace_reduce.Trace([
        ev(trace_reduce.MODULES_LINE, "jit__paged_decode_fn(1)", 0, 20e6),
        ev(trace_reduce.OPS_LINE, f"%fusion.2 = f32[32,1,128,1,8192] fusion({view} %v)", 1e6, 4e6),
        ev(trace_reduce.MODULES_LINE, "jit__paged_prefill_chunk_fn(2)", 30e6, 20e6),
        ev(trace_reduce.OPS_LINE, "%while.4 = (s32[], bf16[1,8192,512]) while((s32[], bf16[1,8192,512]) %t)", 31e6, 5e6),
    ])
    work = [(1024, True, [1025, 1026]), (512, False, [3000])]
    ctx = harness.LayerContext(trace=trace, stats={"slot_occupancy": 0.75, "decode_attn_rows_share": 1.0},
                               counts={"_work": work, "slots": 32}, window_s=30.0, config=REAL,
                               traffic={}, peaks=peaks, rates={})
    read = {name: harness.load_module("layer_metrics", name).compute(ctx) for name in NEW_METRICS}
    contexts = [1025, 1026, 3000]
    total = sum(flops.request_flops(REAL, p, first, later) for p, first, later in work)
    assert read["serve_step_mfu.pangumoe"] == pytest.approx(100 * total / 30 / 197e12)
    assert read["decode_tick_roofline.pangumoe"] == pytest.approx(
        100 * flops.decode_tick_bytes(REAL, 24, contexts) / 819e9 / 20e-3)
    f, b = flops.decode_attention_need(REAL, 24, contexts)
    assert read["mla_decode_roofline"] == pytest.approx(100 * max(f / 197e12, b / 819e9) / 4e-3)
    f, b = flops.chunk_attention_need(REAL, (0 + 256 + 512 + 768) / 4)
    assert read["mla_prefill_roofline"] == pytest.approx(100 * max(f / 197e12, b / 819e9) / 5e-3)
    assert read["decode_attn_rows_share"] == 100.0
    assert all(0 < v <= 100 for v in read.values())


def test_the_benchmarks_reference_is_the_programs_reference():
    """Two plain references written apart (chipbench/models/pangu_ultra_moe.py
    for the chip, accelerate_tpu/models/reference/pangu_ultra_moe.py for
    tier-1) agree on the toy cell's seeded weights, held share included."""
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.models.pangu_ultra_moe import PanguUltraMoeConfig
    from accelerate_tpu.models.reference import pangu_ultra_moe as program_reference
    from chipbench import reference_ops as ops

    cell = harness.load_cell(TOY, CELL)
    family = harness.load_module("models", "pangu_ultra_moe")
    cfg = cell.config
    params = family.make_params(cfg, 11, dtype="float32")
    ids = jnp.asarray(np.random.default_rng(5).integers(1, cfg["vocab_size"], 48), jnp.int32)
    ours = jax.jit(lambda p, i: family.reference_logits(p, i, cfg, ops.matmul("float32")))(
        params, ids)
    first, count = family.held(cfg)
    theirs = program_reference.forward(params, ids, PanguUltraMoeConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        first_k_dense_replace=cfg["first_k_dense_replace"],
        num_attention_heads=cfg["num_attention_heads"], q_lora_rank=cfg["q_lora_rank"],
        kv_lora_rank=cfg["kv_lora_rank"], qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        rope_theta=cfg["rope_theta"], rms_norm_eps=cfg["rms_norm_eps"],
        num_experts=cfg["router_width"], num_experts_per_tok=cfg["num_experts_per_tok"],
        n_shared_experts=cfg["n_shared_experts"],
        routed_scaling_factor=cfg["routed_scaling_factor"], held_experts=(first, count)),
        held=(first, count))
    assert ours.shape == (48, cfg["vocab_size"])
    assert float(jnp.abs(ours - theirs).max()) < 5e-5           # float32, sums ordered apart


def test_the_order_model_tells_a_steady_order_from_one_that_follows_the_arrival_race():
    """chipbench/order_model.py on the cell's traffic: under the committed
    ``pool_seed`` every arrival order of the 32 simultaneous first requests
    gives one window (the same first tokens, the same tails); under
    ``pool_seed`` 1, the first one tried on the chip, the window follows the
    race (my chip runs, PR 33, call 3: three kinds of window in six runs)."""
    from chipbench import order_model

    mix = json.loads((harness.PACKAGE / "traffic" / "closed32-longdoc8k.json").read_text())
    steady = order_model.windows(mix, mix["pool_seed"], orders=8)
    assert len({(r["ttft_n"], round(r["ttft_p95"]), round(r["itl_p95"], 2)) for r in steady}) == 1
    assert all(r["ttft_n"] >= 40 for r in steady)               # the issue's floor on first tokens
    racy = order_model.windows(mix, 1, orders=8)
    assert len({r["prefilled"] for r in racy}) >= 3
    # a hand count: one client, one request a time, two chunks then three more tokens
    one = {"clients": 1, "ramp_requests_per_client": 1, "pool": 1, "pool_seed": 0,
           "prompt_len": {"median": 512, "sigma": 0.1, "min": 512, "max": 512},
           "output_len": {"median": 4, "sigma": 0.1, "min": 4, "max": 4}}
    t = order_model.Times(tick=10.0, tick_host=0.0, chunk_base=5.0, chunk_block=1.0, chunk_host=0.0)
    r = order_model.simulate(one, [0], t, window_s=1.0)
    # chunks of 5 + 1 and 5 + 1 ms (one key block each), first token at 12 ms, then three ticks
    # of 10 ms; the next request is sent 1 ms after the last token and starts after a 1 ms idle poll
    assert r["itl_p95"] == pytest.approx(10.0) and r["ttft_p95"] == pytest.approx(12.0)
    assert r["tok_s"] == pytest.approx(4 * 1000 / 43.0, rel=0.05)
