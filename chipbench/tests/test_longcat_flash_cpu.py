"""The longcat_flash family in the benchmark, on the CPU: its toy cell end to
end with its control (a toy cell in a manifest of its own —
``tests/toy/BENCHMARK.longcat.json`` — because a PR may add benchmark files
and not edit them), broken paths seen to fail on it, the manifest's new
entries, the configuration against the catalog, the traffic, the family's
counts by hand, its readers, and the benchmark's reference against the
program's own."""

import contextlib
import dataclasses
import io
import json

import numpy as np
import pytest

from chipbench import flops_longcat_flash as flops
from chipbench import harness, run, trace_reduce

TOY = harness.PACKAGE / "tests" / "toy" / "BENCHMARK.longcat.json"
CELL = "toy-serve-longcat-flash"
REAL_CELL = "serve-longcat-closed48-chat"
REAL = json.loads((harness.PACKAGE / "configs" / "longcat-flash-omni-l4e16.json").read_text())
NEW_METRICS = ("decode_tick_roofline.longcat", "serve_step_mfu.longcat", "zero_expert_pick_share")
#: the catalog's config of LongCat-Flash-Omni (model-configs guide, architectures.jsonl)
CATALOG = {
    "attention_bias": False, "vocab_size": 131072, "hidden_size": 6144, "ffn_hidden_size": 12288,
    "expert_ffn_hidden_size": 2048, "num_layers": 28, "num_attention_heads": 64,
    "kv_lora_rank": 512, "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "qk_nope_head_dim": 128, "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
    "routed_scaling_factor": 6, "n_routed_experts": 512, "max_position_embeddings": 131072,
    "rms_norm_eps": 1e-05, "rope_theta": 10000000, "attention_method": "MLA",
    "zero_expert_num": 256, "zero_expert_type": "identity", "moe_topk": 12}


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


def drop_identity(monkeypatch):
    """The zero-compute experts' picks add nothing (the token is not returned)."""
    import jax.numpy as jnp

    from accelerate_tpu.ops import moe

    held_apply = moe.moe_held_apply

    def without(*args, identity_experts=0, **kwargs):
        out, stats = held_apply(*args, **kwargs)
        return out, dict(stats, zero_picks=jnp.int32(0))

    monkeypatch.setattr(moe, "moe_held_apply", without)


def unscaled_latent(monkeypatch):
    """The normed latent enters the cache without its scale."""
    from accelerate_tpu.models.longcat_flash import LongcatFlashConfig

    monkeypatch.setattr(LongcatFlashConfig, "latent_scale", property(lambda self: 1.0))


@pytest.mark.parametrize("fault", [drop_identity, unscaled_latent],
                         ids=["identity_picks_dropped", "latent_unscaled"])
def test_a_broken_path_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    last, _ = run_cell(seed=5)
    assert last["correct"] is False and last["failed"] == 0
    assert last["compared"]["gap_mean"]["value"] > 3 * last["compared"]["gap_mean"]["limit"]


def test_the_manifest_gained_the_configuration_the_cell_and_three_metrics():
    manifest = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    assert manifest["configs"][-1]["name"] == REAL["name"] == "longcat-flash-omni-l4e16"
    assert manifest["configs"][-1]["reduced"] == REAL["reduced"]
    assert manifest["configs"][-1]["source"] == REAL["source"]
    entry = manifest["workloads"][-1]
    assert (entry["name"], entry["traffic"], entry["chips"]) == (REAL_CELL, "closed48-chat4k", 1)
    assert tuple(m["name"] for m in manifest["per_layer"][-3:]) == NEW_METRICS
    cell = harness.load_cell(harness.ROOT / "BENCHMARK.json", REAL_CELL)
    # the gap between tokens alone: the first-token tail is a handful of capped
    # prompts and whom they meet in prefill, and the rate moves with the
    # host's stalls by more than half its bound (PERF.md section 7)
    assert {m["name"] for m in cell.end_to_end} == {"itl_p95_ms", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW_METRICS) <= names
    assert {"decode_tick_device_ms", "decode_attn_rows_share", "tick_experts_ms",
            "tick_kv_attn_ms", "tick_relayout_ms"} <= names
    assert all(m["moves"] == "itl_p95_ms" for m in cell.per_layer)
    # nothing that reads another family's counts, and MLA's silent tick metric left out
    assert not {m for m in names if m.endswith((".cohere2moe", ".pangumoe", ".phi4flash"))}
    assert not {"mla_decode_roofline", "mla_prefill_roofline", "kv_dead_rows_share",
                "serve_step_mfu", "decode_tick_roofline"} & names
    for name in names:
        assert (harness.PACKAGE / "layer_metrics" / f"{name}.py").is_file()
    for path in (harness.ROOT / "BENCHMARK.json", TOY):
        for w in json.loads(path.read_text())["workloads"]:
            assert 1 <= len(w["why"]) <= 200
            harness.load_cell(path, w["name"])


def test_the_configuration_keeps_every_published_number():
    reduced = {"num_layers": 4, "n_routed_experts": 16, "vocab_size": 16384}
    assert REAL["reduced"] == list(reduced)
    assert REAL["published"] == {k: CATALOG[k] for k in reduced}
    for key, value in CATALOG.items():
        assert REAL[key] == reduced.get(key, value), key
    assert (REAL["router_width"], REAL["held_experts"]) == (512, [0, 16])
    for reading in ("media", "double_layer", "router", "e_score_correction_bias", "mla_scales",
                    "rotary", "head", "cache"):
        assert reading in REAL["assumed"]
    assert "32 chips share each layer" in REAL["deployment"]
    family = harness.load_module("models", "longcat_flash")
    params = sum(int(np.prod(shape)) for _, shape, _ in family.leaf_table(REAL))
    assert params == 5_172_749_312                              # 10.35 GB in bfloat16


def test_the_traffic_mix():
    from chipbench.traffic import lognormal_quantiles

    mix = json.loads((harness.PACKAGE / "traffic" / "closed48-chat4k.json").read_text())
    assert (mix["driver"], mix["clients"], mix["ramp_requests_per_client"], mix["pool"]) == \
        ("closed_loop", 48, 1, 64)
    assert REAL["assumed"]["max_slots"] == mix["clients"]
    assert (mix["prompt_len"]["median"], mix["prompt_len"]["sigma"]) == (768, 0.8)
    assert (mix["output_len"]["median"], mix["output_len"]["sigma"]) == (512, 0.5)
    prompts = lognormal_quantiles(mix["prompt_len"], 64)
    outputs = lognormal_quantiles(mix["output_len"], 64)
    assert (min(prompts), max(prompts), min(outputs), max(outputs)) == (128, 2432, 153, 1536)
    assert max(prompts) + max(outputs) == 3968 < REAL["assumed"]["max_len"]


def test_counts_by_hand():
    h = 6144
    mla = h * 1536 + 1536 * 64 * 192 + h * 576 + 64 * 128 * h
    kv_b = 512 * 64 * 256
    assert (mla, kv_b) == (9_437_184 + 18_874_368 + 3_538_944 + 50_331_648, 8_388_608)
    dense, router, expert = 3 * h * 12288, h * 768, 3 * h * 2048
    assert flops.layer_params(REAL) == {"mla": mla, "kv_b": kv_b, "dense": dense,
                                        "router": router, "expert": expert}
    assert 2 * (mla + kv_b) + 2 * dense + router == 638_844_928      # a layer outside its experts
    assert flops.held_picks_per_token(REAL) == 0.25             # 12 picks x 16 / 768
    assert flops.zero_pick_share(REAL) == pytest.approx(1 / 3)
    # a zero-compute pick counts nothing: only 0.25 of an expert a token a layer
    matmul = 2 * 4 * (2 * mla + 2 * dense + router + 0.25 * expert)
    assert flops.token_matmul_flops(REAL) == matmul
    # a tick's one query at context 1000: absorbed, 2 (576 + 512) a row a head + the two folds,
    # in 8 attentions
    one = 64 * (1000 * 2176 + 2 * 512 * 256)
    assert flops.token_flops(REAL, 1000) == matmul + 8 * one + 2 * h * 16384
    assert flops.prefill_attention_flops(REAL, 300) == 8 * (
        flops.chunk_attention_flops(REAL, 0, 256) + flops.chunk_attention_flops(REAL, 256, 44))
    assert flops.request_flops(REAL, 300, False, []) == 0
    # 48 slots touch 16 * (1 - (1 - 12/768)^48) = 8.49 of the 16 held experts a layer
    assert flops.experts_touched(REAL, 48) == pytest.approx(8.49, abs=0.01)
    tick = flops.decode_tick_bytes(REAL, 48, [1000, 2000])
    weights = 4 * (2 * (mla + kv_b) + 2 * dense + router
                   + flops.experts_touched(REAL, 48) * expert) + h * 16384
    assert tick == pytest.approx(2 * (weights + 8 * 48 * 1500 * 576))
    assert 8.3e9 < tick < 8.8e9          # 7.9 GB of weights with 8.5 experts + 0.66 GB of rows


def test_the_new_readers_return_nothing_for_another_family_or_an_older_program():
    """Laid over a program that lacks the counter and a configuration of
    another family, each new reader returns None and does not raise."""
    pangu = json.loads((harness.PACKAGE / "configs" / "openpangu-ultra-moe-718b-l5e16.json")
                       .read_text())
    ctx = harness.LayerContext(trace=trace_reduce.Trace([]), stats={"slot_occupancy": 0.9},
                               counts={"_work": [(10, True, [11, 12])], "slots": 8},
                               window_s=30.0, config=pangu, traffic={}, peaks={}, rates={})
    for name in NEW_METRICS:
        assert harness.load_module("layer_metrics", name).compute(ctx) is None
    # the family's own configuration over a trace with no decode program
    ctx = dataclasses.replace(ctx, config=REAL)
    assert harness.load_module("layer_metrics", "decode_tick_roofline.longcat").compute(ctx) is None


def test_the_readers_read_a_made_up_run():
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

    def ev(line, name, start, dur):
        return trace_reduce.Event("/device:TPU:0", line, name, float(start), float(dur))

    trace = trace_reduce.Trace([
        ev(trace_reduce.MODULES_LINE, "jit__paged_decode_fn(1)", 0, 16e6),
        ev(trace_reduce.MODULES_LINE, "jit__paged_prefill_chunk_fn(2)", 20e6, 18e6),
    ])
    work = [(1024, True, [1025, 1026]), (512, False, [2000])]
    ctx = harness.LayerContext(trace=trace, stats={"slot_occupancy": 0.75,
                                                   "moe_zero_pick_share": 0.334},
                               counts={"_work": work, "slots": 48}, window_s=30.0, config=REAL,
                               traffic={}, peaks=peaks, rates={})
    read = {name: harness.load_module("layer_metrics", name).compute(ctx) for name in NEW_METRICS}
    total = sum(flops.request_flops(REAL, p, first, later) for p, first, later in work)
    assert read["serve_step_mfu.longcat"] == pytest.approx(100 * total / 30 / 197e12)
    assert read["decode_tick_roofline.longcat"] == pytest.approx(
        100 * flops.decode_tick_bytes(REAL, 36, [1025, 1026, 2000]) / 819e9 / 16e-3)
    assert read["zero_expert_pick_share"] == pytest.approx(33.4)
    assert all(0 < v <= 100 for v in read.values())


def test_the_benchmarks_reference_is_the_programs_reference():
    """Two plain references written apart (chipbench/models/longcat_flash.py
    for the chip, accelerate_tpu/models/reference/longcat_flash.py for
    tier-1) agree on the toy cell's seeded weights, held share included."""
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.models.longcat_flash import LongcatFlashConfig
    from accelerate_tpu.models.reference import longcat_flash as program_reference
    from chipbench import reference_ops as ops

    cell = harness.load_cell(TOY, CELL)
    family = harness.load_module("models", "longcat_flash")
    cfg = cell.config
    params = family.make_params(cfg, 11, dtype="float32")
    ids = jnp.asarray(np.random.default_rng(5).integers(1, cfg["vocab_size"], 48), jnp.int32)
    ours = jax.jit(lambda p, i: family.reference_logits(p, i, cfg, ops.matmul("float32")))(
        params, ids)
    first, count = family.held(cfg)
    theirs = program_reference.forward(params, ids, LongcatFlashConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        ffn_hidden_size=cfg["ffn_hidden_size"],
        expert_ffn_hidden_size=cfg["expert_ffn_hidden_size"], num_layers=cfg["num_layers"],
        num_attention_heads=cfg["num_attention_heads"], q_lora_rank=cfg["q_lora_rank"],
        kv_lora_rank=cfg["kv_lora_rank"], qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        rope_theta=cfg["rope_theta"], rms_norm_eps=cfg["rms_norm_eps"],
        n_routed_experts=cfg["router_width"], zero_expert_num=cfg["zero_expert_num"],
        moe_topk=cfg["moe_topk"], routed_scaling_factor=cfg["routed_scaling_factor"],
        held_experts=(first, count)), held=(first, count))
    assert ours.shape == (48, cfg["vocab_size"])
    assert float(jnp.abs(ours - theirs).max()) < 5e-5           # float32, sums ordered apart
