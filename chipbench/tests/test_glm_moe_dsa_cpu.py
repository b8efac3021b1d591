"""The glm_moe_dsa family in the benchmark, on the CPU: its toy cell end to
end with its control (a toy cell in a manifest of its own,
``tests/toy/BENCHMARK.glm_moe_dsa.json``), a selection fault seen to fail on it, the
manifest's new entries, the configuration against the catalog, the traffic,
the family's counts by hand, its readers, and the benchmark's reference
against the program's own."""

import contextlib
import dataclasses
import io
import json

import numpy as np
import pytest

from chipbench import flops_glm_moe_dsa as flops
from chipbench import harness, run, trace_reduce

TOY = harness.PACKAGE / "tests" / "toy" / "BENCHMARK.glm_moe_dsa.json"
CELL = "toy-serve-glm-moe-dsa"
REAL_CELL = "serve-glm5-closed16-longctx"
REAL = json.loads((harness.PACKAGE / "configs" / "glm-5.2-l5e16.json").read_text())
NEW_METRICS = ("serve_step_mfu.glm5", "decode_tick_roofline.glm5", "dsa_index_roofline",
               "dsa_selected_share")
SHARED_METRICS = ("decode_tick_device_ms", "host_us_per_tick", "tick_launch_us", "tick_commit_us",
                  "emit_lag_ms", "decode_attn_rows_share", "tick_kv_attn_ms", "tick_experts_ms",
                  "tick_relayout_ms")
#: the catalog's numbers of GLM-5.2 (model-configs guide, architectures.jsonl)
CATALOG = {
    "ep_size": 1, "first_k_dense_replace": 3, "head_dim": 192, "hidden_size": 6144,
    "index_head_dim": 128, "index_n_heads": 32, "index_skip_topk_offset": 3, "index_topk": 2048,
    "index_topk_freq": 4, "intermediate_size": 12288, "kv_lora_rank": 512,
    "max_position_embeddings": 1048576, "moe_intermediate_size": 2048, "moe_layer_freq": 1,
    "n_group": 1, "n_routed_experts": 256, "n_shared_experts": 1, "num_attention_heads": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 78, "num_key_value_heads": 64,
    "num_nextn_predict_layers": 1, "q_lora_rank": 2048, "qk_head_dim": 256,
    "qk_nope_head_dim": 192, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "routed_scaling_factor": 2.5, "topk_group": 1, "v_head_dim": 256, "vocab_size": 154880,
    "rope_parameters": {"rope_theta": 8000000, "rope_type": "default"}}


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


def latest_rows(monkeypatch):
    """Every query reads its LATEST ``index_topk`` rows, whatever the indexer
    scored — in the tick and in the chunk."""
    import jax.numpy as jnp

    from accelerate_tpu.models import llama

    select_paged = llama._paged_index_select

    def paged(cache, q_idx, w_idx, k_idx, cache_pos, topk):
        _, row = select_paged(cache, q_idx, w_idx, k_idx, cache_pos, topk)
        ids = cache_pos - jnp.arange(topk, dtype=jnp.int32)
        return jnp.where(ids < 0, cache_pos + 1 - ids, ids)[None, None], row

    def mask(scores, topk):
        visible = scores > -jnp.inf
        return visible & (jnp.cumsum(visible[..., ::-1], axis=-1)[..., ::-1] <= topk)

    monkeypatch.setattr(llama, "_paged_index_select", paged)
    monkeypatch.setattr(llama, "top_rows_mask", mask)


def test_the_latest_rows_in_place_of_the_indexers_picks_is_not_correct(monkeypatch):
    latest_rows(monkeypatch)
    last, _ = run_cell(seed=5)
    assert last["correct"] is False and last["failed"] == 0
    assert last["compared"]["gap_mean"]["value"] > 3 * last["compared"]["gap_mean"]["limit"]


def test_the_manifest_gained_the_configuration_the_cell_and_four_metrics():
    manifest = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    assert manifest["configs"][-1]["name"] == REAL["name"] == "glm-5.2-l5e16"
    assert manifest["configs"][-1]["reduced"] == REAL["reduced"]
    assert manifest["configs"][-1]["source"] == REAL["source"]
    entry = manifest["workloads"][-1]
    assert (entry["name"], entry["traffic"], entry["chips"]) == (REAL_CELL, "closed16-longctx28k", 1)
    assert tuple(m["name"] for m in manifest["per_layer"][-4:]) == NEW_METRICS
    assert all(m["workloads"] == [REAL_CELL] and m["moves"] == "itl_p95_ms"
               for m in manifest["per_layer"][-4:])
    cell = harness.load_cell(harness.ROOT / "BENCHMARK.json", REAL_CELL)
    # the gap between tokens alone: a window finishes ~25 first tokens, and the
    # rate follows the load generator's first-request race (PERF.md section 7)
    assert {m["name"] for m in cell.end_to_end} == {"itl_p95_ms", "setup_s"}
    assert {m["name"] for m in cell.per_layer} == set(NEW_METRICS) | set(SHARED_METRICS)
    for name in NEW_METRICS + SHARED_METRICS:
        assert (harness.PACKAGE / "layer_metrics" / f"{name}.py").is_file()
    for path in (harness.ROOT / "BENCHMARK.json", TOY):
        for w in json.loads(path.read_text())["workloads"]:
            assert 1 <= len(w["why"]) <= 200
            harness.load_cell(path, w["name"])


def test_the_configuration_keeps_every_published_number():
    reduced = {"num_hidden_layers": 5, "first_k_dense_replace": 1, "n_routed_experts": 16,
               "vocab_size": 19360}
    assert REAL["reduced"] == list(reduced)
    assert REAL["published"] == {k: CATALOG[k] for k in reduced}
    for key, value in CATALOG.items():
        assert REAL[key] == reduced.get(key, value), key
    assert (REAL["router_width"], REAL["held_experts"], REAL["held_layers"]) == \
        (256, [0, 16], [2, 3, 4, 5, 6])
    family = harness.load_module("models", "glm_moe_dsa")
    assert family.layer_types(REAL) == [("dense", "full")] + [("sparse", "shared")] * 3 + [
        ("sparse", "full")]
    for reading in ("held_layers", "index_share", "indexer", "index_keys", "rotary", "router",
                    "e_score_correction_bias", "mtp", "init_std", "cache", "reference_tail"):
        assert reading in REAL["assumed"]
    assert "16 chips share each layer" in REAL["deployment"]
    params = sum(int(np.prod(shape)) for _, shape, _ in family.leaf_table(REAL))
    assert params == 3_881_517_056                               # 7.76 GB in bfloat16


def test_the_traffic_mix():
    from chipbench.traffic import lognormal_quantiles

    mix = json.loads((harness.PACKAGE / "traffic" / "closed16-longctx28k.json").read_text())
    assert (mix["driver"], mix["clients"], mix["ramp_requests_per_client"], mix["pool"]) == \
        ("closed_loop", 16, 1, 64)
    assert REAL["assumed"]["max_slots"] == mix["clients"]
    assert (mix["prompt_len"]["median"], mix["prompt_len"]["sigma"]) == (12288, 0.5)
    assert (mix["output_len"]["median"], mix["output_len"]["sigma"]) == (384, 0.5)
    prompts = lognormal_quantiles(mix["prompt_len"], 64)
    outputs = lognormal_quantiles(mix["output_len"], 64)
    assert min(prompts) > REAL["index_topk"]             # every query picks from more rows
    assert max(prompts) + max(outputs) <= 29696 < REAL["assumed"]["max_len"]
    # no answer is longer than the rows the reference computes past the last non-zero id
    assert max(outputs) <= mix["output_len"]["max"] <= REAL["assumed"]["reference_tail"]


def test_counts_by_hand():
    h = 6144
    proj = h * 2048 + 2048 * 64 * 256 + h * 576 + 64 * 256 * h
    kv_b, indexer = 512 * 64 * 448, 2048 * 32 * 128 + h * 128 + h * 32
    assert (proj, kv_b, indexer) == (150_339_584, 14_680_064, 9_371_648)
    p = flops.layer_params(REAL)
    assert (p["proj"], p["kv_b"], p["indexer"]) == (proj, kv_b, indexer)
    dense, router, expert = 3 * h * 12288, h * 256, 3 * h * 2048
    assert flops.held_picks_per_token(REAL) == 0.5               # 8 picks x 16 / 256
    moe = router + expert + 0.5 * expert                          # shared + half a held expert
    assert flops.token_matmul_flops(REAL) == 2 * (5 * proj + 2 * indexer + dense + 4 * moe)
    # a tick's query at context 14000 reads 2048 rows (absorbed: 2 (576 + 512) a row a head
    # + the two folds) in 5 layers, and scores all 14000 in the 2 indexers
    one = 64 * (2048 * 2176 + 2 * 512 * 448)
    assert flops.token_flops(REAL, 14000) == pytest.approx(
        flops.token_matmul_flops(REAL) + 5 * one + 2 * 14000 * 32 * 258 + 2 * h * 19360)
    # under index_topk rows nothing is left out
    assert flops.selected(REAL, 1000) == 1000 and flops.selected(REAL, 5000) == 2048
    assert flops.request_flops(REAL, 300, False, []) == 0
    tick = flops.decode_tick_bytes(REAL, 16, [14000])
    rows = 16 * (5 * 2048 * 576 + 2 * 14000 * 128)
    assert 4.5e9 < tick - 2 * rows < 5.0e9          # weights, 6.4 of 16 held experts a layer
    need, nbytes = flops.index_tick_need(REAL, 16, [14000])
    assert need == pytest.approx(2 * 16 * (2 * indexer + 14000 * 32 * 258))
    assert nbytes == 2 * (2 * (indexer + 16 * 14000 * 128) + 5 * 16 * 2048 * 576)


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
    for name in ("decode_tick_roofline.glm5", "dsa_index_roofline", "dsa_selected_share"):
        assert harness.load_module("layer_metrics", name).compute(ctx) is None


def test_the_readers_read_a_made_up_run():
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

    def ev(line, name, start, dur):
        return trace_reduce.Event("/device:TPU:0", line, name, float(start), float(dur))

    trace = trace_reduce.Trace([
        ev(trace_reduce.MODULES_LINE, "jit__paged_decode_fn(1)", 0, 30e6),
        ev(trace_reduce.MODULES_LINE, "jit__paged_prefill_chunk_fn(2)", 40e6, 90e6),
    ])
    work = [(14000, True, [14001, 14002]), (9000, False, [9001])]
    ctx = harness.LayerContext(trace=trace, stats={"slot_occupancy": 0.75,
                                                   "dsa_selected_share": 0.15},
                               counts={"_work": work, "slots": 16}, window_s=30.0, config=REAL,
                               traffic={}, peaks=peaks, rates={})
    read = {name: harness.load_module("layer_metrics", name).compute(ctx) for name in NEW_METRICS}
    total = sum(flops.request_flops(REAL, p, first, later) for p, first, later in work)
    assert read["serve_step_mfu.glm5"] == pytest.approx(100 * total / 30 / 197e12)
    assert read["decode_tick_roofline.glm5"] == pytest.approx(
        100 * flops.decode_tick_bytes(REAL, 12, [14001, 14002, 9001]) / 819e9 / 30e-3)
    assert read["dsa_selected_share"] == pytest.approx(15.0)
    assert read["dsa_index_roofline"] is None         # the made-up trace names no part
    assert all(v is None or 0 < v <= 100 for v in read.values())


def test_the_benchmarks_reference_is_the_programs_reference():
    """Two plain references written apart (chipbench/models/glm_moe_dsa.py
    for the chip, accelerate_tpu/models/reference/glm_moe_dsa.py for
    tier-1) agree on the toy cell's seeded weights, held share, selections
    and the rows past the last non-zero id included."""
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.models.glm_moe_dsa import GlmMoeDsaConfig
    from accelerate_tpu.models.reference import glm_moe_dsa as program_reference
    from chipbench import reference_ops as ops

    cell = harness.load_cell(TOY, CELL)
    family = harness.load_module("models", "glm_moe_dsa")
    cfg = cell.config
    params = family.make_params(cfg, 11, dtype="float32")
    n, pad_to = 100, 1024                          # two row blocks, the second past the tail
    ids = jnp.asarray(np.random.default_rng(5).integers(1, cfg["vocab_size"], n), jnp.int32)
    padded = jnp.concatenate([ids, jnp.zeros(pad_to - n, jnp.int32)])
    ours = jax.jit(lambda p, i: family.reference_logits(p, i, cfg, ops.matmul("float32")))(
        params, padded)
    types = family.layer_types(cfg)
    theirs = program_reference.forward(params, ids, GlmMoeDsaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"], q_lora_rank=cfg["q_lora_rank"],
        kv_lora_rank=cfg["kv_lora_rank"], qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        index_n_heads=cfg["index_n_heads"], index_head_dim=cfg["index_head_dim"],
        index_topk=cfg["index_topk"], rope_theta=family.theta(cfg),
        num_experts=cfg["router_width"], num_experts_per_tok=cfg["num_experts_per_tok"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        mlp_layer_types=tuple(m for m, _ in types), indexer_types=tuple(x for _, x in types),
        held_experts=family.held(cfg)), held=family.held(cfg))
    assert ours.shape == (pad_to, cfg["vocab_size"])
    assert float(jnp.abs(ours[:n] - theirs).max()) < 5e-5         # float32, sums ordered apart
    # a row block wholly past the last non-zero id and its reference_tail is not computed
    assert n + cfg["assumed"]["reference_tail"] <= family.ROW_BLOCK < pad_to
    assert float(jnp.abs(ours[family.ROW_BLOCK:]).max()) == 0.0
