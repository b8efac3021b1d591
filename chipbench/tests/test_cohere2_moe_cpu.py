"""The cohere2_moe family in the benchmark, on the CPU: its toy cell end to
end (a third toy cell, in a manifest of its own beside the toy manifest —
``tests/toy/BENCHMARK.cohere2_moe.json`` — because a PR may add benchmark
files and not edit them), the committed-token fault seen to fail on it, the
manifest's hygiene, the family's counts by hand, its trace reader on a
made-up trace, and the benchmark's reference against the program's own."""

import contextlib
import io
import json
import re

import numpy as np
import pytest

from chipbench import flops_cohere2_moe as flops
from chipbench import harness, moe_trace, run, trace_reduce

TOY = harness.PACKAGE / "tests" / "toy" / "BENCHMARK.cohere2_moe.json"
CELL = "toy-serve-cohere2-moe"
REAL = json.loads((harness.PACKAGE / "configs" / "command-a-plus-05-2026-l4e16.json").read_text())


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


@pytest.mark.parametrize("path", [harness.ROOT / "BENCHMARK.json", TOY], ids=["real", "toy"])
def test_manifest_hygiene(path):
    manifest = json.loads(path.read_text())
    cells = {w["name"] for w in manifest["workloads"]}
    e2e = {m["name"]: set(m.get("workloads", cells)) for m in manifest["end_to_end"]}
    for entry in manifest["workloads"] + manifest["configs"]:
        assert 1 <= len(entry["why"]) <= 200
    for m in manifest["per_layer"]:            # every per-layer list inside its arrow's list
        assert set(m["workloads"]) <= e2e[m["moves"]], m
        assert (harness.PACKAGE / "layer_metrics" / f"{m['name']}.py").is_file()
    for c in manifest["configs"]:
        assert json.loads((harness.ROOT / c["file"]).read_text())["reduced"] == c["reduced"]
    for w in manifest["workloads"]:
        harness.load_cell(path, w["name"])


def test_the_configuration_keeps_every_published_number():
    assert REAL["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert REAL["published"] == {"num_hidden_layers": 32, "num_experts": 128,
                                 "vocab_size": 262144}
    assert (REAL["hidden_size"], REAL["intermediate_size"], REAL["num_attention_heads"],
            REAL["num_key_value_heads"], REAL["head_dim"], REAL["sliding_window"],
            REAL["num_experts_per_tok"], REAL["num_shared_experts"], REAL["router_width"]) == \
        (4096, 4096, 128, 8, 128, 4096, 8, 4, 128)
    assert (REAL["num_hidden_layers"], REAL["num_experts"], REAL["vocab_size"],
            REAL["held_experts"]) == (4, 16, 32768, [0, 16])
    assert len(REAL["layer_types"]) == 32                       # the group is copied whole
    assert flops.kinds(REAL) == ["sliding_attention"] * 3 + ["full_attention"]


def test_counts_by_hand():
    attn = 4096 * 16384 * 2 + 4096 * 1024 * 2
    expert = 3 * 4096 * 4096
    p = flops.layer_params(REAL)
    assert p == {"attn": attn, "router": 4096 * 128, "shared": 4 * expert, "expert": expert}
    assert attn + 4096 * 128 + 4 * expert == 344_457_216       # a layer outside the routed experts
    assert flops.held_picks_per_token(REAL) == 1.0              # 8 picks x 16 / 128
    per_layer = 2 * (attn + 4096 * 128 + 4 * expert + expert)
    assert flops.token_matmul_flops(REAL) == per_layer
    # one token at context 6000: the three sliding layers read 4096 keys, the full one 6000
    assert flops.token_flops(REAL, 6000, True) == \
        4 * per_layer + 4 * 16384 * (3 * 4096 + 6000) + 2 * 4096 * 32768
    # a prompt of 5000: a sliding layer reads 4096*4097/2 + 904*4096 keys in all
    assert flops.prefill_keys(REAL, "sliding_attention", 5000) == 4096 * 4097 / 2 + 904 * 4096
    assert flops.prefill_keys(REAL, "full_attention", 5000) == 5000 * 5001 / 2
    assert flops.request_flops(REAL, 100, False, []) == 0
    # 16 slots touch 16 * (1 - (15/16)^16) = 10.3 held experts a layer
    assert flops.experts_touched(REAL, 16) == pytest.approx(10.30, abs=0.01)
    moe = 4 * (4 + flops.experts_touched(REAL, 16)) * expert * 2
    assert flops.moe_bytes(REAL, 16) == pytest.approx(moe)
    tick = flops.decode_tick_bytes(REAL, 16, [2000, 6000])
    rows = 3 * (2000 + 4096) / 2 + (2000 + 6000) / 2            # mean rows a slot, four layers
    assert tick == pytest.approx((4 * (attn + 4096 * 128) + 4096 * 32768) * 2 + moe
                                 + 2 * 1024 * 16 * rows * 2)
    assert 7.5e9 < tick < 8.5e9                                 # 7.2 GB of weights + 0.9 GB of KV rows


def test_the_trace_reader_finds_expert_ops_by_the_stacks_they_read():
    def ev(line, name, start, dur):
        return trace_reduce.Event("/device:TPU:0", line, name, float(start), float(dur))

    stack = "bf16[16,4096,4096]{2,1,0:T(8,128)(2,1)}"
    events = [
        ev(trace_reduce.MODULES_LINE, "jit__paged_decode_fn(1)", 0, 1000),
        ev(trace_reduce.MODULES_LINE, "jit__paged_prefill_chunk_fn(2)", 2000, 4000),
        ev(trace_reduce.MODULES_LINE, "jit__paged_decode_fn(1)", 7000, 1000),
        ev(trace_reduce.OPS_LINE, f"%fusion.1 = bf16[16,16,4096] fusion({stack} %p.1)", 100, 300),
        ev(trace_reduce.OPS_LINE, "%fusion.2 = bf16[16,4096] fusion(bf16[4096,16384] %p.2)", 400, 100),
        ev(trace_reduce.OPS_LINE, "%fusion.3 = f32[4,16,4096] fusion(bf16[4,4096,4096] %p.3)", 500, 100),
        # the chunk's loop over tiles carries the stacks; its body ops lie inside it
        ev(trace_reduce.OPS_LINE, f"%while.4 = (s32[], {stack}) while(%t)", 2500, 2000),
        ev(trace_reduce.OPS_LINE, f"%fusion.5 = bf16[32,4096] fusion({stack} %p.5)", 2600, 500),
        ev(trace_reduce.OPS_LINE, f"%fusion.6 = bf16[16,16,4096] fusion({stack} %p.6)", 7100, 500),
    ]
    trace = trace_reduce.Trace(events)
    assert re.search(moe_trace.stack_pattern(REAL), "bf16[4,4096,4096]{2,1,0}")
    assert moe_trace.expert_ms_per_execution(trace, REAL, r"^jit__paged_decode_fn") == \
        pytest.approx((300 + 100 + 500) * 1e-6 / 2)
    assert moe_trace.expert_ms_per_execution(trace, REAL, r"^jit__paged_prefill_chunk_fn") == \
        pytest.approx(2000 * 1e-6)                              # the while, once
    assert moe_trace.expert_ms_per_execution(trace, REAL, r"^jit_other") is None
    # a program of another family has no such stacks: the readers return nothing
    mixtral = trace_reduce.Trace(events[:3] + [events[4]])
    assert moe_trace.expert_ms_per_execution(mixtral, REAL, r"^jit__paged_decode_fn") is None


def test_the_new_readers_return_nothing_for_another_family():
    """Laid over a program that lacks the counters and a configuration that
    holds no share, each new reader returns None and does not raise."""
    mixtral = json.loads((harness.PACKAGE / "configs" / "mixtral-8x7b-v0.1-d3.json").read_text())
    ctx = harness.LayerContext(trace=trace_reduce.Trace([]), stats={"slot_occupancy": 0.9},
                               counts={"_work": [(10, True, [11, 12])], "slots": 8},
                               window_s=30.0, config=mixtral, traffic={}, peaks={}, rates={})
    for name in ("serve_step_mfu.cohere2moe", "decode_tick_roofline.cohere2moe",
                 "moe_decode_roofline", "moe_prefill_roofline", "kv_dead_rows_share"):
        assert harness.load_module("layer_metrics", name).compute(ctx) is None


def test_the_benchmarks_reference_is_the_programs_reference():
    """Two plain references written apart (chipbench/models/cohere2_moe.py for
    the chip, accelerate_tpu/models/reference/cohere2_moe.py for tier-1)
    agree on the toy cell's seeded weights, held share included."""
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.models.cohere2_moe import Cohere2MoeConfig
    from accelerate_tpu.models.reference import cohere2_moe as program_reference
    from chipbench import reference_ops as ops

    cell = harness.load_cell(TOY, CELL)
    family = harness.load_module("models", "cohere2_moe")
    cfg = cell.config
    params = family.make_params(cfg, 11, dtype="float32")
    ids = jnp.asarray(np.random.default_rng(5).integers(1, cfg["vocab_size"], 48), jnp.int32)
    ours = jax.jit(lambda p, i: family.reference_logits(p, i, cfg, ops.matmul("float32")))(
        params, ids)
    first, count = family.held(cfg)
    theirs = program_reference.forward(params, ids, Cohere2MoeConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"], num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        sliding_window=cfg["sliding_window"], layer_types=tuple(cfg["layer_types"]),
        rope_theta=cfg["rope_theta"], num_experts=cfg["router_width"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        num_shared_experts=cfg["num_shared_experts"], held_experts=(first, count)),
        held=(first, count))
    assert ours.shape == (48, cfg["vocab_size"])
    assert float(jnp.abs(ours - theirs).max()) < 5e-5           # float32, sums ordered apart
