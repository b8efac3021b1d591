"""The longcat_flash family against its plain reference (CPU, tiny: hidden 64,
4 heads over a latent of 8 values and a rotary key of 4, two double layers,
a router over 8 routed + 4 zero-compute experts, top-3, seeded random weights
and a seeded selection bias).

Tolerances. Everything here is float32 on the CPU, where a matmul is exact
float32 at any precision setting; the model and the reference order their
sums differently (the absorbed form folds ``kv_b_proj`` into the query, the
reference expands every head's key and value at once, the program scales the
query through the softmax scale and the reference scales the query), which
moves a logit of magnitude ~4 by a few 1e-6. ``TOL`` = 5e-5 leaves ten times
that; a bfloat16 rounding of the cached latent moves the same logits by
~1e-2 and fails (the control below), and so does leaving out the identity
experts' term, the latent's scale or the shortcut branch (each moves a logit
by 1e-2 or more).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.models.longcat_flash import (LongcatDoubleLayer, LongcatFlashConfig,
                                                 LongcatFlashForCausalLM, LongcatMoE)
from accelerate_tpu.models.reference import longcat_flash as ref
from accelerate_tpu.ops import moe
from accelerate_tpu.serving import ServingEngine

TOL = 5e-5


def seeded_bias(params, cfg, seed=10):
    """A correction bias of std 1 / router columns, of the order of a softmax
    score, so that it moves selections (a trained model's is set by a
    controller; a fresh module's is zero)."""
    for i in range(cfg.num_layers):
        params[f"layers_{i}"]["moe"]["e_score_correction_bias"] = jax.random.normal(
            jax.random.PRNGKey(seed + i), (cfg.router_width,)) / cfg.router_width
    return params


@pytest.fixture(scope="module")
def tiny():
    cfg = LongcatFlashConfig.tiny()
    assert (cfg.num_layers, cfg.router_width, cfg.cache_row_width) == (2, 12, 12)
    assert (cfg.q_scale, cfg.latent_scale) == (2.0, 8 ** 0.5)
    model = LongcatFlashForCausalLM(cfg)
    return cfg, model, seeded_bias(model.init_params(jax.random.PRNGKey(0)), cfg)


def ids_of(n, seed=1, vocab=256):
    return jax.random.randint(jax.random.PRNGKey(seed), (n,), 1, vocab)


def share_of(params, cfg, first, count):
    """The config and parameters of the rank that holds routed experts
    ``first .. first + count - 1`` (everything else is held by every rank)."""
    cut = jax.tree.map(lambda a: a, params)
    for i in range(cfg.num_layers):
        experts = params[f"layers_{i}"]["moe"]["experts"]
        cut[f"layers_{i}"]["moe"]["experts"] = {
            n: w[first:first + count] for n, w in experts.items()}
    return dataclasses.replace(cfg, held_experts=(first, count)), cut


def through_the_cache(model, params, ids, chunk, prompt, cache_dtype=jnp.float32):
    """Logits of ``ids`` served as the engine's programs serve them: a linear
    full-length latent cache, chunks of ``chunk`` up to ``prompt``, then single
    tokens."""
    n = ids.shape[0]
    cache = model.init_cache(1, n, cache_dtype)
    got = []
    for start in list(range(0, prompt, chunk)) + list(range(prompt, n)):
        stop = start + chunk if start < prompt else start + 1
        logits, cache = model.apply({"params": params}, ids[None, start:stop], cache=cache,
                                    cache_pos=jnp.int32(start))
        got.append(logits[0])
    return jnp.concatenate(got), cache


# -- (a) the full forward pass, whole and as a held share ---------------------

def test_full_forward_logits_agree_with_the_reference(tiny):
    cfg, model, params = tiny
    ids = ids_of(40)
    logits = model.apply({"params": params}, ids[None])[0]
    assert float(jnp.abs(logits - ref.forward(params, ids, cfg)).max()) < TOL


def test_a_held_share_forward_agrees_with_the_reference_given_the_same_share(tiny):
    cfg, _, params = tiny
    cfg_h, params_h = share_of(params, cfg, 2, 4)
    ids = ids_of(24, seed=2)
    logits = LongcatFlashForCausalLM(cfg_h).apply({"params": params_h}, ids[None])[0]
    want = ref.forward(params_h, ids, cfg_h, held=(2, 4))
    assert float(jnp.abs(logits - want).max()) < TOL
    # and it is NOT the uncut model: the absent experts' part is left out
    assert float(jnp.abs(logits - ref.forward(params, ids, cfg)).max()) > 100 * TOL


# -- (b) the shares add up to the uncut layer ---------------------------------

@pytest.mark.parametrize("tokens", [5, 40], ids=["dense_path", "sorted_path"])
def test_the_shares_add_up_to_the_uncut_layer(tiny, tokens):
    """Four ranks of two routed experts each (the deployment's 32 ranks of 16
    of 512): a whole double layer on each rank, and what every rank computes
    alike — both attentions, both dense MLPs, the norms and the zero-compute
    experts' identity part — counted once, add up to what the uncut reference
    gives for the whole layer, in the program and in the reference."""
    cfg, _, params = tiny
    layer = params["layers_1"]
    x = jax.random.normal(jax.random.PRNGKey(7), (1, tokens, cfg.hidden_size))
    positions = jnp.arange(tokens)
    # every rank alike: the layer with no routed expert held
    cfg_0, params_0 = share_of(params, cfg, 0, 0)
    alike = ref.layer(x[0], params_0["layers_1"], cfg_0, positions, held=(0, 0))
    program_sum, ref_sum = -3 * alike, -3 * alike
    for first in range(0, 8, 2):
        cfg_h, params_h = share_of(params, cfg, first, 2)
        program_sum = program_sum + LongcatDoubleLayer(cfg_h).apply(
            {"params": params_h["layers_1"]}, x, positions[None])[0][0]
        ref_sum = ref_sum + ref.layer(x[0], params_h["layers_1"], cfg_h, positions, (first, 2))
    whole = ref.layer(x[0], layer, cfg, positions)
    assert float(jnp.abs(program_sum - whole).max()) < TOL
    assert float(jnp.abs(ref_sum - whole).max()) < TOL
    # the identity part is a real share of the whole: the layer without it is another
    _, identity = ref.moe_parts(jax.random.normal(jax.random.PRNGKey(8), (tokens, 64)),
                                layer["moe"], cfg)
    assert float(jnp.abs(identity).max()) > 0.01


# -- (c) the router -----------------------------------------------------------

def test_the_bias_moves_the_selection_and_not_the_gate():
    T, E, k = 64, 12, 3
    logits = jax.random.normal(jax.random.PRNGKey(1), (T, E))
    bias = jax.random.normal(jax.random.PRNGKey(2), (E,)) / E
    plain_gates, plain = moe.route_top_k(logits, k, normalize_gates=False)
    gates, picked = moe.route_top_k(logits, k, normalize_gates=False, selection_bias=bias)
    scores = jax.nn.softmax(logits, axis=-1)
    assert (np.sort(np.asarray(picked), -1) != np.sort(np.asarray(plain), -1)).any(-1).sum() >= 5
    # picked by score + bias ...
    np.testing.assert_array_equal(picked, jax.lax.top_k(scores + bias, k)[1])
    # ... each gated by its score alone, not renormalised
    np.testing.assert_allclose(gates, jnp.take_along_axis(scores, picked, -1), rtol=1e-6)
    np.testing.assert_allclose(plain_gates, jax.lax.top_k(scores, k)[0], rtol=1e-6)
    assert float(gates.sum(-1).max()) < 1.0


def test_a_pick_on_a_zero_compute_expert_returns_the_token_and_reads_no_weights():
    """A router whose every token picks only zero-compute columns: the layer
    is ``x`` times the gates' sum, and on the sorted path (a chunk's) no tile
    runs, so the held experts' weights, NaN here, are never read into it."""
    D, held, zero = 16, 4, 4
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 40, D))
    router = jnp.zeros((D, held + zero))                        # uniform scores of 1/8
    bias = jnp.zeros((held + zero,)).at[held:].set(1.0)         # every pick among the last 4
    nan = {n: jnp.full(s, jnp.nan) for n, s in
           (("gate_proj", (held, D, 8)), ("up_proj", (held, D, 8)), ("down_proj", (held, 8, D)))}
    out, stats = moe.moe_held_apply(nan, router, x, top_k=2, normalize_gates=False,
                                    held=(0, held), identity_experts=zero, selection_bias=bias)
    np.testing.assert_allclose(out, x * 0.25, rtol=1e-6)        # two picks of gate 1/8 each
    assert int(stats["zero_picks"]) == 80 and stats["picks"].tolist() == [0] * held + [80, 0, 0]


def test_the_router_scores_in_float32_on_bfloat16_activations(tiny):
    """Served weights and activations are bfloat16; the router's product and
    softmax are float32 of those values (a bfloat16 router flips the picks of
    near-ties): the picks a layer counts, held and zero-compute, are the
    float32 reference's picks of the same bfloat16 inputs, exactly."""
    cfg, _, params = tiny
    layer = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params["layers_0"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(9), (1, 1024, cfg.hidden_size)).astype(jnp.bfloat16)
    _, sown = LongcatMoE(cfg).apply({"params": layer}, x, mutable=["moe_stats"])
    picks = np.asarray(sown["moe_stats"]["picks"][0])
    gates = ref.gates_of(x[0].astype(jnp.float32), jax.tree.map(
        lambda a: a.astype(jnp.float32), layer), cfg)
    per_column = np.asarray((gates > 0).sum(0))
    assert picks[:8].tolist() == per_column[:8].tolist()
    assert picks[-1] == per_column[8:].sum() and picks[8] == 1024 * cfg.moe_topk


# -- (d) prefill in chunks, then decode, through the cache --------------------

def test_chunked_prefill_then_decode_through_the_cache_gives_the_reference_logits(tiny):
    cfg, model, params = tiny
    ids = ids_of(38, seed=5)
    want = ref.forward(params, ids, cfg)
    got, _ = through_the_cache(model, params, ids, chunk=8, prompt=24)
    assert float(jnp.abs(got - want).max()) < TOL
    # the control: the cached latent in bfloat16 where float32 is stated fails it
    low, _ = through_the_cache(model, params, ids, chunk=8, prompt=24, cache_dtype=jnp.bfloat16)
    assert float(jnp.abs(low - want).max()) > 20 * TOL


def test_the_cache_holds_the_scaled_latent_and_the_unscaled_rotary_key(tiny):
    """The scale of the latent is folded into the cached row (entry ``2i`` is
    layer i's first attention): the row is sqrt(hidden / kv_lora_rank) times
    the normed latent; the rotary key is the plain rotated one."""
    cfg, model, params = tiny
    ids = ids_of(6, seed=4)
    _, cache = through_the_cache(model, params, ids, chunk=6, prompt=6)
    assert len(cache) == 2 * cfg.num_layers
    p = params["layers_0"]
    n = ref.rms_norm(params["embed_tokens"]["embedding"][ids], p["norm_in0"]["scale"],
                     cfg.rms_norm_eps)
    kv = ref._mm(n, p["attn0"]["kv_a_proj"]["kernel"])
    latent = ref.rms_norm(kv[:, :cfg.kv_lora_rank], p["attn0"]["kv_a_norm"]["scale"],
                          cfg.rms_norm_eps)
    np.testing.assert_allclose(cache[0]["latent"][0], latent * 8 ** 0.5, atol=1e-5)
    k_r = ref.rope(kv[:, None, cfg.kv_lora_rank:], jnp.arange(6), cfg.rope_theta)[:, 0]
    np.testing.assert_allclose(cache[0]["rope"][0], k_r, atol=1e-5)


def test_the_paged_engine_serves_the_reference_logits(tiny):
    """Prefill in chunks, then decode, through ``ServingEngine``'s page pool.
    Compared in logits, not tokens: every served token's reference logit lies
    within TOL of the reference's best at its position."""
    cfg, model, params = tiny
    prompt, new = 19, 9
    ids = np.asarray(ids_of(prompt, seed=8))[None]
    eng = ServingEngine(model, params, max_slots=2, max_len=64, prefill_chunk=8, page_size=8,
                        cache_dtype=jnp.float32)
    try:
        req = eng.submit(ids, max_new_tokens=new, ignore_eos=True, block=True)
        assert req.wait(120)
        served, summary = list(req.tokens), eng.stats.summary()
        pool = eng.page_pool_metrics()
    finally:
        eng.shutdown(drain=False)
    full = jnp.asarray(np.concatenate([ids[0], served]))
    logits = ref.forward(params, full, cfg)[prompt - 1:-1]
    gaps = logits.max(-1) - logits[jnp.arange(new), jnp.asarray(served)]
    assert float(gaps.max()) < TOL
    # the cache the engine holds is the declared one: 2 layers x 2 attentions x 12 values,
    # float32; each of the 4 entries is one attention's
    assert summary["kv_bytes_per_token"] == 2 * 2 * 12 * 4
    assert pool["page_bytes"] == 8 * summary["kv_bytes_per_token"]
    assert summary["kv_cache_layers"] == summary["kv_reader_layers"] == 4
    # the zero-compute picks are counted beside the others: a third of all picks in
    # expectation (4 of 12 columns), and the held experts take the rest (all 8 held)
    assert 0.1 < summary["moe_zero_pick_share"] < 0.6
    assert summary["moe_held_pick_share"] + summary["moe_zero_pick_share"] == pytest.approx(1.0)
    assert summary["moe_zero_picks"] > 0


def test_the_zero_picks_counter_merges_and_resets():
    from accelerate_tpu.serving.metrics import ServingStats

    a, b = ServingStats(), ServingStats()
    a.record_tick(1, 1, 2, 0.01, moe_picks=np.array([3, 1, 6, 0, 0]), moe_zero_picks=2)
    b.record_prefill_chunk(1.0, moe_picks=np.array([1, 1, 6, 4, 8]), moe_zero_picks=4)
    m = ServingStats().merge(a).merge(b).summary()
    assert m["moe_zero_picks"] == 6 and m["moe_zero_pick_share"] == 0.5
    a.reset()
    assert a.summary()["moe_zero_picks"] == 0 and a.summary()["moe_zero_pick_share"] == 0.0


# -- (e) the declared cache, and what reads it --------------------------------

def test_the_family_declares_two_latent_entries_a_double_layer():
    from accelerate_tpu.big_modeling import cache_factory_for

    model = LongcatFlashForCausalLM(LongcatFlashConfig(num_layers=4, held_experts=(0, 16)))
    cache = jax.eval_shape(lambda: cache_factory_for(model)(2, 4096, jnp.bfloat16))
    assert len(cache) == 8 and all(set(entry) == {"latent", "rope"} for entry in cache)
    assert all(entry["latent"].shape == (2, 4096, 512) for entry in cache)      # no head axis
    assert sum(np.prod(leaf.shape[2:]) * 2 for leaf in jax.tree.leaves(cache)) == 9216


@pytest.mark.parametrize("kwargs,word", [({"tp": 2}, "tp > 1"), ({"kv_dtype": "int8"}, "kv_dtype")],
                         ids=["tp2", "int8_kv"])
def test_what_assumes_per_head_k_and_v_is_refused_by_name(tiny, kwargs, word):
    _, model, params = tiny
    with pytest.raises(NotImplementedError, match=word):
        ServingEngine(model, params, max_slots=2, max_len=32, prefill_chunk=8, **kwargs)


def test_estimate_reads_the_declared_cache_at_the_cells_cut(capsys):
    from accelerate_tpu.commands.estimate import estimate_command, estimate_command_parser

    rc = estimate_command(estimate_command_parser().parse_args(
        ["longcat-flash-omni", "--held-experts", "16", "--page-size", "256", "--max-pages", "769",
         "--dtypes", "bfloat16", "--spec-tokens", "2", "--draft-rank", "8"]))
    out = capsys.readouterr().out
    assert rc == 0
    assert "(5.17 B params)" in out and "9.63 GiB" in out       # 5.173 G = 10.35 GB in bf16
    assert "holding 16 of 512 routed experts" in out
    assert "16 leaves of 64/512 values a token, no head axis" in out
    assert "bytes per token : 9.00 KiB" in out                  # 8 attentions x 576 x 2 bytes
    # a draft's rows pair with the 8 cache entries, not with the 4 double layers
    assert "2 x 8 layers x 8 x bf16" in out


# -- (f) the other families' programs are the parent's ------------------------

def test_a_router_without_zero_compute_experts_lowers_as_before():
    """The new arguments are off by default: the held layer of the families
    the benchmark already had traces to the same program as without them
    (their whole engine programs are pinned in tests/test_pangu_ultra_moe.py)."""
    D, E, held = 16, 8, 4
    stacks = {"gate_proj": jnp.ones((held, D, 8)), "up_proj": jnp.ones((held, D, 8)),
              "down_proj": jnp.ones((held, 8, D))}
    router, x = jnp.ones((D, E)), jnp.ones((1, 40, D))

    def text(**kw):
        return jax.jit(lambda r, x: moe.moe_held_apply(stacks, r, x, top_k=2, held=(2, held),
                                                       **kw)).lower(router, x).as_text()

    assert text() == text(identity_experts=0, selection_bias=None)
