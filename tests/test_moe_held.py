"""The served path of the Mixtral family: under a cache the expert layer
computes the rows that were routed (``ops.moe.moe_held_apply`` with every
expert held), in tiles whose size comes from the call's shape.

CPU, float32, toy widths. ``TOL`` as in ``test_cohere2_moe.py``: the paths
order their sums differently (one-hot einsums, sorted gathers, a batched
product), which moves an output of magnitude ~1 by a few 1e-6.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.models.mixtral import MixtralConfig, MixtralForCausalLM, MixtralSparseMLP
from accelerate_tpu.ops import moe
from accelerate_tpu.ops.moe import held_tile_rows, moe_held_apply, moe_mlp_apply
from accelerate_tpu.serving import ServingEngine

TOL = 5e-5
TILES = (32, 64, 128, 256)


# -- the tile is a function of the shape --------------------------------------

@pytest.mark.parametrize("tokens,top_k,experts,want", [
    (256, 8, 128, 32),       # Command A+'s chunk: 16 rows an expert expected
    (256, 2, 8, 128),        # Mixtral's chunk: 64 expected
    (256, 1, 8, 64),         # Switch-style top-1: 32 expected
    (256, 4, 60, 64),        # Qwen2-MoE's 60 experts top-4: 17 expected
    (256, 2, 64, 32),        # 64 experts top-2: 8 expected, the least tile
    (33, 2, 8, 32),          # the fewest tokens that are sorted at all
    (64, 2, 8, 32),
    (128, 2, 8, 64),
    (512, 2, 8, 256),
    (4096, 2, 8, 256),       # an offline prompt: the tile stops at the chip's ridge
], ids=lambda v: str(v))
def test_the_tile_comes_from_the_shape(tokens, top_k, experts, want):
    tile = held_tile_rows(tokens, top_k, experts)
    assert tile == want
    assert tile in TILES                                   # a power of two in [32, 256]
    expected_rows = tokens * top_k / experts
    assert tile == 256 or tile >= 2 * expected_rows        # one tile holds a touched expert
    assert tile == 32 or tile < 4 * expected_rows          # and is not mostly padding


def test_the_tile_never_shrinks_as_tokens_grow():
    for top_k, experts in [(1, 4), (2, 8), (4, 60), (8, 128)]:
        tiles = [held_tile_rows(t, top_k, experts) for t in range(1, 5000, 7)]
        assert tiles == sorted(tiles) and set(tiles) <= set(TILES)


def _toy_layer(seed, d=32, f=48, e=8):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    experts = {"gate_proj": jax.random.normal(keys[0], (e, d, f)) * d ** -0.5,
               "up_proj": jax.random.normal(keys[1], (e, d, f)) * d ** -0.5,
               "down_proj": jax.random.normal(keys[2], (e, f, d)) * f ** -0.5}
    return experts, jax.random.normal(keys[3], (d, e))


@pytest.mark.parametrize("tokens,dense", [(1, True), (5, True), (32, True), (33, False),
                                          (256, False)])
def test_few_tokens_go_through_every_expert_and_more_are_sorted(tokens, dense):
    """The threshold is on the call's tokens (``HELD_DENSE_TOKENS``), apart
    from the tile: at most that many -> the batched product, no tile runs."""
    assert moe.HELD_DENSE_TOKENS == 32 <= min(TILES)
    experts, router = _toy_layer(1)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, tokens, 32))
    _, stats = moe_held_apply(experts, router, x, top_k=2)
    *held, all_picks, routed, computed = stats["picks"].tolist()
    assert sum(held) == all_picks == 2 * tokens
    if dense:
        assert routed == computed == 0
    else:
        tile = held_tile_rows(tokens, 2, 8)
        assert routed == 2 * tokens
        assert computed == sum(-(-n // tile) for n in held) * tile
        assert routed <= computed <= routed + 8 * (tile - 1)


# -- no token is dropped, at every tile ---------------------------------------

def _dense_reference(experts, router, x, top_k, normalize):
    """Every expert computes every token; the gates of the experts a token
    did not choose are zero."""
    probs = jax.nn.softmax(x @ router, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, top_k)
    if normalize:
        top_p = top_p / top_p.sum(-1, keepdims=True)
    gates = jnp.zeros_like(probs).at[jnp.arange(x.shape[0])[:, None], top_i].set(top_p)
    outs = jnp.stack([
        (jax.nn.silu(x @ experts["gate_proj"][i]) * (x @ experts["up_proj"][i]))
        @ experts["down_proj"][i] for i in range(router.shape[-1])])
    return jnp.einsum("te,etd->td", gates, outs)


@pytest.mark.parametrize("tile", TILES)
def test_no_token_is_dropped_when_every_token_picks_one_expert(tile):
    """300 tokens that all pick expert 5, then 2: every row is computed,
    whatever the tile (the sorted path alone, at each size)."""
    tokens, d, e = 300, 16, 8
    experts, _ = _toy_layer(3, d=d, f=24, e=e)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(4), (tokens, d))) + 0.1
    router = jnp.zeros((d, e)).at[:, 5].set(1.0).at[:, 2].set(0.5)
    gates, picked = moe.route_top_k(x @ router, 2)
    assert picked.tolist() == [[5, 2]] * tokens
    counts = jnp.zeros((e,), jnp.int32).at[jnp.array([5, 2])].set(tokens)
    out, n_tiles = moe._held_sorted(x, experts["gate_proj"], experts["up_proj"],
                                    experts["down_proj"], gates, picked,
                                    jnp.ones_like(picked, bool), counts, tile)
    assert int(n_tiles) == 2 * -(-tokens // tile)
    want = _dense_reference(experts, router, x, 2, True)
    assert float(jnp.abs(out - want).max()) < TOL


# -- Mixtral's layer under a cache --------------------------------------------

@pytest.mark.parametrize("norm_topk_prob", [None, False], ids=["norm_default", "norm_off"])
@pytest.mark.parametrize("top_k", [1, 2])
@pytest.mark.parametrize("program", ["chunk256", "decode_vmap8"])
def test_the_cached_layer_is_the_full_capacity_layer_and_the_reference(program, top_k,
                                                                       norm_topk_prob):
    """A 256-token chunk (sorted tiles), then one token a slot under
    ``jax.vmap`` over 8 slots (the batched product), against
    ``moe_mlp_apply(capacity_factor=E)`` and the plain reference."""
    cfg = MixtralConfig.tiny_moe(hidden_size=32, intermediate_size=48, num_experts=8,
                                 top_k=top_k, norm_topk_prob=norm_topk_prob)
    layer = MixtralSparseMLP(cfg)
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 256, 32))
    params = layer.init(jax.random.PRNGKey(6), x[:, :4])["params"]

    def cached(x):
        (out, aux), sown = layer.apply({"params": params}, x, cached=True, mutable=["moe_stats"])
        return out, sown["moe_stats"]["picks"][0], aux

    if program == "chunk256":
        got, picks, aux = jax.jit(cached)(x)
        assert picks[-2] == 256 * top_k and picks[-1] >= picks[-2]
    else:
        x = x[:, :8]
        got, picks, aux = jax.jit(jax.vmap(cached))(x.reshape(8, 1, 1, 32))
        got = got.reshape(1, 8, 32)
        assert picks.shape == (8, 8 + 3) and not picks[:, -2:].any()
        assert picks[:, -3].tolist() == [top_k] * 8
    assert not any(np.any(v) for v in aux.values())        # zero router losses under a cache
    normalize = top_k > 1 if norm_topk_prob is None else norm_topk_prob
    full, _ = moe_mlp_apply(params["experts"], params["router"], x, top_k=top_k,
                            capacity_factor=8.0, num_groups=1, normalize_gates=norm_topk_prob)
    want = _dense_reference(params["experts"], params["router"], x[0], top_k, normalize)
    assert float(jnp.abs(got[0] - want).max()) < TOL
    assert float(jnp.abs(got - full).max()) < TOL


def test_the_trainer_path_keeps_its_capacity_and_sows_nothing():
    cfg = MixtralConfig.tiny_moe(hidden_size=32, intermediate_size=48, num_experts=8,
                                 capacity_factor=0.25)
    layer = MixtralSparseMLP(cfg)
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 64, 32))
    params = layer.init(jax.random.PRNGKey(8), x)["params"]
    (out, aux), sown = layer.apply({"params": params}, x, mutable=["moe_stats"])
    assert not jax.tree.leaves(sown)
    assert float(aux["load_balance_loss"]) > 0.0
    want, _ = moe_mlp_apply(params["experts"], params["router"], x, top_k=2,
                            capacity_factor=0.25, num_groups=1)
    assert float(jnp.abs(out - want).max()) == 0.0
    # capacity 8 of 64 x 2 picks: tokens ARE dropped here, and not under a cache
    (kept, _), _ = layer.apply({"params": params}, x, cached=True, mutable=["moe_stats"])
    assert float(jnp.abs(kept - out).max()) > 1e-3


# -- behind the engine --------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_mixtral():
    cfg = MixtralConfig.tiny_moe(use_flash_attention=False, num_experts=8, capacity_factor=8.0)
    model = MixtralForCausalLM(cfg)
    return cfg, model, model.init_params(jax.random.PRNGKey(0))


def _serve(model, params, prompt, new, **kwargs):
    eng = ServingEngine(model, params, max_slots=2, max_len=128, prefill_chunk=64,
                        page_size=16, **kwargs)
    try:
        req = eng.submit(prompt, max_new_tokens=new, ignore_eos=True, block=True)
        assert req.wait(180)
        return list(req.tokens), eng.stats.summary()
    finally:
        eng.shutdown(drain=False)


def test_the_paged_engine_serves_mixtral_through_the_tiles_and_counts_them(tiny_mixtral):
    """A 100-token prompt in two 64-token chunks, then decode: every served
    token's logit lies within TOL of the best of the uncached full forward
    (capacity_factor = E there: nothing dropped on either side), and the
    counters of the sorted tiles come back behind the tokens."""
    cfg, model, params = tiny_mixtral
    prompt, new = 100, 6
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (1, prompt), 1, cfg.vocab_size))
    served, summary = _serve(model, params, ids, new)
    assert len(served) == new
    full = jnp.asarray(np.concatenate([ids[0], served]))[None]
    logits = model.apply({"params": params}, full)[0][0, prompt - 1:-1]
    gaps = logits.max(-1) - logits[jnp.arange(new), jnp.asarray(served)]
    assert float(gaps.max()) < TOL
    # two chunks x 2 layers x 64 tokens x top-2 rows routed, in 32-row tiles
    # (64 x 2 / 8 = 16 rows an expert expected): 8 experts pad at most 31 each
    routed = 2 * 2 * 64 * 2
    assert routed / (routed + 2 * 2 * 8 * 31) <= summary["moe_tile_fill"] <= 1.0
    assert summary["moe_held_pick_share"] == 1.0
    assert summary["moe_load_max_over_mean"] >= 1.0


def test_moe_tile_fill_is_zero_where_no_tile_ran(tiny_mixtral):
    """A prompt of one 8-token chunk never leaves the batched product."""
    cfg, model, params = tiny_mixtral
    eng = ServingEngine(model, params, max_slots=2, max_len=32, prefill_chunk=8,
                        page_size=8)
    try:
        req = eng.submit(np.arange(1, 6, dtype=np.int32)[None], max_new_tokens=3,
                         ignore_eos=True, block=True)
        assert req.wait(120)
        summary = eng.stats.summary()
        eng.stats.reset()
        assert eng.stats.summary()["moe_held_pick_share"] == 0.0
    finally:
        eng.shutdown(drain=False)
    assert summary["moe_tile_fill"] == 0.0 and summary["moe_held_pick_share"] == 1.0


@pytest.mark.skipif(jax.device_count() < 2, reason="a tp=2 slice needs two devices")
def test_a_tp2_slice_serves_mixtral_token_for_token(tiny_mixtral):
    """Under a mesh the stacks are sharded over their widths, the loop over
    tiles still indexes the expert axis, and the tokens are the single
    chip's."""
    cfg, model, params = tiny_mixtral
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(2), (1, 70), 1, cfg.vocab_size))
    one, _ = _serve(model, params, ids, 5)
    two, summary = _serve(model, params, ids, 5, tp=2)
    assert two == one
    assert 0.0 < summary["moe_tile_fill"] <= 1.0


def test_the_config_has_no_serving_knob():
    fields = {f.name for f in dataclasses.fields(MixtralConfig)}
    assert "capacity_factor" in fields                     # the trainer's
    assert not {"no_drop", "tile_rows", "held_tile_rows"} & fields
    assert not hasattr(moe, "HELD_TILE_ROWS")
