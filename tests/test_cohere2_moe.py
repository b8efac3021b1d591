"""The cohere2_moe family against its plain reference (CPU, tiny: hidden 64,
8 experts top-2, 2 shared, 4 layers = one period of three sliding layers and
one full layer, window 8, seeded random weights).

Tolerances. Everything here is float32 on the CPU, where a matmul is exact
float32 at any precision setting; the model and the reference order their
sums differently (grouped einsums, gathers, a mean over experts), which moves
a logit of magnitude ~4 by a few 1e-6. ``TOL`` = 5e-5 leaves ten times that;
a bfloat16 rounding of any operand moves the same logits by ~1e-2 and fails.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.models.cohere2_moe import (Cohere2MoeConfig, Cohere2MoeForCausalLM,
                                                apply_rotary_interleaved)
from accelerate_tpu.models.reference import cohere2_moe as ref
from accelerate_tpu.ops.moe import moe_held_apply, moe_mlp_apply, route_top_k
from accelerate_tpu.serving import ServingEngine
from accelerate_tpu.serving.metrics import ServingStats

TOL = 5e-5
WINDOW = 8


@pytest.fixture(scope="module")
def tiny():
    cfg = Cohere2MoeConfig.tiny()
    assert cfg.layer_types == ("sliding_attention",) * 3 + ("full_attention",)
    assert cfg.sliding_window == WINDOW
    model = Cohere2MoeForCausalLM(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    return cfg, model, params


def ids_of(n, seed=1, vocab=256):
    return jax.random.randint(jax.random.PRNGKey(seed), (n,), 1, vocab)


def share_of(params, cfg, first, count):
    """The model and parameters of the rank that holds experts
    ``first .. first + count - 1`` (everything else is held by every rank)."""
    cut = jax.tree.map(lambda a: a, params)
    for i in range(cfg.num_hidden_layers):
        experts = params[f"layers_{i}"]["mlp"]["experts"]
        cut[f"layers_{i}"]["mlp"]["experts"] = {
            n: w[first:first + count] for n, w in experts.items()}
    return dataclasses.replace(cfg, held_experts=(first, count)), cut


# -- (a) the full forward pass ------------------------------------------------

def test_full_forward_logits_agree_with_the_reference(tiny):
    cfg, model, params = tiny
    ids = ids_of(5 * WINDOW)                       # well past the window
    logits = model.apply({"params": params}, ids[None])[0]
    want = ref.forward(params, ids, cfg)
    assert logits.dtype == jnp.float32 and logits.shape == (5 * WINDOW, cfg.vocab_size)
    assert float(jnp.abs(logits - want).max()) < TOL


def test_a_held_share_forward_agrees_with_the_reference_given_the_same_share(tiny):
    cfg, model, params = tiny
    cfg_h, params_h = share_of(params, cfg, 2, 2)
    ids = ids_of(3 * WINDOW, seed=2)
    logits = Cohere2MoeForCausalLM(cfg_h).apply({"params": params_h}, ids[None])[0]
    want = ref.forward(params_h, ids, cfg_h, held=(2, 2))
    assert float(jnp.abs(logits - want).max()) < TOL
    # and the share is not the whole: the absent experts' part is left out
    assert float(jnp.abs(want - ref.forward(params, ids, cfg)).max()) > 1e-2


# -- (b) the shares add up ----------------------------------------------------

@pytest.mark.parametrize("tokens", [5, 40], ids=["dense_path", "sorted_path"])
def test_the_shares_add_up_to_the_uncut_layer(tiny, tokens):
    """Four ranks of two experts each: their routed parts summed, with
    attention and the shared experts counted once, are the uncut layer."""
    cfg, _, params = tiny
    layer = params["layers_1"]
    x = jax.random.normal(jax.random.PRNGKey(3), (tokens, cfg.hidden_size))
    positions = jnp.arange(tokens)
    attn, routed_all, shared = ref.layer_parts(x, layer, cfg, 1, positions)
    normed = ref.layer_norm(x, layer["input_norm"]["scale"], cfg.layer_norm_eps)
    total = jnp.zeros_like(x)
    for first in range(0, cfg.num_experts, 2):
        held = {n: w[first:first + 2] for n, w in layer["mlp"]["experts"].items()}
        part, stats = moe_held_apply(
            held, layer["mlp"]["router"], normed[None], top_k=cfg.num_experts_per_tok,
            scores="sigmoid", held=(first, 2))
        assert int(stats["picks"][-3]) == tokens * cfg.num_experts_per_tok
        # the reference given the same share agrees part by part
        want = ref.routed_part(normed, dict(layer["mlp"], experts=held), cfg, (first, 2))
        assert float(jnp.abs(part[0] - want).max()) < TOL
        total = total + part[0]
    assert float(jnp.abs(total - routed_all).max()) < TOL
    uncut = x + attn + routed_all + shared
    assert float(jnp.abs((x + attn + total + shared) - uncut).max()) < TOL


# -- (c) sigmoid top-k, renormalised; nothing dropped -------------------------

def test_sigmoid_top_k_renormalises_over_the_picks():
    logits = jnp.array([[2.0, -1.0, 0.5, 3.0], [0.0, 0.0, 1.0, -2.0]])
    gates, experts = route_top_k(logits, 2, scores="sigmoid", normalize_gates=True)
    s = 1 / (1 + np.exp(-np.asarray(logits)))
    assert experts.tolist() == [[3, 0], [2, 0]]
    np.testing.assert_allclose(gates[0], [s[0, 3], s[0, 0]] / (s[0, 3] + s[0, 0]), rtol=1e-6)
    np.testing.assert_allclose(gates.sum(-1), 1.0, rtol=1e-6)
    raw, _ = route_top_k(logits, 2, scores="sigmoid", normalize_gates=False)
    np.testing.assert_allclose(raw[1], [s[1, 2], s[1, 0]], rtol=1e-6)
    with pytest.raises(ValueError):
        route_top_k(logits, 2, scores="tanh")


@pytest.mark.parametrize("tokens", [6, 70], ids=["dense_path", "sorted_path"])
def test_no_token_is_dropped_when_every_token_picks_the_same_expert(tokens):
    d, f, e = 16, 24, 8
    keys = jax.random.split(jax.random.PRNGKey(4), 4)
    experts = {"gate_proj": jax.random.normal(keys[0], (e, d, f)) * d ** -0.5,
               "up_proj": jax.random.normal(keys[1], (e, d, f)) * d ** -0.5,
               "down_proj": jax.random.normal(keys[2], (e, f, d)) * f ** -0.5}
    x = jnp.abs(jax.random.normal(keys[3], (1, tokens, d))) + 0.1      # all positive
    router = jnp.zeros((d, e)).at[:, 5].set(1.0).at[:, 2].set(0.5)     # all pick 5, then 2
    out, stats = moe_held_apply(experts, router, x, top_k=2, scores="sigmoid")
    # per expert, all picks, then the sorted path's rows routed and computed:
    # 70 tokens, top-2 of 8 -> 64-row tiles, and 70 rows of an expert are two
    sorted_rows = [0, 0] if tokens <= 32 else [2 * tokens, 2 * 2 * 64]
    assert stats["picks"].tolist() == [0, 0, tokens, 0, 0, tokens, 0, 0, 2 * tokens] + sorted_rows
    gates, _ = route_top_k(x[0] @ router, 2, scores="sigmoid")
    want = sum(gates[:, j, None] * ref.swiglu(x[0], experts["gate_proj"][i],
                                              experts["up_proj"][i], experts["down_proj"][i])
               for j, i in enumerate((5, 2)))
    assert float(jnp.abs(out[0] - want).max()) < TOL
    assert bool(jnp.all(jnp.any(out[0] != 0, -1)))              # every token got its experts


# -- (d) prefill in chunks, then decode, past the window ----------------------

def test_chunked_prefill_then_decode_through_the_cache_gives_the_reference_logits(tiny):
    """The cache path itself (what the engine's programs run): a linear
    full-length cache as the paged view is, chunks of 8, then single tokens."""
    cfg, model, params = tiny
    n, chunk, prompt = 4 * WINDOW + 6, 8, 3 * WINDOW
    ids = ids_of(n, seed=5)
    want = ref.forward(params, ids, cfg)
    shape = (1, n, cfg.num_key_value_heads, cfg.head_dim)
    cache = tuple({"k": jnp.zeros(shape), "v": jnp.zeros(shape)}
                  for _ in range(cfg.num_hidden_layers))
    got = []
    for start in list(range(0, prompt, chunk)) + list(range(prompt, n)):
        stop = start + chunk if start < prompt else start + 1
        logits, cache = model.apply({"params": params}, ids[None, start:stop], cache=cache,
                                    cache_pos=jnp.int32(start))
        got.append(logits[0])
    assert float(jnp.abs(jnp.concatenate(got) - want).max()) < TOL


def test_the_paged_engine_serves_a_stream_of_four_windows(tiny):
    """Prefill in chunks, then decode, through ``ServingEngine``
    with both layer kinds past the window. Compared in logits, not tokens:
    every served token's reference logit lies within TOL of the reference's
    best at its position (a served token that differs only by a near-tie
    passes; one computed from a wrong cache does not)."""
    cfg, model, params = tiny
    prompt, new = 3 * WINDOW + 3, WINDOW + 5                      # ends past 4 windows
    ids = np.asarray(ids_of(prompt, seed=6))[None]
    eng = ServingEngine(model, params, max_slots=2, max_len=64, prefill_chunk=8,
                        page_size=8)
    try:
        assert eng._page_window is None            # a mixed stack keeps every page
        assert eng._layer_windows == [WINDOW, WINDOW, WINDOW, None]
        req = eng.submit(ids, max_new_tokens=new, ignore_eos=True, block=True)
        assert req.wait(120)
        served = list(req.tokens)
        summary = eng.stats.summary()
    finally:
        eng.shutdown(drain=False)
    assert len(served) == new
    full = jnp.asarray(np.concatenate([ids[0], served]))
    logits = ref.forward(params, full, cfg)[prompt - 1:-1]       # position t scores token t+1
    gaps = logits.max(-1) - logits[jnp.arange(new), jnp.asarray(served)]
    assert float(gaps.max()) < TOL
    # (g) on the way: the counters of this one stream, by hand. After the tick
    # that commits token j (j = 2..new-1; the last tick retires the stream)
    # the stream holds pos = prompt + j - 1 rows in each of 4 layers, and in
    # each of the 3 sliding layers rows 0 .. pos - WINDOW are dead.
    pos = [prompt + j - 1 for j in range(2, new)]
    dead = sum(3 * (p - WINDOW + 1) for p in pos)
    assert summary["kv_dead_rows_share"] == pytest.approx(dead / sum(4 * p for p in pos), abs=1e-6)
    assert summary["moe_held_pick_share"] == 1.0    # all eight experts are held here
    assert summary["moe_load_max_over_mean"] >= 1.0


# -- (e) sliding layers rotate, full layers do not ----------------------------

def test_interleaved_rotary_pairs_neighbours():
    x = jnp.arange(8.0).reshape(1, 1, 1, 8)
    angle = jnp.full((1, 1, 4), jnp.pi / 2)
    out = apply_rotary_interleaved(x, jnp.cos(angle), jnp.sin(angle))
    np.testing.assert_allclose(out[0, 0, 0], [-1, 0, -3, 2, -5, 4, -7, 6], atol=1e-6)


@pytest.mark.parametrize("layer_idx,moves", [(0, True), (3, False)], ids=["sliding", "full"])
def test_moving_all_positions_changes_a_sliding_layer_only(tiny, layer_idx, moves):
    """Attention on its own: rotary is applied (or not) to raw positions, so
    a shift of every position by a constant leaves a layer without
    positional encoding unchanged. (A sliding layer's scores depend on
    differences only, so its OUTPUT is unchanged too — what changes is its
    keys, which is what a cache holds.)"""
    cfg, model, params = tiny
    x = jax.random.normal(jax.random.PRNGKey(7), (12, cfg.hidden_size))
    p = params[f"layers_{layer_idx}"]["self_attn"]
    window = cfg.window_for(layer_idx)
    a0 = ref.attention(x, p, cfg, window, jnp.arange(12))
    a1 = ref.attention(x, p, cfg, window, jnp.arange(12) + 1000)
    assert float(jnp.abs(a0 - a1).max()) < 1e-3    # relative positions only, either way
    from accelerate_tpu.models.cohere2_moe import Cohere2Attention

    def keys_at(start):
        shape = (1, 12, cfg.num_key_value_heads, cfg.head_dim)
        cache = {"k": jnp.zeros(shape), "v": jnp.zeros(shape)}
        positions = (start + jnp.arange(12))[None]
        _, new = Cohere2Attention(cfg, layer_idx).apply(
            {"params": p}, x[None], positions, cache=cache, cache_pos=jnp.int32(0))
        return new["k"]

    changed = float(jnp.abs(keys_at(0) - keys_at(1000)).max())
    assert (changed > 1e-2) if moves else (changed == 0.0)


# -- (f) held = all, softmax: the Mixtral layer -------------------------------

@pytest.mark.parametrize("tokens", [7, 90], ids=["dense_path", "sorted_path"])
def test_held_all_with_softmax_is_moe_mlp_apply_at_full_capacity(tokens):
    d, f, e, k = 32, 48, 8, 2
    keys = jax.random.split(jax.random.PRNGKey(8), 5)
    experts = {"gate_proj": jax.random.normal(keys[0], (e, d, f)) * d ** -0.5,
               "up_proj": jax.random.normal(keys[1], (e, d, f)) * d ** -0.5,
               "down_proj": jax.random.normal(keys[2], (e, f, d)) * f ** -0.5}
    router = jax.random.normal(keys[3], (d, e))
    x = jax.random.normal(keys[4], (1, tokens, d))
    want, _ = moe_mlp_apply(experts, router, x, top_k=k, capacity_factor=float(e), num_groups=1)
    got, stats = jax.jit(lambda p, r, x: moe_held_apply(p, r, x, top_k=k))(
        experts, router, x)
    assert float(jnp.abs(got - want).max()) < TOL
    assert int(stats["picks"][:-3].sum()) == int(stats["picks"][-3]) == tokens * k


def test_the_decode_vmap_of_a_one_token_call_is_the_calls_one_by_one():
    d, f, e = 16, 24, 8
    keys = jax.random.split(jax.random.PRNGKey(9), 5)
    experts = {"gate_proj": jax.random.normal(keys[0], (4, d, f)),
               "up_proj": jax.random.normal(keys[1], (4, d, f)),
               "down_proj": jax.random.normal(keys[2], (4, f, d))}
    router = jax.random.normal(keys[3], (d, e))
    xs = jax.random.normal(keys[4], (6, 1, 1, d))
    call = lambda x: moe_held_apply(experts, router, x, top_k=2, scores="sigmoid", held=(2, 4))  # noqa: E731
    outs, stats = jax.vmap(call)(xs)
    for i in range(6):
        one, st = call(xs[i])
        assert float(jnp.abs(outs[i] - one).max()) < TOL
        assert stats["picks"][i].tolist() == st["picks"].tolist()


def test_held_must_match_the_stacks():
    experts = {n: jnp.zeros((4, 8, 8)) for n in ("gate_proj", "up_proj", "down_proj")}
    with pytest.raises(ValueError):
        moe_held_apply(experts, jnp.zeros((8, 16)), jnp.zeros((1, 2, 8)), top_k=2, held=(14, 4))
    with pytest.raises(ValueError):
        moe_held_apply(experts, jnp.zeros((8, 16)), jnp.zeros((1, 2, 8)), top_k=2)


# -- (g) the counters ---------------------------------------------------------

def test_the_counters_appear_merge_and_reset():
    a, b = ServingStats(), ServingStats()
    keys = ("moe_held_pick_share", "moe_load_max_over_mean", "moe_tile_fill", "kv_dead_rows_share",
            "prefill_attn_rows_share", "prefill_attn_rows_fill")
    for key in keys:
        assert a.summary()[key] == 0.0
    # per held expert, all picks, rows routed and rows computed in sorted tiles
    a.record_tick(2, 2, 4, 0.01, moe_picks=np.array([3, 1, 32, 0, 0]), kv_rows=(10, 100))
    assert a.summary()["moe_tile_fill"] == 0.0      # ticks alone: nothing went through tiles
    a.record_prefill_chunk(1.0, moe_picks=np.array([1, 3, 32, 4, 64]), attn_rows=(16, 12, 64))
    b.record_tick(2, 2, 4, 0.01, moe_picks=[4, 0, 32, 0, 0], kv_rows=(30, 100))
    b.record_prefill_chunk(1.0, moe_picks=[0, 0, 0, 20, 32], attn_rows=(48, 40, 64))
    s = a.summary()
    assert s["moe_held_pick_share"] == pytest.approx(8 / 64)
    assert s["moe_load_max_over_mean"] == pytest.approx(1.0)
    assert s["moe_tile_fill"] == pytest.approx(4 / 64)
    assert s["kv_dead_rows_share"] == pytest.approx(0.1)
    assert (s["prefill_attn_rows_share"], s["prefill_attn_rows_fill"]) == (0.25, 0.75)
    m = ServingStats().merge(a).merge(b).summary()
    assert m["moe_held_pick_share"] == pytest.approx(12 / 96)
    assert m["moe_load_max_over_mean"] == pytest.approx(8 / 6, abs=1e-4)
    assert m["moe_tile_fill"] == pytest.approx(24 / 96)
    assert m["kv_dead_rows_share"] == pytest.approx(0.2)
    assert m["prefill_attn_rows_share"] == 0.5 and m["prefill_attn_rows_fill"] == pytest.approx(52 / 64)
    a.reset()
    assert all(a.summary()[key] == 0.0 for key in keys)


def test_a_held_share_behind_the_engine_counts_its_picks_and_no_dead_rows_below_the_window(tiny):
    cfg, _, params = tiny
    cfg_h, params_h = share_of(params, cfg, 0, 2)                 # 2 of 8 experts
    eng = ServingEngine(Cohere2MoeForCausalLM(cfg_h), params_h, max_slots=2, max_len=32,
                        prefill_chunk=4, page_size=4)
    try:
        req = eng.submit(np.asarray(ids_of(3, seed=10))[None], max_new_tokens=4,
                         ignore_eos=True, block=True)             # ends at 7 < window
        assert req.wait(120)
        s = eng.stats.summary()
    finally:
        eng.shutdown(drain=False)
    assert s["kv_dead_rows_share"] == 0.0
    assert 0.0 <= s["moe_held_pick_share"] < 1.0
    picks = eng.stats._moe_picks[:3]            # [on expert 0, on expert 1, all picks]
    assert len(eng.stats._moe_picks) == 5 and not eng.stats._moe_picks[3:].any()   # no tiles ran
    # one chunk of 4 positions + at least 3 decode ticks (one more if a tick
    # was dispatched ahead of the retirement), each through 4 layers, top-2
    assert len(picks) == 3 and picks[-1] % (4 * 2) == 0 and picks[-1] >= (4 + 3) * 4 * 2
    assert sum(picks[:-1]) <= picks[-1]


# -- (h) a chunk's attention over the key blocks its queries can see ----------

BLOCK = 8


@pytest.fixture(scope="module")
def served_in_blocks(tiny):
    """One engine whose chunk program scores BLOCK key rows at a time: the
    shape rule (``models.llama.cached_key_block``: 64 MiB of scores a block)
    gives one block at any toy size, so the test stands in for it — several
    blocks for a multi-token call, one for a tick's single token, as on the
    chip. Offline ``generate`` runs before, under the real rule."""
    from accelerate_tpu import generation
    from accelerate_tpu.models import llama
    from accelerate_tpu.utils.profiling import CompileWatcher

    cfg, model, params = tiny
    prompts = {"below_the_window": 5, "three_chunks": 2 * WINDOW + 3, "past_the_window": 3 * WINDOW + 3}
    new = WINDOW + 5
    ids = {name: np.asarray(ids_of(n, seed=20 + n))[None] for name, n in prompts.items()}
    offline = {name: np.asarray(generation.generate(model, params, jnp.asarray(p),
                                                    max_new_tokens=new))[0, p.shape[1]:]
               for name, p in ids.items()}
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(llama, "cached_key_block",
                      lambda rows, view: min(BLOCK, view) if rows > cfg.num_attention_heads else view)
        eng = ServingEngine(model, params, max_slots=2, max_len=64, prefill_chunk=8, page_size=8)
        try:
            served, summaries = {}, {}
            with CompileWatcher() as watcher:
                for name, p in ids.items():
                    eng.stats.reset()
                    req = eng.submit(p, max_new_tokens=new, ignore_eos=True, block=True)
                    assert req.wait(180)
                    served[name], summaries[name] = np.asarray(req.tokens), eng.stats.summary()
            chunk_text = eng._prefill_chunk.lower(*_chunk_args(eng, ids["below_the_window"])).as_text()
            return {"served": served, "offline": offline, "summaries": summaries,
                    "compiles": list(watcher.events), "chunk_text": chunk_text,
                    "chunk_executables": eng._prefill_chunk._cache_size(),
                    "decode_executables": eng._decode._cache_size()}
        finally:
            eng.shutdown(drain=False)


def _chunk_args(eng, ids):
    return (eng.params, eng._state, np.resize(ids, (1, eng._chunk)), np.int32(0),
            eng._table[0].copy(), np.int32(0), np.int32(ids.shape[1]), jax.random.PRNGKey(0))


@pytest.mark.parametrize("prompt", ["below_the_window", "three_chunks", "past_the_window"])
def test_chunks_scored_in_blocks_serve_offline_generates_tokens(served_in_blocks, prompt):
    assert np.array_equal(served_in_blocks["served"][prompt], served_in_blocks["offline"][prompt])


def test_the_chunk_program_loops_over_blocks_and_compiles_once_across_offsets(served_in_blocks):
    assert "while" in served_in_blocks["chunk_text"]
    assert served_in_blocks["compiles"] == []          # offsets 0, 8, 16, 24: one warm program
    assert served_in_blocks["chunk_executables"] == 1 and served_in_blocks["decode_executables"] == 1


def test_prefill_attn_rows_share_and_fill_equal_a_hand_count(served_in_blocks):
    """The 19-token prompt: chunks at offsets 0, 8, 16 against views of 64
    rows; one full layer scores blocks [0, hi), three windowed layers (window
    8) blocks from (offset - 7) // 8. Rows scored 8+3x8, 16+3x16, 24+3x16;
    rows visible 8+3x8, 16+3x15, 24+3x15."""
    s = served_in_blocks["summaries"]["three_chunks"]
    scored, visible = 32 + 64 + 72, 32 + 61 + 69
    assert s["prefill_attn_rows_share"] == pytest.approx(scored / (3 * 4 * 64), abs=1e-6)
    assert s["prefill_attn_rows_fill"] == pytest.approx(visible / scored, abs=1e-6)
    one = served_in_blocks["summaries"]["below_the_window"]
    assert one["prefill_attn_rows_share"] == pytest.approx(8 / 64, abs=1e-6)
    assert one["prefill_attn_rows_fill"] == 1.0


def test_a_whole_view_chunk_reads_a_share_of_one(tiny):
    """Under the real rule a toy chunk is one block: share 1.0 by construction."""
    cfg, model, params = tiny
    eng = ServingEngine(model, params, max_slots=2, max_len=32, prefill_chunk=8, page_size=8)
    try:
        eng.stats.reset()
        req = eng.submit(np.asarray(ids_of(11, seed=31))[None], max_new_tokens=2,
                         ignore_eos=True, block=True)
        assert req.wait(120)
        s = eng.stats.summary()
    finally:
        eng.shutdown(drain=False)
    assert s["prefill_attn_rows_share"] == 1.0
    # offsets 0 and 8 of a 32-row view: a full layer sees 8 and 16 rows, a windowed one 8 and 15
    assert s["prefill_attn_rows_fill"] == pytest.approx((4 * 8 + 16 + 3 * 15) / (2 * 4 * 32), abs=1e-6)


# -- (i) the served form: what the engine holds instead of the published q/k --

def _kernels(params, cfg, which):
    return [params[f"layers_{i}"]["self_attn"][which]["kernel"] for i in range(cfg.num_hidden_layers)]


def test_the_served_forms_logits_are_the_published_forms_past_the_window(tiny):
    """Through the modules alone: the same products over the same weights, a
    head's columns in another order (q and k alike) and the q kernel turned."""
    cfg, model, params = tiny
    form = model.served_form()
    ids = ids_of(5 * WINDOW, seed=40)[None]
    want = model.apply({"params": params}, ids)
    got = form.module.apply({"params": form.to_served(params)}, ids)
    assert float(jnp.abs(got - want).max()) < TOL
    assert float(jnp.abs(want - ref.forward(params, ids[0], cfg)).max()) < TOL
    # the served tree is the served module's own: what its init gives, shape for shape
    own = jax.eval_shape(lambda: form.module.init_params(jax.random.PRNGKey(0)))
    assert jax.tree.map(lambda a, b: a.shape == b.shape and a.dtype == b.dtype,
                        form.to_served(params), own) == jax.tree.map(lambda _: True, own)
    assert form.module.served_form() is None           # already in that form: nothing to do


def test_to_served_then_to_published_is_the_tree_bit_for_bit_and_moves_q_and_k_alone(tiny):
    cfg, model, params = tiny
    form = model.served_form()
    served = form.to_served(params)
    moved = {jax.tree_util.keystr(path) for (path, a), b in
             zip(jax.tree_util.tree_flatten_with_path(params)[0], jax.tree.leaves(served)) if a is not b}
    # every q kernel (turned), the k kernels of the three sliding layers (their pairs)
    assert moved == ({f"['layers_{i}']['self_attn']['q_proj']['kernel']" for i in range(4)}
                     | {f"['layers_{i}']['self_attn']['k_proj']['kernel']" for i in range(3)})
    back = form.to_published(served)
    assert jax.tree.structure(back) == jax.tree.structure(params)
    assert all(bool((a == b).all()) for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(back)))
    q, k = _kernels(params, cfg, "q_proj")[0], _kernels(params, cfg, "k_proj")[0]
    sq, sk = _kernels(served, cfg, "q_proj")[0], _kernels(served, cfg, "k_proj")[0]
    d = cfg.head_dim
    assert sq.shape == q.shape[::-1] and sk.shape == k.shape
    # head 1, pair 3: the published columns (d + 6, d + 7) lie at (d + 3, d + 3 + d / 2)
    np.testing.assert_array_equal(sq[d + 3], q[:, d + 6])
    np.testing.assert_array_equal(sq[d + 3 + d // 2], q[:, d + 7])
    np.testing.assert_array_equal(sk[:, d + 3 + d // 2], k[:, d + 7])
    np.testing.assert_array_equal(_kernels(served, cfg, "q_proj")[3], _kernels(params, cfg, "q_proj")[3].T)


def _same_buffer(a, b):
    """Placement hands back a new array object over the buffer that was there."""
    return a.unsafe_buffer_pointer() == b.unsafe_buffer_pointer()


def _serve(model, params, ids, new, **kw):
    eng = ServingEngine(model, params, max_slots=2, max_len=64, prefill_chunk=8, page_size=8, **kw)
    try:
        req = eng.submit(ids, max_new_tokens=new, ignore_eos=True, block=True)
        assert req.wait(300)
        return np.asarray(req.tokens), eng.stats.summary(), eng
    finally:
        eng.shutdown(drain=False)


HOLDERS = {
    "plain": {},
    "tp2": {"tp": 2},                          # the kernels sharded over a slice of two
    "int8_weights": {"weights_dtype": "int8"},
    "int8_pages": {"kv_dtype": "int8"},        # the K pages hold permuted keys
    "lookup": {"spec_lookup": 2, "spec_tokens": 2},
    "draft": "a draft of the same family",
}


@pytest.mark.parametrize("holder", sorted(HOLDERS))
def test_an_engine_given_the_published_tree_serves_generates_tokens(tiny, holder):
    """Chunks, then ticks (or verifies), past the window, against offline
    ``generate`` on the published form; with whatever else holds q/k columns."""
    from accelerate_tpu.generation import generate
    from accelerate_tpu.utils.quantization import dequantize_params

    cfg, model, params = tiny
    kw = HOLDERS[holder]
    if holder == "draft":           # a draft's cache has to be linear: no window inside max_len
        cfg = dataclasses.replace(cfg, sliding_window=128)
        model = Cohere2MoeForCausalLM(cfg)
        kw = {"draft_model": model, "draft_params": params, "spec_tokens": 2}
    prompt, new = 3 * WINDOW + 3, WINDOW + 5
    ids = np.asarray(ids_of(prompt, seed=41))[None]
    served, summary, eng = _serve(model, params, ids, new, **kw)
    reference = params
    if holder == "int8_weights":    # the same ints and the same per-column scales, moved together
        from accelerate_tpu.adapters.quantize import quantize_base_weights
        quantized = quantize_base_weights(params)
        back = model.served_form().to_published(eng.params)
        assert all(bool((a == b).all()) for a, b in zip(jax.tree.leaves(quantized), jax.tree.leaves(back)))
        reference = dequantize_params(quantized, jnp.float32)
    if holder == "int8_pages":      # rounded rows: near-ties may flip, as for any family
        logits = ref.forward(params, jnp.asarray(np.concatenate([ids[0], served])), cfg)[prompt - 1:-1]
        assert float((logits.max(-1) - logits[jnp.arange(new), jnp.asarray(served)]).max()) < 0.05
    else:
        offline = np.asarray(generate(model, reference, jnp.asarray(ids), max_new_tokens=new))[0, prompt:]
        np.testing.assert_array_equal(served, offline)
    # what an engine hands back out: its tree, through the inverse, is the published one
    if holder in ("plain", "tp2"):
        back = model.served_form().to_published(eng.params)
        assert all(bool((np.asarray(a) == np.asarray(b)).all())
                   for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(back)))
        assert eng.module.served and not model.served
    q, k = _kernels(params, cfg, "q_proj"), _kernels(params, cfg, "k_proj")
    moved = q + [k[i] for i in range(cfg.num_hidden_layers) if cfg.window_for(i) is not None]
    twice = 2 if holder == "draft" else 1
    if holder == "int8_weights":    # one byte a weight and a float32 scale a column
        assert summary["weights_served_form_bytes"] == sum(a.size + 4 * a.shape[1] for a in moved)
        assert summary["weights_served_form_leaves"] == 2 * len(moved)
    else:
        assert summary["weights_served_form_bytes"] == twice * sum(a.nbytes for a in moved)
        assert summary["weights_served_form_leaves"] == twice * len(moved)


def test_an_adapter_bank_is_refused_by_name_and_a_family_without_the_hook_moves_nothing(tiny):
    from accelerate_tpu.adapters import AdapterBank, LoRAConfig
    from accelerate_tpu.models.mixtral import MixtralConfig, MixtralForCausalLM

    cfg, model, params = tiny
    bank = AdapterBank(params, config=LoRAConfig(rank=2, target_modules=("q_proj", "k_proj")),
                       max_adapters=2)
    with pytest.raises(NotImplementedError, match="AdapterBank.*q_proj / k_proj.*published"):
        ServingEngine(model, params, max_slots=2, max_len=32, prefill_chunk=8, adapters=bank,
                      autostart=False, warmup=False)
    mixtral = MixtralForCausalLM(MixtralConfig.tiny_moe())
    mparams = mixtral.init_params(jax.random.PRNGKey(0))
    _, summary, eng = _serve(mixtral, mparams, np.asarray(ids_of(11, seed=42))[None], 3)
    assert (summary["weights_served_form_bytes"], summary["weights_served_form_leaves"]) == (0, 0)
    assert eng.module is mixtral
    assert all(_same_buffer(a, b) for a, b in zip(jax.tree.leaves(mparams), jax.tree.leaves(eng.params)))


def test_a_second_engine_from_the_same_tree_finds_the_first_ones_served_leaves(tiny):
    """A fleet's factory hands one tree to every engine it builds (replicas,
    restarts); on an accelerator the first engine donated the leaves it moved."""
    from accelerate_tpu.serving import engine as engine_mod

    cfg, model, params = tiny
    tree = jax.tree.map(jnp.copy, params)
    keys = [id(a) for a in _kernels(tree, cfg, "q_proj") + _kernels(tree, cfg, "k_proj")[:3]]
    build = lambda: ServingEngine(model, tree, max_slots=2, max_len=32, prefill_chunk=8,
                                  autostart=False, warmup=False)
    first, second = build(), build()
    try:
        a, b = _kernels(first.params, cfg, "q_proj"), _kernels(second.params, cfg, "q_proj")
        assert all(_same_buffer(x, y) for x, y in zip(a, b))
        assert all(key in engine_mod._SERVED_TWINS for key in keys)
    finally:
        first.shutdown(drain=False)
        second.shutdown(drain=False)
    del tree, first, second, a, b
    import gc
    gc.collect()
    assert not any(key in engine_mod._SERVED_TWINS for key in keys)      # gone with the published leaves
