"""The glm_moe_dsa family against its plain reference (CPU, tiny: hidden 64,
4 heads over a latent of 16 values and a rotary key of 4, indexers of 4
heads of 8 picking ``index_topk`` = 8 rows, five layers — one dense, four
with 8 experts top-2 — whose indexers are [full, shared, shared, full,
shared]; seeded random weights and a seeded selection bias).

Tolerances. Everything here is float32 on the CPU, where a matmul is exact
float32 at any precision setting; the model and the reference order their
sums differently (the absorbed form folds ``kv_b_proj`` into the query, the
reference expands every head's key and value; the program's index scores run
in key blocks), which moves a logit of magnitude ~4 by a few 1e-6. ``TOL`` =
5e-5 leaves ten times that. Selection is discrete: with contexts of 20 to 40
rows and 8 picked, one row picked wrongly moves logits by far more than
``TOL`` (the planted faults below), so agreement within ``TOL`` is agreement
of every pick.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.models import llama
from accelerate_tpu.models.glm_moe_dsa import (GlmBlock, GlmMoeDsaConfig, GlmMoeDsaForCausalLM,
                                               published_indexer_types)
from accelerate_tpu.models.reference import glm_moe_dsa as ref
from accelerate_tpu.serving import ServingEngine

TOL = 5e-5


def seeded_bias(params, cfg, seed=10):
    """A correction bias of std 1 / experts, so that it moves selections (a
    trained model's is set by a controller; a fresh module's is zero)."""
    for i, kind in enumerate(cfg.mlp_layer_types):
        if kind == "sparse":
            params[f"layers_{i}"]["mlp"]["e_score_correction_bias"] = jax.random.normal(
                jax.random.PRNGKey(seed + i), (cfg.num_experts,)) / cfg.num_experts
    return params


@pytest.fixture(scope="module")
def tiny():
    cfg = GlmMoeDsaConfig.tiny()
    model = GlmMoeDsaForCausalLM(cfg)
    return cfg, model, seeded_bias(model.init_params(jax.random.PRNGKey(0)), cfg)


def ids_of(n, seed=1, vocab=256):
    return jax.random.randint(jax.random.PRNGKey(seed), (n,), 1, vocab)


def through_the_cache(model, params, ids, chunk, prompt):
    """Logits of ``ids`` served as the engine's programs serve them: a linear
    full-length cache, chunks of ``chunk`` up to ``prompt``, then single
    tokens."""
    n = ids.shape[0]
    cache = model.init_cache(1, n, jnp.float32)
    got = []
    for start in list(range(0, prompt, chunk)) + list(range(prompt, n)):
        stop = start + chunk if start < prompt else start + 1
        logits, cache = model.apply({"params": params}, ids[None, start:stop], cache=cache,
                                    cache_pos=jnp.int32(start))
        got.append(logits[0])
    return jnp.concatenate(got), cache


def selections_of(model, params, ids):
    """Every layer's row mask ``[T, T]`` as the program picks it, layer by layer."""
    cfg = model.config
    x = params["embed_tokens"]["embedding"][ids][None]
    positions, selection, masks = jnp.arange(ids.shape[0])[None], None, []
    for i in range(cfg.num_hidden_layers):
        x, _, selection = GlmBlock(cfg, layer_idx=i).apply(
            {"params": params[f"layers_{i}"]}, x, positions, selection=selection)
        masks.append(selection[0])
    return masks


def serve(model, params, prompt_ids, new, **kwargs):
    eng = ServingEngine(model, params, max_slots=2, max_len=64, prefill_chunk=8, page_size=8,
                        cache_dtype=jnp.float32, **kwargs)
    try:
        req = eng.submit(np.asarray(prompt_ids)[None], max_new_tokens=new, ignore_eos=True,
                         block=True)
        assert req.wait(120)
        return list(req.tokens), eng.stats.summary(), eng.page_pool_metrics()
    finally:
        eng.shutdown(drain=False)


def served_gaps(params, cfg, prompt_ids, served):
    """Per served token: the reference's best logit at its position minus its own."""
    full = jnp.concatenate([prompt_ids, jnp.asarray(served)])
    logits = ref.forward(params, full, cfg)[prompt_ids.shape[0] - 1:-1]
    return logits.max(-1) - logits[jnp.arange(len(served)), jnp.asarray(served)]


# -- (a) the whole forward pass and its selections ----------------------------

def test_full_forward_logits_agree_with_the_reference(tiny):
    cfg, model, params = tiny
    ids = ids_of(40)
    logits = model.apply({"params": params}, ids[None])[0]
    assert float(jnp.abs(logits - ref.forward(params, ids, cfg)).max()) < TOL


def test_the_indexers_picks_are_the_references_set_for_set(tiny):
    cfg, model, params = tiny
    ids = ids_of(40, seed=3)
    _, want = ref.forward(params, ids, cfg, selections=True)
    got = selections_of(model, params, ids)
    for i, (mask, expected) in enumerate(zip(got, want)):
        np.testing.assert_array_equal(mask, expected, err_msg=f"layer {i}")
    # selection bites: past its first index_topk rows a query reads index_topk of them
    counts = np.asarray(got[0]).sum(-1)
    assert counts.tolist() == [min(t + 1, cfg.index_topk) for t in range(40)]


def test_a_shared_layer_reads_exactly_its_full_layers_picks(tiny):
    """[full, shared, shared, full, shared]: layers 1 and 2 read layer 0's
    picks, layer 4 reads layer 3's, and the two full layers pick apart."""
    cfg, model, params = tiny
    masks = selections_of(model, params, ids_of(40, seed=4))
    for shared, full in ((1, 0), (2, 0), (4, 3)):
        assert masks[shared] is masks[full] or bool((masks[shared] == masks[full]).all())
    assert not bool((masks[0] == masks[3]).all())
    # a shared layer has no indexer of its own
    assert "indexer" in params["layers_0"]["self_attn"] and "indexer" in params["layers_3"]["self_attn"]
    assert "indexer" not in params["layers_1"]["self_attn"]
    assert "indexer" not in params["layers_4"]["self_attn"]


def test_a_context_no_longer_than_index_topk_is_plain_latent_attention(tiny):
    """Up to ``index_topk`` rows a query reads them all: the logits are those
    of a model whose indexers pick every row (``index_topk`` past the
    context), to the bit."""
    cfg, model, params = tiny
    ids = ids_of(cfg.index_topk, seed=6)
    plain = GlmMoeDsaForCausalLM(dataclasses.replace(cfg, index_topk=1000))
    a = model.apply({"params": params}, ids[None])
    b = plain.apply({"params": params}, ids[None])
    np.testing.assert_array_equal(a, b)
    # ... and past it they part
    ids = ids_of(3 * cfg.index_topk, seed=6)
    assert float(jnp.abs(model.apply({"params": params}, ids[None])
                         - plain.apply({"params": params}, ids[None])).max()) > 100 * TOL


@pytest.mark.parametrize("case", range(6))
def test_the_chunks_row_mask_keeps_what_top_k_picks(case):
    """``top_rows_mask`` finds each query's ``topk``-th score bit by bit and
    keeps what ``jax.lax.top_k`` keeps, ties (the earlier row first), zeros of
    both signs and rows nobody can see included."""
    rng = np.random.default_rng(case)
    L, k = int(rng.integers(20, 300)), int(rng.integers(1, 40))
    x = rng.normal(size=(2, 5, L)).astype(np.float32) * [1e-3, 1.0, 1e4][case % 3]
    if case % 2:
        x = np.round(x)
        x[..., ::3], x[..., 1::7] = 0.0, -0.0
    x = np.where(rng.random(x.shape) < [0.1, 0.5, 1.0][case % 3], x, -np.inf).astype(np.float32)
    scores = jnp.asarray(x)
    ids = jax.lax.top_k(scores, k)[1]
    want = jnp.zeros(x.shape, bool).at[jnp.arange(2)[:, None, None], jnp.arange(5)[None, :, None],
                                       ids].set(True) & (scores > -jnp.inf)
    np.testing.assert_array_equal(llama.top_rows_mask(scores, k), want)


def test_the_published_indexer_types_are_read_from_the_list():
    cfg = GlmMoeDsaConfig()
    assert cfg.indexer_types == published_indexer_types()
    full = [i for i, kind in enumerate(cfg.indexer_types) if kind == "full"]
    assert full == [0, 1, 2] + list(range(6, 78, 4))
    assert cfg.mlp_layer_types.count("dense") == 3 and len(cfg.mlp_layer_types) == 78
    with pytest.raises(ValueError, match="indexer_types\\[0\\]"):
        GlmMoeDsaConfig.tiny(indexer_types=("shared",) * 5)


# -- (b) the held shares add up to the uncut layer ----------------------------

@pytest.mark.parametrize("tokens", [5, 40], ids=["dense_path", "sorted_path"])
def test_the_shares_add_up_to_the_uncut_layer(tiny, tokens):
    """Four ranks of two routed experts each (the deployment's 16 ranks of 16
    of 256): a whole expert layer on each rank, and what every rank computes
    alike — attention with its selection, the norms and the shared expert —
    counted once, add up to what the uncut reference gives for the layer, in
    the program and in the reference."""
    cfg, _, params = tiny

    def share(first, count):
        cut = jax.tree.map(lambda a: a, params["layers_3"])
        cut["mlp"]["experts"] = {n: w[first:first + count]
                                 for n, w in params["layers_3"]["mlp"]["experts"].items()}
        return dataclasses.replace(cfg, held_experts=(first, count)), cut

    x = jax.random.normal(jax.random.PRNGKey(7), (1, tokens, cfg.hidden_size))
    positions = jnp.arange(tokens)
    cfg_0, p_0 = share(0, 0)
    alike = ref.layer(x[0], p_0, cfg_0, 3, positions, held=(0, 0))[0]
    program_sum, ref_sum = -3 * alike, -3 * alike
    for first in range(0, 8, 2):
        cfg_h, p_h = share(first, 2)
        program_sum = program_sum + GlmBlock(cfg_h, layer_idx=3).apply(
            {"params": p_h}, x, positions[None])[0][0]
        ref_sum = ref_sum + ref.layer(x[0], p_h, cfg_h, 3, positions, held=(first, 2))[0]
    whole = ref.layer(x[0], params["layers_3"], cfg, 3, positions)[0]
    assert float(jnp.abs(program_sum - whole).max()) < TOL
    assert float(jnp.abs(ref_sum - whole).max()) < TOL


# -- (c) through the caches ---------------------------------------------------

@pytest.mark.parametrize("key_block", [None, 8], ids=["whole_view", "key_blocks_of_8"])
def test_chunked_prefill_then_decode_through_the_cache_gives_the_reference_logits(
        tiny, key_block, monkeypatch):
    """Chunks score the view's index keys and attend over it in key blocks
    where the view is long (``cached_key_block``): blocks of 8 rows, the
    loops over the visible blocks, give the same picks and logits."""
    cfg, model, params = tiny
    if key_block is not None:
        monkeypatch.setattr(llama, "cached_key_block", lambda rows, view: min(key_block, view))
    ids = ids_of(38, seed=5)
    got, cache = through_the_cache(model, params, ids, chunk=8, prompt=24)
    assert float(jnp.abs(got - ref.forward(params, ids, cfg)).max()) < TOL
    # the full layers' entries hold the index keys beside the latent; the shared ones do not
    assert [sorted(entry) for entry in cache] == [
        ["index_k", "latent", "rope"] if kind == "full" else ["latent", "rope"]
        for kind in cfg.indexer_types]


def test_the_paged_engine_serves_the_reference_logits(tiny):
    """Prefill in chunks, then decode, through ``ServingEngine``'s page pool:
    every served token's reference logit lies within TOL of the reference's
    best at its position; the counters say what the ticks read."""
    cfg, model, params = tiny
    prompt = ids_of(19, seed=8)
    served, summary, pool = serve(model, params, prompt, 12)
    assert float(served_gaps(params, cfg, prompt, served).max()) < TOL
    # 5 layers x (16 + 4) values + 2 full layers x 8 index values, float32
    assert summary["kv_bytes_per_token"] == (5 * 20 + 2 * 8) * 4
    assert pool["page_bytes"] == 8 * summary["kv_bytes_per_token"]
    # 11 ticks at positions 19..29: a query sees pos + 1 rows and reads 8 of them, 5 layers
    visible = sum(range(20, 31))
    assert summary["dsa_visible_rows"] == 5 * visible
    assert summary["dsa_selected_rows"] == 5 * 8 * 11
    assert summary["dsa_selected_share"] == pytest.approx(88 / visible, abs=1e-6)
    # the latent attention scores the selected rows, of 2 slots x 64 view rows x 5 layers
    assert summary["decode_attn_rows_share"] == pytest.approx(5 * 88 / (11 * 5 * 2 * 64),
                                                              abs=1e-6)


def latest_rows(monkeypatch):
    """The selection fault: every query reads its LATEST index_topk rows,
    whatever the indexer scored — in the tick and in the chunk."""
    def paged(cache, q_idx, w_idx, k_idx, cache_pos, topk):
        _, row = select_paged(cache, q_idx, w_idx, k_idx, cache_pos, topk)
        ids = cache_pos - jnp.arange(topk, dtype=jnp.int32)
        return jnp.where(ids < 0, cache_pos + 1 - ids, ids)[None, None], row

    def mask(scores, topk):
        visible = scores > -jnp.inf
        rank = jnp.cumsum(visible[..., ::-1], axis=-1)[..., ::-1]
        return visible & (rank <= topk)

    select_paged = llama._paged_index_select
    monkeypatch.setattr(llama, "_paged_index_select", paged)
    monkeypatch.setattr(llama, "top_rows_mask", mask)


def test_a_planted_latest_rows_selection_fails_the_comparison(tiny, monkeypatch):
    cfg, model, params = tiny
    prompt = ids_of(19, seed=8)
    latest_rows(monkeypatch)
    served, _, _ = serve(model, params, prompt, 12)
    gaps = served_gaps(params, cfg, prompt, served)
    assert float(gaps.max()) > 100 * TOL
    ids = ids_of(38, seed=5)
    got, _ = through_the_cache(model, params, ids, chunk=8, prompt=24)
    assert float(jnp.abs(got - ref.forward(params, ids, cfg)).max()) > 100 * TOL


# -- (d) the declared cache, and what reads it --------------------------------

def test_the_family_declares_index_keys_on_full_layers_only():
    from accelerate_tpu.big_modeling import cache_factory_for

    model = GlmMoeDsaForCausalLM(GlmMoeDsaConfig(
        num_hidden_layers=5, vocab_size=19360, held_experts=(0, 16),
        mlp_layer_types=("dense",) + ("sparse",) * 4,
        indexer_types=("full", "shared", "shared", "shared", "full")))
    cache = jax.eval_shape(lambda: cache_factory_for(model)(2, 32768, jnp.bfloat16))
    assert [sorted(e) for e in cache] == [["index_k", "latent", "rope"]] + [["latent", "rope"]] * 3 \
        + [["index_k", "latent", "rope"]]
    assert cache[0]["index_k"].shape == (2, 32768, 128) and cache[1]["latent"].shape == (2, 32768, 512)
    assert sum(np.prod(leaf.shape[2:]) * 2 for leaf in jax.tree.leaves(cache)) == 6272


@pytest.mark.parametrize("kwargs,word", [({"tp": 2}, "tp > 1"), ({"kv_dtype": "int8"}, "kv_dtype")],
                         ids=["tp2", "int8_kv"])
def test_what_assumes_per_head_k_and_v_is_refused_by_name(tiny, kwargs, word):
    _, model, params = tiny
    with pytest.raises(NotImplementedError, match=word):
        ServingEngine(model, params, max_slots=2, max_len=32, prefill_chunk=8, **kwargs)


def test_the_dsa_counters_merge_and_reset():
    from accelerate_tpu.serving.metrics import ServingStats

    a, b = ServingStats(), ServingStats()
    a.record_tick(1, 1, 2, 0.01, dsa_rows=(30, 100))
    b.record_tick(1, 1, 2, 0.01, dsa_rows=(10, 100))
    m = ServingStats().merge(a).merge(b).summary()
    assert (m["dsa_selected_rows"], m["dsa_visible_rows"], m["dsa_selected_share"]) == (40, 200, 0.2)
    a.reset()
    assert a.summary()["dsa_selected_share"] == 0.0


def test_the_tick_and_the_chunk_name_the_selection_parts(tiny):
    """The tick reads the index keys under ``dsa_index``, picks and gathers
    under ``dsa_select`` and attends under ``kv_attn``, all inside
    ``attn_mla``; the chunk's index scores loop over key blocks under
    ``dsa_index``."""
    import re

    from chipbench import op_scopes

    _, model, params = tiny
    eng = ServingEngine(model, params, max_slots=2, max_len=64, prefill_chunk=8, page_size=8,
                        autostart=False, warmup=False)
    try:
        active, table = np.zeros((2,), bool), eng._table.copy()
        tick = eng._decode.lower(eng.params, eng._state, active, table).as_text(
            debug_info=True)
        chunk = eng._prefill_chunk.lower(
            eng.params, eng._state, np.zeros((1, 8), np.int32), np.int32(0), table[0],
            np.int32(0), np.int32(5), jax.random.PRNGKey(0)).as_text(debug_info=True)
    finally:
        eng.shutdown(drain=False)

    def paths(text):
        return {op_scopes.path_parts(n) for n in re.findall(r'loc\("([^"]+)"', text)}

    tick_paths, chunk_paths = paths(tick), paths(chunk)
    for part in ("dsa_index", "dsa_select", "kv_attn"):
        assert any(p[-2:] == ("attn_mla", part) for p in tick_paths), part
    assert any(p[-2:] == ("attn_mla", "dsa_index") for p in chunk_paths)


def test_estimate_reads_the_declared_cache_at_the_cells_cut(capsys):
    from accelerate_tpu.commands.estimate import estimate_command, estimate_command_parser

    rc = estimate_command(estimate_command_parser().parse_args(
        ["glm-5.2", "--held-experts", "16", "--page-size", "256", "--max-pages", "2048",
         "--dtypes", "bfloat16"]))
    out = capsys.readouterr().out
    assert rc == 0
    assert "(3.88 B params)" in out and "7.23 GiB" in out      # 3.881 G = 7.76 GB in bf16
    assert "holding 16 of 256 routed experts" in out
    assert "bytes per token : 6.12 KiB" in out                # (5 x 576 + 2 x 128) x 2 bytes
