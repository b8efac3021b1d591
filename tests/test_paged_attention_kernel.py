"""The Mosaic paged-attention kernel for flat-row pools
(``ops/paged_attention.py``), run by the Pallas interpreter on the CPU,
against the XLA work list it takes the place of on the TPU
(``models/llama.py`` ``_attend_work_list`` through ``_paged_flat_kv_attend``):
the same pool, table, positions and ``live``, both through the tick's
``jax.vmap`` over batch-1 forwards. Every page no running lane's extent names
holds NaN, so a read outside the live pages fails the comparison; the scratch
page (0), which table holes point at, holds finite data as the engine's does.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.models import llama
from accelerate_tpu.models.llama import PagedCache
from accelerate_tpu.models.phi4flash import Phi4FlashConfig, Phi4FlashForCausalLM
from accelerate_tpu.models.reference import phi4flash as reference
from accelerate_tpu.ops import paged_attention
from accelerate_tpu.serving import ServingEngine

P, NP, HD = 16, 4, 128                   # rows a page, pages a lane, a head's columns
L = P * NP                               # 64 rows a lane
WINDOW = 2 * P + 5                       # "512-like": wider than a page, no multiple of one

# positions at the edges of a page, of the window and of the lane
EDGES = [0, 1, P - 1, P, P + 1, WINDOW - 1, WINDOW, WINDOW + 1, L - 1]
LANES = {
    "edges": (EDGES, [True] * len(EDGES)),
    "idle_lanes_with_a_stale_pos": ([21, L - 1, P, 7, WINDOW + 9, 2 * P],
                                    [True, False, True, False, False, True]),
    "no_lane_runs": ([5, 9, 30, 0], [False] * 4),
    "every_lane_full": ([L - 1] * 3, [True] * 3),
}
HEADS = {"rep1_g3": (1, 3), "rep4_g3": (4, 3), "rep4_g5": (4, 5)}     # (n_rep, KV heads)


@pytest.fixture
def kernel_on(monkeypatch):
    """The TPU branch of the dispatch, on the CPU: the kernel itself then runs
    in the Pallas interpreter (``paged_attention._interpret``)."""
    monkeypatch.setattr(paged_attention, "tpu_backend", lambda: True)


def build(pos, live, n_rep, G, window, dtype, seed=0):
    """A flat-row pool of ``S * NP + 1`` pages, a table, and the lanes' own
    inputs. A running lane's live pages (up to the page of its ``pos``; with a
    window, from the window's first page) and scratch hold data; every other
    page holds NaN. A running lane's pages before its window are freed: holes
    that point at scratch. An idle lane still names its stale pages."""
    rng = np.random.default_rng(seed)
    S = len(pos)
    pool = {n: np.full((S * NP + 1, 1, P, G * HD), np.nan, np.float32) for n in ("k", "v")}
    table = np.zeros((S, NP), np.int32)
    for n in pool:
        pool[n][0] = rng.normal(size=pool[n][0].shape)
    for s in range(S):
        last = pos[s] // P
        first = 0 if window is None else max(pos[s] - window + 1, 0) // P
        for j in range(NP):
            pid = 1 + s * NP + j
            if live[s] and first <= j <= last:
                table[s, j] = pid
                for n in pool:
                    pool[n][pid] = rng.normal(size=pool[n][pid].shape)
            elif not live[s] and j <= last:
                table[s, j] = pid
    H = G * n_rep
    lane = dict(q=rng.normal(size=(S, 1, 1, H, HD)), k=rng.normal(size=(S, 1, 1, G, HD)),
                v=rng.normal(size=(S, 1, 1, G, HD)))
    cast = lambda t: jax.tree.map(lambda x: jnp.asarray(x, dtype), t)          # noqa: E731
    return cast(pool), jnp.asarray(table), cast(lane)


def tick_attention(pool, table, lane, pos, live, n_rep, window):
    """All lanes' attention as the engine's tick runs it: a vmap over batch-1
    calls, each lane's cache a ``PagedCache`` on the shared pool."""
    def one_lane(pages, alive, one, p):
        cache = PagedCache(pool=pool, scales=None, pages=pages, live=alive)
        return llama.update_kv_cache_and_attend(cache, one["q"], one["k"], one["v"], p, n_rep,
                                                sliding_window=window, sm_scale=HD ** -0.5)

    return jax.jit(jax.vmap(one_lane))(table, jnp.asarray(live), lane, jnp.asarray(pos, jnp.int32))


@pytest.mark.parametrize("heads", HEADS)
@pytest.mark.parametrize("lanes", LANES)
@pytest.mark.parametrize("window", [None, WINDOW], ids=["full", "window"])
def test_the_kernel_gives_what_the_work_list_gives(window, lanes, heads, monkeypatch):
    n_rep, G = HEADS[heads]
    pos, live = LANES[lanes]
    pool, table, lane = build(pos, live, n_rep, G, window, jnp.float32)
    want, want_rows = tick_attention(pool, table, lane, pos, live, n_rep, window)
    monkeypatch.setattr(paged_attention, "tpu_backend", lambda: True)
    calls = []
    real = paged_attention.paged_flat_attention
    monkeypatch.setattr(paged_attention, "paged_flat_attention",
                        lambda *a, **kw: calls.append(kw) or real(*a, **kw))
    got, rows = tick_attention(pool, table, lane, pos, live, n_rep, window)
    # all lanes in one call (custom_vmap traces the one-lane form too, for its shapes)
    assert calls and all(c == dict(n_rep=n_rep, sliding_window=window) for c in calls)
    assert got.shape == (len(pos), 1, 1, G * n_rep, HD) and bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    for n in want_rows:
        np.testing.assert_array_equal(rows[n], want_rows[n])


def test_the_triple_is_unnormalised_and_empty_for_a_lane_without_rows(kernel_on):
    """Position 0 and an idle lane hold no pool row: (-1e30, 0, 0); a running
    lane's triple is the softmax's own parts over its visible rows."""
    pos, live = [0, P + 3, 9], [True, True, False]
    pool, table, lane = build(pos, live, 4, 3, None, jnp.float32, seed=2)
    q = lane["q"][:, 0, 0] * HD ** -0.5
    m, l, acc = paged_attention.paged_flat_attention(
        q, pool["k"], pool["v"], table, jnp.asarray(pos, jnp.int32), jnp.asarray(live), n_rep=4)
    for s in (0, 2):
        assert float(m[s].max()) == float(np.float32(-1e30)) and float(jnp.abs(l[s]).max()) == 0.0
        assert float(jnp.abs(acc[s]).max()) == 0.0
    rows = {n: pool[n][table[1]][:, 0].reshape(L, 3, HD)[:pos[1]] for n in pool}
    scores = jnp.einsum("grd,kgd->grk", q[1].reshape(3, 4, HD), rows["k"],
                        precision="highest").reshape(12, -1)
    np.testing.assert_allclose(m[1], scores.max(-1), rtol=1e-5)
    p = jnp.exp(scores - scores.max(-1, keepdims=True))
    np.testing.assert_allclose(l[1], p.sum(-1), rtol=1e-5)
    want = jnp.einsum("grk,kgd->grd", p.reshape(3, 4, -1), rows["v"], precision="highest")
    np.testing.assert_allclose(acc[1], want.reshape(12, HD), rtol=1e-4, atol=1e-4)


def test_bfloat16_rows_are_multiplied_in_their_own_type(monkeypatch):
    """The stated precision: products on the rows' type accumulated in float32,
    probabilities cast to that type before the value product — as the list."""
    pos, live = LANES["edges"]
    pool, table, lane = build(pos, live, 4, 3, WINDOW, jnp.bfloat16, seed=5)
    want, _ = tick_attention(pool, table, lane, pos, live, 4, WINDOW)
    monkeypatch.setattr(paged_attention, "tpu_backend", lambda: True)
    got, _ = tick_attention(pool, table, lane, pos, live, 4, WINDOW)
    assert got.dtype == want.dtype == jnp.bfloat16
    np.testing.assert_allclose(got.astype(np.float32), want.astype(np.float32), atol=3e-2)


@pytest.mark.parametrize("case,why", [
    ("cpu", "the backend"), ("scales", "an int8 pool"), ("sharded", "a sharded pool"),
    ("headed", "a head axis"), ("narrow", "lane tiles"), ("ragged_page", "sublane tiles"),
    ("latent", "not k and v"),
])
def test_the_kernel_is_taken_from_what_the_code_can_see(case, why, monkeypatch):
    leaf = jnp.zeros((5, 1, 16, 256), jnp.bfloat16)
    pool, scales, sharded = {"k": leaf, "v": leaf}, None, False
    if case != "cpu":
        monkeypatch.setattr(paged_attention, "tpu_backend", lambda: True)
        assert paged_attention.paged_attention_available(pool, scales, sharded)
    if case == "scales":
        scales = {"k": jnp.ones((5,)), "v": jnp.ones((5,))}
    elif case == "sharded":
        sharded = True
    elif case == "headed":
        pool = {n: x.reshape(5, 1, 16, 2, 128) for n, x in pool.items()}
    elif case == "narrow":
        pool = {n: x[..., :192] for n, x in pool.items()}
    elif case == "ragged_page":
        pool = {n: x[:, :, :8] for n, x in pool.items()}
    elif case == "latent":
        pool = {"latent": leaf, "rope": leaf}
    assert not paged_attention.paged_attention_available(pool, scales, sharded), why


def test_an_int8_pool_keeps_the_work_list(kernel_on, monkeypatch):
    monkeypatch.setattr(paged_attention, "paged_flat_attention",
                        lambda *a, **kw: pytest.fail("the kernel ran on a pool with scales"))
    pos, live = [P + 3, 9], [True, True]
    pool, table, lane = build(pos, live, 4, 3, None, jnp.float32)
    ints = {n: jnp.clip(jnp.round(jnp.nan_to_num(x) * 20), -127, 127).astype(jnp.int8)
            for n, x in pool.items()}
    scales = {n: jnp.full((x.shape[0],), 0.05, jnp.float32) for n, x in pool.items()}

    def one_lane(pages, one, p):
        cache = PagedCache(pool=ints, scales=scales, pages=pages, live=jnp.asarray(True),
                           dtype=jnp.float32)
        return llama.update_kv_cache_and_attend(cache, one["q"], one["k"], one["v"], p, 4)[0]

    out = jax.vmap(one_lane)(table, lane, jnp.asarray(pos, jnp.int32))
    assert bool(jnp.isfinite(out).all())


# -- the engine's tick ---------------------------------------------------------

@pytest.fixture(scope="module")
def wide():
    """The phi4flash family at a toy size whose key pairs are 128 wide (heads
    of 64, as published): the flat rows are whole lane tiles, pages of 16."""
    cfg = Phi4FlashConfig.tiny(hidden_size=256, num_attention_heads=4, num_key_value_heads=2,
                               intermediate_size=128, sliding_window=24)
    model = Phi4FlashForCausalLM(cfg)
    leaves, tree = jax.tree.flatten(model.init_params(jax.random.PRNGKey(0)))
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    params = jax.tree.unflatten(tree, [
        leaf + 0.1 * jax.random.normal(k, leaf.shape) for leaf, k in zip(leaves, keys)])
    return cfg, model, params


def engine_of(wide, **kwargs):
    _, model, params = wide
    return ServingEngine(model, params, max_slots=3, max_len=64, prefill_chunk=16, page_size=16,
                         prefix_cache_mb=0, cache_dtype=jnp.float32, **kwargs)


def served(wide, prompts):
    with engine_of(wide) as eng:
        eng.stats.reset()
        reqs = [eng.submit(p.astype(np.int32)[None], max_new_tokens=12, ignore_eos=True)
                for p in prompts]
        for r in reqs:
            r.result(timeout=600)
        return [list(r.tokens) for r in reqs], eng.stats.summary()


def test_the_engine_serves_the_same_tokens_through_the_kernel(wide, monkeypatch):
    """Four prompts over three slots — shorter than the window, past it, on a
    page's edge — with the kernel forced on (interpreter): token for token
    what the engine serves through the XLA list, each within ``TOL`` of the
    float32 reference's best logit (``generate`` prefills a prompt's pads, which
    this family's recurrent state takes as steps: the reference is the
    standard, as in tests/test_phi4flash.py), and the gauge and the row
    counters say which path ran."""
    cfg, _, params = wide
    prompts = [np.asarray(jax.random.randint(jax.random.PRNGKey(n), (n,), 1, 256))
               for n in (5, 33, 16, 27)]
    listed, list_summary = served(wide, prompts)
    monkeypatch.setattr(paged_attention, "tpu_backend", lambda: True)
    calls, real = [], paged_attention.paged_flat_attention
    monkeypatch.setattr(paged_attention, "paged_flat_attention",
                        lambda *a, **kw: calls.append(kw["sliding_window"]) or real(*a, **kw))
    tokens, summary = served(wide, prompts)
    assert tokens == listed
    assert {24, None} == set(calls)                 # the windowed readers and layer 5's
    for prompt, toks in zip(prompts, tokens):
        logits = reference.reference_logits(params, jnp.asarray(list(prompt) + toks), cfg)
        at = logits[len(prompt) - 1:len(prompt) - 1 + len(toks)]
        assert float((at.max(-1) - at[np.arange(len(toks)), np.asarray(toks)]).max()) < 5e-4
    assert summary["tick_attn_kernel_readers"] == summary["kv_reader_layers"] == 4
    assert list_summary["tick_attn_kernel_readers"] == 0
    # whole live pages and no step to fill up: fewer rows scored, a larger part of them visible
    assert summary["decode_attn_rows_share"] < list_summary["decode_attn_rows_share"]
    assert summary["decode_attn_rows_fill"] > list_summary["decode_attn_rows_fill"]


def test_the_engine_counts_the_kernels_rows_by_hand(wide, kernel_on):
    """One stream of 40 prompt tokens and 3 ticks at positions 40, 41, 42, in
    an engine of 3 slots x 64 rows, pages of 16; windows 24, 24, none, and the
    cross-attention reads the full layer's entry: a windowed attention scores
    the pages of rows pos - 23 .. pos - 1 (pages 1 and 2), a full one pages 0,
    1 and 2 — whole, and no step to fill up."""
    with engine_of(wide) as eng:
        eng.stats.reset()
        req = eng.submit(np.arange(1, 41, dtype=np.int32)[None], max_new_tokens=4, ignore_eos=True)
        req.result(timeout=600)
        s = eng.stats.summary()
    scored = 3 * (2 * 2 * 16 + 2 * 3 * 16)
    assert s["decode_attn_rows_share"] == pytest.approx(scored / (3 * 4 * 3 * 64), abs=1e-6)
    visible = sum(2 * 23 + 2 * p for p in (40, 41, 42))
    assert s["decode_attn_rows_fill"] == pytest.approx(visible / scored, abs=1e-6)


def test_without_the_kernel_the_gauge_reads_zero(wide):
    with engine_of(wide, autostart=False, warmup=False) as eng:
        assert eng.tick_attn_kernel_readers == 0
    _, model, params = wide
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(paged_attention, "tpu_backend", lambda: True)
        with ServingEngine(model, params, max_slots=2, max_len=64, prefill_chunk=16, page_size=16,
                           prefix_cache_mb=0, kv_dtype="int8", autostart=False,
                           warmup=False) as eng:
            assert eng.tick_attn_kernel_readers == 0          # an int8 pool keeps the list
