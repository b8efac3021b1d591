"""The decode tick's attention over a work list of live (slot, key block)
pairs, read from the page pool in place (models/llama.py
``_attend_work_list``), against the whole-view forms it replaced in the
tick: the same lanes' pages gathered into dense views, the token's row
written in, ``_cached_attention`` / ``_latent_cached_attention`` in one
block. Every page no running lane's extent names holds NaN, so a read
outside the list fails the comparison."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.models import llama
from accelerate_tpu.models.llama import PagedCache
from accelerate_tpu.serving import ServingEngine

S, P, NP = 4, 4, 8                       # lanes, rows a page, pages a lane
L = P * NP                               # 32 rows a lane

KINDS = {
    "kv": dict(),
    "kv_window": dict(window=10),
    "kv_softcap_alibi": dict(softcap=5.0, alibi=True),
    "latent_rope": dict(latent=True),
    "kv_int8": dict(int8=True),
}

# pos, live: positions 0, L - 1 and a block's edge; idle lanes keep a stale pos
LANES = {
    "ragged": ([0, L - 1, 8, 13], [True] * 4),
    "idle_lanes_with_a_stale_pos": ([21, L - 1, 16, 7], [True, False, True, False]),
    "no_lane_runs": ([5, 9, 30, 0], [False] * 4),                 # T = 0
    "every_lane_full": ([L - 1] * 4, [True] * 4),                # T = capacity
    "one_lane_of_many_items": ([3, L - 2, 0, 1], [False, True, False, True]),
}

# (block, group): more items of one slot than a group; one page a block; a
# block that overhangs the lane's last page (3 pages a block, 8 pages a lane)
TILES = {"b8g2": (8, 2), "b4g8": (4, 8), "b12g4": (12, 4)}

H, N_KV, HD = 4, 2, 8                    # k/v leaves
RANK, ROPE, NOPE, VD = 8, 4, 6, 5        # latent + rope leaves


def build(kind, pos, live, seed=0):
    """A pool of S * NP + 1 pages (0 = scratch), a table, and a lane's
    inputs. Pages a running lane's query can reach (up to the page of its
    ``pos``; with a window, from the window's first page) hold data, the
    scratch page too; every other page holds NaN and, where the engine would
    have freed or never allocated it, the table names scratch."""
    rng = np.random.default_rng(seed)
    spec = KINDS[kind]
    window = spec.get("window")
    leaves = ({"latent": (RANK,), "rope": (ROPE,)} if spec.get("latent")
              else {"k": (N_KV, HD), "v": (N_KV, HD)})
    pool = {n: np.full((S * NP + 1, 1, P) + sh, np.nan, np.float32) for n, sh in leaves.items()}
    table = np.zeros((S, NP), np.int32)
    for n in pool:
        pool[n][0] = rng.normal(size=pool[n][0].shape)
    for s in range(S):
        last_page = pos[s] // P
        first_page = 0 if window is None else max(pos[s] - window + 1, 0) // P
        for j in range(NP):
            pid = 1 + s * NP + j
            if live[s] and first_page <= j <= last_page:
                table[s, j] = pid
                for n in pool:
                    pool[n][pid] = rng.normal(size=pool[n][pid].shape)
            elif not live[s] and j <= last_page:
                table[s, j] = pid                      # a stale lane still names its pages
    if spec.get("latent"):
        lane = dict(q_nope=rng.normal(size=(S, 1, 1, H, NOPE)), q_rope=rng.normal(size=(S, 1, 1, H, ROPE)),
                    c_kv=rng.normal(size=(S, 1, 1, RANK)), k_rope=rng.normal(size=(S, 1, 1, ROPE)))
        shared = dict(w_uk=rng.normal(size=(RANK, H, NOPE)), w_uv=rng.normal(size=(RANK, H, VD)))
    else:
        lane = dict(q=rng.normal(size=(S, 1, 1, H, HD)), k=rng.normal(size=(S, 1, 1, N_KV, HD)),
                    v=rng.normal(size=(S, 1, 1, N_KV, HD)))
        shared = dict(alibi=rng.uniform(0.01, 0.2, size=(H,))) if spec.get("alibi") else {}
    f32 = lambda t: jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), t)
    return f32(pool), jnp.asarray(table), f32(lane), f32(shared)


def quantise(pool):
    """An int8 pool and its pages' scales (absmax over the page, as the
    engine's ``_quant_page``); NaN pages stay recognisable as scale NaN."""
    q, scales = {}, {}
    for n, leaf in pool.items():
        amax = jnp.max(jnp.abs(leaf), axis=tuple(range(1, leaf.ndim)))
        scales[n] = jnp.maximum(amax, 1e-6) / 127.0
        s = scales[n].reshape((-1,) + (1,) * (leaf.ndim - 1))
        q[n] = jnp.clip(jnp.round(jnp.nan_to_num(leaf) / s), -127, 127).astype(jnp.int8)
    return q, scales


def attend(kind, cache, lane, shared, pos):
    spec = KINDS[kind]
    if spec.get("latent"):
        return llama.update_latent_cache_and_attend(
            cache, lane["q_nope"], lane["q_rope"], lane["c_kv"], lane["k_rope"],
            shared["w_uk"], shared["w_uv"], pos, 0.3)
    return llama.update_kv_cache_and_attend(
        cache, lane["q"], lane["k"], lane["v"], pos, H // N_KV,
        sliding_window=spec.get("window"), sm_scale=0.4, logit_softcap=spec.get("softcap"),
        alibi_slopes=shared.get("alibi"))


def whole_view(kind, pool, scales, table, lane, shared, pos, s):
    """Lane ``s`` as the tick ran it before: its pages gathered into a dense
    view (dequantised for an int8 pool), the token's row written at ``pos``,
    attention over the whole view in one block."""
    view = {}
    for n, leaf in pool.items():
        rows = leaf[table[s]]
        if scales is not None:
            rows = rows.astype(jnp.float32) * scales[n][table[s]].reshape((-1,) + (1,) * (rows.ndim - 1))
        view[n] = jnp.moveaxis(rows, 0, 1).reshape((1, L) + rows.shape[3:])
    one = jax.tree.map(lambda x: x[s], lane)
    out, new = attend(kind, view, one, shared, pos[s])
    return out, jax.tree.map(lambda x: jax.lax.dynamic_slice_in_dim(x, pos[s], 1, axis=1), new)


def work_list(kind, pool, scales, table, lane, shared, pos, live):
    def one_lane(pages, alive, one, p):
        cache = PagedCache(pool=pool, scales=scales, pages=pages, live=alive,
                           dtype=None if scales is None else jnp.float32)
        return attend(kind, cache, one, shared, p)

    return jax.jit(jax.vmap(one_lane))(table, jnp.asarray(live), lane, jnp.asarray(pos, jnp.int32))


@pytest.mark.parametrize("tiles", TILES)
@pytest.mark.parametrize("lanes", LANES)
@pytest.mark.parametrize("kind", KINDS)
def test_the_work_list_gives_what_the_whole_view_gave(kind, lanes, tiles, monkeypatch):
    monkeypatch.setattr(llama, "tick_key_tiles", lambda *shape: TILES[tiles])
    pos, live = LANES[lanes]
    pool, table, lane, shared = build(kind, pos, live)
    scales = None
    if KINDS[kind].get("int8"):
        pool, scales = quantise(pool)
    out, rows = work_list(kind, pool, scales, table, lane, shared, pos, live)
    assert out.shape[:3] == (S, 1, 1) and bool(jnp.isfinite(out).all())
    for s in range(S):
        if not live[s]:
            continue
        want, want_row = whole_view(kind, pool, scales, table, lane, shared, pos, s)
        np.testing.assert_allclose(out[s], want, rtol=2e-5, atol=2e-5)
        for n in want_row:
            np.testing.assert_array_equal(rows[n][s], want_row[n])


@pytest.mark.parametrize("kind", ["kv_window", "latent_rope"])
def test_one_lane_without_a_vmap_runs_the_same_code(kind, monkeypatch):
    monkeypatch.setattr(llama, "tick_key_tiles", lambda *shape: (8, 2))
    pos, live = LANES["ragged"]
    pool, table, lane, shared = build(kind, pos, live, seed=3)
    for s in (1, 3):
        cache = PagedCache(pool=pool, scales=None, pages=table[s], live=jnp.asarray(True))
        out, _ = attend(kind, cache, jax.tree.map(lambda x: x[s], lane), shared, pos[s])
        want, _ = whole_view(kind, pool, None, table, lane, shared, pos, s)
        np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)


def test_a_pool_that_varies_by_lane_is_refused():
    pos, live = LANES["ragged"]
    pool, table, lane, shared = build("kv", pos, live)
    pools = jax.tree.map(lambda x: jnp.stack([x] * S), pool)

    def one_lane(pool, pages, one, p):
        return attend("kv", PagedCache(pool=pool, scales=None, pages=pages, live=jnp.asarray(True)),
                      one, shared, p)

    with pytest.raises(NotImplementedError, match="shared by every lane"):
        jax.vmap(one_lane)(pools, table, lane, jnp.asarray(pos, jnp.int32))


@pytest.mark.parametrize("shape,want", [
    ((128, 32, 8192, 256), (512, 32)),        # Pangu: 128 heads, 32 lanes
    ((128, 16, 8192, 256), (512, 32)),        # Command A+
    ((32, 8, 1024, 256), (512, 16)),          # Mixtral: the list's capacity, 8 lanes x 2 blocks
    ((4, 2, 64, 8), (64, 2)),                 # a toy engine: one block a lane
    ((4, 3, 88, 8), (88, 2)),
    ((8, 4, 4096, 1024), (1024, 16)),         # a page wider than 512 rows is the block
], ids=["pangu", "cmdaplus", "mixtral", "toy", "toy_odd", "wide_page"])
def test_the_tiles_follow_from_the_shape(shape, want):
    block, group = llama.tick_key_tiles(*shape)
    assert (block, group) == want
    assert block % shape[3] == 0 and group <= shape[1] * -(-shape[2] // block)


@pytest.mark.parametrize("window,pos,want", [
    (None, [0, 1, 8, 9, 31], ([0, 0, 0, 0, 0], [0, 1, 1, 2, 4])),
    (10, [0, 8, 9, 17, 31], ([0, 0, 0, 1, 2], [0, 1, 2, 2, 2])),
], ids=["full", "window"])
def test_the_extent_is_the_blocks_before_the_tokens_own_row(window, pos, want):
    """Blocks of 8: a lane at ``pos`` holds pool rows ``[pos - window + 1, pos)``; at a block's
    edge the token's own row opens the next block, which no pool row is in yet."""
    live = np.asarray([True] * 5)
    first, count = llama.tick_key_extent(np.asarray(pos), live, L, 8, window, lib=np)
    assert (list(first), list(count)) == want
    traced = jax.jit(lambda p: llama.tick_key_extent(p, jnp.asarray(live), L, 8, window))(jnp.asarray(pos))
    assert (list(traced[0]), list(traced[1])) == want
    _, idle = llama.tick_key_extent(np.asarray(pos), ~live, L, 8, window, lib=np)
    assert not idle.any()


# -- the engine's tick ---------------------------------------------------------

def tiny_engine(**kw):
    from accelerate_tpu.models.mixtral import MixtralConfig, MixtralForCausalLM

    model = MixtralForCausalLM(MixtralConfig.tiny_moe())
    params = model.init_params(jax.random.PRNGKey(0))
    # 130 pages of 8 = 1040 rows a lane: a number no other axis of the toy model has
    return ServingEngine(model, params, max_slots=3, max_len=1040, prefill_chunk=8, page_size=8,
                         autostart=False, warmup=False, **kw)


def axes_of(text):
    return {int(d) for dims in re.findall(r"tensor<([0-9x]+)x[a-z]", text) for d in dims.split("x")}


@pytest.mark.parametrize("variant", ["fp", "int8"])
def test_the_lowered_tick_holds_no_view_of_a_lane(variant):
    eng = tiny_engine(**({"kv_dtype": "int8"} if variant == "int8" else {}))
    try:
        active, table = np.zeros((3,), bool), eng._table.copy()
        tick = eng._decode.lower(eng.params, eng._state, active, table).as_text()
        chunk = eng._prefill_chunk.lower(
            eng.params, eng._state, np.zeros((1, 8), np.int32), np.int32(0), table[0],
            np.int32(0), np.int32(5), jax.random.PRNGKey(0)).as_text()
    finally:
        eng.shutdown(drain=False)
    assert 1040 in axes_of(chunk)                    # the chunk still gathers its slot's view
    assert 1040 not in axes_of(tick)                 # the tick gathers key blocks of 512
    assert {512, 130} <= axes_of(tick)               # ... through the table's 130 pages a lane


def test_the_verify_tick_still_gathers_views():
    eng = tiny_engine(spec_tokens=3, spec_lookup=2)
    try:
        text = eng._spec.lower(eng.params, eng._state, np.zeros((3,), bool), eng._table.copy(),
                               np.zeros((3,), np.int32), np.zeros((3, 3), np.int32)).as_text()
    finally:
        eng.shutdown(drain=False)
    assert 1048 in axes_of(text)                     # (1040 + 3) rows in pages of 8, a lane
