"""A multi-token query block's cached attention over the key blocks its
queries can see (``models.llama._bounded_cached_attention``) against the one
pass over the whole view under today's mask (``_grouped_cached_attention``).

Everything is float32 on the CPU: the two forms differ only in the order of
the softmax's sums (a running maximum and sum across blocks), which moves an
output of magnitude ~1 by a few 1e-7. ``TOL`` = 1e-5 leaves ten times that; a
key row wrongly scored or skipped moves it by ~1e-1.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.models import llama
from accelerate_tpu.models.llama import (_bounded_cached_attention, _cached_attention,
                                         _grouped_cached_attention, cached_attention_rows,
                                         cached_key_block, cached_key_extent)

TOL = 1e-5
S, L, HD, WINDOW = 8, 72, 16, 20            # a view of nine pages of 8


def whole_view(q, k, v, cache_pos, n_rep, window=None, **kw):
    """Today's form: every row of the view scored, the mask afterwards."""
    q_pos = cache_pos + jnp.arange(q.shape[1])
    k_pos = jnp.arange(k.shape[1])[None]
    mask = k_pos <= q_pos[:, None]
    if window is not None:
        mask &= k_pos > q_pos[:, None] - window
    return _grouped_cached_attention(q, k, v, mask[None], n_rep, k_positions=k_pos[0], **kw)


def tensors(heads, kv_heads, seed=0, batch=1):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(batch, S, heads, HD)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(batch, L, kv_heads, HD)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(batch, L, kv_heads, HD)), jnp.float32)
    return q, k, v


OFFSETS = {"first_chunk": 0, "mid_view": 24, "pulled_back_mid_page": 37,
           "past_the_window": 48, "ends_the_view": L - S}


@pytest.mark.parametrize("window", [None, WINDOW], ids=["full", "windowed"])
@pytest.mark.parametrize("offset", list(OFFSETS.values()), ids=list(OFFSETS))
@pytest.mark.parametrize("block", [8, 16, 32], ids=["divides", "divides_not_the_window",
                                                    "overhangs_the_view"])
def test_the_visible_blocks_give_the_whole_views_attention(block, offset, window):
    q, k, v = tensors(4, 2)
    got = jax.jit(lambda q, k, v, p: _bounded_cached_attention(
        q, k, v, p, 2, block, sliding_window=window))(q, k, v, jnp.int32(offset))
    want = whole_view(q, k, v, offset, 2, window)
    assert float(jnp.abs(got - want).max()) < TOL


@pytest.mark.parametrize("heads,kv_heads", [(4, 4), (4, 2), (8, 1)], ids=["mha", "gqa2", "mqa"])
@pytest.mark.parametrize("extra", ["softcap", "alibi", "sm_scale", "batch2"])
def test_what_rides_on_the_logits_rides_through_the_blocks(extra, heads, kv_heads):
    kw = {"softcap": {"logit_softcap": 5.0},
          "alibi": {"alibi_slopes": jnp.asarray(2.0 ** -np.arange(1, heads + 1), jnp.float32)},
          "sm_scale": {"sm_scale": 0.4}, "batch2": {}}[extra]
    q, k, v = tensors(heads, kv_heads, seed=3, batch=2 if extra == "batch2" else 1)
    for offset, window in ((37, None), (48, WINDOW)):
        got = _bounded_cached_attention(q, k, v, jnp.int32(offset), heads // kv_heads, 16,
                                        sliding_window=window, **kw)
        want = whole_view(q, k, v, offset, heads // kv_heads, window, **kw)
        assert float(jnp.abs(got - want).max()) < TOL


def test_rows_outside_the_extent_are_never_read():
    """NaNs in every key row no query of the chunk can see: today's form
    survives them only through the mask's replacement; the bounded form does
    not touch them (inside its blocks the mask still replaces)."""
    q, k, v = tensors(4, 2, seed=5)
    offset, block = 40, 8
    lo, hi = offset - WINDOW + 1, offset + S
    seen = (jnp.arange(L) >= lo - lo % block) & (jnp.arange(L) < hi)
    k = jnp.where(seen[None, :, None, None], k, jnp.nan)
    v = jnp.where(seen[None, :, None, None], v, jnp.nan)
    got = _bounded_cached_attention(q, k, v, jnp.int32(offset), 2, block, sliding_window=WINDOW)
    clean = tensors(4, 2, seed=5)
    want = whole_view(*clean, offset, 2, WINDOW)
    assert not bool(jnp.isnan(got).any())
    assert float(jnp.abs(got - want).max()) < TOL


@pytest.mark.parametrize("name,score_rows,view,want", [
    ("command_a_plus_chunk", 128 * 256, 8192, 512),      # 64 MiB of scores a block
    ("mixtral_chunk", 32 * 256, 1024, 1024),             # 32 MiB for the whole view: one block
    ("mixtral_chunk_at_4k", 32 * 256, 4096, 2048),
    ("decode_one_slot", 128, 8192, 8192),
    ("speculative_verify_k4", 128 * 5, 8192 + 4, 8192 + 4),
    ("offline_prefill_2k_of_4k", 32 * 2048, 4096, 256),
    ("a_batch_too_wide_for_any_block", 8 * 32 * 4096, 8192, 128),
])
def test_the_key_block_comes_from_the_shape(name, score_rows, view, want):
    assert cached_key_block(score_rows, view) == want


@pytest.mark.parametrize("offset,window,want", [
    (0, None, (0, 1)), (24, None, (0, 2)), (37, None, (0, 3)), (64, None, (0, 5)),
    (0, WINDOW, (0, 1)), (24, WINDOW, (0, 2)), (37, WINDOW, (1, 3)), (64, WINDOW, (2, 5)),
])
def test_the_extent_is_the_blocks_between_the_windows_start_and_the_last_query(offset, window,
                                                                               want):
    got = cached_key_extent(offset, S, L, 16, window, lib=np)
    assert (int(got[0]), int(got[1])) == want
    traced = jax.jit(lambda p: cached_key_extent(p, S, L, 16, window))(jnp.int32(offset))
    assert (int(traced[0]), int(traced[1])) == want


def test_the_host_counts_the_rows_the_program_scores(monkeypatch):
    """Three offsets by hand, block 16 in a view of 72: scored = blocks x 16,
    visible = rows between the window's start and the last query."""
    monkeypatch.setattr(llama, "cached_key_block", lambda rows, view: min(16, view))
    assert cached_attention_rows(0, S, L, 4 * S) == (16, 8)
    assert cached_attention_rows(37, S, L, 4 * S) == (48, 45)
    assert cached_attention_rows(37, S, L, 4 * S, WINDOW) == (32, 27)         # rows 18..44
    assert cached_attention_rows(64, S, L, 4 * S) == (80, 72)                 # 5 blocks, one pulled back
    assert cached_attention_rows(64, S, L, 4 * S, WINDOW) == (48, 27)         # rows 45..71
    monkeypatch.undo()
    assert cached_attention_rows(37, S, L, 4 * S, WINDOW) == (L, 27)          # the real rule: one block


def test_one_program_serves_every_offset(monkeypatch):
    """The extent is a trip count: the jitted call traces and compiles once
    however the offset moves, and agrees with the one-block form each time."""
    q, k, v = tensors(4, 2, seed=7)
    want = {o: _cached_attention(q, k, v, jnp.int32(o), 2, sliding_window=WINDOW)
            for o in OFFSETS.values()}                   # the real rule: one block at this size
    monkeypatch.setattr(llama, "cached_key_block", lambda rows, view: min(8, view))
    fn = jax.jit(lambda q, k, v, p: _cached_attention(q, k, v, p, 2, sliding_window=WINDOW))
    assert "while" in fn.lower(q, k, v, jnp.int32(0)).as_text()
    for o, ref in want.items():
        assert float(jnp.abs(fn(q, k, v, jnp.int32(o)) - ref).max()) < TOL
    assert fn._cache_size() == 1


@pytest.mark.parametrize("call", ["decode", "verify", "short_view_chunk"])
def test_one_block_lowers_to_the_whole_view_form(call):
    """Where the rule gives one block the function is what it was: no loop,
    and the text of today's mask-then-softmax."""
    s = {"decode": 1, "verify": 5, "short_view_chunk": S}[call]
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(1, s, 4, HD)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, L, 2, HD)), jnp.float32)
    new = jax.jit(lambda q, k, v, p: _cached_attention(q, k, v, p, 2, sliding_window=WINDOW))
    old = jax.jit(lambda q, k, v, p: whole_view(q, k, v, p, 2, WINDOW))
    text = new.lower(q, k, k, jnp.int32(3)).as_text()
    assert "while" not in text
    got, want = new(q, k, k, jnp.int32(3)), old(q, k, k, jnp.int32(3))
    assert np.array_equal(np.asarray(got), np.asarray(want))
