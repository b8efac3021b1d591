"""Flash-attention kernel correctness vs the einsum reference (interpret mode
on CPU; the same kernel code compiles for TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.ops.attention import _einsum_attention
from accelerate_tpu.ops.flash_pallas import pallas_flash_attention


def make_qkv(B=2, S=256, H=2, D=64, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (B, S, H, D)
    q = jax.random.normal(ks[0], shape, dtype)
    k = jax.random.normal(ks[1], shape, dtype)
    v = jax.random.normal(ks[2], shape, dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_forward_matches_reference(causal):
    q, k, v = make_qkv()
    ref = _einsum_attention(q, k, v, causal=causal)
    out = pallas_flash_attention(q, k, v, causal=causal, block_q=128, block_k=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_forward_rectangular_blocks():
    q, k, v = make_qkv(S=256)
    ref = _einsum_attention(q, k, v, causal=True)
    out = pallas_flash_attention(q, k, v, causal=True, block_q=64, block_k=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_backward_matches_reference(causal):
    q, k, v = make_qkv(B=1, S=128, H=2, D=32)

    def loss_flash(q, k, v):
        return (pallas_flash_attention(q, k, v, causal=causal, block_q=64, block_k=64) ** 2).sum()

    def loss_ref(q, k, v):
        return (_einsum_attention(q, k, v, causal=causal) ** 2).sum()

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4, rtol=5e-4,
                                   err_msg=f"d{name} mismatch")


@pytest.mark.parametrize("window", [1, 40, 64, 100])
def test_sliding_window_forward_matches_reference(window):
    """Windows off, at, and across block boundaries (blocks 64)."""
    q, k, v = make_qkv(B=1, S=256, H=2, D=32)
    ref = _einsum_attention(q, k, v, causal=True, sliding_window=window)
    out = pallas_flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                                 sliding_window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("bq,bk,window", [(64, 128, 96), (128, 64, 200), (64, 64, 255)])
def test_sliding_window_banded_grid_rectangular(bq, bk, window):
    """The banded grid must never miss a visible block, whatever the
    block-shape/window alignment."""
    q, k, v = make_qkv(B=1, S=512, H=1, D=32, seed=3)
    ref = _einsum_attention(q, k, v, causal=True, sliding_window=window)
    out = pallas_flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk,
                                 sliding_window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_sliding_window_backward_matches_reference():
    q, k, v = make_qkv(B=1, S=128, H=2, D=32)
    window = 40  # crosses the 64-wide block boundary

    def loss_flash(q, k, v):
        return (pallas_flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                                       sliding_window=window) ** 2).sum()

    def loss_ref(q, k, v):
        return (_einsum_attention(q, k, v, causal=True, sliding_window=window) ** 2).sum()

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4, rtol=5e-4,
                                   err_msg=f"d{name} mismatch")


def test_sliding_window_requires_causal():
    q, k, v = make_qkv(B=1, S=128, H=1, D=32)
    with pytest.raises(ValueError, match="sliding_window requires causal"):
        pallas_flash_attention(q, k, v, causal=False, sliding_window=16)


def _packed_segments(B, S, seed=0):
    """Random packed layout: per-row segment ids 1,1,...,2,2,...,3..."""
    rng = np.random.default_rng(seed)
    segs = np.zeros((B, S), np.int32)
    for b in range(B):
        boundaries = np.sort(rng.choice(np.arange(8, S - 8), size=2, replace=False))
        segs[b, : boundaries[0]] = 1
        segs[b, boundaries[0]:boundaries[1]] = 2
        segs[b, boundaries[1]:] = 3
    return jnp.asarray(segs)


@pytest.mark.parametrize("causal", [True, False])
def test_segment_ids_forward_matches_reference(causal):
    """Packed sequences: cross-segment pairs masked inside the kernel —
    packing keeps flash memory asymptotics instead of the einsum fallback."""
    q, k, v = make_qkv(B=2, S=256, H=2, D=32, seed=5)
    segs = _packed_segments(2, 256, seed=5)
    ref = _einsum_attention(q, k, v, causal=causal, segment_ids=segs)
    out = pallas_flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                                 segment_ids=segs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_segment_ids_backward_matches_reference():
    q, k, v = make_qkv(B=1, S=128, H=2, D=32, seed=6)
    segs = _packed_segments(1, 128, seed=6)

    def loss_flash(q, k, v):
        return (pallas_flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                                       segment_ids=segs) ** 2).sum()

    def loss_ref(q, k, v):
        return (_einsum_attention(q, k, v, causal=True, segment_ids=segs) ** 2).sum()

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4, rtol=5e-4,
                                   err_msg=f"d{name} mismatch")


def test_segment_ids_rectangular_blocks():
    """Segment boundaries crossing block edges, uneven block shapes."""
    q, k, v = make_qkv(B=1, S=256, H=1, D=32, seed=7)
    segs = _packed_segments(1, 256, seed=7)
    ref = _einsum_attention(q, k, v, causal=True, segment_ids=segs)
    out = pallas_flash_attention(q, k, v, causal=True, block_q=64, block_k=128,
                                 segment_ids=segs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_segment_ids_with_sliding_window_compose():
    # Packed sequences + local attention: the banded grid and the segment
    # mask must compose exactly (forward AND backward).
    q, k, v = make_qkv(B=1, S=256, H=2, D=32)
    segs = _packed_segments(1, 256)

    def loss_flash(q, k, v):
        return (pallas_flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                                       sliding_window=70, segment_ids=segs) ** 2).sum()

    def loss_ref(q, k, v):
        return (_einsum_attention(q, k, v, causal=True, sliding_window=70,
                                  segment_ids=segs) ** 2).sum()

    out = pallas_flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                                 sliding_window=70, segment_ids=segs)
    ref = _einsum_attention(q, k, v, causal=True, sliding_window=70, segment_ids=segs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4, rtol=5e-4,
                                   err_msg=f"d{name} mismatch")


def test_bf16_inputs():
    q, k, v = make_qkv(dtype=jnp.bfloat16)
    ref = _einsum_attention(q, k, v, causal=True)
    out = pallas_flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=3e-2, rtol=3e-2
    )


# -- GQA (narrow KV, kernels index the shared head via h // rep) -------------

def make_gqa_qkv(B=1, S=128, H=4, G=2, D=32, dtype=jnp.float32, seed=7):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), dtype)
    k = jax.random.normal(ks[1], (B, S, G, D), dtype)
    v = jax.random.normal(ks[2], (B, S, G, D), dtype)
    return q, k, v


def _repeat_kv(q, k, v):
    rep = q.shape[2] // k.shape[2]
    return jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)


@pytest.mark.parametrize("causal", [True, False])
def test_gqa_forward_matches_repeated(causal):
    q, k, v = make_gqa_qkv()
    kf, vf = _repeat_kv(q, k, v)
    ref = _einsum_attention(q, kf, vf, causal=causal)
    # the grouped einsum branch itself
    ref_gqa = _einsum_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(ref_gqa), np.asarray(ref), atol=2e-5, rtol=2e-5)
    out = pallas_flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_gqa_backward_matches_repeated():
    q, k, v = make_gqa_qkv()

    def loss_flash(q, k, v):
        return (pallas_flash_attention(q, k, v, causal=True, block_q=64, block_k=64) ** 2).sum()

    def loss_ref(q, kf, vf):
        return (_einsum_attention(q, kf, vf, causal=True) ** 2).sum()

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    # Reference grads: expand, differentiate, group-sum dk/dv back.
    rep = q.shape[2] // k.shape[2]
    kf, vf = _repeat_kv(q, k, v)
    gq, gkf, gvf = jax.grad(loss_ref, argnums=(0, 1, 2))(q, kf, vf)
    B, S, H, D = q.shape
    G = k.shape[2]
    # jnp.repeat on axis 2 lays heads out kv-head-major: [g0, g0, g1, g1].
    gk = gkf.reshape(B, S, G, rep, D).sum(axis=3)
    gv = gvf.reshape(B, S, G, rep, D).sum(axis=3)
    for a, b, name in zip(g_flash, (gq, gk, gv), ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4, rtol=5e-4,
                                   err_msg=f"{name} mismatch")


def test_gqa_sliding_window_matches_repeated():
    q, k, v = make_gqa_qkv(S=256)
    kf, vf = _repeat_kv(q, k, v)
    ref = _einsum_attention(q, kf, vf, causal=True, sliding_window=70)
    out = pallas_flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                                 sliding_window=70)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_gqa_segments_match_repeated():
    q, k, v = make_gqa_qkv(S=128)
    segs = _packed_segments(1, 128)
    kf, vf = _repeat_kv(q, k, v)
    ref = _einsum_attention(q, kf, vf, causal=True, segment_ids=segs)
    out = pallas_flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                                 segment_ids=segs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_gqa_rejects_indivisible_heads():
    q, k, v = make_gqa_qkv(H=4, G=3)
    with pytest.raises(ValueError, match="not a multiple"):
        pallas_flash_attention(q, k, v, causal=True)


# -- logit softcapping (Gemma2: cap * tanh(s / cap) inside the kernel) -------

@pytest.mark.parametrize("causal", [True, False])
def test_softcap_forward_matches_reference(causal):
    q, k, v = make_qkv(B=1, S=128, H=2, D=32)
    ref = _einsum_attention(q, k, v, causal=causal, logit_softcap=7.0)
    out = pallas_flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                                 logit_softcap=7.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)
    # the cap must actually change the result
    plain = pallas_flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    assert np.abs(np.asarray(out) - np.asarray(plain)).max() > 1e-4


def test_softcap_backward_matches_reference():
    q, k, v = make_qkv(B=1, S=128, H=2, D=32)

    def loss_flash(q, k, v):
        return (pallas_flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                                       logit_softcap=7.0) ** 2).sum()

    def loss_ref(q, k, v):
        return (_einsum_attention(q, k, v, causal=True, logit_softcap=7.0) ** 2).sum()

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4, rtol=5e-4,
                                   err_msg=f"d{name} mismatch")
        assert np.isfinite(np.asarray(a)).all(), f"d{name} has NaN/inf"


def test_softcap_with_window_and_gqa_backward():
    # softcap + banded grid + narrow KV + custom scale, all at once.
    q, k, v = make_gqa_qkv(S=256, H=4, G=2)

    kw = dict(causal=True, block_q=64, block_k=64, sliding_window=70,
              logit_softcap=5.0, sm_scale=0.17)

    def loss_flash(q, k, v):
        return (pallas_flash_attention(q, k, v, **kw) ** 2).sum()

    rep = 2
    kf, vf = _repeat_kv(q, k, v)

    def loss_ref(q, kf, vf):
        return (_einsum_attention(q, kf, vf, causal=True, sliding_window=70,
                                  logit_softcap=5.0, sm_scale=0.17) ** 2).sum()

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gq, gkf, gvf = jax.grad(loss_ref, argnums=(0, 1, 2))(q, kf, vf)
    B, S, H, D = q.shape
    gk = gkf.reshape(B, S, 2, rep, D).sum(axis=3)
    gv = gvf.reshape(B, S, 2, rep, D).sum(axis=3)
    for a, b, name in zip(g_flash, (gq, gk, gv), ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=7e-4, rtol=7e-4,
                                   err_msg=f"{name} mismatch")


class TestPerShardOnAMesh:
    """``flash_attention`` on a mesh runs the kernel per shard under a
    shard_map (Mosaic kernels cannot be partitioned by GSPMD — see
    tests/test_tpu_compile.py). Here the interpreter checks the arithmetic of
    that wrapper on the virtual CPU mesh: batch over fsdp, heads over tp."""

    @pytest.fixture
    def flash_on(self, monkeypatch):
        from accelerate_tpu.ops import attention

        monkeypatch.setattr(attention, "flash_attention_available", lambda q=None: True)

    @staticmethod
    def _mesh():
        from accelerate_tpu import MeshConfig

        return MeshConfig(fsdp=2, tp=2, devices=jax.devices()[:4]).build()

    @staticmethod
    def _gqa_qkv():
        ks = jax.random.split(jax.random.PRNGKey(3), 3)
        q = jax.random.normal(ks[0], (2, 128, 4, 32), jnp.float32)
        k = jax.random.normal(ks[1], (2, 128, 2, 32), jnp.float32)
        v = jax.random.normal(ks[2], (2, 128, 2, 32), jnp.float32)
        return q, k, v

    def test_specs_follow_the_mesh(self, flash_on):
        from jax.sharding import PartitionSpec as P

        from accelerate_tpu.ops.attention import _per_shard_specs

        q, k, _ = self._gqa_qkv()
        assert _per_shard_specs(q, k) is None  # no ambient mesh: plain call
        with self._mesh() as mesh:
            got_mesh, qkv, seg = _per_shard_specs(q, k)
            assert got_mesh is mesh
            assert qkv == P(("fsdp",), None, "tp", None) and seg == P(("fsdp",), None)
            # 3 batch rows do not split over fsdp=2; 1 KV head not over tp=2.
            _, qkv, _ = _per_shard_specs(q[:1].repeat(3, 0), k[:, :, :1])
            assert qkv == P(None, None, None, None)

    @pytest.mark.parametrize("segments", [False, True], ids=["plain", "segment_ids"])
    def test_forward_and_grads_match_einsum(self, flash_on, segments):
        from accelerate_tpu.ops.attention import flash_attention

        q, k, v = self._gqa_qkv()
        seg = (jnp.asarray(np.repeat([[0, 1], [0, 0]], 64, axis=1), jnp.int32)
               if segments else None)

        def loss(fn):
            return lambda q, k, v: (fn(q, k, v) ** 2).sum()

        flash = lambda q, k, v: flash_attention(q, k, v, causal=True, block_q=64,  # noqa: E731
                                                block_k=64, segment_ids=seg)
        ref = lambda q, k, v: _einsum_attention(q, k, v, causal=True,  # noqa: E731
                                                segment_ids=seg)
        with self._mesh():
            out = jax.jit(flash)(q, k, v)
            grads = jax.jit(jax.grad(loss(flash), argnums=(0, 1, 2)))(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref(q, k, v)),
                                   atol=2e-5, rtol=2e-5)
        for a, b, name in zip(grads, jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v), "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4, rtol=5e-4,
                                       err_msg=f"d{name} mismatch")


# -- tiles sized from the shape (flash_pallas.tile_plan) ----------------------

def _grads(fn, q, k, v):
    return jax.grad(lambda q, k, v: (fn(q, k, v) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)


def _segments_by_thirds(B, S):
    """Three documents a row, boundaries off every tile edge."""
    cuts = (S // 3 + 5, 2 * S // 3 - 7)
    return jnp.asarray(np.tile(np.searchsorted(cuts, np.arange(S), side="right"), (B, 1)),
                       jnp.int32)


# name -> shapes and mask kind; tiles are the chooser's. S 2048 gets 1024-wide
# tiles, 1536 gets 512 (3 x 3: interior, diagonal and hidden tiles all occur),
# 1280 gets 256, 384 and 640 only 128.
CHOSEN_TILE_CASES = {
    "causal_s2048": dict(S=2048),
    "causal_s1536": dict(S=1536),
    "noncausal_s1536": dict(S=1536, causal=False),
    "noncausal_s1024": dict(S=1024, causal=False),
    "window200_s1024": dict(S=1024, sliding_window=200),     # tiles capped at 128
    "window300_s1536": dict(S=1536, sliding_window=300),     # 256-wide tiles, band of 4
    "window1024_s2048": dict(S=2048, sliding_window=1024),   # a tile as wide as the band
    "segments_causal_s1536": dict(S=1536, segments=True),
    "segments_noncausal_s1024": dict(S=1024, causal=False, segments=True),
    "segments_window300_s1536": dict(S=1536, sliding_window=300, segments=True),
    "gqa_s1536": dict(S=1536, H=4, G=2),
    "softcap_s1536": dict(S=1536, logit_softcap=7.0),
    "softcap_gqa_window_s1024": dict(S=1024, H=4, G=2, sliding_window=300, logit_softcap=5.0),
    "s384": dict(S=384),
    "s640": dict(S=640),
    "s1280": dict(S=1280),
    "s1280_noncausal_gqa": dict(S=1280, causal=False, H=4, G=2),
}


@pytest.mark.parametrize("name", list(CHOSEN_TILE_CASES))
def test_chosen_tiles_match_reference(name):
    """Forward and backward at the tiles ``tile_plan`` picks (no block given)
    against the einsum reference, one case per mask kind and sequence family."""
    case = dict(CHOSEN_TILE_CASES[name])
    S, H, G = case.pop("S"), case.pop("H", 2), case.pop("G", 2)
    causal = case.pop("causal", True)
    q, k, v = make_gqa_qkv(B=1, S=S, H=H, G=G, D=32, seed=11)
    seg = _segments_by_thirds(1, S) if case.pop("segments", False) else None
    kf, vf = _repeat_kv(q, k, v)
    rep = H // G

    def flash(q, k, v):
        return pallas_flash_attention(q, k, v, causal=causal, segment_ids=seg, **case)

    def ref(q, kf, vf):
        return _einsum_attention(q, kf, vf, causal=causal, segment_ids=seg, **case)

    np.testing.assert_allclose(np.asarray(flash(q, k, v)), np.asarray(ref(q, kf, vf)),
                               atol=3e-5, rtol=3e-5)
    gq, gkf, gvf = _grads(ref, q, kf, vf)
    gk = gkf.reshape(1, S, G, rep, 32).sum(axis=3)
    gv = gvf.reshape(1, S, G, rep, 32).sum(axis=3)
    for a, b, n in zip(_grads(flash, q, k, v), (gq, gk, gv), ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-3, rtol=1e-3,
                                   err_msg=f"{n} mismatch")


def _visible_tiles(S_q, S_k, bq, bk, causal, window):
    """Brute force from the pair mask: [num_q, num_k] booleans — does tile
    (qi, ki) hold a kept pair, and does it hold both kept and masked pairs."""
    qp, kp = np.arange(S_q)[:, None], np.arange(S_k)[None, :]
    keep = np.ones((S_q, S_k), bool)
    if causal:
        keep &= kp <= qp
    if window is not None:
        keep &= kp > qp - window
    tiles = keep.reshape(S_q // bq, bq, S_k // bk, bk)
    return tiles.any(axis=(1, 3)), tiles.any(axis=(1, 3)) & ~tiles.all(axis=(1, 3))


PLAN_SHAPES = {
    "cell_s4096_d128_bf16": dict(S_q=4096, S_k=4096, D=128, dtype="bfloat16"),
    "fp32_s2048": dict(S_q=2048, S_k=2048, D=128, dtype="float32"),
    "window1024_s4096": dict(S_q=4096, S_k=4096, D=128, dtype="bfloat16", window=1024),
    "window200_s1024": dict(S_q=1024, S_k=1024, D=64, dtype="bfloat16", window=200),
    "window300_s1536": dict(S_q=1536, S_k=1536, D=64, dtype="bfloat16", window=300),
    "d256_softcap_fp32": dict(S_q=2048, S_k=2048, D=256, dtype="float32", softcap=True),
    "noncausal_s1536": dict(S_q=1536, S_k=1536, D=64, dtype="bfloat16", causal=False),
    "s384": dict(S_q=384, S_k=384, D=64, dtype="bfloat16"),
    "s640": dict(S_q=640, S_k=640, D=64, dtype="bfloat16"),
    "s1280": dict(S_q=1280, S_k=1280, D=64, dtype="bfloat16"),
    "pinned_64x128_window96": dict(S_q=512, S_k=512, D=32, dtype="float32", window=96,
                                   block_q=64, block_k=128),
    "pinned_128x64_window200": dict(S_q=512, S_k=512, D=32, dtype="float32", window=200,
                                    block_q=128, block_k=64),
}


@pytest.mark.parametrize("kernel", ["fwd", "dq", "dkdv"])
@pytest.mark.parametrize("name", list(PLAN_SHAPES))
def test_tile_plan_counts_match_the_mask(name, kernel):
    """The plan's tiles divide the sequence, stay inside the window's band and
    the VMEM budget, and its counts are the mask's own: steps that compute =
    tiles with a kept pair, steps that mask = tiles the mask's edge crosses."""
    from accelerate_tpu.ops import flash_pallas

    shape = PLAN_SHAPES[name]
    plan = flash_pallas.tile_plan(kernel=kernel, **shape)
    S_q, S_k, window = shape["S_q"], shape["S_k"], shape.get("window")
    assert S_q % plan.block_q == 0 and S_k % plan.block_k == 0
    if "block_q" not in shape:
        assert plan.block_q in flash_pallas.TILE_SIZES and plan.block_k in flash_pallas.TILE_SIZES
        assert plan.vmem_bytes <= flash_pallas.VMEM_BUDGET
        if window is not None:
            assert max(plan.block_q, plan.block_k) <= max(window, 128)
    assert plan.vmem_bytes < plan.vmem_limit_bytes
    visible, crossed = _visible_tiles(S_q, S_k, plan.block_q, plan.block_k,
                                      shape.get("causal", True), window)
    assert plan.compute_steps == visible.sum()
    assert plan.masked_steps == crossed.sum()
    assert plan.compute_steps <= plan.steps == plan.grid[0] * plan.grid[1]


@pytest.mark.parametrize("name,want", [
    ("s384", 128), ("s640", 128), ("s1280", 256), ("fp32_s2048", 1024),
    ("window200_s1024", 128), ("window300_s1536", 256), ("window1024_s4096", 1024)])
def test_tile_plan_falls_back_where_nothing_larger_divides_or_fits_the_band(name, want):
    from accelerate_tpu.ops.flash_pallas import tile_plan

    for kernel in ("fwd", "dq", "dkdv"):
        plan = tile_plan(kernel=kernel, **PLAN_SHAPES[name])
        assert (plan.block_q, plan.block_k) == (want, want), (kernel, plan)


def test_tile_plan_shrinks_tiles_to_the_vmem_budget(monkeypatch):
    """Halve the budget and the widest kernel (dk/dv, fp32, D 256, softcap)
    steps down; its wide side stays k."""
    from accelerate_tpu.ops import flash_pallas

    shape = PLAN_SHAPES["d256_softcap_fp32"]
    full = flash_pallas.tile_plan(kernel="dkdv", **shape)
    monkeypatch.setattr(flash_pallas, "VMEM_BUDGET", full.vmem_bytes // 2)
    flash_pallas.tile_plan.cache_clear()
    try:
        small = flash_pallas.tile_plan(kernel="dkdv", **shape)
    finally:
        flash_pallas.tile_plan.cache_clear()
    assert small.vmem_bytes <= full.vmem_bytes // 2
    assert small.block_q * small.block_k < full.block_q * full.block_k
    assert small.block_k >= small.block_q


@pytest.mark.parametrize("kernel", ["fwd", "dq", "dkdv"])
def test_cell_shape_takes_a_sixteenth_of_the_steps(kernel):
    """Mistral-7B at seq 4096 (the training cell): 128 x 128 tiles made
    32 x 32 = 1024 grid steps a head and kernel."""
    from accelerate_tpu.ops.flash_pallas import tile_plan

    plan = tile_plan(kernel=kernel, **PLAN_SHAPES["cell_s4096_d128_bf16"])
    assert plan.steps * 16 <= (4096 // 128) ** 2
    pinned = tile_plan(kernel=kernel, block_q=128, block_k=128,
                       **PLAN_SHAPES["cell_s4096_d128_bf16"])
    assert pinned.steps == 1024 and pinned.compute_steps == 32 * 33 // 2


def _captured_calls(monkeypatch, fn, *args):
    """Run ``fn`` with ``pl.pallas_call`` replaced by a recorder: the grids and
    BlockSpecs the kernels would be launched with, and zeros for results."""
    from accelerate_tpu.ops import flash_pallas

    calls = []

    def fake_pallas_call(kernel, *, grid, in_specs, out_specs, out_shape, **kw):
        calls.append(dict(grid=grid, in_specs=in_specs, kernel=kernel))
        shapes = out_shape if isinstance(out_shape, (list, tuple)) else [out_shape]
        outs = [jnp.zeros(s.shape, s.dtype) for s in shapes]
        return lambda *a: outs if isinstance(out_shape, (list, tuple)) else outs[0]

    monkeypatch.setattr(flash_pallas.pl, "pallas_call", fake_pallas_call)
    fn(*args)
    return calls


@pytest.mark.parametrize("name,opts", [
    ("causal_512", dict(block_q=128, block_k=128)),
    ("causal_chosen_1536", dict(S=1536)),
    ("causal_rect_64x128", dict(block_q=64, block_k=128)),
    ("causal_rect_128x64", dict(block_q=128, block_k=64)),
    ("window96_64x128", dict(block_q=64, block_k=128, window=96)),
    ("window200_128x64", dict(block_q=128, block_k=64, window=200)),
    ("segments_window70", dict(block_q=64, block_k=64, window=70, segments=True)),
])
def test_hidden_steps_repeat_the_previous_block_index(name, opts, monkeypatch):
    """No DMA for a tile nothing can see: walking each kernel's grid in order,
    the blocks fetched along the banded axis are exactly the tiles with a kept
    pair, each once and in order — so every other step's index, in every index
    map, repeats the step before it and the pipeline fetches nothing."""
    from accelerate_tpu.ops import flash_pallas

    opts = dict(opts)
    S, window = opts.pop("S", 512), opts.pop("window", None)
    H, G, D = 4, 2, 32
    q = jnp.zeros((1, H, S, D), jnp.float32)
    kv = jnp.zeros((1, G, S, D), jnp.float32)
    lse = jnp.zeros((1, H, S, flash_pallas.LANES), jnp.float32)
    seg = jnp.zeros((1, 1, S), jnp.int32) if opts.pop("segments", False) else None
    bq, bk = opts.get("block_q"), opts.get("block_k")

    def launch_all():
        flash_pallas._flash_fwd(q, kv, kv, 1.0, True, window, bq, bk, segment_ids=seg)
        flash_pallas._flash_bwd(1.0, True, window, bq, bk, None, (q, kv, kv, q, lse), q,
                                segment_ids=seg)

    fwd, dkdv, dq = _captured_calls(monkeypatch, launch_all)
    for kernel, call in (("fwd", fwd), ("dq", dq), ("dkdv", dkdv)):
        plan = flash_pallas.tile_plan(S, S, D, "float32", window, seg is not None, kernel,
                                      block_q=bq, block_k=bk)
        visible, _ = _visible_tiles(S, S, plan.block_q, plan.block_k, True, window)
        grid = call["grid"]
        assert grid[2] == plan.grid[0] and grid[3] % plan.grid[1] == 0
        # the operands blocked along the banded axis: k, v (+ k segments) for the
        # q-major kernels; q, do, lse, delta (+ q segments) for dk/dv
        banded = {"fwd": [1, 2, 4], "dq": [1, 2, 7], "dkdv": [0, 3, 4, 5, 6]}[kernel]
        banded = banded if seg is not None else banded[:-1]
        for major in range(grid[2]):
            want = np.flatnonzero(visible[major] if kernel != "dkdv" else visible[:, major])
            for i in banded:
                index_map = call["in_specs"][i].index_map
                steps = [tuple(int(x) for x in index_map(0, 1, major, j))
                         for j in range(grid[3])]
                tile_axis = 2   # (b, head, tile, 0) and, for segment ids, (b, 0, tile)
                band = plan.grid[1]   # dk/dv sweeps the band once per query head of the group
                for sweep in (steps[i:i + band] for i in range(0, len(steps), band)):
                    fetched = [s for j, s in enumerate(sweep) if j == 0 or s != sweep[j - 1]]
                    assert [s[tile_axis] for s in fetched] == list(want), (kernel, i, major)


@pytest.mark.parametrize("bq,bk,window", [
    (128, 128, None), (64, 128, None), (128, 64, None), (256, 256, 300), (64, 128, 96),
    (128, 64, 200), (64, 64, 1), (128, 128, 512)])
def test_mask_is_applied_only_on_crossed_tiles(bq, bk, window):
    """``_tile_crossed`` — the predicate that sends a visible tile down the
    masked or the plain path — is true exactly where the tile holds both a kept
    and a masked pair, for tiles of either aspect and any window alignment."""
    from accelerate_tpu.ops.flash_pallas import _tile_crossed

    S = 1024
    visible, crossed = _visible_tiles(S, S, bq, bk, True, window)
    qi, ki = np.arange(S // bq)[:, None], np.arange(S // bk)[None, :]
    got = np.broadcast_to(_tile_crossed(qi, ki, bq, bk, True, window), visible.shape)
    np.testing.assert_array_equal(got[visible], crossed[visible])
    if window is None or window >= 2 * max(bq, bk):
        assert crossed[visible].sum() < visible.sum()   # interior tiles exist and go unmasked


def test_noncausal_kernels_never_build_a_mask(monkeypatch):
    from accelerate_tpu.ops import flash_pallas

    def no_mask(*a, **kw):
        raise AssertionError("_pair_mask traced for an unmasked call")

    monkeypatch.setattr(flash_pallas, "_pair_mask", no_mask)
    q, k, v = make_qkv(B=1, S=256, H=1, D=32)
    out = pallas_flash_attention(q, k, v, causal=False)
    ref = _einsum_attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_plans_are_logged_once_per_shape(caplog):
    import logging

    from accelerate_tpu.ops import flash_pallas

    q, k, v = make_qkv(B=1, S=384, H=1, D=32, seed=9)
    flash_pallas._log_plans.cache_clear()
    with caplog.at_level(logging.DEBUG, logger=flash_pallas.logger.name):
        pallas_flash_attention(q, k, v, causal=True)
        pallas_flash_attention(q, k, v, causal=True)
    lines = [r.getMessage() for r in caplog.records if r.name == flash_pallas.logger.name]
    assert len(lines) == 3 and all("tiles 128x128" in m and "9 steps" in m for m in lines)
    assert [m.split()[1] for m in lines] == ["fwd", "dq", "dkdv"]
