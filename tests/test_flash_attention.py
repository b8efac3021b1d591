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
