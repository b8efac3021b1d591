"""The Pallas selective scan (``ops/selective_scan.py``), run by the Pallas
interpreter on the CPU, against the Mamba-1 recurrence stepped over time in
float32 and against the plain reference's Mamba layer
(``models/reference/phi4flash.py``). The channels span two blocks of 128 and
the states 16 sublanes, as at the published widths; a prompt longer than a
time block takes the state across blocks in one call, a chunk's state is
handed to the next call, and a step with ``dt == 0`` leaves the state bit for
bit as it is.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.models import phi4flash
from accelerate_tpu.models.reference import phi4flash as reference
from accelerate_tpu.ops import selective_scan as kernel

N, D = 16, 256                          # states; channels: two blocks of 128
scan = jax.jit(kernel.selective_scan)
family_scan = jax.jit(phi4flash.selective_scan)      # one step: the plain update


def inputs(b, S, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(k[0], (b, S, D))
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, S, D)) - 2.0)
    A = -jnp.exp(jax.random.normal(k[2], (N, D)))
    B, C = jax.random.normal(k[3], (b, S, N)), jax.random.normal(k[4], (b, S, N))
    return x, dt, A, B, C, jax.random.normal(k[5], (b, N, D))


def stepped(x, dt, A, B, C, h0):
    """The recurrence as written, one step at a time."""
    def step(h, t):
        x, dt, B, C = t
        h = jnp.exp(dt[:, None, :] * A) * h + (dt * x)[:, None, :] * B[:, :, None]
        return h, jnp.einsum("bnd,bn->bd", h, C, precision="highest")

    h, ys = jax.lax.scan(step, h0, tuple(jnp.moveaxis(a, 1, 0) for a in (x, dt, B, C)))
    return jnp.moveaxis(ys, 0, 1), h


def close(got, want):
    return float(jnp.abs(got - want).max()) < 1e-5 * max(1.0, float(jnp.abs(want).max()))


def test_the_tiles_follow_the_shape():
    assert kernel.scan_tiles(256, 5120) == (256, kernel.WIDTH)
    assert kernel.scan_tiles(100, 256) == (104, 128)            # whole groups of 8
    assert kernel.scan_tiles(2, 24) == (8, 24)                  # a width under a lane tile
    assert kernel.scan_tiles(4096, 5120)[0] == kernel.MAX_STEPS


@pytest.mark.parametrize("b", [1, 3])
@pytest.mark.parametrize("S", [2, 100, 256])
def test_the_kernel_is_the_stepped_recurrence(S, b):
    args = inputs(b, S, seed=S + b)
    y, h = scan(*args)
    want_y, want_h = stepped(*args)
    assert y.shape == (b, S, D) and h.shape == (b, N, D)
    assert y.dtype == h.dtype == jnp.float32
    assert close(y, want_y) and close(h, want_h)


def test_steps_of_size_zero_leave_the_state_bit_for_bit():
    """A chunk padded past its prompt (``valid_len``): the last 37 of 100
    steps have ``dt == 0``; the state is the one after step 63 exactly, and
    so are the outputs of the real steps. A call of nothing but such steps
    hands ``h0`` back as it came."""
    x, dt, A, B, C, h0 = inputs(2, 100, seed=7)
    real = 63
    y, h = scan(x, dt.at[:, real:].set(0.0), A, B, C, h0)
    y_real, h_real = scan(x[:, :real], dt[:, :real], A, B[:, :real], C[:, :real], h0)
    assert bool(jnp.all(h == h_real)) and bool(jnp.all(y[:, :real] == y_real))
    _, kept = scan(x, jnp.zeros_like(dt), A, B, C, h0)
    assert bool(jnp.all(kept == h0))


def test_the_state_carries_across_time_blocks_and_calls():
    """300 steps: two time blocks of one call, against the same steps as
    three chunks whose states are handed on (the last a single step, the
    plain update of a tick)."""
    args = inputs(3, 300, seed=11)
    x, dt, A, B, C, h0 = args
    want_y, want_h = stepped(*args)
    y, h = scan(*args)
    assert close(y, want_y) and close(h, want_h)
    ys, h = [], h0
    for lo, hi in ((0, 256), (256, 299), (299, 300)):
        y, h = family_scan(x[:, lo:hi], dt[:, lo:hi], A, B[:, lo:hi], C[:, lo:hi], h)
        ys.append(y)
    assert close(jnp.concatenate(ys, 1), want_y) and close(h, want_h)


def test_a_mamba_layer_is_the_references():
    """The family's Mamba layer over a 40-token prompt (the kernel's path)
    against the plain reference's, which steps the recurrence itself."""
    cfg = phi4flash.Phi4FlashConfig.tiny()
    layer = phi4flash.Mamba(cfg)
    u = jax.random.normal(jax.random.PRNGKey(5), (1, 40, cfg.hidden_size))
    params = layer.init(jax.random.PRNGKey(6), u)["params"]
    params = dict(params, A_log=jax.random.normal(jax.random.PRNGKey(8), params["A_log"].shape),
                  dt_bias=jnp.full_like(params["dt_bias"], -2.0))
    out, y, state = jax.jit(layer.apply)({"params": params}, u)
    want_out, want_y = reference.mamba(u[0], params, cfg)
    np.testing.assert_allclose(y[0], want_y, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(out[0], want_out, rtol=1e-4, atol=1e-4)
    assert state["ssm"].shape == (1, cfg.mamba_d_state, cfg.d_inner)
