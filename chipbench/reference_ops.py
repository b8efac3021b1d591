"""Plain jax.numpy pieces of the reference implementations: float32, no
kernels, no cache, no batching tricks, nothing imported from the program.

``matmul(precision)`` is the one place a precision enters. ``float32``
multiplies at ``highest``; the lower ones round BOTH operands of every
product (forward and backward) to that type first — ``float8`` with one scale
per tensor, as fp8 training does — and then multiply exactly. They are the
controls of "How correct is decided": what a later PR would be tempted by.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _round_bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _round_fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0   # e4m3's largest finite
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


ROUNDERS = {"float32": lambda x: x, "bfloat16": _round_bf16, "float8": _round_fp8}

# The nearest precision below the one a configuration states.
CONTROL_OF = {"float32": "bfloat16", "bfloat16": "float8", "float16": "float8"}


def matmul(precision: str):
    """``f(a [..., k], b [k, n]) -> [..., n]`` in float32 with both operands
    rounded to ``precision``; its backward rounds the cotangent too."""
    rnd = ROUNDERS[precision]

    def exact(a, b):
        return jnp.matmul(a, b, precision=HIGHEST, preferred_element_type=jnp.float32)

    @jax.custom_vjp
    def mm(a, b):
        return exact(rnd(a), rnd(b))

    def fwd(a, b):
        return mm(a, b), (a, b)

    def bwd(res, g):
        a, b = res
        g, a, b = rnd(g), rnd(a), rnd(b)
        da = exact(g, b.T)
        db = exact(a.reshape(-1, a.shape[-1]).T, g.reshape(-1, g.shape[-1]))
        return da, db

    mm.defvjp(fwd, bwd)
    return mm


def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rope(x, theta):
    """x: [S, heads, head_dim] at positions 0..S-1; rotates the two halves of
    each head (the Hugging Face layout of the Mistral family)."""
    s, _, d = x.shape
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def causal_attention(q, k, v, window=None):
    """q: [S, H, D]; k, v: [S, G, D] (grouped-query: H // G query heads share
    a KV head). One KV group at a time, rematerialised in the backward, so
    that the [S, S] scores of all heads never coexist."""
    s, h, d = q.shape
    g = k.shape[1]
    qg = q.reshape(s, g, h // g, d).transpose(1, 2, 0, 3)      # [G, R, S, D]
    kg, vg = k.transpose(1, 0, 2), v.transpose(1, 0, 2)        # [G, S, D]
    pos = jnp.arange(s)
    mask = pos[None, :] <= pos[:, None]
    if window is not None and window < s:
        mask &= pos[None, :] > pos[:, None] - window

    @jax.checkpoint
    def one_group(args):
        qq, kk, vv = args
        scores = jnp.einsum("rsd,td->rst", qq, kk, precision=HIGHEST) * d ** -0.5
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return jnp.einsum("rst,td->rsd", probs, vv, precision=HIGHEST)

    out = jax.lax.map(one_group, (qg, kg, vg))                 # [G, R, S, D]
    return out.transpose(2, 0, 1, 3).reshape(s, h * d)


def attention_block(x, p, cfg, mm):
    """Pre-normed x [S, hidden] -> attention output [S, hidden]; ``p`` holds
    q_proj/k_proj/v_proj/o_proj kernels laid out [in, out]."""
    s = x.shape[0]
    n_q, n_kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg.get("head_dim") or cfg["hidden_size"] // n_q
    q = rope(mm(x, p["q_proj"]["kernel"]).reshape(s, n_q, d), cfg["rope_theta"])
    k = rope(mm(x, p["k_proj"]["kernel"]).reshape(s, n_kv, d), cfg["rope_theta"])
    v = mm(x, p["v_proj"]["kernel"]).reshape(s, n_kv, d)
    return mm(causal_attention(q, k, v, cfg.get("sliding_window")), p["o_proj"]["kernel"])


def swiglu(x, gate, up, down, mm):
    return mm(jax.nn.silu(mm(x, gate)) * mm(x, up), down)


def in_chunks(fn, x, n_chunks):
    """``fn`` over ``n_chunks`` row-blocks of x, rematerialised, so that wide
    intermediates exist one block at a time."""
    rows = x.shape[0]
    if n_chunks <= 1 or rows % n_chunks:
        return fn(x)
    out = jax.lax.map(jax.checkpoint(fn), x.reshape(n_chunks, rows // n_chunks, *x.shape[1:]))
    return out.reshape(rows, *out.shape[2:])


def leaf_norms(tree) -> jnp.ndarray:
    """Frobenius norm of every leaf, in flattening order."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


def seed_key(seed: int):
    """A key from any whole number up to 2**63 (PRNGKey alone takes 32 bits)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def make_leaf(key, index, shape, std, dtype):
    """Leaf ``index`` of a seeded weight tree: ones where ``std`` is None
    (norm scales), else normal(0, std), made in float32 and cast."""
    if std is None:
        return jnp.ones(shape, dtype)
    draw = jax.random.normal(jax.random.fold_in(key, index), shape, jnp.float32)
    return (draw * std).astype(dtype)


def make_tree(table, key, dtype):
    """``table``: [(path tuple, shape, std)] -> nested dict of seeded leaves.
    Trace it inside one jit with ``key`` as the argument."""
    tree: dict = {}
    for i, (path, shape, std) in enumerate(table):
        node = tree
        for name in path[:-1]:
            node = node.setdefault(name, {})
        node[path[-1]] = make_leaf(key, i, shape, std, dtype)
    return tree


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _delta_norm(leaf, key, index, std, shape, is_ones, dtype_name):
    seeded = make_leaf(key, index, shape, None if is_ones else std, jnp.dtype(dtype_name))
    diff = leaf.astype(jnp.float32) - seeded.astype(jnp.float32)
    return jnp.sqrt(jnp.sum(jnp.square(diff)))


def delta_norms(tree, table, key, dtype) -> list:
    """Per leaf ``|leaf - seeded leaf|``, one leaf at a time so that no second
    copy of the weights ever exists on the device."""
    out = []
    for i, (path, shape, std) in enumerate(table):
        leaf = tree
        for name in path:
            leaf = leaf[name]
        out.append(_delta_norm(leaf, key, i, 0.0 if std is None else std, tuple(shape),
                               std is None, jnp.dtype(dtype).name))
    return [float(x) for x in jax.device_get(out)]


def f32_lazy(tree):
    """The small leaves of a layer as float32 (kernels of a few MB; stacked
    expert weights stay as they are and are cast one expert at a time)."""
    return jax.tree.map(lambda a: a.astype(jnp.float32) if a.ndim < 3 else a, tree)
