"""Family ``mistral_dense``: the dense decoder block of the Mistral family
(RMSNorm, RoPE, grouped-query causal attention with a sliding window,
SwiGLU), as a training job.

Three parts, found by a configuration's ``"family"``:

* ``leaf_table`` / ``make_params``: the seeded weights, made by the benchmark
  (not by the program's initialiser) so that the reference can make them again;
* ``build_trainer``: the program under test, through its normal entry points;
* ``reference_train``: the plain float32 reference of the same steps. It
  imports nothing of the program and is given nothing the program made.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import harness
from chipbench import reference_ops as ops


def leaf_table(cfg: dict) -> list:
    """[(path, shape, std)] in the layout of the program's parameter tree
    (which is the published checkpoint's naming, kernels stored [in, out])."""
    h, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    d = cfg.get("head_dim") or h // cfg["num_attention_heads"]
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    table = [(("model", "embed_tokens", "embedding"), (v, h), 1.0)]
    for i in range(cfg["num_hidden_layers"]):
        layer = ("model", f"layers_{i}")
        table += [
            (layer + ("input_norm", "scale"), (h,), None),
            (layer + ("self_attn", "q_proj", "kernel"), (h, q), h ** -0.5),
            (layer + ("self_attn", "k_proj", "kernel"), (h, kv), h ** -0.5),
            (layer + ("self_attn", "v_proj", "kernel"), (h, kv), h ** -0.5),
            (layer + ("self_attn", "o_proj", "kernel"), (q, h), q ** -0.5),
            (layer + ("post_attn_norm", "scale"), (h,), None),
            (layer + ("mlp", "gate_proj", "kernel"), (h, f), h ** -0.5),
            (layer + ("mlp", "up_proj", "kernel"), (h, f), h ** -0.5),
            (layer + ("mlp", "down_proj", "kernel"), (f, h), f ** -0.5),
        ]
    table += [(("model", "norm", "scale"), (h,), None),
              (("lm_head", "kernel"), (h, v), h ** -0.5)]
    return table


def make_params(cfg: dict, seed: int, dtype=jnp.float32):
    """The whole weight tree on the device in one jitted call from the seed."""
    table = leaf_table(cfg)
    return jax.jit(lambda key: ops.make_tree(table, key, dtype))(ops.seed_key(seed))


# ---------------------------------------------------------------------------
# The program under test
# ---------------------------------------------------------------------------

class Trainer:
    """What ``build_trainer`` hands the driver: the compiled step with its
    state. ``step(batch)`` is the callable ``compile_train_step`` returned."""

    def __init__(self, accelerator, model, optimizer, loader, step):
        self.accelerator, self.model, self.optimizer = accelerator, model, optimizer
        self.loader, self.step = loader, step

    @property
    def params(self):
        return self.model.params

    def adam_mu(self):
        """Adam's first moment out of the optimizer's state."""
        found = [s.mu for s in jax.tree.leaves(
            self.optimizer.opt_state, is_leaf=lambda s: hasattr(s, "mu")) if hasattr(s, "mu")]
        if len(found) != 1:
            raise RuntimeError(f"expected one Adam state in the optimizer, found {len(found)}")
        return found[0]

    def free(self):
        """Drop the state and the compiled step so the reference has the chip."""
        self.accelerator.free_memory()
        self.model.params = None
        self.optimizer.opt_state = None
        self.step = self.loader = self.accelerator = None
        harness.release_program_state()


def build_trainer(cfg: dict, params, dataset, batch_size: int) -> Trainer:
    import optax

    from accelerate_tpu import Accelerator, Model, NumpyDataLoader
    from accelerate_tpu.models.llama import LlamaConfig, LlamaForCausalLM, causal_lm_loss

    a = cfg["assumed"]
    module = LlamaForCausalLM(LlamaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        max_position_embeddings=cfg["max_position_embeddings"],
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        sliding_window=cfg["sliding_window"], remat=a["remat"],
        use_flash_attention=a["flash_attention"]))
    accelerator = Accelerator(mixed_precision="bf16")
    tx = optax.adamw(a["learning_rate"], b1=a["adam_b1"], b2=a["adam_b2"], eps=a["adam_eps"],
                     weight_decay=a["weight_decay"])
    model, optimizer, loader = accelerator.prepare(
        Model(module, params), tx, NumpyDataLoader(dataset, batch_size=batch_size))
    step = accelerator.compile_train_step(causal_lm_loss(module.apply),
                                          max_grad_norm=a["max_grad_norm"])
    return Trainer(accelerator, model, optimizer, loader, step)


# ---------------------------------------------------------------------------
# The plain reference
# ---------------------------------------------------------------------------

def reference_logits(params, ids, cfg: dict, mm):
    """ids [S] -> hidden states before the head, [S, hidden] (float32)."""
    p = params["model"]
    x = p["embed_tokens"]["embedding"][ids]
    for i in range(cfg["num_hidden_layers"]):
        layer = p[f"layers_{i}"]

        @jax.checkpoint
        def block(x, layer=layer):
            eps = cfg["rms_norm_eps"]
            x = x + ops.attention_block(ops.rms_norm(x, layer["input_norm"]["scale"], eps),
                                        layer["self_attn"], cfg, mm)
            m = layer["mlp"]
            mlp = lambda t: ops.swiglu(t, m["gate_proj"]["kernel"], m["up_proj"]["kernel"],
                                       m["down_proj"]["kernel"], mm)
            normed = ops.rms_norm(x, layer["post_attn_norm"]["scale"], eps)
            return x + ops.in_chunks(mlp, normed, 8)

        x = block(x)
    return ops.rms_norm(x, p["norm"]["scale"], cfg["rms_norm_eps"])


def reference_loss(params, batch, cfg: dict, mm):
    """Mean next-token cross-entropy over every row's S-1 targets."""
    head = params["lm_head"]["kernel"]

    def row_nll(ids):
        hidden = reference_logits(params, ids, cfg, mm)[:-1]
        targets = ids[1:]

        n = hidden.shape[0]
        pad = (-n) % 8                      # S-1 rows: pad to 8 equal chunks, masked
        hid = jnp.pad(hidden, ((0, pad), (0, 0))).reshape(8, -1, hidden.shape[1])
        tgt = jnp.pad(targets, (0, pad)).reshape(8, -1)
        live = (jnp.arange(n + pad) < n).reshape(8, -1)

        def masked(args):
            hid, tgt, live = args
            logp = jax.nn.log_softmax(mm(hid, head), axis=-1)
            nll = -jnp.take_along_axis(logp, tgt[:, None], axis=-1)[:, 0]
            return jnp.sum(jnp.where(live, nll, 0.0))

        return jax.lax.map(jax.checkpoint(masked), (hid, tgt, live)).sum()

    # Rows unrolled, not lax.map: the scan's gradient carry costs 1.1 GiB more.
    total = sum(row_nll(batch[r]) for r in range(batch.shape[0]))
    return total / (batch.shape[0] * (batch.shape[1] - 1))


def reference_train(cfg: dict, seed: int, batches, precision: str = "float32",
                    fault: str | None = None) -> dict:
    """Follow ``len(batches)`` steps of AdamW with the global-norm clip from
    the seeded weights. Returns each step's loss, the first step's gradient
    norm before the clip, the per-leaf norms of the first gradient as the
    optimizer gets it (after the clip), and the per-leaf norms of the
    parameters' change over all the steps.

    ``fault`` plants, in the reference put in the program's place, a fault a
    training cell can have: ``half_batch`` (the second half of the rows left
    out, the mean taken over the rest)."""
    a = cfg["assumed"]
    mm = ops.matmul(precision)
    table = leaf_table(cfg)
    key = ops.seed_key(seed)
    params = jax.jit(lambda k: ops.make_tree(table, k, jnp.float32))(key)
    zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p))
    mu, nu = zeros(params), zeros(params)
    b1, b2, eps = a["adam_b1"], a["adam_b2"], a["adam_eps"]
    lr, wd, clip = a["learning_rate"], a["weight_decay"], a["max_grad_norm"]

    # Two programs, not one: fused, XLA schedules 8.7 GiB of temporaries on top
    # of the 7.8 GiB of state (compile for a described v5e), which does not fit.
    grads_of = jax.jit(jax.value_and_grad(functools.partial(reference_loss, cfg=cfg, mm=mm)))

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def update(params, mu, nu, grads, t):
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
        factor = jnp.minimum(1.0, clip / (gnorm + 1e-6))
        grads = jax.tree.map(lambda g: g * factor, grads)
        clipped_norms = ops.leaf_norms(grads)
        mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
        nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
        c1, c2 = 1 - b1 ** t, 1 - b2 ** t
        params = jax.tree.map(
            lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps) + wd * p),
            params, mu, nu)
        return params, mu, nu, gnorm, clipped_norms

    losses, gnorm1, grad_norms = [], None, None
    for t, batch in enumerate(batches, start=1):
        batch = jnp.asarray(batch)
        if fault == "half_batch":
            batch = batch[: batch.shape[0] // 2]
        loss, grads = grads_of(params, batch)
        params, mu, nu, gnorm, clipped = update(params, mu, nu, grads, jnp.float32(t))
        del grads
        losses.append(float(loss))
        if t == 1:
            gnorm1, grad_norms = float(gnorm), np.asarray(clipped).tolist()
    return {"losses": losses, "gnorm": gnorm1, "grad_norms": grad_norms,
            "delta_norms": ops.delta_norms(params, table, key, jnp.float32),
            "leaves": ["/".join(path) for path, _, _ in table]}
