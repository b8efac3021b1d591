"""Family ``mixtral``: the sparse decoder block of the Mixtral family
(RMSNorm, RoPE, grouped-query causal attention, a top-k router over SwiGLU
experts), as a served model.

* ``leaf_table`` / ``make_params``: the seeded weights in the served type;
* ``build_server``: the program under test, built the way ``accelerate-tpu
  serve`` builds it and fronted by its HTTP gateway;
* ``reference_logits``: the plain float32 reference of one full forward pass.
  It imports nothing of the program and is given nothing the program made.
"""

from __future__ import annotations

import contextlib
import sys

import jax
import jax.numpy as jnp

from chipbench import harness
from chipbench import reference_ops as ops


def leaf_table(cfg: dict) -> list:
    """[(path, shape, std)] in the layout of the program's parameter tree."""
    h, f, v, e = (cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"],
                  cfg["num_local_experts"])
    d = cfg.get("head_dim") or h // cfg["num_attention_heads"]
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    table = [(("embed_tokens", "embedding"), (v, h), 1.0)]
    for i in range(cfg["num_hidden_layers"]):
        layer = (f"layers_{i}",)
        table += [
            (layer + ("input_norm", "scale"), (h,), None),
            (layer + ("self_attn", "q_proj", "kernel"), (h, q), h ** -0.5),
            (layer + ("self_attn", "k_proj", "kernel"), (h, kv), h ** -0.5),
            (layer + ("self_attn", "v_proj", "kernel"), (h, kv), h ** -0.5),
            (layer + ("self_attn", "o_proj", "kernel"), (q, h), q ** -0.5),
            (layer + ("post_attn_norm", "scale"), (h,), None),
            (layer + ("mlp", "router"), (h, e), h ** -0.5),
            (layer + ("mlp", "experts", "gate_proj"), (e, h, f), h ** -0.5),
            (layer + ("mlp", "experts", "up_proj"), (e, h, f), h ** -0.5),
            (layer + ("mlp", "experts", "down_proj"), (e, f, h), f ** -0.5),
        ]
    table += [(("norm", "scale"), (h,), None), (("lm_head", "kernel"), (h, v), h ** -0.5)]
    return table


def make_params(cfg: dict, seed: int, dtype=None):
    """The whole weight tree on the device in one jitted call from the seed,
    in the type it is served in."""
    dtype = jnp.dtype(dtype or cfg["assumed"]["weights_dtype"])
    table = leaf_table(cfg)
    return jax.jit(lambda key: ops.make_tree(table, key, dtype))(ops.seed_key(seed))


# ---------------------------------------------------------------------------
# The program under test
# ---------------------------------------------------------------------------

class Server:
    """The fleet ``commands.serve.build_fleet`` built, behind the gateway
    ``serve`` puts in front of it."""

    def __init__(self, replica_set, gateway):
        self.replica_set, self.gateway = replica_set, gateway

    @property
    def url(self) -> str:
        return self.gateway.url

    def reset_stats(self) -> None:
        self.replica_set.engine(0).stats.reset()

    def stats(self) -> dict:
        return dict(self.replica_set.engine(0).stats.summary())

    def slots(self) -> int:
        return self.replica_set.engine(0).max_slots

    def shutdown(self) -> None:
        self.gateway.shutdown(drain=True)

    def free(self) -> None:
        """Drop the engines so the reference has the chip."""
        self.replica_set = self.gateway = None
        harness.release_program_state()


def build_server(cfg: dict, params) -> Server:
    from accelerate_tpu.commands import serve
    from accelerate_tpu.models.mixtral import MixtralConfig, MixtralForCausalLM
    from accelerate_tpu.serving import ServingGateway

    a = cfg["assumed"]
    module = MixtralForCausalLM(MixtralConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        max_position_embeddings=cfg["max_position_embeddings"],
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        sliding_window=cfg["sliding_window"],
        num_experts=cfg["num_local_experts"], top_k=cfg["num_experts_per_tok"]))
    argv = ["--port", "0", "--max-slots", str(a["max_slots"]), "--max-len", str(a["max_len"]),
            "--prefill-chunk", str(a["prefill_chunk"])]
    if a.get("max_pages") is not None:
        argv += ["--max-pages", str(a["max_pages"])]
    args = serve.serve_command_parser().parse_args(argv)
    with contextlib.redirect_stdout(sys.stderr):      # its progress lines
        replica_set = serve.build_fleet(args, module, params)
    gateway = ServingGateway(replica_set, config=serve.gateway_config(args))
    gateway.start()
    return Server(replica_set, gateway)


# ---------------------------------------------------------------------------
# The plain reference
# ---------------------------------------------------------------------------

def reference_logits(params, ids, cfg: dict, mm):
    """ids [S] -> logits [S, vocab] (float32): one full causal forward pass,
    every expert computed for every token and weighted by its gate (zero for
    the experts a token did not choose)."""
    eps, k = cfg["rms_norm_eps"], cfg["num_experts_per_tok"]
    x = params["embed_tokens"]["embedding"][ids].astype(jnp.float32)
    for i in range(cfg["num_hidden_layers"]):
        layer = ops.f32_lazy(params[f"layers_{i}"])
        x = x + ops.attention_block(ops.rms_norm(x, layer["input_norm"]["scale"], eps),
                                    layer["self_attn"], cfg, mm)
        normed = ops.rms_norm(x, layer["post_attn_norm"]["scale"], eps)
        probs = jax.nn.softmax(mm(normed, layer["mlp"]["router"]), axis=-1)
        top_p, top_i = jax.lax.top_k(probs, k)
        top_p = top_p / top_p.sum(-1, keepdims=True)
        gates = jnp.zeros_like(probs).at[jnp.arange(x.shape[0])[:, None], top_i].set(top_p)
        experts = params[f"layers_{i}"]["mlp"]["experts"]

        def one_expert(w, normed=normed):
            gate, up, down = (t.astype(jnp.float32) for t in w)
            return ops.swiglu(normed, gate, up, down, mm)

        outs = jax.lax.map(one_expert, (experts["gate_proj"], experts["up_proj"],
                                        experts["down_proj"]))          # [E, S, hidden]
        x = x + jnp.einsum("se,esh->sh", gates, outs, precision=ops.HIGHEST)
    x = ops.rms_norm(x, params["norm"]["scale"].astype(jnp.float32), eps)
    return mm(x, params["lm_head"]["kernel"].astype(jnp.float32))
