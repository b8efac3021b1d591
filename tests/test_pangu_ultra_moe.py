"""The pangu_ultra_moe family against its plain reference (CPU, tiny: hidden
64, 4 heads over a latent of 16 values and a rotary key of 4, one dense layer and three expert
layers of 8 experts top-2 with one shared expert, seeded random weights).

Tolerances. Everything here is float32 on the CPU, where a matmul is exact
float32 at any precision setting; the model and the reference order their
sums differently (the absorbed form folds ``kv_b_proj`` into the query, the
expanded form makes keys and values a block at a time, the reference makes
them all at once), which moves a logit of magnitude ~4 by a few 1e-6. ``TOL``
= 5e-5 leaves ten times that; a bfloat16 rounding of the cached latent moves
the same logits by ~1e-2 and fails (the control below).
"""

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.models import llama
from accelerate_tpu.models.pangu_ultra_moe import (PanguMoeMLP, PanguUltraMoeConfig,
                                                    PanguUltraMoeForCausalLM)
from accelerate_tpu.models.reference import pangu_ultra_moe as ref
from accelerate_tpu.serving import ServingEngine
from accelerate_tpu.serving.metrics import ServingStats

TOL = 5e-5


@pytest.fixture(scope="module")
def tiny():
    cfg = PanguUltraMoeConfig.tiny()
    assert (cfg.num_hidden_layers, cfg.first_k_dense_replace, cfg.cache_row_width) == (4, 1, 20)
    model = PanguUltraMoeForCausalLM(cfg)
    return cfg, model, model.init_params(jax.random.PRNGKey(0))


def ids_of(n, seed=1, vocab=256):
    return jax.random.randint(jax.random.PRNGKey(seed), (n,), 1, vocab)


def share_of(params, cfg, first, count):
    """The model and parameters of the rank that holds experts
    ``first .. first + count - 1`` (everything else is held by every rank)."""
    cut = jax.tree.map(lambda a: a, params)
    for i in range(cfg.first_k_dense_replace, cfg.num_hidden_layers):
        experts = params[f"layers_{i}"]["mlp"]["experts"]
        cut[f"layers_{i}"]["mlp"]["experts"] = {
            n: w[first:first + count] for n, w in experts.items()}
    return dataclasses.replace(cfg, held_experts=(first, count)), cut


def through_the_cache(model, params, ids, chunk, prompt, cache_dtype=jnp.float32):
    """Logits of ``ids`` served as the engine's programs serve them: a linear
    full-length latent cache, chunks of ``chunk`` up to ``prompt``, then single
    tokens."""
    n = ids.shape[0]
    cache = model.init_cache(1, n, cache_dtype)
    got = []
    for start in list(range(0, prompt, chunk)) + list(range(prompt, n)):
        stop = start + chunk if start < prompt else start + 1
        logits, cache = model.apply({"params": params}, ids[None, start:stop], cache=cache,
                                    cache_pos=jnp.int32(start))
        got.append(logits[0])
    return jnp.concatenate(got)


# -- (a) the full forward pass, whole and as a held share ---------------------

def test_full_forward_logits_agree_with_the_reference(tiny):
    cfg, model, params = tiny
    ids = ids_of(40)
    logits = model.apply({"params": params}, ids[None])[0]
    assert float(jnp.abs(logits - ref.forward(params, ids, cfg)).max()) < TOL


def test_a_held_share_forward_agrees_with_the_reference_given_the_same_share(tiny):
    cfg, _, params = tiny
    cfg_h, params_h = share_of(params, cfg, 2, 4)
    ids = ids_of(24, seed=2)
    logits = PanguUltraMoeForCausalLM(cfg_h).apply({"params": params_h}, ids[None])[0]
    want = ref.forward(params_h, ids, cfg_h, held=(2, 4))
    assert float(jnp.abs(logits - want).max()) < TOL
    # and it is NOT the uncut model: the absent experts' part is left out
    assert float(jnp.abs(logits - ref.forward(params, ids, cfg)).max()) > 100 * TOL


# -- (b) the shares add up to the uncut layer ---------------------------------

@pytest.mark.parametrize("tokens", [5, 40], ids=["dense_path", "sorted_path"])
def test_the_shares_add_up_to_the_uncut_layer(tiny, tokens):
    """Four ranks of two experts each (the deployment's 16 of 16): the routed
    parts of all shares, with the shared expert counted once, add up to what
    the uncut expert layer gives, in the program and in the reference. (The
    post norm that follows is not linear: the parts add up before it. The
    dense layer, attention and the norms are what every rank computes alike.)"""
    cfg, _, params = tiny
    layer = params["layers_2"]["mlp"]
    x = jax.random.normal(jax.random.PRNGKey(7), (1, tokens, cfg.hidden_size))
    whole = PanguMoeMLP(cfg).apply({"params": layer}, x)
    shared = ref.dense_mlp(x[0], layer["shared_experts"])
    routed_sum, ref_sum = 0.0, 0.0
    for first in range(0, 8, 2):
        cfg_h, params_h = share_of(params, cfg, first, 2)
        part = PanguMoeMLP(cfg_h).apply({"params": params_h["layers_2"]["mlp"]}, x)
        routed_sum = routed_sum + (part[0] - shared)
        ref_sum = ref_sum + ref.routed_part(x[0], params_h["layers_2"]["mlp"], cfg_h, (first, 2))
    assert float(jnp.abs(routed_sum + shared - whole[0]).max()) < TOL
    uncut_routed, uncut_alike = ref.mlp_parts(x[0], layer, cfg, 2)
    assert float(jnp.abs(ref_sum - uncut_routed).max()) < TOL
    assert float(jnp.abs(uncut_routed + uncut_alike - whole[0]).max()) < TOL


def test_the_router_is_a_scaled_renormalised_sigmoid_top_k(tiny):
    cfg, _, params = tiny
    n = jax.random.normal(jax.random.PRNGKey(3), (6, cfg.hidden_size))
    gates = ref.gates_of(n, params["layers_1"]["mlp"]["router"], cfg)
    assert ((gates > 0).sum(-1) == cfg.num_experts_per_tok).all()
    np.testing.assert_allclose(gates.sum(-1), cfg.routed_scaling_factor, rtol=1e-6)


# -- (c) prefill in chunks, then decode, through the cache --------------------

def test_chunked_prefill_then_decode_through_the_cache_gives_the_reference_logits(tiny):
    cfg, model, params = tiny
    ids = ids_of(38, seed=5)
    want = ref.forward(params, ids, cfg)
    got = through_the_cache(model, params, ids, chunk=8, prompt=24)
    assert float(jnp.abs(got - want).max()) < TOL
    # the control: the cached latent in bfloat16 where float32 is stated fails it
    low = through_the_cache(model, params, ids, chunk=8, prompt=24, cache_dtype=jnp.bfloat16)
    assert float(jnp.abs(low - want).max()) > 20 * TOL


@pytest.mark.parametrize("block", [None, 8], ids=["one_block", "key_blocks_of_8"])
def test_the_absorbed_and_the_expanded_form_agree(tiny, block, monkeypatch):
    """Both forms of the latent attention, for a chunk and for single tokens,
    over the whole view in one block and over the visible key blocks."""
    cfg, model, params = tiny
    ids = ids_of(38, seed=6)
    want = ref.forward(params, ids, cfg)
    if block is not None:
        monkeypatch.setattr(llama, "cached_key_block", lambda rows, view: min(block, view))
    for form in ("absorbed", "expanded"):
        monkeypatch.setattr(llama, "latent_attention_form", lambda *a, form=form: form)
        got = through_the_cache(model, params, ids, chunk=8, prompt=24)
        assert float(jnp.abs(got - want).max()) < TOL, form


def test_the_form_follows_from_the_shape():
    cfg = PanguUltraMoeConfig()
    shape = (cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.v_head_dim)
    assert llama.latent_attention_form(1, *shape) == "absorbed"        # a tick
    assert llama.latent_attention_form(5, *shape) == "absorbed"        # a speculative verify
    assert llama.latent_attention_form(170, *shape) == "absorbed"
    assert llama.latent_attention_form(171, *shape) == "expanded"      # 2*512*256 / 1536 = 170.7
    assert llama.latent_attention_form(256, *shape) == "expanded"      # a prefill chunk
    assert llama.latent_attention_form(10 ** 6, 64, 128, 128) == "absorbed"   # heads wider than the latent


def test_the_paged_engine_serves_the_reference_logits(tiny):
    """Prefill in chunks, then decode, through ``ServingEngine``'s page pool.
    Compared in logits, not tokens: every served token's reference logit lies
    within TOL of the reference's best at its position."""
    cfg, model, params = tiny
    prompt, new = 19, 9
    ids = np.asarray(ids_of(prompt, seed=8))[None]
    eng = ServingEngine(model, params, max_slots=2, max_len=64, prefill_chunk=8, page_size=8,
                        cache_dtype=jnp.float32)
    try:
        req = eng.submit(ids, max_new_tokens=new, ignore_eos=True, block=True)
        assert req.wait(120)
        served, summary = list(req.tokens), eng.stats.summary()
        per_chip, pool = eng.kv_cache_per_chip_bytes(), eng.page_pool_metrics()
        bytes_per_token = eng.kv_bytes_per_token
    finally:
        eng.shutdown(drain=False)
    full = jnp.asarray(np.concatenate([ids[0], served]))
    logits = ref.forward(params, full, cfg)[prompt - 1:-1]
    gaps = logits.max(-1) - logits[jnp.arange(new), jnp.asarray(served)]
    assert float(gaps.max()) < TOL
    # (d) the cache the engine holds is the declared one: 4 layers x 20 float32 values a token
    assert bytes_per_token == summary["kv_bytes_per_token"] == 4 * 20 * 4
    assert pool["page_bytes"] == 8 * bytes_per_token
    assert per_chip == (pool["pages_total"] + 1) * pool["page_bytes"]
    # (e) the ticks' rows, by hand. Ticks feed token j (j = 1..new-1; one more may have been
    # dispatched ahead of the retirement) at position prompt + j - 1. One lane of two runs: its
    # one key block of 64 rows is one item, the step of two items is scored whole, in each of
    # 4 layers; the idle lane owns nothing. The running lane sees the pos rows before its own.
    assert summary["decode_attn_rows_share"] == 1.0
    ticks = [new - 1, new]
    fills = [sum(prompt + j - 1 for j in range(1, t + 1)) / (t * 2 * 64) for t in ticks]
    assert any(summary["decode_attn_rows_fill"] == pytest.approx(f, abs=1e-6) for f in fills)
    assert summary["prefill_attn_rows_share"] == 1.0          # a toy chunk is one block
    assert summary["moe_held_pick_share"] == 1.0              # all eight experts are held


def test_the_tick_counter_counts_the_running_lanes_blocks_and_no_idle_lane(tiny):
    cfg, model, params = tiny
    eng = ServingEngine(model, params, max_slots=4, max_len=32, prefill_chunk=8, page_size=8,
                        autostart=False, warmup=False)
    try:
        assert llama.tick_key_tiles(cfg.num_attention_heads, 4, 32, 8) == (32, 4)
        rows = eng._tick_attn_rows([3, 10])
        none = eng._tick_attn_rows([])
        start = eng._tick_attn_rows([0, 0, 0])
    finally:
        eng.shutdown(drain=False)
    # two running lanes own one 32-row block each: one step of 4 items x 32 rows x 4 layers
    # scored, of 4 lanes x 32 rows x 4 layers held; positions 3 and 10 see 3 and 10 pool rows
    assert rows == (4 * 32 * 4, (3 + 10) * 4, 4 * 32 * 4)
    # no stream, or streams whose only row is their own: no item, no step
    assert none == start == (0, 0, 4 * 32 * 4)


def test_the_counters_merge_and_reset():
    a, b = ServingStats(), ServingStats()
    a.record_tick(1, 1, 2, 0.01, attn_rows=(512, 100, 512))
    b.record_tick(2, 2, 2, 0.01, attn_rows=(256, 156, 512))
    a.record_pages(1, 1, 2, kv_bytes_per_token=5760)
    m = ServingStats().merge(a).merge(b).summary()
    assert m["decode_attn_rows_share"] == 0.75 and m["decode_attn_rows_fill"] == pytest.approx(1 / 3)
    assert m["kv_bytes_per_token"] == 5760
    a.reset()
    s = a.summary()
    assert s["decode_attn_rows_share"] == s["decode_attn_rows_fill"] == s["kv_bytes_per_token"] == 0


# -- (f) the declared cache, and what reads it --------------------------------

def test_the_family_declares_a_latent_cache_of_576_values_and_no_head_axis():
    from accelerate_tpu.big_modeling import cache_factory_for

    model = PanguUltraMoeForCausalLM(PanguUltraMoeConfig(num_hidden_layers=5, held_experts=(0, 16)))
    factory = cache_factory_for(model)
    cache = jax.eval_shape(lambda: factory(2, 8192, jnp.bfloat16, ring_slack=3))
    assert len(cache) == 5 and all(set(layer) == {"latent", "rope"} for layer in cache)
    assert all(layer["latent"].shape == (2, 8192, 512) for layer in cache)     # no head axis
    assert all(layer["rope"].shape == (2, 8192, 64) for layer in cache)        # one key for all heads
    assert sum(np.prod(leaf.shape[2:]) * 2 for leaf in jax.tree.leaves(cache)) == 5760   # 576 values


def test_the_ladder_still_serves_the_per_head_families():
    from accelerate_tpu.big_modeling import cache_factory_for
    from accelerate_tpu.models.mixtral import MixtralConfig, MixtralForCausalLM

    cache = jax.eval_shape(lambda: cache_factory_for(
        MixtralForCausalLM(MixtralConfig.tiny_moe()))(1, 16, jnp.bfloat16))
    assert set(cache[0]) == {"k", "v"} and cache[0]["k"].shape == (1, 16, 2, 16)


@pytest.mark.parametrize("kwargs,word", [({"tp": 2}, "tp > 1"), ({"kv_dtype": "int8"}, "kv_dtype")],
                         ids=["tp2", "int8_kv"])
def test_what_assumes_per_head_k_and_v_is_refused_by_name(tiny, kwargs, word):
    _, model, params = tiny
    with pytest.raises(NotImplementedError, match="PanguUltraMoeForCausalLM declares its own KV"):
        ServingEngine(model, params, max_slots=2, max_len=32, prefill_chunk=8, **kwargs)
    with pytest.raises(NotImplementedError, match=word):
        ServingEngine(model, params, max_slots=2, max_len=32, prefill_chunk=8, **kwargs)


def run_estimate(capsys, *argv):
    from accelerate_tpu.commands.estimate import estimate_command, estimate_command_parser

    rc = estimate_command(estimate_command_parser().parse_args(list(argv)))
    return rc, capsys.readouterr().out


def test_estimate_reads_the_declared_cache(capsys):
    rc, out = run_estimate(capsys, "openpangu-ultra-moe", "--held-experts", "16", "--page-size",
                           "256", "--max-pages", "1024", "--dtypes", "bfloat16")
    assert rc == 0
    assert "holding 16 of 256 routed experts" in out
    assert "122 leaves of 64/512 values a token, no head axis" in out
    assert "bytes per token : 68.62 KiB" in out               # 61 layers x 576 x 2 bytes
    assert "bytes per page  : 17.16 MiB" in out


@pytest.mark.parametrize("extra", [["--tp", "2"], ["--kv-dtype", "int8"]], ids=["tp2", "int8_kv"])
def test_estimate_refuses_what_the_engine_refuses(capsys, extra):
    rc, out = run_estimate(capsys, "openpangu-ultra-moe", "--page-size", "256", "--dtypes",
                           "bfloat16", *extra)
    assert rc == 2 and "declares its own KV cache" in out


# -- (g) the published parameter names ----------------------------------------

PUBLISHED = dict(
    model_type="pangu_ultra_moe", vocab_size=96, hidden_size=32, intermediate_size=48,
    moe_intermediate_size=16, num_hidden_layers=3, first_k_dense_replace=1,
    num_attention_heads=4, num_key_value_heads=4, q_lora_rank=12, kv_lora_rank=8,
    qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8, max_position_embeddings=256,
    rms_norm_eps=1e-5, rope_theta=25600000, n_routed_experts=8, num_experts_per_tok=2,
    n_shared_experts=1, routed_scaling_factor=2.5, norm_topk_prob=True, sandwich_norm=True,
    tie_word_embeddings=False, attention_bias=False, num_nextn_predict_layers=1,
    hidden_act="silu")


def test_hf_names_round_trip_on_made_up_tensors():
    from accelerate_tpu.utils.hf_interop import (config_from_hf, convert_hf_state_dict,
                                                 detect_family, export_hf_state_dict,
                                                 model_from_config)

    assert detect_family(PUBLISHED) == "pangu_ultra_moe"
    cfg = config_from_hf(PUBLISHED)
    assert isinstance(cfg, PanguUltraMoeConfig)
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.n_shared_experts, cfg.held) == (8, 2, 1, (0, 8))
    assert (cfg.cache_row_width, cfg.routed_scaling_factor, cfg.rope_theta) == (12, 2.5, 25.6e6)
    model = model_from_config(cfg, "pangu_ultra_moe")
    params = jax.tree.map(np.asarray, model.init_params(jax.random.PRNGKey(0)))
    exported = export_hf_state_dict(params, "pangu_ultra_moe")
    shapes = {k: v.shape for k, v in exported.items()}
    assert shapes["model.layers.1.self_attn.q_a_proj.weight"] == (12, 32)
    assert shapes["model.layers.1.self_attn.q_a_layernorm.weight"] == (12,)
    assert shapes["model.layers.1.self_attn.q_b_proj.weight"] == (4 * 12, 12)
    assert shapes["model.layers.1.self_attn.kv_a_proj_with_mqa.weight"] == (8 + 4, 32)
    assert shapes["model.layers.1.self_attn.kv_a_layernorm.weight"] == (8,)
    assert shapes["model.layers.1.self_attn.kv_b_proj.weight"] == (4 * 16, 8)
    assert shapes["model.layers.1.self_attn.o_proj.weight"] == (32, 32)
    for norm in ("input_layernorm", "post_attention_layernorm", "pre_mlp_layernorm",
                 "post_mlp_layernorm"):
        assert shapes[f"model.layers.2.{norm}.weight"] == (32,)
    assert shapes["model.layers.0.mlp.gate_proj.weight"] == (48, 32)            # the dense layer
    assert shapes["model.layers.1.mlp.gate.weight"] == (8, 32)                  # the router
    assert shapes["model.layers.2.mlp.experts.7.down_proj.weight"] == (32, 16)
    assert shapes["model.layers.2.mlp.shared_experts.up_proj.weight"] == (16, 32)
    assert shapes["lm_head.weight"] == (96, 32)                                 # untied
    back = convert_hf_state_dict(exported, "pangu_ultra_moe", strict=True)
    ids = jnp.asarray((np.arange(20).reshape(1, 20) * 7) % 96, jnp.int32)
    np.testing.assert_array_equal(model.apply({"params": params}, ids),
                                  model.apply({"params": back}, ids))


@pytest.mark.parametrize("key,value", [("sandwich_norm", False), ("tie_word_embeddings", True),
                                       ("n_group", 8), ("rope_scaling", {"type": "yarn"})])
def test_what_is_not_implemented_is_refused(key, value):
    from accelerate_tpu.utils.hf_interop import config_from_hf

    with pytest.raises(NotImplementedError):
        config_from_hf(dict(PUBLISHED, **{key: value}))


# -- (h) the programs of the families the benchmark already had ---------------

# sha256 of ``jit(...).lower(...).as_text()`` (CPU, toy shapes) of the engine
# programs of Mixtral, windowed Mixtral, cohere2_moe and this family. The chunk
# and the two speculative ticks are as commit 885e459 (PR 33's tree) lowers
# them, and before it c545906: no shared helper that these programs trace has
# changed since. The plain tick (``decode``) was re-pinned by PR 34, which
# rewrote it on purpose: it reads the page pool in place over a work list of
# live (slot, key block) pairs and gathers no view
# (tests/test_paged_tick_attention.py). The six ``cohere2_moe`` lines were
# re-pinned by PR 36, which changed them on purpose: the engine holds that
# family's q and k kernels in its served form (``served_form``: a head's
# columns half-split, the q kernel ``[heads x head_dim, hidden]``) and its
# programs trace the module that reads them, so the q product contracts the
# kernel's second axis and the rotary is models/llama.py ``apply_rotary``; the
# 18 lines of the families without the hook stand. A PR that changes one of
# these programs on purpose re-pins its line and says why.
PARENT_PROGRAMS = {
    "mixtral/fp/decode": "cfe3cee0d4738767eee56c4ac9a88e8d2e3dce83692890ba5e8dda1eae10093b",      # PR 34
    "mixtral/fp/chunk": "90f38ab0fc0c2b1e8bf36456a6b9be38ba03f935f66867a20f835d56f3392347",
    "mixtral/int8/decode": "3bd3c45a82dbb872d2cd55574da1eecc54d78d7086752ccdebc4d5e2c686f2ca",      # PR 34
    "mixtral/int8/chunk": "73ed7b8cba4c384430745115ee7b945600c029ff4a803426cde03e0f70bf1c10",
    "mixtral/lookup/chunk": "2668e42054dfb9dfe75526d36ebd5c701058640880703859995dc00010e6d173",
    "mixtral/lookup/spec_lookup": "d4cab2eb976b7da973baa568c37b0b444ec4907b2a09d038824b1d00016e46af",
    "mixtral/draft/chunk": "0f26ece5af0ee5af027ca001598bd43fb2e38f70c75b05d1ee274abaffd56031",
    "mixtral/draft/spec": "df29a110e2d7ee3bb63f758c6d4aab5609e24e37f1af0838193e6fe0c023a83b",
    "mixtral_window/fp/decode": "3d7f02c8f083e2c5300541e8bf04bff004f3d2f5e1a964155a23dacb66dcff0c",      # PR 34
    "mixtral_window/fp/chunk": "a213839db13069fe93423af31073b49b6fd5d4bb6f11f87aefb337d64222bb41",
    "mixtral_window/int8/decode": "b8ae9da650a4e7a51eb522ba5f537a63469c7750ee4ed7ae70ea867ab8ae219c",      # PR 34
    "mixtral_window/int8/chunk": "60ab0c97a91ed460cb405ee1617b7a8b3dc63e1d81de4c37d843c5e66d74b2de",
    "mixtral_window/lookup/chunk": "433d483978531e5788baad65063b9c0b591fd1093304f6c2794aa16e7c6457aa",
    "mixtral_window/lookup/spec_lookup": "fccc57f5d9e2216ca1b9bd1c7024add6f846a76126498360fba1e790f46551ac",
    "cohere2_moe/fp/decode": "0ae5166c24b07dca7e521f57e3137efa01fa7a86b8874daeafd59c02eb88b37f",      # PR 36
    "cohere2_moe/fp/chunk": "edbb18314a6008443775c7d6b3e101a5e91810842ed318aa0bcb2d3b23d96500",      # PR 36
    "cohere2_moe/int8/decode": "ae3cf36ccb6815255c9ee049cc5d52b9fa45f32d758d29c64b5ed7864fef12d3",      # PR 36
    "cohere2_moe/int8/chunk": "f86d1128ff264e42d41c0c32b16739b55ca139b2434e14541648b5f34a8c9f05",      # PR 36
    "cohere2_moe/lookup/chunk": "422b85c030da65ade72f3fdf5bb142a38168b03edef04166c20062a9ee035853",      # PR 36
    "cohere2_moe/lookup/spec_lookup": "72efeba7c28a509cbb692737933a53cbd5f8c27495449d5546e2f8434b36049b",      # PR 36
    "pangu_ultra_moe/fp/decode": "7e4c2629ec755e7ccf63cc429c916d96bc1aa89550baec0a3f625b40bacae3b4",      # PR 34
    "pangu_ultra_moe/fp/chunk": "7dfaaab512641f1030afdc32870036cd2ea91a00720720ce1ac5f2131dc1dbf4",
    "pangu_ultra_moe/lookup/chunk": "03bb625a5bf7726d04d24956a1feb04a9f7019d99419d2b5cbbc0ca54554b766",
    "pangu_ultra_moe/lookup/spec_lookup": "81b930fc94aedfb988aba2311423c4cecc8e84b681fda3e0c2b1f6afa10f3e1f",
}


def lowered_programs(family, variant):
    from accelerate_tpu.models.cohere2_moe import Cohere2MoeConfig, Cohere2MoeForCausalLM
    from accelerate_tpu.models.mixtral import MixtralConfig, MixtralForCausalLM

    model = {"mixtral": lambda: MixtralForCausalLM(MixtralConfig.tiny_moe()),
             "mixtral_window": lambda: MixtralForCausalLM(MixtralConfig.tiny_moe(sliding_window=8)),
             "cohere2_moe": lambda: Cohere2MoeForCausalLM(Cohere2MoeConfig.tiny()),
             "pangu_ultra_moe": lambda: PanguUltraMoeForCausalLM(PanguUltraMoeConfig.tiny())}[family]()
    params = model.init_params(jax.random.PRNGKey(0))
    kw = {"fp": {}, "int8": {"kv_dtype": "int8"}, "lookup": {"spec_tokens": 3, "spec_lookup": 2},
          "draft": {"spec_tokens": 3, "draft_model": model, "draft_params": params}}[variant]
    eng = ServingEngine(model, params, max_slots=2, max_len=64, prefill_chunk=8, page_size=8,
                        autostart=False, warmup=False, **kw)
    try:
        active, table = np.zeros((2,), bool), eng._table.copy()
        remaining = np.zeros((2,), np.int32)
        draft = (eng._draft_params, eng._dtable[0].copy()) if variant == "draft" else ()
        texts = {
            "decode": eng._decode.lower(eng.params, eng._state, active, table),
            "chunk": eng._prefill_chunk.lower(
                eng.params, eng._state, np.zeros((1, 8), np.int32), np.int32(0), table[0],
                np.int32(0), np.int32(5), jax.random.PRNGKey(0), *draft),
        }
        if variant == "lookup":
            texts["spec_lookup"] = eng._spec.lower(eng.params, eng._state, active, table,
                                                   remaining, np.zeros((2, 3), np.int32))
        if variant == "draft":
            texts["spec"] = eng._spec.lower(eng.params, eng._draft_params, eng._state, active,
                                            table, eng._dtable.copy(), remaining)
        return {k: hashlib.sha256(v.as_text().encode()).hexdigest() for k, v in texts.items()}
    finally:
        eng.shutdown(drain=False)


@pytest.mark.parametrize("family,variant", sorted({tuple(k.split("/")[:2]) for k in PARENT_PROGRAMS}))
def test_the_other_families_programs_lower_to_the_parents_text(family, variant):
    got = lowered_programs(family, variant)
    for name, want in PARENT_PROGRAMS.items():
        fam, var, prog = name.split("/")
        if (fam, var) == (family, variant):
            assert got[prog] == want, name
