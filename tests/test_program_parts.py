"""The programs name their parts: every served family's tick and chunk, and
the trainer's step, carry the vocabulary of
``accelerate_tpu/observability/program_parts.py`` on their compiled
instructions' ``op_name`` — where a profiler trace keeps it and
``chipbench/op_scopes.py`` reads it."""

import re

import jax
import numpy as np
import pytest

from accelerate_tpu.models import llama
from accelerate_tpu.observability.program_parts import PROGRAM_PARTS, program_part
from accelerate_tpu.serving import ServingEngine
from chipbench import op_scopes

TICK_EPILOGUE = {"embed", "kv_attn", "kv_write", "lm_head", "sample"}


def _llama():
    from accelerate_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    return LlamaForCausalLM(LlamaConfig.tiny())


def _mixtral():
    from accelerate_tpu.models.mixtral import MixtralConfig, MixtralForCausalLM
    return MixtralForCausalLM(MixtralConfig.tiny_moe(sliding_window=8))


def _cohere2_moe():
    from accelerate_tpu.models.cohere2_moe import Cohere2MoeConfig, Cohere2MoeForCausalLM
    return Cohere2MoeForCausalLM(Cohere2MoeConfig.tiny())


def _pangu_ultra_moe():
    from accelerate_tpu.models.pangu_ultra_moe import PanguUltraMoeConfig, PanguUltraMoeForCausalLM
    return PanguUltraMoeForCausalLM(PanguUltraMoeConfig.tiny())


def _longcat_flash():
    from accelerate_tpu.models.longcat_flash import LongcatFlashConfig, LongcatFlashForCausalLM
    return LongcatFlashForCausalLM(LongcatFlashConfig.tiny())


def _phi4flash():
    from accelerate_tpu.models.phi4flash import Phi4FlashConfig, Phi4FlashForCausalLM
    return Phi4FlashForCausalLM(Phi4FlashConfig.tiny())


#: family -> (model, its attention parts, its other parts, engine options)
FAMILIES = {
    "llama": (_llama, {"attn_global"}, {"mlp_dense"}, {}),
    "mixtral": (_mixtral, {"attn_local"}, {"moe_router", "moe_experts"}, {}),
    "cohere2_moe": (_cohere2_moe, {"attn_local", "attn_global"},
                    {"moe_router", "moe_experts", "moe_shared"}, {}),
    "pangu_ultra_moe": (_pangu_ultra_moe, {"attn_mla"},
                        {"mla_q", "mla_latent", "mla_out", "mlp_dense", "moe_router",
                         "moe_experts", "moe_shared"}, {}),
    "longcat_flash": (_longcat_flash, {"attn_mla"},
                      {"mla_q", "mla_latent", "mla_out", "mlp_dense", "moe_router",
                       "moe_experts"}, {}),
    "phi4flash": (_phi4flash, {"attn_diff_local", "attn_diff_global", "attn_cross_shared"},
                  {"ssm_in", "ssm_conv", "ssm_scan", "ssm_out", "gmu", "mlp_dense"},
                  {"prefix_cache_mb": 0}),
}


@pytest.fixture
def no_compile_cache():
    """A scope is not in the persistent compile cache's key: an entry another
    tree wrote would hand back that tree's ``op_name``s."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def op_names(compiled) -> list:
    return sorted(set(re.findall(r'op_name="([^"]+)"', compiled.as_text())))


def parts_of(names) -> set:
    return {p for n in names for p in op_scopes.path_parts(n)}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_served_familys_tick_and_chunk_name_their_parts(family, monkeypatch, no_compile_cache):
    build, attention, others, options = FAMILIES[family]
    # a chunk scores its 64-row view in key blocks of 8: the loop of a long view
    monkeypatch.setattr(llama, "cached_key_block", lambda rows, view: min(8, view))
    model = build()
    params = model.init_params(jax.random.PRNGKey(0))
    eng = ServingEngine(model, params, max_slots=2, max_len=64, prefill_chunk=8, page_size=8,
                        autostart=False, warmup=False, **options)
    try:
        active, table = np.zeros((2,), bool), eng._table.copy()
        tick = op_names(eng._decode.lower(eng.params, eng._state, active, table).compile())
        chunk = op_names(eng._prefill_chunk.lower(
            eng.params, eng._state, np.zeros((1, 8), np.int32), np.int32(0), table[0],
            np.int32(0), np.int32(5), jax.random.PRNGKey(0)).compile())
    finally:
        eng.shutdown(drain=False)
    want = TICK_EPILOGUE | attention | others
    assert parts_of(tick) == want
    assert parts_of(chunk) == want | {"kv_view"}
    for program, names in (("tick", tick), ("chunk", chunk)):
        # the tick's work-list loop / the chunk's key-block loop: every loop of
        # an attention lies under kv_attn, inside the family's own attn_* part
        loops = [op_scopes.path_parts(n) for n in names if n.endswith("/while")]
        in_attention = [path for path in loops if set(path) & attention]
        assert in_attention, program
        assert all(path[-1] == "kv_attn" and path[-2] in attention for path in in_attention), \
            (program, in_attention)
        assert {path[-2] for path in in_attention} == attention, program
        # token selection (generation._next_token: an argmax over the vocabulary
        # in a greedy engine) lies under sample, and under nothing else
        selection = [n for n in names if op_scopes.path_parts(n) == ("sample",)]
        assert any(n.rsplit("/", 1)[-1] in ("reduce", "argmax") for n in selection), program
    # the tick runs the model under jax.vmap, which wraps the scope next to it
    assert any("vmap(sample)" in n for n in tick)


def test_a_part_that_is_not_in_the_vocabulary_raises():
    with pytest.raises(ValueError, match="kv_atn"):
        program_part("kv_atn")
    with program_part("kv_attn"):
        pass


def test_the_readers_vocabulary_is_the_programs():
    assert op_scopes.PARTS == PROGRAM_PARTS
    assert len(set(PROGRAM_PARTS)) == len(PROGRAM_PARTS)
    assert "mla_scores" not in PROGRAM_PARTS          # what it wrapped is kv_attn + kv_write


def test_the_train_step_names_its_loss_and_its_optimizer(no_compile_cache):
    import optax

    from accelerate_tpu import Accelerator, Model
    from accelerate_tpu.models.llama import LlamaConfig, LlamaForCausalLM, causal_lm_loss
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

    for cls in (AcceleratorState, GradientState, PartialState):
        cls._reset_state()
    module = LlamaForCausalLM(LlamaConfig.tiny())
    accelerator = Accelerator()
    model, optimizer = accelerator.prepare(
        Model(module, module.init_params(jax.random.PRNGKey(0))), optax.adamw(1e-3))
    step = accelerator.compile_train_step(causal_lm_loss(module.apply), model, optimizer,
                                          max_grad_norm=1.0)
    batch = {"input_ids": np.zeros((8, 16), np.int32)}
    names = op_names(step._jitted.lower(model.params, optimizer.opt_state, optimizer.loss_scale,
                                        batch, jax.random.PRNGKey(0)).compile())
    paths = {op_scopes.path_parts(n) for n in names}
    assert {"loss", "optimizer"} <= parts_of(names)
    # the model's own parts lie inside the loss, forward and backward
    assert ("loss", "attn_global") in paths and ("loss", "mlp_dense") in paths
    assert ("loss", "lm_head") in paths and ("loss", "embed") in paths
    assert any(n.startswith("jit(train_step)/loss/transpose(") for n in names)
    # the update is one named part (it read jit(train_step)/add): nothing of
    # the model's under it, and no arithmetic of the step outside the two
    assert {p for p in paths if p and p[0] == "optimizer"} == {("optimizer",)}
    bare = [n for n in names if n.startswith("jit(train_step)/") and not op_scopes.path_parts(n)]
    assert not [n for n in bare if n.rsplit("/", 1)[-1] in ("add", "mul", "sqrt", "dot_general")], bare


def test_a_cached_program_of_a_tree_without_a_scope_is_not_handed_to_the_tree_with_it(tmp_path):
    """Metadata is not in the persistent cache's key unless asked for
    (``enable_compilation_cache`` asks): the second tree's trace would show
    the first tree's parts."""
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    from accelerate_tpu.utils.platforms import enable_compilation_cache

    keys = ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes", "jax_enable_compilation_cache",
            "jax_compilation_cache_include_metadata_in_key")
    was = {k: getattr(jax.config, k) for k in keys}

    def tree(part):
        def f(x):
            with program_part(part):
                return jnp.sin(x) @ x
        return op_names(jax.jit(f).lower(jnp.ones((16, 16))).compile())

    try:
        enable_compilation_cache()
        for key, value in zip(keys, (str(tmp_path), 0.0, 0, True)):
            jax.config.update(key, value)
        compilation_cache.reset_cache()
        assert parts_of(tree("kv_view")) == {"kv_view"}
        assert any(tmp_path.iterdir())                       # the first tree's entry is there
        assert parts_of(tree("kv_attn")) == {"kv_attn"}
    finally:
        for key, value in was.items():
            jax.config.update(key, value)
        compilation_cache.reset_cache()
