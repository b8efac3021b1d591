"""The main path's Pallas kernels, compiled by the TPU's own compiler for a
described (not attached) v5e chip, at the widths the chip runs them.

Interpret-mode tests prove the arithmetic; only Mosaic says whether a kernel
lowers: tiling alignment, VMEM budget, block shapes. ``libtpu`` is installed
in the sandbox and compiles for a topology that is merely described, so these
guard every later PR at no chip time. A compile that passes here is a
compile, not a run.

Everything that touches the TPU library happens inside fixtures of THIS file
(never at import, in conftest, in a skipif or in parametrize arguments): only
one process may load libtpu, xdist workers all import every test file, and
only the worker that runs this file may load it. Keep these tests in this one
file for the same reason, and compile in the test's own process.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from accelerate_tpu.ops import attention, flash_pallas

# name -> kernel shapes/options. H/G are query/KV heads. The first row is the
# trainer's attention in chip_smoke.py (Mistral-7B widths, sequence 4096).
# A case without block_q/block_k compiles at the tiles flash_pallas.tile_plan
# chooses for it (1024-wide at these lengths), under the vmem_limit_bytes the
# plan asks for: so the VMEM estimate of every shape family is checked by
# Mosaic itself. ``blocks512_s4096`` stays as the pinned-size case.
CASES = {
    "gqa32x8_d128_bf16_s4096": dict(S=4096, H=32, G=8, D=128, dtype=jnp.bfloat16),
    "gqa32x8_d128_fp32_s2048": dict(S=2048, H=32, G=8, D=128, dtype=jnp.float32),
    "window1024_s4096": dict(S=4096, H=32, G=8, D=128, dtype=jnp.bfloat16,
                             sliding_window=1024),
    # batch 2: a (1, block) tile of [B, S] segment ids lowers only at batch 1
    "segment_ids_s2048": dict(S=2048, H=32, G=8, D=128, dtype=jnp.bfloat16, segments=True,
                              batch=2),
    "d256_softcap_s2048": dict(S=2048, H=16, G=8, D=256, dtype=jnp.bfloat16,
                               logit_softcap=50.0),
    "blocks512_s4096": dict(S=4096, H=32, G=8, D=128, dtype=jnp.bfloat16,
                            block_q=512, block_k=512),
    "d96_s2048": dict(S=2048, H=32, G=8, D=96, dtype=jnp.bfloat16),
}


@pytest.fixture(scope="module")
def topo():
    """A described v5e 2x2, with the persistent compile cache off around the
    whole module: an executable compiled for a described chip is written to
    the cache but cannot be read back without one, and would warn on every
    later run."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe means "cannot test here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", cache_was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mosaic(monkeypatch):
    """Steer the code that asks ``jax.default_backend()`` (the CPU, here) onto
    its TPU branch: Mosaic lowering, and the flash path available."""
    monkeypatch.setattr(flash_pallas, "_interpret", lambda: False)
    monkeypatch.setattr(
        attention, "flash_attention_available",
        lambda q=None: q is None or (q.shape[1] >= 128 and q.shape[1] % 128 == 0
                                     and q.shape[-1] <= 256))


def _shapes(case, sharding, seg_sharding=None, batch=None):
    batch = batch or case.get("batch", 1)
    S, H, G, D, dtype = case["S"], case["H"], case["G"], case["D"], case["dtype"]
    q = jax.ShapeDtypeStruct((batch, S, H, D), dtype, sharding=sharding)
    kv = jax.ShapeDtypeStruct((batch, S, G, D), dtype, sharding=sharding)
    seg = (jax.ShapeDtypeStruct((batch, S), jnp.int32, sharding=seg_sharding or sharding)
           if case.get("segments") else None)
    return q, kv, seg


def _kernel(case):
    opts = {k: case[k] for k in ("sliding_window", "logit_softcap", "block_q", "block_k")
            if k in case}

    def fwd(q, k, v, seg=None):
        return flash_pallas.pallas_flash_attention(q, k, v, causal=True, segment_ids=seg,
                                                   **opts)

    return fwd


def _with_backward(fwd):
    def fwd_bwd(q, k, v, seg=None):
        out, vjp = jax.vjp(lambda q, k, v: fwd(q, k, v, seg), q, k, v)
        return vjp(out)

    return fwd_bwd


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
@pytest.mark.parametrize("name", list(CASES))
def test_flash_kernel_compiles_for_v5e(name, direction, one_chip, mosaic):
    case = CASES[name]
    q, kv, seg = _shapes(case, one_chip)
    for kernel in ("fwd",) if direction == "fwd" else ("dq", "dkdv"):
        plan = flash_pallas.tile_plan(
            case["S"], case["S"], case["D"], jnp.dtype(case["dtype"]).name,
            case.get("sliding_window"), seg is not None, kernel,
            softcap="logit_softcap" in case, block_q=case.get("block_q"),
            block_k=case.get("block_k"))
        if "block_q" in case:
            assert (plan.block_q, plan.block_k) == (case["block_q"], case["block_k"])
        else:   # the chooser's: far fewer steps than the 128 x 128 these cases used to pin
            assert plan.steps * 16 <= (case["S"] // 128) ** 2, plan
    fn = _kernel(case) if direction == "fwd" else _with_backward(_kernel(case))
    args = (q, kv, kv) + ((seg,) if seg is not None else ())
    compiled = jax.jit(fn).lower(*args).compile()
    calls = compiled.as_text().count("tpu_custom_call")
    # fwd is one Mosaic call; bwd re-runs fwd for residuals, then dq and dk/dv.
    assert calls >= (1 if direction == "fwd" else 3), f"{calls} Mosaic calls in the HLO"


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_flash_runs_per_shard_on_a_mesh(direction, topo, mosaic):
    """Under fsdp=2 x tp=2 the entry point the models call wraps the kernel in
    a shard_map (Mosaic refuses GSPMD partitioning): each chip's call sees one
    batch row of two and half the heads, and q/k/v are not gathered."""
    from accelerate_tpu import MeshConfig

    mesh = MeshConfig(fsdp=2, tp=2, devices=list(topo.devices)).build()
    case = CASES["gqa32x8_d128_bf16_s4096"]
    q, kv, _ = _shapes(case, NamedSharding(mesh, P("fsdp", None, "tp", None)), batch=2)

    def fwd(q, k, v, seg=None):
        return attention.flash_attention(q, k, v, causal=True)

    fn = fwd if direction == "fwd" else _with_backward(fwd)
    with mesh:
        compiled = jax.jit(fn).lower(q, kv, kv).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # Per-shard operand shapes in [B, H, S, D] kernel layout: 1 row, 16 of 32
    # query heads, 4 of 8 KV heads.
    assert "bf16[1,16,4096,128]" in text and "bf16[1,4,4096,128]" in text
    assert "bf16[2,32,4096,128]" not in text, "q was gathered to its global shape"


def test_unsharded_call_on_a_mesh_is_refused_by_mosaic(topo, mosaic):
    """What the wrapper is for: the bare kernel on GSPMD-sharded operands does
    not lower. If this starts passing, jax learned to partition Mosaic calls
    and ``ops.attention._per_shard_specs`` can go."""
    from accelerate_tpu import MeshConfig

    mesh = MeshConfig(fsdp=2, tp=2, devices=list(topo.devices)).build()
    case = CASES["gqa32x8_d128_bf16_s4096"]
    q, kv, _ = _shapes(case, NamedSharding(mesh, P("fsdp", None, "tp", None)), batch=2)
    with pytest.raises(NotImplementedError, match="cannot be automatically partitioned"):
        jax.jit(_kernel(case)).lower(q, kv, kv).compile()


# ---------------------------------------------------------------------------
# The cohere2_moe family's serving programs at the benchmark cell's widths
# ---------------------------------------------------------------------------

def _serving_programs(model, num_layers, kv_heads, head_dim, one_chip, *, max_len, slots, chunk,
                      latent_row=None):
    """The model's part of the paged engine's two programs over the linear
    full-length view the engine gathers: a ``chunk``-token prefill chunk, and
    the decode tick — ``jax.vmap`` over ``slots`` of a batch-1 forward. Both
    ask for the module's pick counters, as the engine does. ``latent_row``
    ``(rank, rope)``: the view is a latent cache, not per-head K and V."""
    def struct(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    params = jax.tree.map(lambda a: struct(a.shape, jnp.bfloat16), shapes)

    def views(lead):
        if latent_row is not None:
            rank, rope = latent_row
            return tuple({"latent": struct(lead + (1, max_len, rank), jnp.bfloat16),
                          "rope": struct(lead + (1, max_len, rope), jnp.bfloat16)}
                         for _ in range(num_layers))
        kv = struct(lead + (1, max_len, kv_heads, head_dim), jnp.bfloat16)
        return tuple({"k": kv, "v": kv} for _ in range(num_layers))

    def apply(params, ids, cache, pos):
        (logits, cache), sown = model.apply({"params": params}, ids, cache=cache,
                                            cache_pos=pos, mutable=["moe_stats"])
        return logits, cache, sum(jax.tree.leaves(sown))

    def decode(params, toks, caches, positions):
        def one_slot(tok, cache, pos):
            logits, cache, picks = apply(params, tok[None, None], cache, pos)
            return jnp.argmax(logits[0, -1]), cache, picks

        return jax.vmap(one_slot)(toks, caches, positions)

    return {
        "prefill_chunk": (apply, (params, struct((1, chunk), jnp.int32), views(()),
                                  struct((), jnp.int32))),
        "decode_tick": (decode, (params, struct((slots,), jnp.int32), views((slots,)),
                                 struct((slots,), jnp.int32))),
    }


def _cohere2_moe_programs(one_chip):
    """At the widths of ``command-a-plus-05-2026-l4e16`` (hidden 4096, 128/8
    heads of 128, 16 of 128 experts held, 4 shared, 4 layers, vocab slice
    32768): max_len 8192, 16 slots, 256-token chunks."""
    from accelerate_tpu.models.cohere2_moe import Cohere2MoeConfig, Cohere2MoeForCausalLM

    cfg = Cohere2MoeConfig(vocab_size=32768, num_hidden_layers=4, held_experts=(0, 16))
    return _serving_programs(Cohere2MoeForCausalLM(cfg), 4, cfg.num_key_value_heads,
                             cfg.head_dim, one_chip, max_len=8192, slots=16, chunk=256)


@pytest.mark.parametrize("program", ["prefill_chunk", "decode_tick"])
def test_cohere2_moe_serving_program_compiles_for_v5e(program, one_chip):
    fn, args = _cohere2_moe_programs(one_chip)[program]
    compiled = jax.jit(fn, donate_argnums=(2,)).lower(*args).compile()
    memory = compiled.memory_analysis()
    weights = 9.6e9                       # 4 layers with 16 experts + the vocabulary slice, bf16
    assert weights * 0.95 < memory.argument_size_in_bytes - (
        0 if program == "prefill_chunk" else 2.15e9) < weights * 1.05
    # what the program needs beside its arguments fits beside weights + page pool + views
    assert memory.temp_size_in_bytes < 2.0e9, memory
    text = compiled.as_text()
    # no copy or transpose of a whole expert stack: each product reads the weights in place
    import re

    assert not re.search(r"= bf16\[(16|4),4096,4096\]\S* (copy|transpose)\(", text)
    if program == "prefill_chunk":        # the loop over occupied expert tiles is there
        assert " while(" in text
        # attention scores 512 key rows at a time (models.llama.cached_key_block: 64 MiB of
        # float32 scores a block) and never the 8192-row view's 1.07 GB a layer; the parent's
        # program needed 1.09 GB beside its arguments for them
        assert "f32[1,8,16,256,512]" in text and "f32[1,8,16,256,8192]" not in text
        assert memory.temp_size_in_bytes < 0.5e9, memory
    else:                                 # a tick's one token scores its whole view in one pass
        assert "f32[16,1,8,16,1,8192]" in text


# ---------------------------------------------------------------------------
# Mixtral's serving programs at the benchmark cell's widths
# ---------------------------------------------------------------------------

def _mixtral_programs(one_chip):
    """At the widths of ``mixtral-8x7b-v0.1-d3`` (hidden 4096, 32/8 heads of
    128, 8 experts top-2 of width 14336, vocab 32000; depth 1 is enough for
    the lowering): max_len 1024, 8 slots, 256-token chunks."""
    from accelerate_tpu.models.mixtral import MixtralConfig, MixtralForCausalLM

    cfg = MixtralConfig.mixtral_8x7b(num_hidden_layers=1, use_flash_attention=False)
    return _serving_programs(MixtralForCausalLM(cfg), 1, cfg.num_key_value_heads, 128,
                             one_chip, max_len=1024, slots=8, chunk=256)


@pytest.mark.parametrize("program", ["prefill_chunk", "decode_tick"])
def test_mixtral_serving_program_compiles_for_v5e(program, one_chip):
    import re

    fn, args = _mixtral_programs(one_chip)[program]
    compiled = jax.jit(fn, donate_argnums=(2,)).lower(*args).compile()
    memory = compiled.memory_analysis()
    weights = 3.43e9        # one layer (8 experts 2.82 GB) + embedding + head, bf16; views <= 34 MB
    assert weights * 0.98 < memory.argument_size_in_bytes < weights * 1.02
    assert memory.temp_size_in_bytes < 1.0e9, memory
    text = compiled.as_text()
    # no copy or transpose of an expert stack, nor of one expert's matrix
    assert not re.search(r"= bf16\[(8,)?(4096,14336|14336,4096)\]\S* (copy|transpose)\(", text)
    # the experts compute routed rows: no [.., 8 experts, 512 slots] dispatch or
    # combine one-hot, no 512-row capacity an expert
    assert not re.search(r"\[(\d+,)*8,512(,\d+)*\]", text)
    if program == "prefill_chunk":        # the loop over occupied 128-row tiles is there
        assert " while(" in text
        assert re.search(r"bf16\[128,14336\]", text)
        # a 1024-row view's scores are 32 MiB: one block, the whole view in one pass
        assert "f32[1,8,4,256,1024]" in text


# ---------------------------------------------------------------------------
# The pangu_ultra_moe family's serving programs at the benchmark cell's widths
# ---------------------------------------------------------------------------

def _pangu_programs(one_chip):
    """At the widths of ``openpangu-ultra-moe-718b-l5e16`` (hidden 7680, 128
    heads over a 512 + 64 wide latent row, 16 of 256 experts held, 1 dense + 4
    expert layers, vocab slice 19200): max_len 8192, 32 slots, 256-token chunks."""
    from accelerate_tpu.models.pangu_ultra_moe import (PanguUltraMoeConfig,
                                                        PanguUltraMoeForCausalLM)

    cfg = PanguUltraMoeConfig(vocab_size=19200, num_hidden_layers=5, first_k_dense_replace=1,
                              held_experts=(0, 16))
    return _serving_programs(PanguUltraMoeForCausalLM(cfg), 5, None, None, one_chip,
                             max_len=8192, slots=32, chunk=256,
                             latent_row=(cfg.kv_lora_rank, cfg.qk_rope_head_dim))


@pytest.mark.parametrize("program", ["prefill_chunk", "decode_tick"])
def test_pangu_ultra_moe_serving_program_compiles_for_v5e(program, one_chip):
    fn, args = _pangu_programs(one_chip)[program]
    compiled = jax.jit(fn, donate_argnums=(2,)).lower(*args).compile()
    memory = compiled.memory_analysis()
    weights = 9.84e9                      # 1 dense + 4 expert layers of 16 experts + the slices, bf16
    views = 0 if program == "prefill_chunk" else 32 * 8192 * 576 * 2 * 5     # 1.51 GB of latent rows
    assert weights * 0.98 < memory.argument_size_in_bytes - views < weights * 1.02
    # beside weights (9.84 GB), the page pool and the tick's views (1.51 GB each) there is ~3 GB
    assert memory.temp_size_in_bytes < 1.0e9, memory
    text = compiled.as_text()
    import re

    if program == "prefill_chunk":
        # 256 queries share their rows: the expanded form, 512 key rows at a time (64 MiB of
        # float32 scores a block), in a loop over the visible blocks; never the whole view's scores
        assert " while(" in text
        assert "f32[1,128,256,512]" in text and "f32[1,128,256,8192]" not in text
        assert memory.temp_size_in_bytes < 0.5e9, memory
    else:
        # one query a lane: the absorbed form, every lane's whole view in one pass; no
        # per-head keys or values of a view's rows ever exist (they would be 17 GB)
        assert "f32[32,1,128,1,8192]" in text
        assert "[32,1,8192,128,128]" not in text and "[32,8192,128,128]" not in text
        # no lane's view is copied or laid out anew: the products read the gathered leaves
        assert not re.search(r"= bf16\[32,(1,)?8192,512\]\S* (copy|transpose)\(", text)


# ---------------------------------------------------------------------------
# The phi4flash family's serving programs at the benchmark cell's widths
# ---------------------------------------------------------------------------

def _phi4flash_programs(one_chip):
    """Phi-4-mini-flash-reasoning whole (32 layers, hidden 2560, 40/20 heads of
    64, inner width 5120 x 16 states, vocabulary 200064) as the paged engine
    runs it in ``serve-phi4flash-closed48-reason``: 48 slots x 4096, 448 pages
    of 256 rows. The chunk: one slot's gathered views of the 9 K/V entries and
    its 9 recurrent entries, a padded prompt (``valid_len``). The tick:
    ``jax.vmap`` over 48 lanes of a batch-1 forward whose K/V entries are
    ``PagedCache`` (the pool shared, read in place) and whose recurrent entries
    are the lane's own rows."""
    from accelerate_tpu.models.llama import PagedCache
    from accelerate_tpu.models.phi4flash import Phi4FlashConfig, Phi4FlashForCausalLM

    cfg = Phi4FlashConfig()
    model = Phi4FlashForCausalLM(cfg)
    slots, max_len, chunk, pages, page = 48, 4096, 256, 448, 256

    def struct(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    params = jax.tree.map(lambda a: struct(a.shape, jnp.bfloat16), shapes)
    entries = jax.eval_shape(lambda: model.init_cache(1, max_len, jnp.bfloat16))
    paged = [i for i, e in enumerate(entries) if "k" in e]

    def place(tree, lead=()):
        return jax.tree.map(lambda a: struct(lead + a.shape, a.dtype), tree)

    views = tuple(place(e) for e in entries)                    # the chunk's cache
    pool = tuple(jax.tree.map(lambda a: struct((pages + 1, 1, page) + a.shape[2:], a.dtype),
                              entries[i]) for i in paged)
    recurrent = tuple(place(e, (slots,)) for i, e in enumerate(entries) if i not in paged)

    def chunk_fn(params, ids, cache, pos, valid):
        return model.apply({"params": params}, ids, cache=cache, cache_pos=pos, valid_len=valid)

    def tick_fn(params, toks, pool, recurrent, table, positions, live):
        def one_slot(tok, rec, pages_row, pos, alive):
            kv, rec = iter(pool), iter(rec)
            cache = tuple(PagedCache(pool=next(kv), scales=None, pages=pages_row, live=alive)
                          if i in paged else next(rec) for i in range(len(entries)))
            logits, rows = model.apply({"params": params}, tok[None, None], cache=cache,
                                       cache_pos=pos)
            return jnp.argmax(logits[0, -1]), rows

        return jax.vmap(one_slot)(toks, recurrent, table, positions, live)

    i32 = jnp.int32
    return {
        "prefill_chunk": (chunk_fn, (params, struct((1, chunk), i32), views, struct((), i32),
                                     struct((), i32))),
        "decode_tick": (tick_fn, (params, struct((slots,), i32), pool, recurrent,
                                  struct((slots, max_len // page), i32), struct((slots,), i32),
                                  struct((slots,), jnp.bool_))),
    }


@pytest.fixture
def paged_kernel(monkeypatch):
    """The paged-attention kernel's dispatch on its TPU branch, lowered by
    Mosaic: the code asks ``jax.default_backend()``, which is the CPU here."""
    from accelerate_tpu.ops import paged_attention

    monkeypatch.setattr(paged_attention, "tpu_backend", lambda: True)
    monkeypatch.setattr(paged_attention, "_interpret", lambda: False)


@pytest.mark.parametrize("program", ["prefill_chunk", "decode_tick"])
def test_phi4flash_serving_program_compiles_for_v5e(program, one_chip, paged_kernel):
    import re

    fn, args = _phi4flash_programs(one_chip)[program]
    compiled = jax.jit(fn).lower(*args).compile()
    memory = compiled.memory_analysis()
    weights = 7.70e9                                             # 3.85 G parameters, bf16
    state = 48 * 3225600                                         # 0.15 GB of recurrent rows
    held = (9 * 4096 * 2560 * 2 + 3225600 if program == "prefill_chunk"
            else 449 * 256 * 46080 + state)                      # one slot's views / the pool
    assert weights * 0.98 < memory.argument_size_in_bytes - held < weights * 1.02
    text = compiled.as_text()
    if program == "prefill_chunk":
        # attention over 1024-row key blocks, 40 score heads; never the whole view's scores
        assert " while(" in text
        assert "f32[1,10,4,256,1024]" in text and "256,4096]" not in text
        assert memory.temp_size_in_bytes < 1.5e9, memory
    else:
        # no lane's view is gathered: each of the 16 attentions is one Mosaic kernel call that
        # reads the pool's live pages in place ...
        assert "[48,1,4096," not in text and "[48,4096," not in text
        assert memory.temp_size_in_bytes < 1.0e9, memory
        calls = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
        assert len(calls) == 16 and all("kv_attn/paged_flat_attention" in c for c in calls)
        # ... as it lies: a call's two pool operands are the program's own parameters, and no
        # other instruction reads, copies, reshapes or slices a whole pool leaf
        leaf = r"bf16\[449,1,256,1280\]"
        pool = set(re.findall(rf"(%\S+) = {leaf}\S* parameter\(", text))
        assert len(pool) == 18
        for call in calls:
            operands = re.search(r"custom-call\(([^)]*)\)", call).group(1).split(", ")
            assert len(pool & {op.split("*/")[-1] for op in operands}) == 2, call
        for line in text.splitlines():
            if re.search(leaf, line) and "parameter(" not in line and line not in calls:
                assert line.lstrip().startswith(("HloModule", "ENTRY")), line
        # the work list's loops and its 1280-wide weighted rows are gone
        assert not re.search(rf"\swhile\(.*{leaf}", text) and "f32[64,10,4,1280]" not in text
