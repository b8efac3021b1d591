"""Mesh-sliced tensor-parallel serving (serving.mesh_exec + engine tp=/mesh=).

Runs on conftest's 8 emulated CPU devices. The acceptance-critical
properties pinned here:

* TOKEN PARITY — a tp=2 slice engine emits bit-identical tokens to the
  single-chip engine (and offline ``generation.generate``) across greedy,
  sampled, eos-latched, and multi-tenant adapter requests: GSPMD shards
  the arithmetic, never the semantics.
* ZERO RECOMPILES — after warmup a tp=2 engine serves a mixed prompt-length
  round through exactly the three warm executables (chunk / decode tick /
  restore_prefix), with jax.monitoring's per-compile listener silent.
* PER-CHIP FOOTPRINT — live KV state bytes per chip are 1/tp of the
  single-chip engine's, and a fresh ``memory_analysis()`` compile plans
  ~1/tp the argument bytes, without touching the warm executables.
* FLEET OF SLICES — ``ReplicaSet.from_mesh`` carves disjoint tp-wide
  slices sharing ONE host-portable PrefixCache: a prefix prefilled on one
  slice is a bit-exact hit on another, and killing a slice mid-stream
  fails over token-exactly (the existing router machinery, unchanged).
* MESH-PREPARED MODELS — params sharded across a non-tensor-parallel
  training mesh raise a clear error instead of silently compiling a
  replicated engine; a tp-only prepared mesh auto-routes into the sliced
  path; unsharded params under a dp accelerator keep the single-chip path.
"""

import os
import sys
import time
import types

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from accelerate_tpu import generation  # noqa: E402
from accelerate_tpu.adapters import AdapterBank, LoRAConfig  # noqa: E402
from accelerate_tpu.adapters.lora import (  # noqa: E402
    _get_path,
    adapter_module_paths,
    init_lora_params,
)
from accelerate_tpu.models.llama import LlamaConfig, LlamaForCausalLM  # noqa: E402
from accelerate_tpu.parallel.mesh import MeshConfig  # noqa: E402
from accelerate_tpu.serving import PrefixCache, ReplicaSet, ServingEngine  # noqa: E402
from accelerate_tpu.serving.mesh_exec import (  # noqa: E402
    SliceExec,
    SlicePlan,
    validate_serving_mesh,
)
from accelerate_tpu.utils.profiling import CompileWatcher  # noqa: E402

pytestmark = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="mesh-sliced serving tests need >= 4 devices "
           "(XLA_FLAGS=--xla_force_host_platform_device_count)")

EOS = 7

PROMPTS = [
    np.array([[3, 5, 7, 11, 2]], np.int32),
    np.array([[1, 4, 9]], np.int32),
    np.array([[8, 6, 4, 2, 10, 12, 14]], np.int32),
    np.array([[42]], np.int32),
]

# Spans one-chunk and multi-chunk admission at prefill_chunk=8.
LONG_PROMPT = np.arange(1, 20, dtype=np.int32)[None]


@pytest.fixture(scope="module")
def tiny():
    cfg = LlamaConfig.tiny(use_flash_attention=False)
    m = LlamaForCausalLM(cfg)
    params = m.init_params(jax.random.PRNGKey(0), batch_size=2, seq_len=8)
    return cfg, m, params


@pytest.fixture(scope="module")
def tp2_engine(tiny):
    """Shared greedy tp=2 slice engine (warmup paid once per module)."""
    _, m, params = tiny
    eng = ServingEngine(m, params, tp=2, max_slots=3, max_len=64,
                        eos_token_id=EOS, prefill_chunk=8)
    yield eng
    if eng.running:
        eng.shutdown(drain=False)


@pytest.fixture(scope="module")
def tp1_engine(tiny):
    """Single-chip twin of tp2_engine — the parity baseline."""
    _, m, params = tiny
    eng = ServingEngine(m, params, max_slots=3, max_len=64,
                        eos_token_id=EOS, prefill_chunk=8)
    yield eng
    if eng.running:
        eng.shutdown(drain=False)


def _offline(m, params, prompt, n, seed=None, **kw):
    rng = None if seed is None else jax.random.PRNGKey(seed)
    out = generation.generate(m, params, prompt, max_new_tokens=n,
                              eos_token_id=EOS, rng=rng, **kw)
    return np.asarray(out)[0, prompt.shape[1]:]


def _assert_matches_offline(got, ref, n):
    got = np.asarray(got)
    assert np.array_equal(got, ref[: len(got)]), (got, ref)
    if len(got) < n:
        assert got[-1] == EOS and np.all(ref[len(got):] == EOS), (got, ref)


def _test_adapter(params, seed=1, rank=4):
    """LoRA adapter with a nonzero delta (random b — init_lora_params
    zeros b, which would make adapter == base and the parity vacuous)."""
    adapter = init_lora_params(jax.random.PRNGKey(seed), params,
                               LoRAConfig(rank=rank))
    for i, dotted in enumerate(adapter_module_paths(adapter)):
        mod = _get_path(adapter, dotted)
        mod["b"] = jax.random.normal(
            jax.random.PRNGKey(100 * seed + i), mod["b"].shape) * 0.1
    return adapter


class TestSlicePlan:
    def test_carves_disjoint_slices(self):
        plan = SlicePlan.plan(2)
        assert plan.tp == 2 and len(plan) == jax.device_count() // 2
        seen = set()
        for s in plan.slices:
            assert len(s) == 2
            ids = {d.id for d in s}
            assert not ids & seen
            seen |= ids

    def test_num_slices_and_mesh_shape(self):
        plan = SlicePlan.plan(2, num_slices=2)
        assert len(plan) == 2
        mesh = plan.build_mesh(1)
        assert dict(mesh.shape)["tp"] == 2 and mesh.devices.size == 2
        assert {d.id for d in mesh.devices.flat} == {d.id for d in plan.slices[1]}

    def test_plan_validation(self):
        with pytest.raises(ValueError, match="tp"):
            SlicePlan.plan(0)
        with pytest.raises(ValueError, match="devices"):
            SlicePlan.plan(2, num_slices=jax.device_count())
        with pytest.raises(ValueError, match="devices"):
            SlicePlan.plan(jax.device_count() + 1)

    def test_validate_serving_mesh_rejects_data_axes(self):
        dp_mesh = MeshConfig(devices=jax.devices()[:4]).build()
        with pytest.raises(ValueError, match="from_mesh"):
            validate_serving_mesh(dp_mesh)

    def test_heads_axis_selection(self):
        mesh = SlicePlan.plan(2, num_slices=1).build_mesh(0)
        exec_ = SliceExec(mesh)
        # KV template [1, L, n_kv, hd]: heads axis 2 when n_kv divides.
        assert exec_.heads_axis((1, 64, 2, 16), 1) == 2
        # Odd kv-head count falls back to the head_dim axis.
        assert exec_.heads_axis((1, 64, 3, 16), 1) == 3
        # Nothing divisible -> replicate.
        assert exec_.heads_axis((1, 64, 3, 5), 1) is None


class TestTokenParity:
    def test_greedy_matches_single_chip_and_offline(self, tiny, tp1_engine,
                                                    tp2_engine):
        _, m, params = tiny
        n = 16
        for p in PROMPTS + [LONG_PROMPT]:
            ref = _offline(m, params, p, n)
            got1 = np.asarray(
                tp1_engine.submit(p, max_new_tokens=n, block=True).result(120))
            got2 = np.asarray(
                tp2_engine.submit(p, max_new_tokens=n, block=True).result(120))
            assert np.array_equal(got1, got2), (p, got1, got2)
            _assert_matches_offline(got2, ref, n)

    def test_eos_latch_matches(self, tiny, tp1_engine, tp2_engine):
        """Greedy on the tiny model hits EOS naturally for some prompts;
        whatever the single-chip engine does (stop early or run full), the
        slice must do bit-identically."""
        for p in PROMPTS:
            a = np.asarray(
                tp1_engine.submit(p, max_new_tokens=24, block=True).result(120))
            b = np.asarray(
                tp2_engine.submit(p, max_new_tokens=24, block=True).result(120))
            assert np.array_equal(a, b), (p, a, b)

    def test_sampled_matches_single_chip(self, tiny):
        _, m, params = tiny
        kw = dict(max_slots=2, max_len=64, prefill_chunk=8, do_sample=True,
                  temperature=0.9, top_k=40, eos_token_id=EOS)
        e1 = ServingEngine(m, params, **kw)
        e2 = ServingEngine(m, params, tp=2, **kw)
        try:
            for i, p in enumerate(PROMPTS):
                a = np.asarray(e1.submit(p, max_new_tokens=12, seed=123 + i,
                                         block=True).result(120))
                b = np.asarray(e2.submit(p, max_new_tokens=12, seed=123 + i,
                                         block=True).result(120))
                assert np.array_equal(a, b), (p, a, b)
        finally:
            e1.shutdown(drain=False)
            e2.shutdown(drain=False)

    def test_async_matches_sync_at_tp2(self, tiny, tp2_engine):
        """One-tick-ahead dispatch must stay bit-exact when the tick is
        a GSPMD-sliced executable: the shared tp=2 engine against
        offline generate over mixed-length traffic submitted at once."""
        _, m, params = tiny
        n = 16
        prompts = PROMPTS + [LONG_PROMPT]
        reqs = [tp2_engine.submit(p, max_new_tokens=n) for p in prompts]
        for r, p in zip(reqs, prompts):
            _assert_matches_offline(r.result(120), _offline(m, params, p, n),
                                    n)

    def test_multi_tenant_adapters_match(self, tiny):
        """Adapter and base streams through bank-equipped engines: tp=2
        == single-chip for both, and the adapter actually changes tokens
        (a zero-delta bank would make this parity vacuous)."""
        _, m, params = tiny
        adapter = _test_adapter(params)

        def bank():
            return AdapterBank(params, config=LoRAConfig(rank=4),
                               max_adapters=3)

        kw = dict(max_slots=2, max_len=64, prefill_chunk=8, eos_token_id=EOS)
        e1 = ServingEngine(m, params, adapters=bank(), **kw)
        e2 = ServingEngine(m, params, adapters=bank(), tp=2, **kw)
        try:
            for e in (e1, e2):
                e.register_adapter("t1", adapter)
            p = PROMPTS[0]
            a_ad = np.asarray(e1.submit(p, max_new_tokens=12, adapter="t1",
                                        ignore_eos=True, block=True).result(120))
            b_ad = np.asarray(e2.submit(p, max_new_tokens=12, adapter="t1",
                                        ignore_eos=True, block=True).result(120))
            a_base = np.asarray(e1.submit(p, max_new_tokens=12, ignore_eos=True,
                                          block=True).result(120))
            b_base = np.asarray(e2.submit(p, max_new_tokens=12, ignore_eos=True,
                                          block=True).result(120))
            assert np.array_equal(a_ad, b_ad), (a_ad, b_ad)
            assert np.array_equal(a_base, b_base), (a_base, b_base)
            assert not np.array_equal(a_ad, a_base), "adapter delta is zero"
        finally:
            e1.shutdown(drain=False)
            e2.shutdown(drain=False)


class TestZeroRecompileMesh:
    def test_three_warm_executables_no_recompiles(self, tp2_engine):
        """After warmup a tp=2 slice serves a mixed-length round (one- and
        multi-chunk prompts, a repeat prompt for the restore path) through
        EXACTLY the three warm executables with zero new XLA compiles."""
        with CompileWatcher() as watcher:
            reqs = []
            for i, p in enumerate(PROMPTS + [LONG_PROMPT, LONG_PROMPT]):
                reqs.append(tp2_engine.submit(p, max_new_tokens=8,
                                              block=True))
                time.sleep(0.002 * i)
            for r in reqs:
                r.result(timeout=120)
        assert not watcher.events, (
            f"XLA recompiled after warmup: {watcher.events} — mesh slicing "
            "must shard the three warm programs, not multiply them")
        assert tp2_engine._prefill_chunk._cache_size() == 1
        assert tp2_engine._decode._cache_size() == 1
        # Paged + private alias cache: prefix restores are host page-table
        # writes, so there is no compiled restore program to pin.
        if tp2_engine._restore_prefix is not None:
            assert tp2_engine._restore_prefix._cache_size() == 1


class TestSlicedSpeculation:
    """tp=2 column of the universal-speculation exactness matrix: a
    sliced engine speculates (replicated draft feeding the tp-sharded
    verify) with streams bit-identical to the single-chip non-speculative
    engine, under the same zero-recompile pin."""

    def _run(self, eng, prompts=PROMPTS, n=24, **kw):
        reqs = []
        for p in prompts:
            reqs.append(eng.submit(p, max_new_tokens=n, **kw))
            time.sleep(0.01)
        return [np.asarray(r.result(timeout=180)) for r in reqs]

    def test_tp2_draft_spec_matches_tp1_and_pins_compiles(self, tiny,
                                                          tp1_engine):
        _, m, params = tiny
        eng = ServingEngine(m, params, tp=2, max_slots=3, max_len=64,
                            eos_token_id=EOS, prefill_chunk=8,
                            prefix_cache_mb=0.0,
                            draft_model=m, draft_params=params,
                            spec_tokens=4)
        try:
            with CompileWatcher() as watcher:
                a = self._run(eng)
            b = self._run(tp1_engine)
            s = eng.stats.summary()
            assert s["spec_ticks"] > 0, s
            assert eng._spec._cache_size() == 1
            assert eng._prefill_chunk._cache_size() == 1
        finally:
            eng.shutdown(drain=False)
        for x, y in zip(a, b):
            assert np.array_equal(x, y), (x, y)
        assert not watcher.events, (
            f"XLA recompiled after warmup: {watcher.events} — the sliced "
            "_spec program must treat draft pages and acceptance as data")

    def test_tp2_lookup_spec_matches_tp1(self, tiny, tp1_engine):
        _, m, params = tiny
        eng = ServingEngine(m, params, tp=2, max_slots=3, max_len=64,
                            eos_token_id=EOS, prefill_chunk=8,
                            prefix_cache_mb=0.0, spec_lookup=2,
                            spec_tokens=4)
        try:
            a = self._run(eng)
            b = self._run(tp1_engine)
            assert eng.stats.summary()["spec_ticks"] > 0
        finally:
            eng.shutdown(drain=False)
        for x, y in zip(a, b):
            assert np.array_equal(x, y), (x, y)


class TestPerChipFootprint:
    def test_kv_per_chip_halved(self, tp1_engine, tp2_engine):
        kv1 = tp1_engine.kv_cache_per_chip_bytes()
        kv2 = tp2_engine.kv_cache_per_chip_bytes()
        assert kv1 > 0 and kv2 * 2 == kv1, (kv1, kv2)

    def test_memory_analysis_args_shrink_without_new_executables(
            self, tp1_engine, tp2_engine):
        """XLA's own compiled-memory accounting must see ~1/tp argument
        bytes (params + state are the arguments), and probing it must not
        add entries to the warm serving jits."""
        m1 = tp1_engine.decode_memory_analysis()
        m2 = tp2_engine.decode_memory_analysis()
        a1 = getattr(m1, "argument_size_in_bytes", None)
        a2 = getattr(m2, "argument_size_in_bytes", None)
        if a1 is None or a2 is None:
            pytest.skip("memory_analysis lacks argument sizes on this backend")
        # Not exactly /2: replicated scalars/norms and the membership rows
        # stay whole on every chip.
        assert a2 < 0.6 * a1, (a1, a2)
        assert tp2_engine._prefill_chunk._cache_size() == 1
        assert tp2_engine._decode._cache_size() == 1
        if tp2_engine._restore_prefix is not None:
            assert tp2_engine._restore_prefix._cache_size() == 1


class TestShardedPrefixCache:
    def test_blocks_are_host_portable_and_roundtrip_bit_exact(self, tiny):
        """A tp=2 engine's PRIVATE prefix cache holds host page-id tuples
        (the paged engine aliases pages instead of copying KV); an engine
        sharing an EXTERNAL cache keeps device_get host-numpy blocks — the
        slice-portable representation failover relies on. Both restore a
        repeat prompt bit-identically."""
        _, m, params = tiny
        eng = ServingEngine(m, params, tp=2, max_slots=2, max_len=64,
                            eos_token_id=EOS, prefill_chunk=8)
        try:
            first = np.asarray(eng.submit(LONG_PROMPT, max_new_tokens=10,
                                          block=True).result(120))
            cache = eng.prefix_cache
            assert len(cache) > 0
            for block, _nbytes in cache._entries.values():
                for leaf in jax.tree.leaves(block):
                    assert isinstance(leaf, int), type(leaf)  # page ids
            again = np.asarray(eng.submit(LONG_PROMPT, max_new_tokens=10,
                                          block=True).result(120))
            assert np.array_equal(first, again), (first, again)
            s = eng.serving_metrics()
            assert s["prefix_cache_hit_chunks"] >= 2
            assert s["prefix_alias_chunks"] >= 2
        finally:
            eng.shutdown(drain=False)
        shared = PrefixCache(4 * 1024 * 1024)
        eng = ServingEngine(m, params, tp=2, max_slots=2, max_len=64,
                            eos_token_id=EOS, prefill_chunk=8,
                            prefix_cache=shared)
        try:
            third = np.asarray(eng.submit(LONG_PROMPT, max_new_tokens=10,
                                          block=True).result(120))
            assert np.array_equal(first, third), (first, third)
            assert len(shared) > 0
            for block, _nbytes in shared._entries.values():
                for leaf in jax.tree.leaves(block):
                    assert isinstance(leaf, np.ndarray), type(leaf)
            fourth = np.asarray(eng.submit(LONG_PROMPT, max_new_tokens=10,
                                           block=True).result(120))
            assert np.array_equal(first, fourth), (first, fourth)
            assert eng.serving_metrics()["prefix_cache_hit_chunks"] >= 2
        finally:
            eng.shutdown(drain=False)

    def test_cross_slice_hit_after_shared_prefill(self, tiny):
        """One slice prefills, the OTHER slice hits: the fleet-shared
        cache's host blocks restore bit-exactly across slices (the prefix
        half of the failover resume path, tested in isolation)."""
        _, m, params = tiny
        fleet = ReplicaSet.from_mesh(m, params, tp=2, num_slices=2,
                                     max_slots=2, max_len=64,
                                     eos_token_id=EOS, prefill_chunk=8)
        try:
            e0, e1 = fleet.engine(0), fleet.engine(1)
            assert e0.prefix_cache is e1.prefix_cache
            ref = _offline(m, params, LONG_PROMPT, 10)
            a = np.asarray(e0.submit(LONG_PROMPT, max_new_tokens=10,
                                     block=True).result(120))
            b = np.asarray(e1.submit(LONG_PROMPT, max_new_tokens=10,
                                     block=True).result(120))
            assert np.array_equal(a, b)
            _assert_matches_offline(b, ref, 10)
            s1 = e1.serving_metrics()
            assert s1["prefix_cache_hit_chunks"] >= 2, (
                "slice 1 recomputed a prefix slice 0 already cached")
        finally:
            fleet.shutdown()


class TestFromMeshFleet:
    def test_failover_between_slices_token_exact(self, tiny):
        """Kill one of two tp=2 slices mid-stream: the survivor resumes
        every in-flight request with zero lost or duplicated tokens
        (greedy = bit-exact against offline)."""
        _, m, params = tiny
        fleet = ReplicaSet.from_mesh(m, params, tp=2, num_slices=2,
                                     max_slots=2, max_len=64,
                                     eos_token_id=EOS, prefill_chunk=8)
        n = 40
        ref = _offline(m, params, LONG_PROMPT, n, seed=None)
        try:
            r = fleet.submit(LONG_PROMPT, max_new_tokens=n, ignore_eos=True)
            deadline = time.monotonic() + 60
            while len(r.tokens) < 4 and time.monotonic() < deadline:
                time.sleep(0.002)
            assert len(r.tokens) >= 4, "stream stalled before the kill"
            victim = r.replica_trail[0]
            fleet.kill_replica(victim)
            assert r.wait(timeout=120)
            got = np.asarray(r.tokens)
            full = _offline(m, params, LONG_PROMPT, n)
            assert np.array_equal(got, full[: len(got)]), (got, full)
            assert r.failovers == 1
            assert r.replica_trail == [victim, 1 - victim]
        finally:
            fleet.shutdown()
        del ref

    def test_from_mesh_plan_and_engine_affinity(self, tiny):
        _, m, params = tiny
        fleet = ReplicaSet.from_mesh(m, params, tp=2, num_slices=2,
                                     max_slots=2, max_len=32,
                                     prefill_chunk=8)
        try:
            assert len(fleet) == 2 and fleet.slice_plan.tp == 2
            d0 = {d.id for d in fleet.engine(0).mesh.devices.flat}
            d1 = {d.id for d in fleet.engine(1).mesh.devices.flat}
            assert d0 and d1 and not (d0 & d1), (d0, d1)
            assert fleet.engine(0).tp == fleet.engine(1).tp == 2
        finally:
            fleet.shutdown()

    def test_per_slice_adapter_banks_required(self, tiny):
        """One AdapterBank cannot be placed on two slices; from_mesh's
        make_adapters factory gives each slice its own."""
        _, m, params = tiny
        shared = AdapterBank(params, config=LoRAConfig(rank=4), max_adapters=3)
        kw = dict(max_slots=1, max_len=32, prefill_chunk=8)
        e0 = ServingEngine(m, params, adapters=shared,
                           mesh=SlicePlan.plan(2, num_slices=2).build_mesh(0),
                           **kw)
        try:
            with pytest.raises(ValueError, match="OWN bank"):
                ServingEngine(m, params, adapters=shared,
                              mesh=SlicePlan.plan(2, num_slices=2).build_mesh(1),
                              **kw)
        finally:
            e0.shutdown(drain=False)


class TestMeshPreparedModels:
    def test_sharded_params_on_training_mesh_raise(self, tiny):
        """The regression this PR fixes: params genuinely sharded across a
        non-tensor-parallel mesh must raise a clear error instead of
        silently compiling a replicated (gathering) engine."""
        from jax.sharding import NamedSharding, PartitionSpec

        _, m, params = tiny
        mesh = MeshConfig(dp=1, fsdp=4, devices=jax.devices()[:4]).build()
        sharded = jax.device_put(
            params, NamedSharding(mesh, PartitionSpec()))
        # Shard at least one real axis so the leaves span all 4 devices.
        emb = sharded["model"]["embed_tokens"]["embedding"]
        sharded["model"]["embed_tokens"]["embedding"] = jax.device_put(
            emb, NamedSharding(mesh, PartitionSpec("fsdp", None)))
        acc = types.SimpleNamespace(policy=None, mesh=mesh,
                                    preemption_requested=False)
        with pytest.raises(ValueError, match="Re-prepare|tp="):
            ServingEngine(m, sharded, accelerator=acc, max_slots=1,
                          max_len=32, prefill_chunk=8, autostart=False)

    def test_tp_only_prepared_mesh_autoroutes(self, tiny):
        """A model prepared under MeshConfig(dp=1, tp=2) serves through the
        sliced path without any explicit tp=/mesh= argument."""
        _, m, params = tiny
        mesh = MeshConfig(dp=1, tp=2, devices=jax.devices()[:2]).build()
        acc = types.SimpleNamespace(policy=None, mesh=mesh,
                                    preemption_requested=False)
        eng = ServingEngine(m, params, accelerator=acc, max_slots=2,
                            max_len=64, eos_token_id=EOS, prefill_chunk=8)
        try:
            assert eng.tp == 2 and eng._exec is not None
            ref = _offline(m, params, PROMPTS[0], 8)
            got = np.asarray(eng.submit(PROMPTS[0], max_new_tokens=8,
                                        block=True).result(120))
            _assert_matches_offline(got, ref, 8)
        finally:
            eng.shutdown(drain=False)

    def test_unsharded_params_on_dp_mesh_stay_single_chip(self, tiny):
        """A default data-parallel accelerator whose params were never
        sharded keeps the status-quo single-chip path (no gather risk)."""
        _, m, params = tiny
        mesh = MeshConfig(devices=jax.devices()).build()  # dp=-1 absorbs all
        acc = types.SimpleNamespace(policy=None, mesh=mesh,
                                    preemption_requested=False)
        eng = ServingEngine(m, params, accelerator=acc, max_slots=1,
                            max_len=32, prefill_chunk=8, autostart=False)
        assert eng.tp == 1 and eng._exec is None

    def test_monolithic_prefill_rejected_under_tp(self, tiny):
        _, m, params = tiny
        with pytest.raises(ValueError, match="only prefill"):
            ServingEngine(m, params, tp=2, max_slots=1, max_len=32,
                          prefill_chunk=None, autostart=False)

    def test_tp_mesh_conflict_rejected(self, tiny):
        _, m, params = tiny
        mesh = SlicePlan.plan(2, num_slices=1).build_mesh(0)
        with pytest.raises(ValueError, match="tp"):
            ServingEngine(m, params, tp=4, mesh=mesh, max_slots=1,
                          max_len=32, prefill_chunk=8, autostart=False)
