"""Bring-up invariants: one compile-cache path that can be placed from
outside, no fallback that hides the device, CompileWatcher on the installed
jax, one compile of the fused train step, and replicas on their own devices.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# compile cache placement
# ---------------------------------------------------------------------------

#: an entry is keyed with the program's metadata, the named parts among it
METADATA_IN_KEY = ("jax_compilation_cache_include_metadata_in_key", True)


class TestCompilationCachePlacement:
    @pytest.fixture
    def config_writes(self, monkeypatch):
        """Record (and swallow) every jax.config.update call."""
        writes = []
        monkeypatch.setattr(jax.config, "update", lambda k, v: writes.append((k, v)))
        return writes

    def test_variable_set_means_the_directory_is_not_written(self, monkeypatch, config_writes, tmp_path):
        from accelerate_tpu.utils.platforms import enable_compilation_cache

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compilation_cache() == str(tmp_path)
        assert config_writes == [METADATA_IN_KEY]

    def test_unset_means_the_fixed_checkout_path(self, monkeypatch, config_writes):
        from accelerate_tpu.utils import platforms

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = os.path.join(REPO, ".jax_cache")
        assert platforms.DEFAULT_COMPILATION_CACHE == want
        assert platforms.enable_compilation_cache() == want
        assert config_writes == [METADATA_IN_KEY, ("jax_compilation_cache_dir", want)]

    def test_accelerator_goes_through_the_helper(self, monkeypatch, config_writes, tmp_path):
        from accelerate_tpu import Accelerator

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        Accelerator()
        assert not [w for w in config_writes if w[0] == "jax_compilation_cache_dir"]
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

        for cls in (AcceleratorState, GradientState, PartialState):
            cls._reset_state()
        Accelerator()
        assert ("jax_compilation_cache_dir", os.path.join(REPO, ".jax_cache")) in config_writes

    def test_serve_turns_the_cache_on_before_anything_else(self, monkeypatch):
        from accelerate_tpu.commands import serve
        from accelerate_tpu.utils import platforms

        class Reached(Exception):
            pass

        def stop():
            raise Reached

        monkeypatch.setattr(platforms, "enable_compilation_cache", stop)
        with pytest.raises(Reached):
            serve.serve_command(serve.serve_command_parser().parse_args([]))

    def test_jit_config_no_longer_places_the_cache(self):
        from accelerate_tpu.utils import JitConfig

        assert "persistent_cache_dir" not in JitConfig.__dataclass_fields__
        assert not hasattr(JitConfig, "apply")

    def test_one_place_writes_the_cache_dir_and_old_knobs_are_gone(self):
        """Only utils/platforms.py may name jax_compilation_cache_dir in a
        config write; the two ACCELERATE_TPU_* cache variables are gone."""
        writers, old_knobs = [], []
        roots = ["accelerate_tpu", "benchmarks", "examples", "docs", "bench.py",
                 "chip_smoke.py", "__graft_entry__.py", "README.md"]
        for root in roots:
            path = os.path.join(REPO, root)
            files = [path] if os.path.isfile(path) else [
                os.path.join(d, f) for d, _, fs in os.walk(path) for f in fs
                if f.endswith((".py", ".md", ".yaml"))]
            for f in files:
                text = open(f, encoding="utf-8").read()
                if re.search(r"update\(\s*[\"']jax_compilation_cache_dir", text):
                    writers.append(os.path.relpath(f, REPO))
                if re.search(r"ACCELERATE_TPU_COMPIL(E|ATION)_CACHE", text):
                    old_knobs.append(os.path.relpath(f, REPO))
        assert writers == ["accelerate_tpu/utils/platforms.py"]
        assert old_knobs == []


# ---------------------------------------------------------------------------
# no fallback that hides the device
# ---------------------------------------------------------------------------

class TestNoSilentFallback:
    def test_platforms_has_no_probe_or_resolver(self):
        from accelerate_tpu.utils import platforms

        for gone in ("resolve_backend", "probe_backend_info", "probe_default_backend",
                     "run_with_group_timeout", "same_chip", "PROBE_FILE_CACHE_TTL"):
            assert not hasattr(platforms, gone), gone

    @pytest.mark.parametrize("backend,interpret", [("cpu", True), ("tpu", False),
                                                   ("gpu", False), ("mystery", False)])
    def test_interpret_only_where_the_backend_is_positively_the_cpu(
            self, monkeypatch, backend, interpret):
        from accelerate_tpu.ops import flash_pallas

        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        # The old environment override is ignored.
        monkeypatch.setenv("ACCELERATE_TPU_PALLAS_INTERPRET", "1")
        assert flash_pallas._interpret() is interpret

    def test_interpret_and_availability_have_no_exception_path(self, monkeypatch):
        from accelerate_tpu.ops import attention, flash_pallas

        def broken():
            raise RuntimeError("backend failed to initialise")

        monkeypatch.setattr(jax, "default_backend", broken)
        with pytest.raises(RuntimeError, match="failed to initialise"):
            flash_pallas._interpret()
        with pytest.raises(RuntimeError, match="failed to initialise"):
            attention.flash_attention_available()

    @pytest.mark.parametrize("backend,shape,ok", [
        ("tpu", (1, 4096, 32, 128), True),
        ("tpu", (1, 100, 32, 128), False),     # seq not a multiple of 128
        ("tpu", (1, 4096, 32, 512), False),    # head_dim > 256
        ("cpu", (1, 4096, 32, 128), False),
        ("gpu", (1, 4096, 32, 128), False),
    ])
    def test_flash_availability_is_backend_plus_shape_rule(self, monkeypatch, backend,
                                                           shape, ok):
        from accelerate_tpu.ops import attention

        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        q = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
        assert attention.flash_attention_available(q) is ok

    def test_env_command_reports_the_device_it_was_given(self, capsys):
        from accelerate_tpu.commands.env import env_command, env_command_parser

        parser = env_command_parser()
        assert "--probe_timeout" not in parser.format_help()
        assert env_command(parser.parse_args([])) == 0
        out = capsys.readouterr().out
        assert "- Backend: cpu" in out and "- Device kind: cpu" in out
        assert "- Device count: 8" in out

    def test_import_does_not_rewrite_the_platform(self):
        """accelerate_tpu no longer mirrors platform variables into
        jax.config at import (jax reads JAX_PLATFORMS itself)."""
        import accelerate_tpu

        src = open(accelerate_tpu.__file__).read()
        assert "jax_platforms" not in src and "ACCELERATE_TPU_PLATFORM" not in src


# ---------------------------------------------------------------------------
# CompileWatcher on the installed jax
# ---------------------------------------------------------------------------

class TestCompileWatcherLifecycle:
    @staticmethod
    def _compile_something(n):
        jax.jit(lambda x: x * n + n)(jnp.arange(n + 3.0)).block_until_ready()

    def test_start_stop_restart(self):
        from accelerate_tpu.utils.profiling import CompileWatcher

        w = CompileWatcher().start()
        self._compile_something(101)
        seen = w.total
        assert seen > 0
        w.stop()                       # the call that raised on jax 0.9.0
        self._compile_something(102)
        assert w.total == seen, "still listening after stop()"
        w.start()                      # restart re-registers
        self._compile_something(103)
        assert w.total > seen
        w.stop()
        w.stop()                       # idempotent

    def test_stop_unregisters_exactly_its_own_listeners(self):
        from accelerate_tpu.utils.profiling import CompileWatcher

        a, b = CompileWatcher().start(), CompileWatcher().start()
        a.stop()
        self._compile_something(104)
        assert a.total == 0 and b.total > 0
        b.stop()

    def test_context_manager_and_reset(self):
        from accelerate_tpu.utils.profiling import CompileWatcher

        with CompileWatcher() as w:
            self._compile_something(105)
            assert w.events
            w.reset()
            assert not w.events and w.summary()["compile_events"] == 0
        self._compile_something(106)
        assert not w.events


# ---------------------------------------------------------------------------
# the fused train step compiles once
# ---------------------------------------------------------------------------

class TestTrainStepCompilesOnce:
    """Step 2 used to compile the whole step a second time: Adam's count came
    in uncommitted and went out committed (any mesh), and on a tp mesh GSPMD
    handed replicated norm scales back tp-sharded."""

    @pytest.mark.parametrize("mesh", [{}, {"fsdp": 2, "tp": 2}], ids=["one_device", "fsdp2_tp2"])
    def test_three_steps_one_executable(self, mesh):
        import optax

        from accelerate_tpu import Accelerator, MeshConfig, Model
        from accelerate_tpu.data_loader import make_global_batch
        from accelerate_tpu.models.llama import LlamaConfig, LlamaForCausalLM, causal_lm_loss
        from accelerate_tpu.utils import FullyShardedDataParallelPlugin, TensorParallelPlugin

        n = int(np.prod(list(mesh.values()) or [1]))
        acc = Accelerator(
            mixed_precision="bf16",
            mesh_config=MeshConfig(**mesh, devices=jax.devices()[:n]),
            fsdp_plugin=FullyShardedDataParallelPlugin() if "fsdp" in mesh else None,
            tp_plugin=TensorParallelPlugin(tp_size=mesh["tp"]) if "tp" in mesh else None)
        module = LlamaForCausalLM(LlamaConfig.tiny())
        params = module.init_params(jax.random.PRNGKey(0))
        model, opt = acc.prepare(Model(module, params), optax.adamw(1e-3))
        step = acc.compile_train_step(causal_lm_loss(module.apply), max_grad_norm=1.0)
        ids = np.random.default_rng(0).integers(0, 256, (2, 32)).astype(np.int32)
        layouts = []
        for _ in range(3):
            step(make_global_batch({"input_ids": ids}, acc.mesh))
            layouts.append([str(x.sharding.spec) if hasattr(x.sharding, "spec") else "-"
                            for x in jax.tree.leaves((model.params, opt.opt_state))])
        assert step._jitted._cache_size() == 1
        assert layouts[0] == layouts[1] == layouts[2], "a step changed a leaf's layout"
        assert all(x.committed for x in jax.tree.leaves(opt.opt_state))


# ---------------------------------------------------------------------------
# replicas on their own devices
# ---------------------------------------------------------------------------

def _devices_of(tree):
    return {d.id for leaf in jax.tree.leaves(tree) for d in leaf.devices()}


class TestReplicaPlacement:
    @pytest.fixture(scope="class")
    def tiny(self):
        from accelerate_tpu.models.llama import LlamaConfig, LlamaForCausalLM

        module = LlamaForCausalLM(LlamaConfig.tiny())
        return module, module.init_params(jax.random.PRNGKey(0))

    def _factory(self, tiny, **kw):
        from accelerate_tpu.serving import ServingEngine

        module, params = tiny
        return lambda: ServingEngine(module, params, max_slots=2, max_len=64,
                                     prefill_chunk=16, **kw)

    def test_from_factory_puts_each_replica_on_its_own_device(self, tiny):
        from accelerate_tpu.serving import ReplicaSet

        prompt = np.arange(1, 21, dtype=np.int32)[None]
        with ReplicaSet.from_factory(self._factory(tiny), 3) as rs:
            tokens = []
            for i in range(3):
                engine = rs.engine(i)
                req = engine.submit(prompt, max_new_tokens=6)
                assert req.wait(120)
                tokens.append(list(req.tokens))
                # after serving, params AND the donated, rewritten KV state
                # are still on replica i's device
                assert _devices_of(engine.params) == {i}
                assert _devices_of(engine._state) == {i}
                assert engine._decode._cache_size() == 1
            assert tokens[0] == tokens[1] == tokens[2]

    def test_more_replicas_than_devices_wrap_around(self, tiny, monkeypatch):
        from accelerate_tpu.serving import ReplicaSet

        monkeypatch.setattr(jax, "local_devices", lambda: jax.devices()[:2])
        with ReplicaSet.from_factory(self._factory(tiny), 3) as rs:
            assert [_devices_of(rs.engine(i).params) for i in range(3)] == [{0}, {1}, {0}]

    def test_restart_and_unpark_return_to_the_same_device(self, tiny):
        from accelerate_tpu.serving import ReplicaSet

        factory = self._factory(tiny)
        with ReplicaSet.from_factory(factory, 2) as rs:
            rs.engine(1).shutdown()
            rs._fence(rs.replicas[1])
            rs.restart_replica(1)
            assert _devices_of(rs.engine(1)._state) == {1}
            idx = rs.add_parked(factory)
            assert idx == 2
            rs.unpark_replica(idx)
            assert _devices_of(rs.engine(idx).params) == {2}

    def test_adapter_bank_follows_its_replica(self, tiny):
        from accelerate_tpu.adapters import AdapterBank, LoRAConfig, init_lora_params
        from accelerate_tpu.serving import ReplicaSet, ServingEngine

        module, params = tiny

        def factory():
            bank = AdapterBank(params, config=LoRAConfig(rank=4), max_adapters=3)
            return ServingEngine(module, params, max_slots=2, max_len=64,
                                 prefill_chunk=16, adapters=bank)

        with ReplicaSet.from_factory(factory, 2) as rs:
            adapter = init_lora_params(jax.random.PRNGKey(1), params, LoRAConfig(rank=4))
            rs.register_adapter("tenant", adapter)
            req = rs.engine(1).submit(np.arange(1, 9, dtype=np.int32)[None],
                                      max_new_tokens=4, adapter="tenant")
            assert req.wait(120) and len(req.tokens) == 4
            # the row write ran on, and left the bank on, replica 1's device
            assert _devices_of(rs.engine(1)._adapters.stacks) == {1}
            assert _devices_of(rs.engine(0)._adapters.stacks) == {0}
