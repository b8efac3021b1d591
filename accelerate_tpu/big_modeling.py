"""Big-model inference: run models larger than HBM (L7).

TPU-native re-design of the reference's big-model stack (reference:
src/accelerate/big_modeling.py — init_empty_weights :57, cpu_offload :170,
disk_offload :231, dispatch_model :306, load_checkpoint_and_dispatch :504;
src/accelerate/hooks.py — AlignDevicesHook :220).

The reference's mechanism is per-module forward *hooks* that move torch
weights between disk/CPU/GPU around each submodule call. Hooks don't exist
in JAX — and aren't wanted: under jit every weight movement would be traced
away or force a host sync. The TPU-native design instead:

* "meta device" init        → ``jax.eval_shape`` (zero-memory abstract tree)
* device-map solver         → pure math over the abstract tree
  (``utils/modeling.infer_auto_device_map``) with HBM → host DRAM → disk tiers
* hook-based streaming      → a **block-wise executor**: the model is split
  into an embed block, N identical layer blocks, and a head block; one jitted
  block function is compiled *once* and reused for every layer (identical
  shapes → one XLA executable), while a background thread prefetches the next
  block's weights host→HBM (``jax.device_put`` is async, so transfer overlaps
  compute). Disk tiers are lazy references into the original safetensors
  shards — no duplicate offload copy is written unless requested.

Peak HBM = largest block × 2 (double buffer), matching the reference's
"peak GPU memory == module size" property (reference:
benchmarks/big_model_inference/README.md:43-45).
"""

from __future__ import annotations

import dataclasses
import json
import os
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Optional, Union

import numpy as np

import jax
import jax.numpy as jnp

from .utils.modeling import (
    DeviceId,
    check_device_map,
    get_balanced_memory,
    infer_auto_device_map,
    named_parameters,
)

SAFE_INDEX = "model.safetensors.index.json"


# ---------------------------------------------------------------------------
# Abstract ("meta") initialization
# ---------------------------------------------------------------------------

def init_empty_weights(module, *example_args, rng=None, **example_kwargs):
    """Abstract parameter tree with zero memory (reference: init_empty_weights
    :57 patches ``register_parameter`` onto the meta device; here
    ``jax.eval_shape`` traces ``module.init`` without allocating).

    Returns the inner param tree (no ``{"params": ...}`` wrapper) of
    ``jax.ShapeDtypeStruct`` leaves.
    """
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    if not example_args and not example_kwargs:
        example_args = (jnp.zeros((1, 8), jnp.int32),)
    variables = jax.eval_shape(lambda: module.init(rng, *example_args, **example_kwargs))
    return _unwrap_params(variables)


def _unwrap_params(tree):
    """Strip the flax ``{"params": ...}`` wrapper so names match flattened
    safetensors keys. Extra variable collections (e.g. BatchNorm
    ``batch_stats``) are dropped — the streaming executor targets inference
    on param-only architectures; stateful collections must be handled by the
    caller."""
    if hasattr(tree, "keys") and "params" in set(tree.keys()):
        return dict(tree)["params"]
    return tree


def _subtree(tree, prefix: str):
    node = tree
    for part in prefix.split("."):
        node = node[part]
    return node


def _nest(flat: dict) -> dict:
    out: dict = {}
    for key, val in flat.items():
        parts = key.split(".")
        node = out
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = val
    return out


# ---------------------------------------------------------------------------
# Weight store: flat name -> resident array | lazy disk reference
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class LazyWeight:
    """A tensor still on disk: either a safetensors shard member or a raw
    offload memmap (reference: OffloadedWeightsLoader :127 / set_module_tensor
    staging). Materialized only when its block is fetched."""

    path: str
    key: str
    dtype: Optional[Any] = None  # cast target
    memmap_info: Optional[dict] = None  # set for raw .dat memmaps (utils/offload.py)
    transform: Optional[str] = None  # "t" = transpose on load (HF torch layout)

    def load(self) -> np.ndarray:
        """Read the tensor from its backing store into host memory."""
        if self.memmap_info is not None:
            from .utils.offload import load_offloaded_weight

            arr = np.asarray(load_offloaded_weight(self.path, self.memmap_info))
        else:
            from safetensors import safe_open

            with safe_open(self.path, framework="numpy") as f:
                arr = f.get_tensor(self.key)
        if self.transform == "t":
            arr = np.ascontiguousarray(arr.T)
        if self.dtype is not None:
            arr = arr.astype(self.dtype)
        return arr


@dataclasses.dataclass
class LazyStack:
    """A stacked tensor whose members are still on disk (mixtral experts:
    E per-expert matrices -> one (E, in, out) param). Loaded and stacked
    only when the owning block is fetched."""

    members: list  # [(shard_path, ckpt_key, member_op)] in stack order
    dtype: Optional[Any] = None

    def load(self) -> np.ndarray:
        """Read the tensor from its backing store into host memory."""
        from safetensors import safe_open

        from .utils.hf_interop import _apply_op

        # One safe_open per distinct shard (members usually share one file;
        # re-parsing its header per member would recur on every block fetch).
        parts: list = [None] * len(self.members)
        by_path: dict[str, list[int]] = {}
        for i, (path, _, _) in enumerate(self.members):
            by_path.setdefault(path, []).append(i)
        for path, idxs in by_path.items():
            with safe_open(path, framework="numpy") as f:
                for i in idxs:
                    _, key, op = self.members[i]
                    parts[i] = _apply_op(f.get_tensor(key), op or "copy")
        arr = np.stack(parts)
        return arr if self.dtype is None else arr.astype(self.dtype)


class WeightStore:
    """Flat ``{param_name: entry}`` with per-name placement. Entries are
    jax.Arrays (resident in HBM), numpy arrays (host DRAM), or LazyWeight
    (disk)."""

    def __init__(self):
        self.entries: dict[str, Any] = {}
        self.placement: dict[str, DeviceId] = {}

    def put(self, name: str, value, device: DeviceId):
        """Store a tensor under ``name`` on the given placement tier."""
        self.placement[name] = device
        self.entries[name] = value

    def names_under(self, prefix: str) -> list[str]:
        """All stored parameter names with this prefix."""
        return [n for n in self.entries if n == prefix or n.startswith(prefix + ".")]

    def fetch_subtree(self, prefix: str, device=None):
        """Materialize the subtree under ``prefix`` (relative names) onto
        ``device``. Lazy/disk and host entries are read + transferred;
        resident entries pass through."""
        flat = {}
        for name in self.names_under(prefix):
            rel = name[len(prefix) + 1:] if name != prefix else name.rsplit(".", 1)[-1]
            val = self.entries[name]
            if isinstance(val, (LazyWeight, LazyStack)):
                val = val.load()
            if device is not None and not _on_device(val, device):
                val = jax.device_put(val, device)
            flat[rel] = val
        return _nest(flat)

    def total_bytes(self, kind: Optional[str] = None) -> int:
        """Total stored bytes, optionally for one placement kind."""
        total = 0
        for name, val in self.entries.items():
            place = self.placement.get(name)
            lazy = isinstance(val, (LazyWeight, LazyStack))
            k = "disk" if lazy else ("cpu" if place == "cpu" else "device")
            if kind is None or k == kind:
                if lazy:
                    total += 0
                else:
                    total += int(np.prod(val.shape)) * val.dtype.itemsize if hasattr(val, "shape") else 0
        return total


def _on_device(val, device) -> bool:
    if not isinstance(val, jax.Array):
        return False
    try:
        return list(val.devices()) == [device]
    except Exception:
        return False


# ---------------------------------------------------------------------------
# Block specs: how a model family splits into streamable blocks
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class BlockSpec:
    """One streamable unit. ``apply(ptrees, *activations)`` where ``ptrees``
    is a tuple of param subtrees, one per prefix in order. The tuple (not a
    prefix-keyed dict) keeps the jit treedef identical across layers, so
    blocks sharing ``kind`` share one jitted executable (all layer blocks
    have identical param shapes -> exactly one XLA compilation).

    ``cached_apply`` (optional) is the KV-cached decode form:
    ``cached_apply(ptrees, args, cache, pos) -> (args, new_cache)`` where
    ``cache`` is this block's KV subtree (None for stateless blocks) and
    ``pos`` the global write offset. Blocks providing it (plus a model-level
    ``cache_factory``) enable StreamedModel's cached generate."""

    name: str
    prefixes: tuple[str, ...]
    apply: Callable
    kind: str = "unique"
    cached_apply: Optional[Callable] = None
    # Encoder-decoder models tag blocks "enc"/"dec" so the executor can run
    # the encoder once and loop only the decoder during generation.
    stage: str = "main"
    # True for blocks that own a KV-cache slot during cached decode.
    cache_slot: bool = False


def block_specs_for(module) -> Optional[list[BlockSpec]]:
    """Auto-derive block specs for the shipped model families. Returns None
    for unknown architectures (caller must pass specs explicitly)."""
    from .models.gpt2 import GPT2LMHeadModel
    from .models.llama import LlamaForCausalLM
    from .models.mixtral import MixtralForCausalLM
    from .models.t5 import T5ForConditionalGeneration

    from .models.gpt_neox import GPTNeoXForCausalLM
    from .models.gptj import GPTJForCausalLM
    from .models.opt import OPTForCausalLM
    from .models.phi import PhiForCausalLM

    if isinstance(module, MixtralForCausalLM):  # before its Llama parent check
        return _mixtral_block_specs(module.config)
    if isinstance(module, LlamaForCausalLM):
        return _llama_block_specs(module.config)
    if isinstance(module, GPT2LMHeadModel):
        return _gpt2_block_specs(module.config)
    if isinstance(module, GPTJForCausalLM):
        return _gptj_block_specs(module.config)
    if isinstance(module, GPTNeoXForCausalLM):
        return _gpt_neox_block_specs(module.config)
    if isinstance(module, OPTForCausalLM):
        return _opt_block_specs(module.config)
    if isinstance(module, PhiForCausalLM):
        return _phi_block_specs(module.config)
    from .models.bloom import BloomForCausalLM

    if isinstance(module, BloomForCausalLM):
        return _bloom_block_specs(module.config)
    if isinstance(module, T5ForConditionalGeneration):
        return _t5_block_specs(module.config)
    return None


def _decoder_block_specs(cfg, block_cls, scope: str, has_aux: bool) -> list[BlockSpec]:
    """Shared decoder-only spec builder: llama (params under "model.",
    blocks return x) and mixtral (flat params, blocks return (x, aux) —
    router losses, dropped at inference)."""
    import flax.linen as nn
    from .models.llama import RMSNorm

    # Gemma/Gemma2 knobs (absent on non-llama configs): sqrt(hidden)
    # embedding scaling, zero-centered (1 + w) final-norm scales, final
    # logit softcapping, and per-layer attention structure (layer_windows).
    embed_scale = (cfg.hidden_size ** 0.5) if getattr(cfg, "scale_embeddings", False) else None
    norm_unit_offset = getattr(cfg, "rms_norm_unit_offset", False)
    final_softcap = getattr(cfg, "final_logit_softcapping", None)

    def embed_apply(ptrees, input_ids):
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, param_dtype=jnp.float32)
        x = embed.apply({"params": ptrees[0]}, input_ids)
        if embed_scale is not None:
            x = x * jnp.asarray(embed_scale, x.dtype)
        positions = jnp.broadcast_to(
            jnp.arange(input_ids.shape[1], dtype=jnp.int32)[None, :], input_ids.shape)
        return x, positions

    # One block instance per layer: layer structure can differ (Gemma2's
    # local/global window mixture keys off layer_idx). Field introspection,
    # not try/except — an unrelated TypeError must not silently degrade
    # every layer to layer_idx=0.
    import dataclasses as _dc

    takes_layer_idx = "layer_idx" in {f.name for f in _dc.fields(block_cls)}

    def make_block(i):
        return block_cls(cfg, layer_idx=i) if takes_layer_idx else block_cls(cfg)

    blocks = [make_block(i) for i in range(cfg.num_hidden_layers)]

    def layer_apply_for(block):
        def layer_apply(ptrees, x, positions):
            out = block.apply({"params": ptrees[0]}, x, positions)
            if has_aux:
                out, _aux = out
            return out, positions
        return layer_apply

    def head_apply(ptrees, x, positions):
        h = RMSNorm(cfg.rms_norm_eps, unit_offset=norm_unit_offset).apply(
            {"params": ptrees[0]}, x)
        if cfg.tie_word_embeddings:
            kernel = ptrees[1]["embedding"].T
        else:
            kernel = ptrees[1]["kernel"]
        from .ops.attention import softcap_logits

        logits = h @ kernel.astype(h.dtype)
        return softcap_logits(logits, final_softcap)

    # KV-cached decode forms (StreamedModel.generate). ``pos`` is a traced
    # scalar, so every decode token reuses one executable per block kind.
    def embed_cached(ptrees, args, cache, pos):
        (input_ids,) = args
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, param_dtype=jnp.float32)
        x = embed.apply({"params": ptrees[0]}, input_ids)
        if embed_scale is not None:
            x = x * jnp.asarray(embed_scale, x.dtype)
        positions = pos + jnp.arange(input_ids.shape[1], dtype=jnp.int32)[None, :]
        positions = jnp.broadcast_to(positions, input_ids.shape)
        return (x, positions), None

    def layer_cached_for(block):
        def layer_cached(ptrees, args, cache, pos):
            x, positions = args
            out = block.apply({"params": ptrees[0]}, x, positions, cache=cache, cache_pos=pos)
            if has_aux:
                x, _aux, new_cache = out
            else:
                x, new_cache = out
            return (x, positions), new_cache
        return layer_cached

    def head_cached(ptrees, args, cache, pos):
        x, positions = args
        return (head_apply(ptrees, x, positions),), None

    specs = [
        BlockSpec("embed", (f"{scope}embed_tokens",), embed_apply, kind="embed",
                  cached_apply=embed_cached)
    ]
    for i in range(cfg.num_hidden_layers):
        # Blocks sharing `kind` share one jitted executable, so per-layer
        # structure MUST split the kind: Gemma2's local/global mixture gets
        # one executable per distinct window, and Qwen2-MoE's dense
        # (mlp_only) layers must not reuse a sparse layer's trace (their
        # param trees differ).
        window = cfg.window_for(i) if hasattr(cfg, "window_for") else None
        kind = "layer" if window is None else f"layer_w{window}"
        if i in getattr(cfg, "mlp_only_layers", ()):
            kind += "_dense"
        specs.append(BlockSpec(f"layers_{i}", (f"{scope}layers_{i}",),
                               layer_apply_for(blocks[i]),
                               kind=kind, cache_slot=True,
                               cached_apply=layer_cached_for(blocks[i])))
    head_prefixes = ((f"{scope}norm", f"{scope}embed_tokens") if cfg.tie_word_embeddings
                     else (f"{scope}norm", "lm_head"))
    specs.append(BlockSpec("head", head_prefixes, head_apply, kind="head",
                           cached_apply=head_cached))
    return specs


def _llama_block_specs(cfg) -> list[BlockSpec]:
    from .models.llama import LlamaBlock

    return _decoder_block_specs(cfg, LlamaBlock, "model.", has_aux=False)


def _cache_dtype_kwargs(factory: Callable, cache_dtype) -> dict:
    """kwargs to forward a caller's ``cache_dtype`` to a cache factory.

    Only passes dtype when the caller asked for one — a user-supplied
    factory may not take it, and an unconditional ``dtype=`` would clobber
    its own default. When the caller DID ask and the factory can't honor
    it, raise descriptively instead of a bare TypeError deep inside
    generate (mirrors the ring_slack introspection in
    StreamedModel._generate_speculative)."""
    if cache_dtype is None:
        return {}
    import inspect

    if "dtype" not in inspect.signature(factory).parameters:
        raise TypeError(
            "cache_dtype was passed but this model's cache_factory does not "
            "accept a 'dtype' parameter; add one (registry factories from "
            "cache_factory_for all do) or drop cache_dtype")
    return {"dtype": cache_dtype}


def cache_factory_for(module) -> Optional[Callable]:
    """``(batch, max_len, dtype=bf16) -> per-layer KV cache tuple`` for model
    families with cache threading; None otherwise. Layer caches pair, in
    order, with the specs marked ``cache_slot=True`` (``kind == "layer"`` is
    honored as a legacy alias for externally-built spec lists).

    A module that declares its own cache (``init_cache(batch, max_len,
    dtype)``: a latent cache has no K and V to size from head counts) is
    asked first, duck-typed as ``window_for`` is; the ladder below serves the
    families whose cache is per-head K and V."""
    if hasattr(module, "init_cache"):
        return module.init_cache

    from .models.bloom import BloomForCausalLM
    from .models.cohere2_moe import Cohere2MoeForCausalLM
    from .models.gpt2 import GPT2LMHeadModel
    from .models.gpt_neox import GPTNeoXForCausalLM
    from .models.gptj import GPTJForCausalLM
    from .models.llama import LlamaForCausalLM, init_kv_cache
    from .models.mixtral import MixtralForCausalLM
    from .models.opt import OPTForCausalLM
    from .models.phi import PhiForCausalLM

    if isinstance(module, (LlamaForCausalLM, GPT2LMHeadModel, MixtralForCausalLM,
                           GPTJForCausalLM, GPTNeoXForCausalLM, OPTForCausalLM,
                           PhiForCausalLM, BloomForCausalLM, Cohere2MoeForCausalLM)):
        cfg = module.config  # non-Llama configs duck-type the kv-cache fields

        def factory(batch, max_len, dtype=jnp.bfloat16, ring_slack=0):
            return init_kv_cache(cfg, batch, max_len, dtype, ring_slack=ring_slack)

        return factory

    from .models.t5 import T5ForConditionalGeneration

    if isinstance(module, T5ForConditionalGeneration):
        cfg = module.config

        def t5_factory(batch, max_len, dtype=jnp.bfloat16, src_len=None):
            if src_len is None:
                raise ValueError("T5 decode caches need src_len (cross K/V width)")
            self_shape = (batch, max_len, cfg.num_heads, cfg.head_dim)
            cross_shape = (batch, src_len, cfg.num_heads, cfg.head_dim)
            return tuple(
                {"k": jnp.zeros(self_shape, dtype), "v": jnp.zeros(self_shape, dtype),
                 "ck": jnp.zeros(cross_shape, dtype), "cv": jnp.zeros(cross_shape, dtype)}
                for _ in range(cfg.num_layers)
            )

        return t5_factory
    return None


def _gpt2_block_specs(cfg) -> list[BlockSpec]:
    import flax.linen as nn
    from .models.gpt2 import GPT2Block

    def embed_apply(ptrees, input_ids):
        wte = ptrees[0]["embedding"]
        wpe = ptrees[1]["embedding"]
        x = wte[input_ids] + wpe[jnp.arange(input_ids.shape[1])][None, :]
        return (x,)

    block = GPT2Block(cfg)

    def layer_apply(ptrees, x):
        return (block.apply({"params": ptrees[0]}, x),)

    def head_apply(ptrees, x):
        h = nn.LayerNorm(epsilon=cfg.layer_norm_eps).apply({"params": ptrees[0]}, x)
        return h @ ptrees[1]["embedding"].T.astype(h.dtype)

    # KV-cached decode forms (StreamedModel.generate).
    def embed_cached(ptrees, args, cache, pos):
        (input_ids,) = args
        wte = ptrees[0]["embedding"]
        wpe = ptrees[1]["embedding"]
        positions = pos + jnp.arange(input_ids.shape[1], dtype=jnp.int32)
        x = wte[input_ids] + wpe[positions][None, :]
        return (x,), None

    def layer_cached(ptrees, args, cache, pos):
        (x,) = args
        x, new_cache = block.apply({"params": ptrees[0]}, x, cache=cache, cache_pos=pos)
        return (x,), new_cache

    def head_cached(ptrees, args, cache, pos):
        (x,) = args
        return (head_apply(ptrees, x),), None

    specs = [BlockSpec("embed", ("wte", "wpe"), embed_apply, kind="embed",
                       cached_apply=embed_cached)]
    for i in range(cfg.num_hidden_layers):
        specs.append(BlockSpec(f"h_{i}", (f"h_{i}",), layer_apply, kind="layer",
                               cache_slot=True, cached_apply=layer_cached))
    specs.append(BlockSpec("head", ("ln_f", "wte"), head_apply, kind="head",
                           cached_apply=head_cached))
    return specs


def _gptlike_block_specs(cfg, block, layer_fmt: str, embed_prefixes: tuple,
                         embed_fn, head_prefixes: tuple, head_fn) -> list[BlockSpec]:
    """Shared builder for GPT-J / GPT-NeoX / OPT streaming: blocks take
    (x[, cache, cache_pos]) and compute their own positions, so only the
    embedding and head closures differ per family."""

    def embed_apply(ptrees, input_ids):
        return (embed_fn(ptrees, input_ids, 0),)

    def layer_apply(ptrees, x):
        return (block.apply({"params": ptrees[0]}, x),)

    def head_apply(ptrees, x):
        return head_fn(ptrees, x)

    def embed_cached(ptrees, args, cache, pos):
        (input_ids,) = args
        return (embed_fn(ptrees, input_ids, pos),), None

    def layer_cached(ptrees, args, cache, pos):
        (x,) = args
        x, new_cache = block.apply({"params": ptrees[0]}, x, cache=cache, cache_pos=pos)
        return (x,), new_cache

    def head_cached(ptrees, args, cache, pos):
        (x,) = args
        return (head_fn(ptrees, x),), None

    specs = [BlockSpec("embed", embed_prefixes, embed_apply, kind="embed",
                       cached_apply=embed_cached)]
    for i in range(cfg.num_hidden_layers):
        name = layer_fmt.format(i=i)
        specs.append(BlockSpec(name, (name,), layer_apply, kind="layer",
                               cache_slot=True, cached_apply=layer_cached))
    specs.append(BlockSpec("head", head_prefixes, head_apply, kind="head",
                           cached_apply=head_cached))
    return specs


def _gptj_block_specs(cfg) -> list[BlockSpec]:
    import flax.linen as nn
    from .models.gptj import GPTJBlock

    def embed(ptrees, input_ids, pos):
        return ptrees[0]["embedding"][input_ids]

    def head(ptrees, x):
        h = nn.LayerNorm(epsilon=cfg.layer_norm_eps).apply({"params": ptrees[0]}, x)
        return h @ ptrees[1]["kernel"].astype(h.dtype) + ptrees[1]["bias"].astype(h.dtype)

    return _gptlike_block_specs(cfg, GPTJBlock(cfg), "h_{i}", ("wte",), embed,
                                ("ln_f", "lm_head"), head)


def _gpt_neox_block_specs(cfg) -> list[BlockSpec]:
    import flax.linen as nn
    from .models.gpt_neox import GPTNeoXBlock

    def embed(ptrees, input_ids, pos):
        return ptrees[0]["embedding"][input_ids]

    def head(ptrees, x):
        h = nn.LayerNorm(epsilon=cfg.layer_norm_eps).apply({"params": ptrees[0]}, x)
        return h @ ptrees[1]["kernel"].astype(h.dtype)

    return _gptlike_block_specs(cfg, GPTNeoXBlock(cfg), "layers_{i}", ("embed_in",), embed,
                                ("final_layer_norm", "embed_out"), head)


def _opt_block_specs(cfg) -> list[BlockSpec]:
    import flax.linen as nn
    from .models.opt import POSITION_OFFSET, OPTBlock

    def embed(ptrees, input_ids, pos):
        positions = POSITION_OFFSET + pos + jnp.arange(input_ids.shape[1], dtype=jnp.int32)
        return (ptrees[0]["embedding"][input_ids]
                + ptrees[1]["embedding"][positions][None, :])

    def head(ptrees, x):
        h = nn.LayerNorm(epsilon=cfg.layer_norm_eps).apply({"params": ptrees[0]}, x)
        return h @ ptrees[1]["embedding"].T.astype(h.dtype)  # tied

    return _gptlike_block_specs(cfg, OPTBlock(cfg), "layers_{i}",
                                ("embed_tokens", "embed_positions"), embed,
                                ("final_layer_norm", "embed_tokens"), head)


def _phi_block_specs(cfg) -> list[BlockSpec]:
    import flax.linen as nn
    from .models.phi import PhiBlock

    def embed(ptrees, input_ids, pos):
        return ptrees[0]["embedding"][input_ids]

    def head(ptrees, x):
        h = nn.LayerNorm(epsilon=cfg.layer_norm_eps).apply({"params": ptrees[0]}, x)
        return h @ ptrees[1]["kernel"].astype(h.dtype) + ptrees[1]["bias"].astype(h.dtype)

    return _gptlike_block_specs(cfg, PhiBlock(cfg), "layers_{i}", ("embed_tokens",), embed,
                                ("final_layernorm", "lm_head"), head)


def _bloom_block_specs(cfg) -> list[BlockSpec]:
    import flax.linen as nn
    from .models.bloom import BloomBlock

    def embed(ptrees, input_ids, pos):
        x = ptrees[0]["embedding"][input_ids]
        return nn.LayerNorm(epsilon=cfg.layer_norm_epsilon).apply(
            {"params": ptrees[1]}, x)

    def head(ptrees, x):
        h = nn.LayerNorm(epsilon=cfg.layer_norm_epsilon).apply({"params": ptrees[0]}, x)
        return h @ ptrees[1]["embedding"].T.astype(h.dtype)  # tied

    return _gptlike_block_specs(cfg, BloomBlock(cfg), "layers_{i}",
                                ("word_embeddings", "word_embeddings_layernorm"),
                                embed, ("ln_f", "word_embeddings"), head)


def _mixtral_block_specs(cfg) -> list[BlockSpec]:
    """Sparse-MoE decoder streaming: shared decoder builder with flat param
    names (models/mixtral.py:130) and aux-carrying blocks. Stacked expert
    tensors arrive via LazyStack for HF per-expert shards."""
    from .models.mixtral import MixtralBlock

    return _decoder_block_specs(cfg, MixtralBlock, "", has_aux=True)


def _t5_block_specs(cfg) -> list[BlockSpec]:
    """Encoder-decoder streaming (the reference's T0pp-11B benchmark row is
    this shape). Stages: "enc" blocks run once per input, "dec" blocks run
    per decode step. Activations thread ``(x, bias, decoder_ids)`` through
    the encoder and ``(enc, y, dbias)`` through the decoder; the relative
    bias is computed by each stack's layer-0 block (its param tree has the
    bucket table, hence a distinct kind/compile) and shared onward.

    Cached decode (``cached_apply``): decoder self-attention uses the
    standard KV buffers; cross-attention K/V are computed from ``enc`` on
    the prefill call (pos == 0, via lax.cond so one executable serves both)
    and stored in the same per-layer cache dict.
    """
    from .models.t5 import T5DecoderBlock, T5EncoderBlock, T5LayerNorm

    enc_block0 = T5EncoderBlock(cfg, has_relative_bias=True)
    enc_block = T5EncoderBlock(cfg)
    dec_block0 = T5DecoderBlock(cfg, has_relative_bias=True)
    dec_block = T5DecoderBlock(cfg)
    norm = T5LayerNorm(cfg.layer_norm_eps)

    def embed_enc(ptrees, input_ids, decoder_ids):
        x = ptrees[0]["embedding"][input_ids]
        return x, decoder_ids

    def enc_layer0_apply(ptrees, x, decoder_ids):
        x, bias = enc_block0.apply({"params": ptrees[0]}, x, None, None)
        return x, bias, decoder_ids

    def enc_layer_apply(ptrees, x, bias, decoder_ids):
        x, bias = enc_block.apply({"params": ptrees[0]}, x, None, bias)
        return x, bias, decoder_ids

    def enc_norm_apply(ptrees, x, bias, decoder_ids):
        return norm.apply({"params": ptrees[0]}, x), decoder_ids

    def dec_layer0_apply(ptrees, enc, y):
        y, dbias = dec_block0.apply({"params": ptrees[0]}, y, enc)
        return enc, y, dbias

    def dec_layer_apply(ptrees, enc, y, dbias):
        y, dbias = dec_block.apply({"params": ptrees[0]}, y, enc, position_bias=dbias)
        return enc, y, dbias

    def head_apply(ptrees, enc, y, dbias):
        h = norm.apply({"params": ptrees[0]}, y)
        if cfg.tie_word_embeddings:
            kernel = ptrees[1]["embedding"].T
            return (h * (cfg.hidden_size ** -0.5)) @ kernel.astype(h.dtype)
        return h @ ptrees[1]["kernel"].astype(h.dtype)

    # ---- cached decode forms (decoder stage only; encoder runs uncached
    # once via the "enc"-stage specs). Cache per dec layer:
    # {"k","v"} self-attention buffers + {"ck","cv"} cross K/V.
    def _dec_cached(block, has_bias):
        def fn(ptrees, args, cache, pos):
            enc, y, *maybe_bias = args
            dbias = maybe_bias[0] if maybe_bias else None
            self_cache = {"k": cache["k"], "v": cache["v"]}

            def _zero_bias(y, cache):
                L = cache["k"].shape[1]
                return jnp.zeros((1, cfg.num_heads, y.shape[1], L), jnp.float32)

            def _ckv_cached(ckv):
                # Both cond branches must return identical avals: the prefill
                # branch computes cross K/V in the activation dtype while the
                # decode branch reads the cache dtype — cast INSIDE each.
                return (ckv[0].astype(cache["ck"].dtype),
                        ckv[1].astype(cache["cv"].dtype))

            def prefill_branch(operands):
                y, enc, self_cache = operands
                out, bias, new_self, ckv = block.apply(
                    {"params": ptrees[0]}, y, enc, position_bias=dbias,
                    cache=self_cache, cache_pos=pos)
                return (out, (bias if has_bias else _zero_bias(y, cache)),
                        new_self, _ckv_cached(ckv))

            def decode_branch(operands):
                y, enc, self_cache = operands
                out, bias, new_self, ckv = block.apply(
                    {"params": ptrees[0]}, y, enc, position_bias=dbias,
                    cache=self_cache, cache_pos=pos,
                    cross_kv=(cache["ck"], cache["cv"]))
                return (out, (bias if has_bias else _zero_bias(y, cache)),
                        new_self, _ckv_cached(ckv))

            out, bias, new_self, ckv = jax.lax.cond(
                pos == 0, prefill_branch, decode_branch, (y, enc, self_cache))
            new_cache = {"k": new_self["k"], "v": new_self["v"],
                         "ck": ckv[0], "cv": ckv[1]}
            new_args = (enc, out, bias) if has_bias else (enc, out, dbias)
            return new_args, new_cache

        return fn

    def embed_dec_cached(ptrees, args, cache, pos):
        enc, decoder_ids = args
        y = ptrees[0]["embedding"][decoder_ids]
        return (enc, y), None

    def head_cached(ptrees, args, cache, pos):
        enc, y, dbias = args
        return (head_apply(ptrees, enc, y, dbias),), None

    specs = [
        BlockSpec("embed_enc", ("shared_embedding",), embed_enc,
                  kind="t5_embed_enc", stage="enc"),
        BlockSpec("encoder_layer_0", ("encoder_layer_0",), enc_layer0_apply,
                  kind="t5_enc_layer0", stage="enc"),
    ]
    for i in range(1, cfg.num_layers):
        specs.append(BlockSpec(f"encoder_layer_{i}", (f"encoder_layer_{i}",),
                               enc_layer_apply, kind="t5_enc_layer", stage="enc"))
    specs.append(BlockSpec("encoder_norm", ("encoder_norm",),
                           enc_norm_apply, kind="t5_enc_norm", stage="enc"))
    # The decoder's embedding lookup is its own tiny spec so the cached
    # per-step loop can start from token ids.
    specs.append(BlockSpec("embed_dec", ("shared_embedding",),
                           lambda ptrees, enc, decoder_ids: (enc, ptrees[0]["embedding"][decoder_ids]),
                           kind="t5_embed_dec", stage="dec",
                           cached_apply=embed_dec_cached))
    specs.append(BlockSpec("decoder_layer_0", ("decoder_layer_0",), dec_layer0_apply,
                           kind="t5_dec_layer0", stage="dec", cache_slot=True,
                           cached_apply=_dec_cached(dec_block0, True)))
    for i in range(1, cfg.num_layers):
        specs.append(BlockSpec(f"decoder_layer_{i}", (f"decoder_layer_{i}",),
                               dec_layer_apply, kind="t5_dec_layer", stage="dec",
                               cache_slot=True,
                               cached_apply=_dec_cached(dec_block, False)))
    head_prefixes = (("decoder_norm", "shared_embedding") if cfg.tie_word_embeddings
                     else ("decoder_norm", "lm_head"))
    specs.append(BlockSpec("head", head_prefixes, head_apply, kind="t5_head",
                           stage="dec", cached_apply=head_cached))
    return specs


# ---------------------------------------------------------------------------
# Streamed executor
# ---------------------------------------------------------------------------

def _compiled_drafter(draft_module, K: int):
    """(prefill, K-step greedy decode) jitted pair for a draft model,
    cached per (draft config, K) in generation's executable cache —
    repeated streamed-assisted calls must not re-trace the drafter."""
    from .generation import _cache_key, _cache_put, _generate_cache

    key = _cache_key(draft_module, "streamed_drafter", K)
    hit = _generate_cache.get(key) if key is not None else None
    if hit is not None:
        return hit

    prefill_d = jax.jit(lambda dp, ids, c: draft_module.apply(
        {"params": dp}, ids, cache=c, cache_pos=0)[1])

    @jax.jit
    def draft_k(dp, tok, dcache, pos):
        def dstep(carry, _):
            tok, dcache, pos = carry
            logits, dcache = draft_module.apply(
                {"params": dp}, tok, cache=dcache, cache_pos=pos)
            nxt = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(tok.dtype)
            return (nxt, dcache, pos + 1), nxt[0, 0]

        (_, dcache, _), draft = jax.lax.scan(dstep, (tok, dcache, pos),
                                             None, length=K)
        return draft, dcache

    return _cache_put(key, (prefill_d, draft_k))


class StreamedModel:
    """Executes a block-split model whose weights live across HBM / host DRAM
    / disk, double-buffering host→HBM transfers (reference equivalent:
    AlignDevicesHook pre/post_forward, hooks.py:323-390 — redesigned as
    ahead-of-time block prefetch instead of per-module hooks).

    ``__call__`` is eager Python over jitted per-kind block functions; the
    layer blocks all share one executable. With everything resident in HBM
    the fetch is a no-op passthrough.
    """

    def __init__(self, specs: list[BlockSpec], store: WeightStore,
                 execution_device=None, prefetch: bool = True,
                 cache_factory: Optional[Callable] = None,
                 position_bound: Optional[int] = None):
        self.specs = specs
        self.store = store
        self.device = execution_device if execution_device is not None else jax.local_devices()[0]
        self.prefetch = prefetch
        self.cache_factory = cache_factory
        self.position_bound = position_bound  # learned-position table size, if any
        self._jitted: dict[str, Callable] = {}
        self._pool: Optional[ThreadPoolExecutor] = None
        self._resident_cache: dict[str, Any] = {}
        self._lock = threading.Lock()

    def _submit(self, fn, *args):
        if self._pool is None:
            self._pool = ThreadPoolExecutor(max_workers=1, thread_name_prefix="weight-prefetch")
        return self._pool.submit(fn, *args)

    def close(self):
        """Release device buffers and close backing files."""
        if self._pool is not None:
            self._pool.shutdown(wait=False)
            self._pool = None

    def __del__(self):  # best-effort; close() is the real API
        try:
            self.close()
        except Exception:
            pass

    # -- weight movement ---------------------------------------------------
    def _fetch(self, spec: BlockSpec) -> tuple:
        cached = self._resident_cache.get(spec.name)
        if cached is not None:
            return cached
        ptrees = tuple(self.store.fetch_subtree(p, self.device) for p in spec.prefixes)
        if all(self.store.placement.get(n) not in ("cpu", "disk")
               for p in spec.prefixes for n in self.store.names_under(p)):
            with self._lock:
                self._resident_cache[spec.name] = ptrees
        return ptrees

    def _apply(self, spec: BlockSpec, ptrees: tuple, args: tuple):
        fn = self._jitted.get(spec.kind)
        if fn is None:
            fn = jax.jit(spec.apply)
            self._jitted[spec.kind] = fn
        return fn(ptrees, *args)

    # -- forward -----------------------------------------------------------
    def _iter_blocks(self, specs=None):
        """Yield (spec, ptrees) with the next block's weights prefetching on
        the transfer thread while the current block computes."""
        specs = self.specs if specs is None else specs
        nxt = self._submit(self._fetch, specs[0]) if self.prefetch else None
        for i, spec in enumerate(specs):
            ptrees = nxt.result() if nxt is not None else self._fetch(spec)
            if self.prefetch and i + 1 < len(specs):
                nxt = self._submit(self._fetch, specs[i + 1])
            else:
                nxt = None
            yield spec, ptrees

    def __call__(self, input_ids, *extra):
        """Forward through every block. Extra positional inputs (e.g. an
        encoder-decoder model's ``decoder_input_ids``) thread into the first
        block alongside ``input_ids``."""
        args: tuple = tuple(
            jax.device_put(jnp.asarray(a), self.device) for a in (input_ids, *extra))
        for spec, ptrees in self._iter_blocks():
            out = self._apply(spec, ptrees, args)
            args = out if isinstance(out, tuple) else (out,)
        return args[0] if len(args) == 1 else args

    # -- generation --------------------------------------------------------
    def _apply_cached(self, spec: BlockSpec, ptrees: tuple, args: tuple, cache, pos,
                      static_pos: bool = False):
        key = spec.kind + ("/cached_prefill" if static_pos else "/cached")
        fn = self._jitted.get(key)
        if fn is None:
            # Donate the cache: its output aliases the input buffer, so the
            # decode loop never holds two copies of a layer's KV.
            fn = jax.jit(spec.cached_apply, donate_argnums=(2,),
                         static_argnums=(3,) if static_pos else ())
            self._jitted[key] = fn
        return fn(ptrees, args, cache, pos)

    def _cached_pass(self, args: tuple, caches: list, pos: int, specs=None,
                     static_pos=None, return_logits: bool = False):
        """One full pass (prefill, single-token decode, or a speculative
        verification chunk) through the given blocks (default: all), updating
        layer caches in place. Returns the greedy prediction at EVERY chunk
        position, [B, chunk_len] (single-token callers take ``[:, -1]``) —
        or the raw logits [B, chunk_len, V] with ``return_logits=True``
        (sampling paths need the distribution, not the argmax).

        ``static_pos`` None infers: multi-token chunks keep ``pos`` STATIC
        (a Python int) — the initial prefill's executable is shape-distinct
        from decode anyway, so the specialization is free. Speculative
        chunks pass ``static_pos=False``: their position changes every
        iteration and must stay traced to share one executable."""
        if static_pos is None:
            static_pos = args[0].shape[1] > 1
        if static_pos:
            pos = int(pos)
        else:
            pos = jnp.asarray(pos, jnp.int32)
        li = 0
        for spec, ptrees in self._iter_blocks(specs):
            # cache_slot is the contract; kind == "layer" kept for
            # externally-built spec lists written against the documented
            # decoder-only convention (cache_factory_for docstring).
            if spec.cache_slot or spec.kind == "layer":
                args, caches[li] = self._apply_cached(spec, ptrees, args, caches[li], pos,
                                                      static_pos=static_pos)
                li += 1
            else:
                args, _ = self._apply_cached(spec, ptrees, args, None, pos,
                                             static_pos=static_pos)
        logits = args[0]
        return logits if return_logits else jnp.argmax(logits, axis=-1)

    def _bucketed_caches(self, batch: int, cache_len: int, extra_slack: int,
                         cache_dtype) -> tuple[list, bool]:
        """Build KV caches with the length bucketed to a 128-multiple and
        decide whether the prompt may be right-padded for prefill reuse.

        Without bucketing, every distinct (prompt length, max_new_tokens)
        pair gives new cache shapes and a new prompt shape — re-jitting
        every block kind's prefill AND decode executables per call in
        interactive use. Bucketing shares them per 128-bucket; the pad KV
        is provably never attended (full caches mask ``k_pos <= q_pos``
        and pad slots stay ahead of the committed frontier until decode
        overwrites them; ring caches mask by stored position — see
        generation._compiled_lookup_generate for the full argument).

        Ring (sliding-window) caches additionally need ``ring_slack``
        covering the pad (< 128) plus the caller's ``extra_slack`` so pad
        writes can't evict in-window prompt keys. A user-supplied factory
        without a ring_slack parameter that builds ring caches gets NO
        padding (correctness first — the caller keeps exact-length
        prefill); the speculative paths separately reject that factory
        shape as before. Returns (device-placed caches, pad_ok).
        """
        import inspect

        L = -(-cache_len // 128) * 128
        dt = _cache_dtype_kwargs(self.cache_factory, cache_dtype)
        takes_slack = "ring_slack" in inspect.signature(self.cache_factory).parameters
        if takes_slack:
            caches = list(self.cache_factory(batch, L,
                                             ring_slack=extra_slack + 128, **dt))
            pad_ok = True
        else:
            caches = list(self.cache_factory(batch, L, **dt))
            pad_ok = not any("pos" in c for c in caches)
        return [jax.device_put(c, self.device) for c in caches], pad_ok

    def _pad_prompt(self, ids, pad_ok: bool, extra=None):
        """Edge-pad the prompt to its 128-bucket via generation's ONE
        bucketing rule (capped at this model's position table and
        ``extra`` — an assistant draft module or raw bound). The pad KV is
        masked; the caller reads predictions at the true last position.
        No-op when padding is unsafe or already aligned."""
        if not pad_ok:
            return ids
        from .generation import _bucket_and_pad

        caps = [b for b in (self.position_bound, extra) if b is not None]
        return _bucket_and_pad(ids, *caps)[0]

    def generate(self, input_ids, max_new_tokens: int = 20,
                 eos_token_id: Optional[int] = None, use_cache: bool = True,
                 prompt_lookup_num_tokens: Optional[int] = None,
                 lookup_ngram: int = 2,
                 assistant_module=None, assistant_params=None,
                 num_draft: int = 5,
                 do_sample: bool = False, temperature: float = 1.0,
                 top_k: Optional[int] = None, top_p: Optional[float] = None,
                 rng=None, cache_dtype=None):
        """Streamed decoding — greedy by default, sampled with
        ``do_sample=True`` (temperature/top-k/top-p) — the reference
        capability: hook-streamed ``model.generate``; per-token latency
        table in benchmarks/big_model_inference/README.md:26-45.

        With cache support (``cached_apply`` on every spec + a
        ``cache_factory``) decoding is KV-cached: one prefill pass writes the
        prompt's KV, then each token runs single-query attention against the
        cache — O(1) forward work per token instead of O(seq). Weights still
        stream per block with the same double-buffered prefetch. Without
        cache support (or ``use_cache=False``) falls back to full re-forward
        per token.

        ``prompt_lookup_num_tokens=K`` turns on prompt-lookup speculation
        (batch 1, greedy — see generation.prompt_lookup_generate): each pass
        verifies K drafted tokens plus one bonus in a single streamed
        forward, so the offloaded weights stream once per ACCEPTED RUN
        instead of once per token — on the cpu/disk tiers, where weight
        traffic dominates the per-token latency, acceptance translates
        almost directly into speedup. Output equals plain greedy exactly.

        ``assistant_module``/``assistant_params`` (transformers'
        ``assistant_model=``) switch the drafter to a small device-resident
        draft model proposing ``num_draft`` tokens per round — the same
        weights-stream-once-per-accepted-run economics on arbitrary text,
        not just self-repetitive text. Mutually exclusive with
        prompt-lookup; same exactness contract.

        ``cache_dtype`` sets the KV-cache element dtype for every cache
        this call builds — the target's and, under assisted generation,
        the draft's (matching generation.assisted_generate). None keeps
        each factory's own default (bf16 for registry factories).

        Cache lengths and the prompt are bucketed to 128-multiples
        (:meth:`_bucketed_caches`), so interactive use with varied prompt
        lengths re-jits each block kind once per bucket, not once per
        exact (prompt, max_new_tokens) pair."""
        if any(s.stage == "enc" for s in self.specs):
            raise TypeError(
                "this is an encoder-decoder model; use seq2seq_generate")
        ids = jnp.asarray(input_ids)
        if max_new_tokens <= 0:
            return ids
        if assistant_module is not None and prompt_lookup_num_tokens:
            raise ValueError(
                "assistant_module and prompt_lookup_num_tokens are mutually "
                "exclusive drafters")
        cached = (
            use_cache
            and self.cache_factory is not None
            and all(s.cached_apply is not None for s in self.specs)
        )
        if (prompt_lookup_num_tokens or assistant_module is not None) and not cached:
            # Never silently fall back to the slowest path when the caller
            # explicitly asked for speculation (which presupposes a cache).
            raise ValueError(
                "speculative decoding requires KV-cache support "
                "(cached_apply on every block spec + a cache_factory) and "
                "use_cache=True")
        sampling = (float(temperature), top_k, top_p) if do_sample else None
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        if sampling is not None:
            from .generation import _make_warper

            warp = _make_warper(sampling)  # built once, not per token

        def pick(logits_row, key):
            # logits_row [B, V] -> [B] next tokens (greedy or warped sample).
            if sampling is None:
                return jnp.argmax(logits_row, axis=-1)
            return jax.random.categorical(key, warp(logits_row), axis=-1)

        if not cached:
            for _ in range(max_new_tokens):
                logits = self(ids)
                rng, key = jax.random.split(rng)
                nxt = pick(logits[:, -1, :], key)[:, None].astype(ids.dtype)
                ids = jnp.concatenate([ids, nxt], axis=1)
                if eos_token_id is not None and bool((nxt == eos_token_id).all()):
                    break
            return ids

        B, S = ids.shape
        # Highest position a verification chunk can touch is
        # S + max_new_tokens + K - 2 (the last chunk starts at
        # S + max_new_tokens - 2 and spans K + 1), so the needed slack is
        # K - 1 — keep in lockstep with generation._check_position_bound's
        # speculative call site.
        spec_k = int(prompt_lookup_num_tokens or 0) or (
            int(num_draft) if assistant_module is not None else 0)
        slack = (spec_k - 1) if spec_k else 0
        if self.position_bound is not None and S + max_new_tokens + slack > self.position_bound:
            label = ("prompt + max_new_tokens + speculative slack" if slack
                     else "prompt + max_new_tokens")
            raise ValueError(
                f"{label} = {S + max_new_tokens + slack} exceeds the "
                f"model's position table ({self.position_bound}); learned-position "
                "lookups would silently clamp."
            )
        if assistant_module is not None:
            return self._generate_assisted(
                ids, max_new_tokens, eos_token_id, int(num_draft),
                assistant_module, assistant_params, sampling=sampling, rng=rng,
                cache_dtype=cache_dtype)
        if prompt_lookup_num_tokens:
            return self._generate_prompt_lookup(
                ids, max_new_tokens, eos_token_id,
                int(prompt_lookup_num_tokens), int(lookup_ngram),
                sampling=sampling, rng=rng, cache_dtype=cache_dtype)
        caches, pad_ok = self._bucketed_caches(B, S + max_new_tokens, 0, cache_dtype)
        ids_p = self._pad_prompt(ids, pad_ok)
        sample = sampling is not None
        out = self._cached_pass((jax.device_put(ids_p, self.device),), caches, 0,
                                return_logits=sample)
        rng, key = jax.random.split(rng)
        tok = pick(out[:, S - 1, :], key) if sample else out[:, S - 1]
        pieces = [ids, tok[:, None].astype(ids.dtype)]
        for t in range(1, max_new_tokens):
            if eos_token_id is not None and bool((tok == eos_token_id).all()):
                break
            out = self._cached_pass((tok[:, None].astype(ids.dtype),), caches,
                                    S + t - 1, return_logits=sample)
            rng, key = jax.random.split(rng)
            tok = pick(out[:, -1, :], key) if sample else out[:, -1]
            pieces.append(tok[:, None].astype(ids.dtype))
        return jnp.concatenate(pieces, axis=1)

    def _generate_prompt_lookup(self, ids, max_new_tokens: int, eos_token_id,
                                K: int, ngram: int, sampling=None, rng=None,
                                cache_dtype=None):
        """Prompt-lookup speculation: draft in Python (the committed ids
        are host-side anyway), verify through the shared streamed
        speculative loop."""
        if ids.shape[0] != 1:
            raise ValueError("prompt_lookup_num_tokens is batch-1 only")
        if ngram < 1 or K < 1:
            raise ValueError(f"lookup_ngram and prompt_lookup_num_tokens must be >= 1 "
                             f"(got {ngram}, {K})")

        def drafter(committed, state):
            cur = len(committed)
            draft: list = []
            if cur > ngram:
                pat = committed[-ngram:]
                for i in range(cur - ngram - 1, -1, -1):
                    if committed[i:i + ngram] == pat:
                        draft = committed[i + ngram:i + ngram + K]
                        break
            draft += [committed[-1]] * (K - len(draft))   # pad: rejected cheaply
            return draft, state

        return self._generate_speculative(ids, max_new_tokens, eos_token_id, K,
                                          drafter, None, sampling=sampling, rng=rng,
                                          cache_dtype=cache_dtype)

    def _generate_assisted(self, ids, max_new_tokens: int, eos_token_id,
                           K: int, draft_module, draft_params,
                           sampling=None, rng=None, cache_dtype=None):
        """Draft-model speculation for streamed weights: the (small,
        device-resident) draft proposes K tokens by a compiled greedy
        cached scan; the streamed target verifies the chunk in one pass,
        so offloaded weights stream once per accepted run. The draft's KV
        cache self-heals rejected positions exactly like the target's
        (drafting restarts from the last committed token)."""
        import numpy as np

        from .generation import _check_position_bound

        if ids.shape[0] != 1:
            raise ValueError("assistant_module speculation is batch-1 only")
        if K < 1:
            raise ValueError(f"num_draft must be >= 1 (got {K})")
        if hasattr(draft_module, "init_decode_cache"):
            raise TypeError("the assistant model must be decoder-only")
        dfactory = cache_factory_for(draft_module)
        if dfactory is None:
            raise TypeError(
                f"{type(draft_module).__name__} (assistant) does not thread a KV cache")
        S = ids.shape[1]
        # The draft decodes at positions up to S + max_new_tokens + K - 3.
        _check_position_bound(draft_module, S + max_new_tokens + K - 2,
                              label="prompt + max_new_tokens + draft slack")
        # Cache length and prompt bucketed like the target's (registry
        # factories always take ring_slack; +128 covers the pad writes).
        L = -(-(S + max_new_tokens + K + 1) // 128) * 128
        # The draft cache follows the caller's cache dtype (matching
        # generation.assisted_generate): a bf16-forced cache on an fp32
        # draft can lower acceptance rate, costing target passes.
        dcache = dfactory(1, L, cache_dtype or jnp.bfloat16, ring_slack=K + 1 + 128)
        prefill_d, draft_k = _compiled_drafter(draft_module, K)
        dcache = prefill_d(
            draft_params,
            self._pad_prompt(jnp.asarray(ids), True, extra=draft_module),
            dcache)

        def drafter(committed, dcache):
            tok = jnp.asarray([[committed[-1]]], jnp.asarray(ids).dtype)
            draft, dcache = draft_k(draft_params, tok, dcache,
                                    jnp.asarray(len(committed) - 1, jnp.int32))
            return [int(t) for t in np.asarray(draft)], dcache

        return self._generate_speculative(ids, max_new_tokens, eos_token_id, K,
                                          drafter, dcache, sampling=sampling, rng=rng,
                                          cache_dtype=cache_dtype)

    def _generate_speculative(self, ids, max_new_tokens: int, eos_token_id,
                              K: int, drafter, drafter_state,
                              sampling=None, rng=None, cache_dtype=None):
        """Shared verify/commit loop for streamed speculation: ``drafter``
        maps (committed token list, state) -> (K proposed tokens, state);
        each round verifies K+1 tokens in ONE streamed pass. Greedy by
        default; ``sampling`` switches the accept rule to exact speculative
        sampling (generation.speculative_accept). Rejected positions leave
        stale KV that the next chunk overwrites before any query attends
        it; ring caches get K+1 slots of eviction slack."""
        import numpy as np

        S = ids.shape[1]
        caches, pad_ok = self._bucketed_caches(1, S + max_new_tokens + K + 1,
                                               K + 1, cache_dtype)
        if not pad_ok:
            # _bucketed_caches only reports pad_ok False for ring caches
            # from a factory without ring_slack support: there the
            # overshooting verification chunks would evict in-window keys.
            raise ValueError(
                "this model's cache_factory builds ring (sliding-window) "
                "caches but does not accept ring_slack — speculation "
                "would evict in-window keys; add ring_slack support "
                "(see big_modeling.cache_factory_for)")
        ids_p = self._pad_prompt(ids, pad_ok)
        sample = sampling is not None
        if sample:
            from .generation import _make_warper, speculative_accept

            warp = _make_warper(sampling)
            rng = rng if rng is not None else jax.random.PRNGKey(0)
        out = self._cached_pass((jax.device_put(ids_p, self.device),), caches, 0,
                                return_logits=sample)
        if sample:
            rng, key = jax.random.split(rng)
            first = jax.random.categorical(key, warp(out[:, S - 1, :]), axis=-1)[0]
        else:
            first = out[0, S - 1]
        committed = np.asarray(ids[0]).tolist() + [int(first)]
        eos_done = eos_token_id is not None and int(first) == eos_token_id
        while len(committed) - S < max_new_tokens and not eos_done:
            cur = len(committed)
            draft, drafter_state = drafter(committed, drafter_state)
            chunk = jnp.asarray([[committed[-1], *draft]], ids.dtype)   # [1, K+1]
            out = self._cached_pass((chunk,), caches, cur - 1, static_pos=False,
                                    return_logits=sample)
            if sample:
                rng, key = jax.random.split(rng)
                m_arr, final = speculative_accept(
                    warp(out[0]), jnp.asarray(draft), key)
                m = int(m_arr)
                preds = draft[:m] + [int(final)]  # truncated to [:m+1] below
            else:
                preds = np.asarray(out[0])
                m = 0
                while m < K and draft[m] == int(preds[m]):
                    m += 1
            emit = [int(p) for p in preds[: m + 1]]
            emit = emit[: max_new_tokens - (cur - S)]
            if eos_token_id is not None and eos_token_id in emit:
                emit = emit[: emit.index(eos_token_id) + 1]
                eos_done = True
            committed.extend(emit)
        return jnp.asarray([committed], ids.dtype)

    def seq2seq_generate(self, input_ids, max_new_tokens: int = 20,
                         decoder_start_token_id: int = 0,
                         eos_token_id: Optional[int] = None,
                         use_cache: bool = True, cache_dtype=None):
        """Greedy encoder-decoder decoding with streamed weights (the
        reference's T0pp-class benchmark rows). The encoder blocks run
        exactly once; decode loops only the "dec"-stage blocks, with
        per-layer self-attention KV buffers plus cross K/V computed at
        prefill — both carried across steps while weights keep streaming.

        Returns [B, 1 + generated] decoder ids (leading start token)."""
        enc_specs = [s for s in self.specs if s.stage == "enc"]
        dec_specs = [s for s in self.specs if s.stage == "dec"]
        if not enc_specs or not dec_specs:
            raise TypeError("seq2seq_generate needs enc/dec-staged block specs")
        ids = jax.device_put(jnp.asarray(input_ids), self.device)
        B, S_enc = ids.shape
        start = jnp.full((B, 1), decoder_start_token_id, ids.dtype)
        if max_new_tokens <= 0:
            return start

        # Encoder: once. The final enc-stage block hands over
        # (encoder_states, decoder_ids).
        args: tuple = (ids, start)
        for spec, ptrees in self._iter_blocks(enc_specs):
            out = self._apply(spec, ptrees, args)
            args = out if isinstance(out, tuple) else (out,)
        enc = args[0]

        cached = use_cache and all(s.cached_apply is not None for s in dec_specs)
        if not cached:
            dec = start
            for _ in range(max_new_tokens):
                d_args = (enc, dec)
                for spec, ptrees in self._iter_blocks(dec_specs):
                    out = self._apply(spec, ptrees, d_args)
                    d_args = out if isinstance(out, tuple) else (out,)
                nxt = jnp.argmax(d_args[0][:, -1, :], axis=-1)[:, None].astype(dec.dtype)
                dec = jnp.concatenate([dec, nxt], axis=1)
                if eos_token_id is not None and bool((nxt == eos_token_id).all()):
                    break
            return dec

        if self.cache_factory is None:
            raise TypeError("cached seq2seq decode needs a cache_factory")
        caches = list(self.cache_factory(B, max_new_tokens,
                                         dtype=cache_dtype or jnp.bfloat16,
                                         src_len=S_enc))
        caches = [jax.device_put(c, self.device) for c in caches]
        # static_pos=False explicitly: args[0] here is the ENCODER tensor
        # (its width would wrongly infer a static — per-token retraced —
        # position for the decode loop).
        tok = self._cached_pass((enc, start), caches, 0, specs=dec_specs,
                                static_pos=False)[:, -1]
        pieces = [start, tok[:, None].astype(ids.dtype)]
        for t in range(1, max_new_tokens):
            if eos_token_id is not None and bool((tok == eos_token_id).all()):
                break
            tok = self._cached_pass((enc, tok[:, None].astype(ids.dtype)),
                                    caches, t, specs=dec_specs,
                                    static_pos=False)[:, -1]
            pieces.append(tok[:, None].astype(ids.dtype))
        return jnp.concatenate(pieces, axis=1)

    @property
    def hbm_resident_bytes(self) -> int:
        """Bytes of weights permanently resident on device."""
        return self.store.total_bytes("device")


# ---------------------------------------------------------------------------
# Loading + dispatch
# ---------------------------------------------------------------------------

def _resolve_device(dev: DeviceId):
    if isinstance(dev, int):
        return jax.local_devices()[dev]
    return None


def _placement_for(name: str, device_map: dict) -> DeviceId:
    best, best_len = None, -1
    for prefix, dev in device_map.items():
        if prefix == "" or name == prefix or name.startswith(prefix + "."):
            if len(prefix) > best_len:
                best, best_len = dev, len(prefix)
    if best is None:
        raise ValueError(f"{name} not covered by device_map")
    return best


def _checkpoint_shards(checkpoint: str) -> list[tuple[str, list[str]]]:
    """[(shard_path, [keys])] for a safetensors file / dir / sharded dir."""
    from safetensors import safe_open

    if os.path.isfile(checkpoint):
        paths = [checkpoint]
    else:
        index = os.path.join(checkpoint, SAFE_INDEX)
        if os.path.isfile(index):
            with open(index) as f:
                weight_map = json.load(f)["weight_map"]
            paths = [os.path.join(checkpoint, s) for s in sorted(set(weight_map.values()))]
        else:
            single = os.path.join(checkpoint, "model.safetensors")
            if not os.path.isfile(single):
                raise FileNotFoundError(f"No safetensors checkpoint under {checkpoint}")
            paths = [single]
    out = []
    for p in paths:
        with safe_open(p, framework="numpy") as f:
            out.append((p, list(f.keys())))
    return out


def load_checkpoint_in_model(
    abstract_params,
    checkpoint: str,
    device_map: Optional[dict] = None,
    dtype=None,
    offload_folder: Optional[str] = None,
    offload_to_memmap: bool = False,
    key_map: Optional[Callable[[str], Optional[tuple[str, str]]]] = None,
) -> WeightStore:
    """Stream safetensors shards into a placed WeightStore (reference:
    load_checkpoint_in_model, utils/modeling.py:1683-1905).

    Placement per tensor follows ``device_map`` (longest-prefix match):
    ints → ``jax.device_put`` to that local device; ``"cpu"`` → host numpy;
    ``"disk"`` → a LazyWeight pointing back into the original shard (no
    copy), or a memmap copy under ``offload_folder`` when
    ``offload_to_memmap=True`` (reference behavior, utils/offload.py:25).
    Host RSS stays ~one shard at a time.

    ``key_map`` translates foreign checkpoint names (e.g. HF Transformers)
    to our param names on the fly: ``key_map(ckpt_key) -> (our_name, op)``
    or None to skip. op "t" transposes (torch Linear layout); for disk-tier
    weights the transpose is deferred into the LazyWeight.
    """
    from safetensors import safe_open

    from .utils.hf_interop import _apply_op

    device_map = device_map or {"": 0}
    store = WeightStore()
    expected = set(named_parameters(abstract_params).keys()) if abstract_params is not None else None
    seen = set()
    memmap_index: dict = {}
    # key -> {member_index: (shard_path, ckpt_key, member_op)} for params
    # aggregated from several checkpoint tensors (op "stack:<e>[:t]").
    stack_parts: dict[str, dict[int, tuple]] = {}

    for shard_path, keys in _checkpoint_shards(checkpoint):
        with safe_open(shard_path, framework="numpy") as f:
            for ckpt_key in keys:
                op = None
                if key_map is not None:
                    mapped = key_map(ckpt_key)
                    if mapped is None:
                        continue
                    key, op = mapped
                else:
                    key = ckpt_key
                if expected is not None and key not in expected:
                    continue
                if op is not None and op.startswith("stack:"):
                    _, idx, *rest = op.split(":")
                    stack_parts.setdefault(key, {})[int(idx)] = (
                        shard_path, ckpt_key, rest[0] if rest else None)
                    continue
                seen.add(key)
                place = _placement_for(key, device_map)
                if place == "disk" and not offload_to_memmap:
                    store.put(key, LazyWeight(shard_path, ckpt_key, dtype, transform=op), place)
                    continue
                arr = _apply_op(f.get_tensor(ckpt_key), op or "copy")
                if dtype is not None:
                    arr = arr.astype(dtype)
                if place == "disk":
                    from .utils.offload import offload_weight

                    memmap_index = offload_weight(arr, key, offload_folder, memmap_index)
                    store.put(key, LazyWeight(os.path.join(offload_folder, f"{key}.dat"), key,
                                              None, memmap_info=memmap_index[key]), place)
                elif place == "cpu":
                    store.put(key, arr, place)
                else:
                    store.put(key, jax.device_put(arr, _resolve_device(place)), place)
    abstract_flat = named_parameters(abstract_params) if abstract_params is not None else {}
    for key, parts in stack_parts.items():
        # The abstract shape's leading dim is the authoritative member count
        # (a truncated shard set missing *tail* experts must not pass).
        n_members = (abstract_flat[key].shape[0] if key in abstract_flat
                     else max(parts) + 1)
        missing_members = set(range(n_members)) - set(parts)
        if missing_members:
            raise ValueError(
                f"{key}: missing stacked members {sorted(missing_members)}")
        seen.add(key)
        members = [parts[i] for i in sorted(parts)]
        place = _placement_for(key, device_map)
        lazy = LazyStack(members, dtype)
        if place == "disk" and not offload_to_memmap:
            store.put(key, lazy, place)
        elif place == "disk":
            # Honor offload_to_memmap like single tensors: the offload
            # folder must stand alone (original shards may be deleted).
            from .utils.offload import offload_weight

            arr = lazy.load()
            memmap_index = offload_weight(arr, key, offload_folder, memmap_index)
            store.put(key, LazyWeight(os.path.join(offload_folder, f"{key}.dat"), key,
                                      None, memmap_info=memmap_index[key]), place)
        elif place == "cpu":
            store.put(key, lazy.load(), place)
        else:
            store.put(key, jax.device_put(lazy.load(), _resolve_device(place)), place)
    if memmap_index and offload_folder:
        from .utils.offload import save_offload_index

        save_offload_index(memmap_index, offload_folder)
    if expected is not None:
        missing = expected - seen
        if missing:
            raise ValueError(f"Checkpoint {checkpoint} is missing keys: {sorted(missing)[:5]}...")
    return store


def store_from_params(params, device_map: dict) -> WeightStore:
    """Place an in-memory param tree per device_map (dispatch without a
    checkpoint — reference: dispatch_model on a materialized model)."""
    store = WeightStore()
    for name, leaf in named_parameters(params).items():
        place = _placement_for(name, device_map)
        if place == "cpu":
            store.put(name, np.asarray(jax.device_get(leaf)), place)
        elif place == "disk":
            raise ValueError("store_from_params cannot disk-offload; use load_checkpoint_in_model "
                             "or offload_state_dict first")
        else:
            store.put(name, jax.device_put(leaf, _resolve_device(place)), place)
    return store


def dispatch_model(
    module,
    params=None,
    store: Optional[WeightStore] = None,
    device_map: Optional[dict] = None,
    block_specs: Optional[list[BlockSpec]] = None,
    execution_device=None,
) -> StreamedModel:
    """Wrap a model for execution with weights spread over HBM/host/disk
    (reference: dispatch_model, big_modeling.py:306 — hook attachment
    replaced by the block-streaming executor)."""
    specs = block_specs or block_specs_for(module)
    if specs is None:
        raise ValueError(
            f"No block specs known for {type(module).__name__}; pass block_specs=[BlockSpec(...)]")
    if store is None:
        if params is None:
            raise ValueError("dispatch_model needs params or a WeightStore")
        device_map = device_map or {"": 0}
        store = store_from_params(params, device_map)
    exec_dev = execution_device
    if exec_dev is None:
        dev_ids = [d for d in store.placement.values() if isinstance(d, int)]
        exec_dev = jax.local_devices()[dev_ids[0] if dev_ids else 0]
    bound = getattr(getattr(module, "config", None), "max_position_embeddings", None)
    return StreamedModel(specs, store, exec_dev, cache_factory=cache_factory_for(module),
                         position_bound=bound)


def load_checkpoint_and_dispatch(
    module,
    checkpoint: str,
    device_map: Union[str, dict, None] = "auto",
    max_memory: Optional[dict] = None,
    no_split_module_classes: Optional[list[str]] = None,
    dtype=None,
    offload_folder: Optional[str] = None,
    offload_to_memmap: bool = False,
    example_args: tuple = (),
    block_specs: Optional[list[BlockSpec]] = None,
    key_map: Optional[Callable[[str], Optional[tuple[str, str]]]] = None,
) -> StreamedModel:
    """One-call big-model load (reference: load_checkpoint_and_dispatch,
    big_modeling.py:504): abstract init → device-map solve → shard-streamed
    load → streaming executor. ``key_map`` translates foreign checkpoint
    names per tensor (see load_checkpoint_in_model)."""
    abstract = init_empty_weights(module, *example_args)
    if device_map in ("auto", "balanced", None):
        balanced = device_map == "balanced"
        mm = (get_balanced_memory(abstract, max_memory=max_memory,
                                  no_split_module_classes=no_split_module_classes, dtype=dtype)
              if balanced else max_memory)
        device_map = infer_auto_device_map(
            abstract, max_memory=mm, no_split_module_classes=no_split_module_classes, dtype=dtype)
    check_device_map(abstract, device_map)
    store = load_checkpoint_in_model(
        abstract, checkpoint, device_map=device_map, dtype=dtype,
        offload_folder=offload_folder, offload_to_memmap=offload_to_memmap,
        key_map=key_map)
    return dispatch_model(module, store=store, block_specs=block_specs)


def load_hf_checkpoint_and_dispatch(
    checkpoint_dir: str,
    device_map: Union[str, dict, None] = "auto",
    max_memory: Optional[dict] = None,
    dtype=None,
    offload_folder: Optional[str] = None,
    offload_to_memmap: bool = False,
    config=None,
):
    """Big-model load straight from a HuggingFace checkpoint directory.

    The reference consumes Hub checkpoints natively because it wraps torch
    modules (reference: load_checkpoint_and_dispatch, big_modeling.py:504);
    here the HF->flax translation (utils/hf_interop.py) is applied
    *per-tensor during the shard stream*, so weights go disk -> placed
    without an intermediate full state dict, and disk-tier weights keep lazy
    refs into the original HF shards (the transpose happens at block-fetch
    time). Returns ``(streamed_model, module)``.

    Supported: llama, mistral, qwen2, gemma, gpt2, gptj, gpt_neox, opt (the
    reference's big-model benchmark families), mixtral (per-expert HF shards aggregate
    lazily into stacked (E, in, out) tensors — LazyStack — so even the
    disk tier never holds more than a block of experts), and t5
    (encoder-decoder; generate via ``streamed.seq2seq_generate``).
    """
    from .utils.hf_interop import map_hf_key, open_hf_checkpoint

    family, config, module = open_hf_checkpoint(checkpoint_dir, config)
    streamable = ("llama", "mistral", "qwen2", "qwen2_moe", "gemma", "gemma2",
                  "gpt2", "gptj", "gpt_neox", "bloom", "opt", "phi", "t5",
                  "mixtral")
    if family not in streamable:
        raise ValueError(
            f"streamed dispatch supports {'/'.join(streamable)} (got "
            f"{family!r}); use utils.load_hf_checkpoint + dispatch_model for "
            "other families")

    ids = np.zeros((1, 8), np.int32)
    streamed = load_checkpoint_and_dispatch(
        module, checkpoint_dir, device_map=device_map, max_memory=max_memory,
        dtype=dtype, offload_folder=offload_folder,
        offload_to_memmap=offload_to_memmap,
        example_args=(ids, ids) if family == "t5" else (ids,),
        key_map=lambda key: map_hf_key(key, family))
    return streamed, module


def cpu_offload(module, params, execution_device=None, block_specs=None) -> StreamedModel:
    """All weights in host DRAM, streamed block-by-block into HBM
    (reference: cpu_offload, big_modeling.py:170)."""
    return dispatch_model(module, params=params, device_map={"": "cpu"},
                          block_specs=block_specs, execution_device=execution_device)


class UserCpuOffloadHook:
    """Manual-offload handle (reference: UserCpuOffloadHook, hooks.py —
    returned by cpu_offload_with_hook so model pipelines can free the
    accelerator between stages). Streaming already keeps weights
    host-resident between calls, so ``offload`` only releases whatever the
    executor left resident on device."""

    def __init__(self, model: StreamedModel):
        self.model = model

    def offload(self):
        """Release device-resident buffers (host copies stay)."""
        # Stop (and drain) the prefetch pool FIRST: an in-flight fetch
        # finishing after the clear would silently repopulate the cache.
        if self.model._pool is not None:
            self.model._pool.shutdown(wait=True, cancel_futures=True)
            self.model._pool = None
        self.model._resident_cache.clear()

    def remove(self):
        """Reference-parity alias: detaching the hook == releasing residency."""
        self.offload()


def cpu_offload_with_hook(module, params, execution_device=None, block_specs=None,
                          prev_module_hook: Optional[UserCpuOffloadHook] = None):
    """``(streamed_model, hook)`` pair (reference: cpu_offload_with_hook,
    big_modeling.py:231): run several models on one chip and call
    ``hook.offload()`` between them. ``prev_module_hook`` (the previous
    stage's hook, reference-parity chaining) is offloaded immediately —
    with the streaming executor residency is lazy, so "offload before the
    next model runs" and "offload now" coincide."""
    if prev_module_hook is not None:
        prev_module_hook.offload()
    streamed = cpu_offload(module, params, execution_device=execution_device,
                           block_specs=block_specs)
    return streamed, UserCpuOffloadHook(streamed)


def init_on_device(device, include_buffers: Optional[bool] = None):
    """Context manager placing newly created arrays on ``device``
    (reference: init_on_device, big_modeling.py:125 patches torch's
    register_parameter; JAX has a first-class ambient default device).
    ``include_buffers`` is accepted for signature parity and ignored —
    jax has no parameter/buffer distinction."""
    del include_buffers
    return jax.default_device(device)


def disk_offload(module, checkpoint: str, offload_folder: Optional[str] = None,
                 execution_device=None, block_specs=None, example_args=()) -> StreamedModel:
    """All weights on disk, streamed per block (reference: disk_offload,
    big_modeling.py:231). Without ``offload_folder`` the store keeps lazy
    refs into the original safetensors shards (zero-copy); with one, weights
    are re-written as raw memmaps there (reference behavior)."""
    abstract = init_empty_weights(module, *example_args)
    store = load_checkpoint_in_model(abstract, checkpoint, device_map={"": "disk"},
                                     offload_folder=offload_folder,
                                     offload_to_memmap=offload_folder is not None)
    return dispatch_model(module, store=store, block_specs=block_specs,
                          execution_device=execution_device)
