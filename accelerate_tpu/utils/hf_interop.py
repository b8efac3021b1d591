"""HuggingFace Transformers checkpoint interop.

The reference framework operates directly on HF ``torch.nn.Module``s, so any
Hub checkpoint "just works" (reference: big_modeling.py:504
``load_checkpoint_and_dispatch`` + utils/modeling.py:1683
``load_checkpoint_in_model``). This framework defines its own flax model
families; capability parity therefore needs a *weight bridge*: bidirectional
name/layout translation between HF state dicts (torch conventions:
``Linear.weight`` is ``(out, in)``, dot-separated names) and our param
pytrees (flax: ``kernel`` is ``(in, out)``, nested dicts).

Supported families mirror ``accelerate_tpu.models``: llama, mixtral, cohere2_moe, pangu_ultra_moe, bloom, gpt2,
bert, t5. Each family is a table of bidirectional rules; conversion is pure
numpy (no torch import needed when reading safetensors).

    params = load_hf_checkpoint("/path/to/hf_llama_dir")       # dir with
    #   config.json + *.safetensors -> (our_config, params pytree)
    params = convert_hf_state_dict(sd, "llama", config=cfg)    # in-memory
    sd = export_hf_state_dict(params, "llama", config=cfg)     # inverse
"""

from __future__ import annotations

import json
import os
import re
from typing import Callable, Optional

import numpy as np

__all__ = [
    "detect_family",
    "config_from_hf",
    "convert_hf_state_dict",
    "export_hf_state_dict",
    "load_hf_checkpoint",
]


# ---------------------------------------------------------------------------
# Rule tables. Each rule: (hf_template, ours_template, op).
#   - ``{i}``/``{j}`` match layer indices, ``{p}`` matches the projection
#     alternatives listed in the 4th slot (if present).
#   - op "t" transposes 2D weights (torch Linear <-> flax Dense, self-inverse);
#     op "copy" passes through (embeddings, norms, biases, GPT-2's Conv1D
#     weights, which are already (in, out)).
# HF keys with no rule (tied heads, position-id buffers) are skipped on
# import; our params with no rule raise on export (nothing may be dropped
# silently in that direction).
# ---------------------------------------------------------------------------

_LLAMA_RULES = [
    ("model.embed_tokens.weight", "model/embed_tokens/embedding", "copy", None),
    ("model.layers.{i}.self_attn.{p}_proj.weight",
     "model/layers_{i}/self_attn/{p}_proj/kernel", "t", ("q", "k", "v", "o")),
    ("model.layers.{i}.mlp.{p}_proj.weight",
     "model/layers_{i}/mlp/{p}_proj/kernel", "t", ("gate", "up", "down")),
    ("model.layers.{i}.input_layernorm.weight",
     "model/layers_{i}/input_norm/scale", "copy", None),
    ("model.layers.{i}.post_attention_layernorm.weight",
     "model/layers_{i}/post_attn_norm/scale", "copy", None),
    ("model.norm.weight", "model/norm/scale", "copy", None),
    ("lm_head.weight", "lm_head/kernel", "t", None),
]

# Mixtral: llama attention/norms + routed experts. Our MixtralForCausalLM is
# flat (no "model" scope — models/mixtral.py:130), and the per-expert
# w1/w2/w3 Linears are stacked into (E, in, out) tensors by the special-case
# code below (our experts are a single batched einsum, not E separate
# modules).
_MIXTRAL_RULES = [
    (hf_t, ours_t.removeprefix("model/"), op, alts)
    for hf_t, ours_t, op, alts in _LLAMA_RULES if ".mlp." not in hf_t
] + [
    ("model.layers.{i}.block_sparse_moe.gate.weight",
     "layers_{i}/mlp/router", "t", None),
]
_MIXTRAL_EXPERT_RE = re.compile(
    r"model\.layers\.(\d+)\.block_sparse_moe\.experts\.(\d+)\.w([123])\.weight")
# HF w1 = gate (F,D), w2 = down (D,F), w3 = up (F,D).
_MIXTRAL_W_TO_NAME = {"1": "gate_proj", "2": "down_proj", "3": "up_proj"}

# Per-expert HF Linears -> our stacked (E, in, out) tensors. Per family:
# (regex with (layer, expert, proj-token) groups, token -> our proj name,
# exporter (layer, expert, token) -> HF key, name of the stack under
# ``layers_{i}/mlp/``); a family lists one such tuple per stack.
_SWIGLU_PROJ = {p: p for p in ("gate_proj", "up_proj", "down_proj")}


def _swiglu_stack(stack: str):
    return (
        re.compile(rf"model\.layers\.(\d+)\.mlp\.{stack}\.(\d+)"
                   r"\.(gate_proj|up_proj|down_proj)\.weight"),
        _SWIGLU_PROJ,
        lambda layer, e, tok: f"model.layers.{layer}.mlp.{stack}.{e}.{tok}.weight",
        stack,
    )


_EXPERT_CONVENTIONS = {
    "mixtral": [(
        _MIXTRAL_EXPERT_RE,
        _MIXTRAL_W_TO_NAME,
        lambda layer, e, tok: f"model.layers.{layer}.block_sparse_moe.experts.{e}.w{tok}.weight",
        "experts",
    )],
    "qwen2_moe": [_swiglu_stack("experts")],
    # cohere2_moe: the routed experts and the always-on shared experts are
    # both stacks of per-expert SwiGLU Linears.
    "cohere2_moe": [_swiglu_stack("experts"), _swiglu_stack("shared_experts")],
    # pangu_ultra_moe: routed experts are per-expert Linears; the shared
    # experts are ONE module of their joint width (plain rules below).
    "pangu_ultra_moe": [_swiglu_stack("experts")],
}


def _match_expert(key: str, family: str):
    """``(stack path under the layer, expert index, proj name)`` of a
    per-expert HF tensor name, or None."""
    for expert_re, tok_to_name, _, stack in _EXPERT_CONVENTIONS.get(family, ()):
        em = expert_re.match(key)
        if em:
            return (f"layers_{em.group(1)}/mlp/{stack}/{tok_to_name[em.group(3)]}",
                    int(em.group(2)))
    return None

_GPT2_RULES = [
    ("wte.weight", "wte/embedding", "copy", None),
    ("wpe.weight", "wpe/embedding", "copy", None),
    ("h.{i}.ln_1.weight", "h_{i}/ln_1/scale", "copy", None),
    ("h.{i}.ln_1.bias", "h_{i}/ln_1/bias", "copy", None),
    # Conv1D weights are already (in, out): straight copy, fused qkv order
    # matches (q|k|v concatenated on the output axis).
    ("h.{i}.attn.c_attn.weight", "h_{i}/qkv/kernel", "copy", None),
    ("h.{i}.attn.c_attn.bias", "h_{i}/qkv/bias", "copy", None),
    ("h.{i}.attn.c_proj.weight", "h_{i}/attn_out/kernel", "copy", None),
    ("h.{i}.attn.c_proj.bias", "h_{i}/attn_out/bias", "copy", None),
    ("h.{i}.ln_2.weight", "h_{i}/ln_2/scale", "copy", None),
    ("h.{i}.ln_2.bias", "h_{i}/ln_2/bias", "copy", None),
    ("h.{i}.mlp.c_fc.weight", "h_{i}/fc1/kernel", "copy", None),
    ("h.{i}.mlp.c_fc.bias", "h_{i}/fc1/bias", "copy", None),
    ("h.{i}.mlp.c_proj.weight", "h_{i}/fc2/kernel", "copy", None),
    ("h.{i}.mlp.c_proj.bias", "h_{i}/fc2/bias", "copy", None),
    ("ln_f.weight", "ln_f/scale", "copy", None),
    ("ln_f.bias", "ln_f/bias", "copy", None),
]

_BLOOM_RULES = [
    ("word_embeddings.weight", "word_embeddings/embedding", "copy", None),
    ("word_embeddings_layernorm.weight", "word_embeddings_layernorm/scale", "copy", None),
    ("word_embeddings_layernorm.bias", "word_embeddings_layernorm/bias", "copy", None),
    ("h.{i}.input_layernorm.weight", "layers_{i}/input_layernorm/scale", "copy", None),
    ("h.{i}.input_layernorm.bias", "layers_{i}/input_layernorm/bias", "copy", None),
    # Fused per-head QKV: output dim is H blocks of [q|k|v] (3D) — the
    # HF view(B, S, H, 3, D) layout survives a plain transpose.
    ("h.{i}.self_attention.query_key_value.weight",
     "layers_{i}/query_key_value/kernel", "t", None),
    ("h.{i}.self_attention.query_key_value.bias",
     "layers_{i}/query_key_value/bias", "copy", None),
    ("h.{i}.self_attention.dense.weight", "layers_{i}/dense/kernel", "t", None),
    ("h.{i}.self_attention.dense.bias", "layers_{i}/dense/bias", "copy", None),
    ("h.{i}.post_attention_layernorm.weight",
     "layers_{i}/post_attention_layernorm/scale", "copy", None),
    ("h.{i}.post_attention_layernorm.bias",
     "layers_{i}/post_attention_layernorm/bias", "copy", None),
    ("h.{i}.mlp.dense_h_to_4h.weight", "layers_{i}/dense_h_to_4h/kernel", "t", None),
    ("h.{i}.mlp.dense_h_to_4h.bias", "layers_{i}/dense_h_to_4h/bias", "copy", None),
    ("h.{i}.mlp.dense_4h_to_h.weight", "layers_{i}/dense_4h_to_h/kernel", "t", None),
    ("h.{i}.mlp.dense_4h_to_h.bias", "layers_{i}/dense_4h_to_h/bias", "copy", None),
    ("ln_f.weight", "ln_f/scale", "copy", None),
    ("ln_f.bias", "ln_f/bias", "copy", None),
]

_OPT_RULES = [
    ("embed_tokens.weight", "embed_tokens/embedding", "copy", None),
    ("embed_positions.weight", "embed_positions/embedding", "copy", None),
    ("layers.{i}.self_attn.{p}_proj.weight",
     "layers_{i}/{p}_proj/kernel", "t", ("q", "k", "v", "out")),
    ("layers.{i}.self_attn.{p}_proj.bias",
     "layers_{i}/{p}_proj/bias", "copy", ("q", "k", "v", "out")),
    ("layers.{i}.self_attn_layer_norm.weight",
     "layers_{i}/self_attn_layer_norm/scale", "copy", None),
    ("layers.{i}.self_attn_layer_norm.bias",
     "layers_{i}/self_attn_layer_norm/bias", "copy", None),
    ("layers.{i}.fc1.weight", "layers_{i}/fc1/kernel", "t", None),
    ("layers.{i}.fc1.bias", "layers_{i}/fc1/bias", "copy", None),
    ("layers.{i}.fc2.weight", "layers_{i}/fc2/kernel", "t", None),
    ("layers.{i}.fc2.bias", "layers_{i}/fc2/bias", "copy", None),
    ("layers.{i}.final_layer_norm.weight",
     "layers_{i}/final_layer_norm/scale", "copy", None),
    ("layers.{i}.final_layer_norm.bias",
     "layers_{i}/final_layer_norm/bias", "copy", None),
    ("final_layer_norm.weight", "final_layer_norm/scale", "copy", None),
    ("final_layer_norm.bias", "final_layer_norm/bias", "copy", None),
]

_GPTJ_RULES = [
    ("wte.weight", "wte/embedding", "copy", None),
    ("h.{i}.ln_1.weight", "h_{i}/ln_1/scale", "copy", None),
    ("h.{i}.ln_1.bias", "h_{i}/ln_1/bias", "copy", None),
    ("h.{i}.attn.{p}_proj.weight",
     "h_{i}/{p}_proj/kernel", "t", ("q", "k", "v", "out")),
    ("h.{i}.mlp.fc_in.weight", "h_{i}/fc_in/kernel", "t", None),
    ("h.{i}.mlp.fc_in.bias", "h_{i}/fc_in/bias", "copy", None),
    ("h.{i}.mlp.fc_out.weight", "h_{i}/fc_out/kernel", "t", None),
    ("h.{i}.mlp.fc_out.bias", "h_{i}/fc_out/bias", "copy", None),
    ("ln_f.weight", "ln_f/scale", "copy", None),
    ("ln_f.bias", "ln_f/bias", "copy", None),
    # GPT-J's head is untied AND biased.
    ("lm_head.weight", "lm_head/kernel", "t", None),
    ("lm_head.bias", "lm_head/bias", "copy", None),
]

_GPT_NEOX_RULES = [
    ("embed_in.weight", "embed_in/embedding", "copy", None),
    ("layers.{i}.input_layernorm.weight",
     "layers_{i}/input_layernorm/scale", "copy", None),
    ("layers.{i}.input_layernorm.bias",
     "layers_{i}/input_layernorm/bias", "copy", None),
    # Fused per-head QKV: output-dim layout (H x [q|k|v]) matches after "t".
    ("layers.{i}.attention.query_key_value.weight",
     "layers_{i}/query_key_value/kernel", "t", None),
    ("layers.{i}.attention.query_key_value.bias",
     "layers_{i}/query_key_value/bias", "copy", None),
    ("layers.{i}.attention.dense.weight", "layers_{i}/dense/kernel", "t", None),
    ("layers.{i}.attention.dense.bias", "layers_{i}/dense/bias", "copy", None),
    ("layers.{i}.post_attention_layernorm.weight",
     "layers_{i}/post_attention_layernorm/scale", "copy", None),
    ("layers.{i}.post_attention_layernorm.bias",
     "layers_{i}/post_attention_layernorm/bias", "copy", None),
    ("layers.{i}.mlp.dense_h_to_4h.weight",
     "layers_{i}/dense_h_to_4h/kernel", "t", None),
    ("layers.{i}.mlp.dense_h_to_4h.bias",
     "layers_{i}/dense_h_to_4h/bias", "copy", None),
    ("layers.{i}.mlp.dense_4h_to_h.weight",
     "layers_{i}/dense_4h_to_h/kernel", "t", None),
    ("layers.{i}.mlp.dense_4h_to_h.bias",
     "layers_{i}/dense_4h_to_h/bias", "copy", None),
    ("final_layer_norm.weight", "final_layer_norm/scale", "copy", None),
    ("final_layer_norm.bias", "final_layer_norm/bias", "copy", None),
    ("embed_out.weight", "embed_out/kernel", "t", None),
]

_PHI_RULES = [
    ("embed_tokens.weight", "embed_tokens/embedding", "copy", None),
    ("layers.{i}.input_layernorm.weight", "layers_{i}/input_layernorm/scale", "copy", None),
    ("layers.{i}.input_layernorm.bias", "layers_{i}/input_layernorm/bias", "copy", None),
    ("layers.{i}.self_attn.{p}_proj.weight",
     "layers_{i}/{p}_proj/kernel", "t", ("q", "k", "v")),
    ("layers.{i}.self_attn.{p}_proj.bias",
     "layers_{i}/{p}_proj/bias", "copy", ("q", "k", "v")),
    ("layers.{i}.self_attn.dense.weight", "layers_{i}/dense/kernel", "t", None),
    ("layers.{i}.self_attn.dense.bias", "layers_{i}/dense/bias", "copy", None),
    ("layers.{i}.mlp.fc1.weight", "layers_{i}/fc1/kernel", "t", None),
    ("layers.{i}.mlp.fc1.bias", "layers_{i}/fc1/bias", "copy", None),
    ("layers.{i}.mlp.fc2.weight", "layers_{i}/fc2/kernel", "t", None),
    ("layers.{i}.mlp.fc2.bias", "layers_{i}/fc2/bias", "copy", None),
    ("final_layernorm.weight", "final_layernorm/scale", "copy", None),
    ("final_layernorm.bias", "final_layernorm/bias", "copy", None),
    # Phi's head is untied AND biased.
    ("lm_head.weight", "lm_head/kernel", "t", None),
    ("lm_head.bias", "lm_head/bias", "copy", None),
]

_BERT_RULES = [
    ("embeddings.word_embeddings.weight", "encoder/word_embeddings/embedding", "copy", None),
    ("embeddings.position_embeddings.weight",
     "encoder/position_embeddings/embedding", "copy", None),
    ("embeddings.token_type_embeddings.weight",
     "encoder/token_type_embeddings/embedding", "copy", None),
    ("embeddings.LayerNorm.weight", "encoder/embed_norm/scale", "copy", None),
    ("embeddings.LayerNorm.bias", "encoder/embed_norm/bias", "copy", None),
    ("encoder.layer.{i}.attention.self.{p}.weight",
     "encoder/layer_{i}/attention/{p}/kernel", "t", ("query", "key", "value")),
    ("encoder.layer.{i}.attention.self.{p}.bias",
     "encoder/layer_{i}/attention/{p}/bias", "copy", ("query", "key", "value")),
    ("encoder.layer.{i}.attention.output.dense.weight",
     "encoder/layer_{i}/attention/attn_out/kernel", "t", None),
    ("encoder.layer.{i}.attention.output.dense.bias",
     "encoder/layer_{i}/attention/attn_out/bias", "copy", None),
    ("encoder.layer.{i}.attention.output.LayerNorm.weight",
     "encoder/layer_{i}/attn_norm/scale", "copy", None),
    ("encoder.layer.{i}.attention.output.LayerNorm.bias",
     "encoder/layer_{i}/attn_norm/bias", "copy", None),
    ("encoder.layer.{i}.intermediate.dense.weight",
     "encoder/layer_{i}/intermediate/kernel", "t", None),
    ("encoder.layer.{i}.intermediate.dense.bias",
     "encoder/layer_{i}/intermediate/bias", "copy", None),
    ("encoder.layer.{i}.output.dense.weight",
     "encoder/layer_{i}/mlp_out/kernel", "t", None),
    ("encoder.layer.{i}.output.dense.bias",
     "encoder/layer_{i}/mlp_out/bias", "copy", None),
    ("encoder.layer.{i}.output.LayerNorm.weight",
     "encoder/layer_{i}/mlp_norm/scale", "copy", None),
    ("encoder.layer.{i}.output.LayerNorm.bias",
     "encoder/layer_{i}/mlp_norm/bias", "copy", None),
    ("pooler.dense.weight", "pooler/kernel", "t", None),
    ("pooler.dense.bias", "pooler/bias", "copy", None),
    ("classifier.weight", "classifier/kernel", "t", None),
    ("classifier.bias", "classifier/bias", "copy", None),
]

_T5_RULES = [
    ("shared.weight", "shared_embedding/embedding", "copy", None),
    # Encoder.
    ("encoder.block.{i}.layer.0.SelfAttention.q.weight",
     "encoder_layer_{i}/attention/query/kernel", "t", None),
    ("encoder.block.{i}.layer.0.SelfAttention.k.weight",
     "encoder_layer_{i}/attention/key/kernel", "t", None),
    ("encoder.block.{i}.layer.0.SelfAttention.v.weight",
     "encoder_layer_{i}/attention/value/kernel", "t", None),
    ("encoder.block.{i}.layer.0.SelfAttention.o.weight",
     "encoder_layer_{i}/attention/attn_out/kernel", "t", None),
    ("encoder.block.{i}.layer.0.SelfAttention.relative_attention_bias.weight",
     "encoder_layer_{i}/attention/relative_attention_bias/embedding", "copy", None),
    ("encoder.block.{i}.layer.0.layer_norm.weight",
     "encoder_layer_{i}/attn_norm/scale", "copy", None),
    ("encoder.block.{i}.layer.1.DenseReluDense.wi.weight",
     "encoder_layer_{i}/mlp/intermediate/kernel", "t", None),
    # Gated variants (t5-v1.1/flan): wi_0 is the activated projection, wi_1
    # the linear gate (HF T5DenseGatedActDense).
    ("encoder.block.{i}.layer.1.DenseReluDense.wi_0.weight",
     "encoder_layer_{i}/mlp/intermediate/kernel", "t", None),
    ("encoder.block.{i}.layer.1.DenseReluDense.wi_1.weight",
     "encoder_layer_{i}/mlp/intermediate_gate/kernel", "t", None),
    ("encoder.block.{i}.layer.1.DenseReluDense.wo.weight",
     "encoder_layer_{i}/mlp/mlp_out/kernel", "t", None),
    ("encoder.block.{i}.layer.1.layer_norm.weight",
     "encoder_layer_{i}/mlp_norm/scale", "copy", None),
    ("encoder.final_layer_norm.weight", "encoder_norm/scale", "copy", None),
    # Decoder.
    ("decoder.block.{i}.layer.0.SelfAttention.q.weight",
     "decoder_layer_{i}/self_attention/query/kernel", "t", None),
    ("decoder.block.{i}.layer.0.SelfAttention.k.weight",
     "decoder_layer_{i}/self_attention/key/kernel", "t", None),
    ("decoder.block.{i}.layer.0.SelfAttention.v.weight",
     "decoder_layer_{i}/self_attention/value/kernel", "t", None),
    ("decoder.block.{i}.layer.0.SelfAttention.o.weight",
     "decoder_layer_{i}/self_attention/attn_out/kernel", "t", None),
    ("decoder.block.{i}.layer.0.SelfAttention.relative_attention_bias.weight",
     "decoder_layer_{i}/self_attention/relative_attention_bias/embedding", "copy", None),
    ("decoder.block.{i}.layer.0.layer_norm.weight",
     "decoder_layer_{i}/self_norm/scale", "copy", None),
    ("decoder.block.{i}.layer.1.EncDecAttention.q.weight",
     "decoder_layer_{i}/cross_attention/query/kernel", "t", None),
    ("decoder.block.{i}.layer.1.EncDecAttention.k.weight",
     "decoder_layer_{i}/cross_attention/key/kernel", "t", None),
    ("decoder.block.{i}.layer.1.EncDecAttention.v.weight",
     "decoder_layer_{i}/cross_attention/value/kernel", "t", None),
    ("decoder.block.{i}.layer.1.EncDecAttention.o.weight",
     "decoder_layer_{i}/cross_attention/attn_out/kernel", "t", None),
    ("decoder.block.{i}.layer.1.layer_norm.weight",
     "decoder_layer_{i}/cross_norm/scale", "copy", None),
    ("decoder.block.{i}.layer.2.DenseReluDense.wi.weight",
     "decoder_layer_{i}/mlp/intermediate/kernel", "t", None),
    ("decoder.block.{i}.layer.2.DenseReluDense.wi_0.weight",
     "decoder_layer_{i}/mlp/intermediate/kernel", "t", None),
    ("decoder.block.{i}.layer.2.DenseReluDense.wi_1.weight",
     "decoder_layer_{i}/mlp/intermediate_gate/kernel", "t", None),
    ("decoder.block.{i}.layer.2.DenseReluDense.wo.weight",
     "decoder_layer_{i}/mlp/mlp_out/kernel", "t", None),
    ("decoder.block.{i}.layer.2.layer_norm.weight",
     "decoder_layer_{i}/mlp_norm/scale", "copy", None),
    ("decoder.final_layer_norm.weight", "decoder_norm/scale", "copy", None),
    # Untied head (v1.1/flan). For tied checkpoints the duplicate
    # lm_head.weight is dropped by convert_hf_state_dict's T5 pre-pass.
    ("lm_head.weight", "lm_head/kernel", "t", None),
]

_VIT_RULES = [
    ("embeddings.cls_token", "cls_token", "copy", None),
    ("embeddings.position_embeddings", "position_embeddings", "copy", None),
    # torch Conv2d kernel [D, C, p, p] <-> dense over (c, ph, pw)-flattened
    # patches [C*p*p, D] (models/vit.py patchify order matches exactly).
    ("embeddings.patch_embeddings.projection.weight",
     "patch_projection/kernel", "cf", None),
    ("embeddings.patch_embeddings.projection.bias",
     "patch_projection/bias", "copy", None),
    ("encoder.layer.{i}.layernorm_before.weight", "layer_{i}/norm_before/scale", "copy", None),
    ("encoder.layer.{i}.layernorm_before.bias", "layer_{i}/norm_before/bias", "copy", None),
    ("encoder.layer.{i}.attention.attention.{p}.weight",
     "layer_{i}/attention/{p}/kernel", "t", ("query", "key", "value")),
    ("encoder.layer.{i}.attention.attention.{p}.bias",
     "layer_{i}/attention/{p}/bias", "copy", ("query", "key", "value")),
    ("encoder.layer.{i}.attention.output.dense.weight",
     "layer_{i}/attention/attn_out/kernel", "t", None),
    ("encoder.layer.{i}.attention.output.dense.bias",
     "layer_{i}/attention/attn_out/bias", "copy", None),
    ("encoder.layer.{i}.layernorm_after.weight", "layer_{i}/norm_after/scale", "copy", None),
    ("encoder.layer.{i}.layernorm_after.bias", "layer_{i}/norm_after/bias", "copy", None),
    ("encoder.layer.{i}.intermediate.dense.weight", "layer_{i}/intermediate/kernel", "t", None),
    ("encoder.layer.{i}.intermediate.dense.bias", "layer_{i}/intermediate/bias", "copy", None),
    ("encoder.layer.{i}.output.dense.weight", "layer_{i}/mlp_out/kernel", "t", None),
    ("encoder.layer.{i}.output.dense.bias", "layer_{i}/mlp_out/bias", "copy", None),
    ("layernorm.weight", "norm/scale", "copy", None),
    ("layernorm.bias", "norm/bias", "copy", None),
    ("classifier.weight", "classifier/kernel", "t", None),
    ("classifier.bias", "classifier/bias", "copy", None),
]

# Qwen2: llama-named tensors plus biases on the q/k/v projections.
_QWEN2_RULES = _LLAMA_RULES + [
    ("model.layers.{i}.self_attn.{p}_proj.bias",
     "model/layers_{i}/self_attn/{p}_proj/bias", "copy", ("q", "k", "v")),
]

# Qwen2-MoE: qwen2 attention (qkv biases) + routed experts + an always-on
# sigmoid-gated shared expert; dense (mlp_only) layers keep llama MLP names.
# Flat scope like mixtral (our MixtralForCausalLM has no "model" wrapper).
_QWEN2_MOE_RULES = [
    (hf_t, ours_t.removeprefix("model/"), op, alts)
    for hf_t, ours_t, op, alts in _QWEN2_RULES if ".mlp." not in hf_t
] + [
    ("model.layers.{i}.mlp.gate.weight", "layers_{i}/mlp/router", "t", None),
    ("model.layers.{i}.mlp.shared_expert.{p}_proj.weight",
     "layers_{i}/mlp/shared_{p}_proj/kernel", "t", ("gate", "up", "down")),
    ("model.layers.{i}.mlp.shared_expert_gate.weight",
     "layers_{i}/mlp/shared_expert_gate/kernel", "t", None),
    ("model.layers.{i}.mlp.{p}_proj.weight",
     "layers_{i}/mlp/{p}_proj/kernel", "t", ("gate", "up", "down")),
]

# Gemma2: llama-named tensors plus the sandwich-norm pair around the MLP
# (input/post_attention norms reuse the llama rules; semantics switch on
# LlamaConfig.post_norms).
_GEMMA2_RULES = _LLAMA_RULES + [
    ("model.layers.{i}.pre_feedforward_layernorm.weight",
     "model/layers_{i}/pre_ffn_norm/scale", "copy", None),
    ("model.layers.{i}.post_feedforward_layernorm.weight",
     "model/layers_{i}/post_ffn_norm/scale", "copy", None),
]

# Cohere2-MoE (models/cohere2_moe.py; flat scope, tied head). Attention and
# norm names are Cohere2's; the MoE names (mlp.gate, mlp.experts.N,
# mlp.shared_experts.N) follow the qwen2_moe / deepseek layout, which is an
# assumption: no checkpoint of this family was at hand.
_COHERE2_MOE_RULES = [
    ("model.embed_tokens.weight", "embed_tokens/embedding", "copy", None),
    ("model.layers.{i}.self_attn.{p}_proj.weight",
     "layers_{i}/self_attn/{p}_proj/kernel", "t", ("q", "k", "v", "o")),
    ("model.layers.{i}.input_layernorm.weight", "layers_{i}/input_norm/scale", "copy", None),
    ("model.layers.{i}.mlp.gate.weight", "layers_{i}/mlp/router", "t", None),
    ("model.norm.weight", "norm/scale", "copy", None),
]

# openPangu-Ultra-MoE (models/pangu_ultra_moe.py; flat scope, untied head):
# latent attention under the published names (q_a_proj, q_a_layernorm,
# q_b_proj, kv_a_proj_with_mqa, kv_a_layernorm, kv_b_proj, o_proj), the four
# sandwich norms, mlp.gate.weight for the router, mlp.shared_experts.* for the
# shared expert and llama's mlp.* for the leading dense layers.
_PANGU_ULTRA_MOE_RULES = [
    ("model.embed_tokens.weight", "embed_tokens/embedding", "copy", None),
    ("model.layers.{i}.self_attn.{p}_proj.weight",
     "layers_{i}/self_attn/{p}_proj/kernel", "t", ("q_a", "q_b", "kv_b", "o")),
    ("model.layers.{i}.self_attn.kv_a_proj_with_mqa.weight",
     "layers_{i}/self_attn/kv_a_proj/kernel", "t", None),
    ("model.layers.{i}.self_attn.q_a_layernorm.weight",
     "layers_{i}/self_attn/q_a_norm/scale", "copy", None),
    ("model.layers.{i}.self_attn.kv_a_layernorm.weight",
     "layers_{i}/self_attn/kv_a_norm/scale", "copy", None),
    ("model.layers.{i}.input_layernorm.weight", "layers_{i}/input_norm/scale", "copy", None),
    ("model.layers.{i}.post_attention_layernorm.weight",
     "layers_{i}/post_attn_norm/scale", "copy", None),
    ("model.layers.{i}.pre_mlp_layernorm.weight", "layers_{i}/pre_mlp_norm/scale", "copy", None),
    ("model.layers.{i}.post_mlp_layernorm.weight", "layers_{i}/post_mlp_norm/scale", "copy", None),
    ("model.layers.{i}.mlp.gate.weight", "layers_{i}/mlp/router", "t", None),
    ("model.layers.{i}.mlp.shared_experts.{p}_proj.weight",
     "layers_{i}/mlp/shared_experts/{p}_proj/kernel", "t", ("gate", "up", "down")),
    ("model.layers.{i}.mlp.{p}_proj.weight",
     "layers_{i}/mlp/{p}_proj/kernel", "t", ("gate", "up", "down")),
    ("model.norm.weight", "norm/scale", "copy", None),
    ("lm_head.weight", "lm_head/kernel", "t", None),
]

_FAMILY_RULES = {
    "llama": _LLAMA_RULES,
    "cohere2_moe": _COHERE2_MOE_RULES,
    "pangu_ultra_moe": _PANGU_ULTRA_MOE_RULES,
    "vit": _VIT_RULES,
    # Mistral checkpoints are llama-named tensor-for-tensor; the config adds
    # sliding_window (handled in config_from_hf).
    "mistral": _LLAMA_RULES,
    "qwen2": _QWEN2_RULES,
    "qwen2_moe": _QWEN2_MOE_RULES,
    # Gemma is llama-named too; the differences (GeGLU, 1+w norms, embedding
    # scaling, decoupled head_dim, tied head) live in config_from_hf.
    "gemma": _LLAMA_RULES,
    "gemma2": _GEMMA2_RULES,
    "mixtral": _MIXTRAL_RULES,
    "gpt2": _GPT2_RULES,
    "gptj": _GPTJ_RULES,
    "gpt_neox": _GPT_NEOX_RULES,
    "bloom": _BLOOM_RULES,
    "opt": _OPT_RULES,
    "phi": _PHI_RULES,
    "bert": _BERT_RULES,
    "t5": _T5_RULES,
}

# Top-level prefixes HF wrapper classes add around the base model; stripped
# before matching so both BertModel and BertForSequenceClassification load.
_STRIP_PREFIXES = {
    "gpt2": ("transformer.",),
    "gptj": ("transformer.",),
    "gpt_neox": ("gpt_neox.",),
    "bloom": ("transformer.",),
    "opt": ("model.decoder.", "decoder."),
    "phi": ("model.",),
    "bert": ("bert.",),
    "vit": ("vit.",),
    "llama": (),
    "cohere2_moe": (),
    "pangu_ultra_moe": (),
    "mixtral": (),
    "t5": (),
    "qwen2": (),
    "qwen2_moe": (),
    "gemma": (),
    "gemma2": (),
}

# HF keys that are legitimately rule-less: tied copies and index buffers.
_SKIPPABLE = re.compile(
    r"(^|\.)(lm_head\.weight|predictions\..*|position_ids"
    r"|encoder\.embed_tokens\.weight|decoder\.embed_tokens\.weight"
    r"|attn\.(bias|masked_bias)|attention\.(bias|masked_bias)"
    r"|rotary_emb\.inv_freq)$"
)


def _compile_rules(rules):
    compiled = []
    for hf_t, ours_t, op, alts in rules:
        alt = "|".join(alts) if alts else None
        hf_re = re.escape(hf_t).replace(r"\{i\}", r"(?P<i>\d+)")
        ours_re = re.escape(ours_t).replace(r"\{i\}", r"(?P<i>\d+)")
        if alt:
            hf_re = hf_re.replace(r"\{p\}", f"(?P<p>{alt})")
            ours_re = ours_re.replace(r"\{p\}", f"(?P<p>{alt})")
        compiled.append((re.compile(f"^{hf_re}$"), re.compile(f"^{ours_re}$"),
                         hf_t, ours_t, op))
    return compiled


_COMPILED = {fam: _compile_rules(rules) for fam, rules in _FAMILY_RULES.items()}


def _apply_op(value: np.ndarray, op: str) -> np.ndarray:
    if op == "t":
        if value.ndim != 2:
            raise ValueError(f"op 't' expects a 2D weight, got shape {value.shape}")
        return np.ascontiguousarray(value.T)
    if op == "cf":
        # torch Conv2d kernel [out, in, kh, kw] -> dense kernel over
        # (c, kh, kw)-flattened patches: [in*kh*kw, out].
        if value.ndim != 4:
            raise ValueError(f"op 'cf' expects a 4D conv kernel, got {value.shape}")
        return np.ascontiguousarray(value.reshape(value.shape[0], -1).T)
    return value


def _fill(template: str, match: re.Match) -> str:
    out = template
    for name, val in match.groupdict().items():
        out = out.replace("{" + name + "}", val)
    return out


def _nest(flat: dict[str, np.ndarray]) -> dict:
    tree: dict = {}
    for key, value in flat.items():
        parts = key.split("/")
        node = tree
        for part in parts[:-1]:
            node = node.setdefault(part, {})
        node[parts[-1]] = value
    return tree


def _flatten(tree: dict, prefix: str = "") -> dict[str, np.ndarray]:
    flat = {}
    for key, value in tree.items():
        path = f"{prefix}/{key}" if prefix else key
        if isinstance(value, dict):
            flat.update(_flatten(value, path))
        else:
            flat[path] = np.asarray(value)
    return flat


#: HF gelu spellings the flax models evaluate faithfully: "gelu" and
#: "gelu_python" are the exact erf form, the rest the tanh approximation.
#: Anything else (quick_gelu, gelu_10, ...) is rejected loudly.
_GELU_VARIANTS = {"gelu", "gelu_python", "gelu_new", "gelu_fast", "gelu_pytorch_tanh"}


def detect_family(hf_config: dict) -> str:
    """Family name from an HF ``config.json`` dict (its ``model_type``)."""
    model_type = str(hf_config.get("model_type", "")).lower()
    for fam in _FAMILY_RULES:
        if model_type == fam:
            return fam
    raise ValueError(
        f"unsupported model_type {model_type!r}; supported: {sorted(_FAMILY_RULES)}")


def config_from_hf(hf_config: dict, family: Optional[str] = None):
    """Build the matching ``accelerate_tpu.models`` config dataclass from an
    HF ``config.json`` dict."""
    family = family or detect_family(hf_config)
    get = hf_config.get
    if family in ("llama", "mistral", "mixtral", "qwen2", "qwen2_moe", "gemma", "gemma2"):
        from ..models.llama import LlamaConfig, scale_rope_frequencies
        from ..models.mixtral import MixtralConfig

        if family in ("gemma", "gemma2"):
            # transformers: an ABSENT hidden_activation is coerced to the
            # tanh-approximate gelu (the checkpoints were trained so, even
            # where a legacy hidden_act says "gelu"); an EXPLICIT value is
            # honored as written — "gelu" means the exact erf form.
            act = get("hidden_activation") or "gelu_pytorch_tanh"
            if act not in ("gelu", "gelu_pytorch_tanh"):
                raise NotImplementedError(
                    f"hidden_activation {act!r}: the flax {family} MLP is GeGLU (gelu)")
        else:
            act = get("hidden_act", "silu")
            if act not in ("silu", "swish"):
                raise NotImplementedError(
                    f"hidden_act {act!r}: the flax {family} MLP is SwiGLU (silu)")
        rope_scaling = get("rope_scaling") or None
        if rope_scaling:
            import jax.numpy as jnp

            # Validate the scaling type NOW (supported: default/linear/llama3)
            # rather than at first forward — an unrepresentable checkpoint
            # must not convert silently (same policy as the T5 untied head).
            scale_rope_frequencies(jnp.ones((2,), jnp.float32), rope_scaling)
        kwargs = dict(
            rope_scaling=rope_scaling,
            vocab_size=get("vocab_size", 32000),
            hidden_size=get("hidden_size", 4096),
            intermediate_size=get("intermediate_size", 11008),
            num_hidden_layers=get("num_hidden_layers", 32),
            num_attention_heads=get("num_attention_heads", 32),
            num_key_value_heads=get("num_key_value_heads",
                                    get("num_attention_heads", 32)),
            max_position_embeddings=get("max_position_embeddings", 4096),
            rms_norm_eps=get("rms_norm_eps", 1e-5),
            rope_theta=get("rope_theta", 10000.0),
            tie_word_embeddings=get("tie_word_embeddings", False),
        )
        if family == "mistral":
            return LlamaConfig(**kwargs, sliding_window=get("sliding_window"))
        if family == "llama":
            return LlamaConfig(**kwargs)
        def qwen_windows():
            # Sliding window only when the config opts in (use_sliding_window,
            # off by default); the first max_window_layers layers stay
            # full-attention, represented per layer via layer_windows.
            # Returns (uniform_sliding, layer_windows) with one of them None.
            if not get("use_sliding_window"):
                return None, None
            n_layers = kwargs["num_hidden_layers"]
            layer_types = get("layer_types")
            if layer_types:
                windows = tuple(
                    get("sliding_window") if t == "sliding_attention" else None
                    for t in layer_types)
            else:
                full = get("max_window_layers", n_layers)
                windows = tuple(
                    None if i < full else get("sliding_window")
                    for i in range(n_layers))
            if len(set(windows)) == 1:  # uniform: keep the simple knob
                return windows[0], None
            return None, windows

        if family == "qwen2":
            # Qwen2 biases q/k/v (never o).
            sliding, windows = qwen_windows()
            return LlamaConfig(**kwargs, attention_qkv_bias=True,
                               sliding_window=sliding, layer_windows=windows)
        if family == "qwen2_moe":
            # Experts use moe_intermediate_size; the config's plain
            # intermediate_size is the width of the DENSE (mlp_only /
            # decoder_sparse_step) layers. HF: a layer is sparse iff it is
            # not in mlp_only_layers and (i + 1) % decoder_sparse_step == 0.
            step = get("decoder_sparse_step", 1) or 1
            n_layers = kwargs["num_hidden_layers"]
            only = set(get("mlp_only_layers") or ())
            dense_layers = tuple(sorted(
                i for i in range(n_layers) if i in only or (i + 1) % step != 0))
            sliding, windows = qwen_windows()
            return MixtralConfig(
                **{**kwargs, "intermediate_size": get("moe_intermediate_size", 1408)},
                attention_qkv_bias=True,
                sliding_window=sliding, layer_windows=windows,
                num_experts=get("num_experts", 60),
                top_k=get("num_experts_per_tok", 4),
                norm_topk_prob=bool(get("norm_topk_prob", False)),
                shared_expert_intermediate_size=get("shared_expert_intermediate_size"),
                mlp_only_layers=dense_layers,
                dense_intermediate_size=get("intermediate_size"),
                router_aux_coef=get("router_aux_loss_coef", 0.001),
            )
        if family in ("gemma", "gemma2"):
            gemma_kwargs = dict(
                **{**kwargs, "rms_norm_eps": get("rms_norm_eps", 1e-6),
                   "tie_word_embeddings": get("tie_word_embeddings", True)},
                mlp_activation="gelu_tanh" if act == "gelu_pytorch_tanh" else "gelu_exact",
                rms_norm_unit_offset=True,
                scale_embeddings=True, head_dim_override=get("head_dim"))
            if family == "gemma":
                return LlamaConfig(**gemma_kwargs)
            # Gemma2: sandwich norms, logit softcaps, decoupled attention
            # scale, and the local/global mixture from layer_types.
            layer_types = get("layer_types")
            if layer_types:
                windows = tuple(
                    get("sliding_window") if t == "sliding_attention" else None
                    for t in layer_types)
            else:  # older configs: even layers slide
                windows = tuple(
                    get("sliding_window") if i % 2 == 0 else None
                    for i in range(kwargs["num_hidden_layers"]))
            return LlamaConfig(
                **gemma_kwargs,
                post_norms=True,
                layer_windows=windows,
                attn_logit_softcapping=get("attn_logit_softcapping"),
                final_logit_softcapping=get("final_logit_softcapping"),
                query_pre_attn_scalar=get("query_pre_attn_scalar"))
        return MixtralConfig(**kwargs,
                             sliding_window=get("sliding_window"),
                             num_experts=get("num_local_experts", 8),
                             top_k=get("num_experts_per_tok", 2))
    if family == "cohere2_moe":
        from ..models.cohere2_moe import Cohere2MoeConfig

        if get("use_qk_norm") or get("first_k_dense_replace") or not get("use_parallel_block", True):
            raise NotImplementedError(
                "cohere2_moe: q/k norm, leading dense layers and the sequential "
                "block are not implemented (models/cohere2_moe.py)")
        if get("position_embedding_type", "rope_gptj") != "rope_gptj" or get("rotary_pct", 1) != 1:
            raise NotImplementedError("cohere2_moe: only rope_gptj over all of head_dim")
        if get("shared_expert_combination_strategy", "average") != "average":
            raise NotImplementedError("cohere2_moe: shared experts are averaged")
        if not get("tie_word_embeddings", True):
            raise NotImplementedError("cohere2_moe: the head is the tied embedding")
        if not get("layer_types") and get("layer_switch", 4) != 4:
            raise NotImplementedError("cohere2_moe: without layer_types the period is 4")
        heads = get("num_attention_heads", 128)
        return Cohere2MoeConfig(
            vocab_size=get("vocab_size", 262144), hidden_size=get("hidden_size", 4096),
            intermediate_size=get("intermediate_size", 4096),
            num_hidden_layers=get("num_hidden_layers", 32), num_attention_heads=heads,
            num_key_value_heads=get("num_key_value_heads", heads),
            head_dim=get("head_dim") or get("hidden_size", 4096) // heads,
            max_position_embeddings=get("max_position_embeddings", 200000),
            layer_norm_eps=get("layer_norm_eps", 1e-5),
            rope_theta=float(get("rope_theta", 50000.0)),
            sliding_window=get("sliding_window", 4096),
            layer_types=tuple(get("layer_types")) if get("layer_types") else None,
            num_experts=get("num_experts", 128),
            num_experts_per_tok=get("num_experts_per_tok", 8),
            num_shared_experts=get("num_shared_experts", 4),
            expert_selection_fn=get("expert_selection_fn", "sigmoid"),
            norm_topk_prob=bool(get("norm_topk_prob", True)),
            logit_scale=float(get("logit_scale", 1.0)))
    if family == "pangu_ultra_moe":
        from ..models.pangu_ultra_moe import PanguUltraMoeConfig

        if not get("sandwich_norm", True):
            raise NotImplementedError("pangu_ultra_moe: only the sandwich-norm block")
        if get("tie_word_embeddings", False):
            raise NotImplementedError("pangu_ultra_moe: the head has its own matrix")
        if get("rope_scaling") or get("attention_bias", False):
            raise NotImplementedError("pangu_ultra_moe: no rope scaling, no attention bias")
        if get("scoring_func", "sigmoid") != "sigmoid" or get("n_group", 1) not in (None, 1):
            raise NotImplementedError(
                "pangu_ultra_moe: the router is a sigmoid with a plain top-k (no groups)")
        return PanguUltraMoeConfig(
            vocab_size=get("vocab_size", 153600), hidden_size=get("hidden_size", 7680),
            intermediate_size=get("intermediate_size", 18432),
            moe_intermediate_size=get("moe_intermediate_size", 2048),
            num_hidden_layers=get("num_hidden_layers", 61),
            first_k_dense_replace=get("first_k_dense_replace", 3),
            num_attention_heads=get("num_attention_heads", 128),
            q_lora_rank=get("q_lora_rank", 1536), kv_lora_rank=get("kv_lora_rank", 512),
            qk_nope_head_dim=get("qk_nope_head_dim", 128),
            qk_rope_head_dim=get("qk_rope_head_dim", 64), v_head_dim=get("v_head_dim", 128),
            max_position_embeddings=get("max_position_embeddings", 131072),
            rms_norm_eps=get("rms_norm_eps", 1e-5), rope_theta=float(get("rope_theta", 25.6e6)),
            num_experts=get("n_routed_experts", 256),
            num_experts_per_tok=get("num_experts_per_tok", 8),
            n_shared_experts=get("n_shared_experts", 1),
            routed_scaling_factor=float(get("routed_scaling_factor", 2.5)),
            norm_topk_prob=bool(get("norm_topk_prob", True)))
    if family == "gpt2":
        from ..models.gpt2 import GPT2Config

        return GPT2Config(
            vocab_size=get("vocab_size", 50257),
            hidden_size=get("n_embd", 768),
            num_hidden_layers=get("n_layer", 12),
            num_attention_heads=get("n_head", 12),
            max_position_embeddings=get("n_positions", 1024),
            layer_norm_eps=get("layer_norm_epsilon", 1e-5),
        )
    if family == "opt":
        from ..models.opt import OPTConfig

        if not get("do_layer_norm_before", True):
            raise NotImplementedError(
                "do_layer_norm_before=False OPT variants (350m) are post-LN; "
                "the flax decoder is pre-LN only")
        if get("word_embed_proj_dim", get("hidden_size")) != get("hidden_size"):
            raise NotImplementedError(
                "word_embed_proj_dim != hidden_size (OPT-350m projection) is "
                "not representable")
        if not get("enable_bias", True) or not get("layer_norm_elementwise_affine", True):
            raise NotImplementedError(
                "bias-less / non-affine-LN OPT variants are not representable "
                "(the flax decoder declares biased projections and affine norms)")
        act = get("activation_function", "relu")
        if act not in ("relu", "gelu"):
            raise NotImplementedError(f"activation_function {act!r} (relu/gelu only)")
        return OPTConfig(
            vocab_size=get("vocab_size", 50272),
            hidden_size=get("hidden_size", 768),
            intermediate_size=get("ffn_dim", 3072),
            num_hidden_layers=get("num_hidden_layers", 12),
            num_attention_heads=get("num_attention_heads", 12),
            max_position_embeddings=get("max_position_embeddings", 2048),
            activation=act,
        )
    if family == "gptj":
        from ..models.gptj import GPTJConfig

        act = get("activation_function", "gelu_new")
        if act not in _GELU_VARIANTS:
            raise NotImplementedError(
                f"activation_function {act!r} (supported: {sorted(_GELU_VARIANTS)})")
        return GPTJConfig(
            vocab_size=get("vocab_size", 50400),
            hidden_size=get("n_embd", 4096),
            intermediate_size=get("n_inner") or 4 * get("n_embd", 4096),
            num_hidden_layers=get("n_layer", 28),
            num_attention_heads=get("n_head", 16),
            max_position_embeddings=get("n_positions", 2048),
            rotary_dim=get("rotary_dim") or (get("n_embd", 4096) // get("n_head", 16)),
            activation=act,
            layer_norm_eps=get("layer_norm_epsilon", 1e-5),
        )
    if family == "phi":
        from ..models.phi import PhiConfig

        act = get("hidden_act", "gelu_new")
        if act not in _GELU_VARIANTS:
            raise NotImplementedError(
                f"hidden_act {act!r} (supported: {sorted(_GELU_VARIANTS)})")
        if get("qk_layernorm", False):
            raise NotImplementedError(
                "qk_layernorm Phi variants are not representable (the flax "
                "attention has no per-head q/k norms)")
        return PhiConfig(
            vocab_size=get("vocab_size", 51200),
            hidden_size=get("hidden_size", 2560),
            intermediate_size=get("intermediate_size", 10240),
            num_hidden_layers=get("num_hidden_layers", 32),
            num_attention_heads=get("num_attention_heads", 32),
            num_key_value_heads=get("num_key_value_heads",
                                    get("num_attention_heads", 32)),
            max_position_embeddings=get("max_position_embeddings", 2048),
            partial_rotary_factor=get("partial_rotary_factor", 0.4),
            rope_theta=get("rope_theta", 10000.0),
            hidden_act=act,
            layer_norm_eps=get("layer_norm_eps", 1e-5),
        )
    if family == "bloom":
        from ..models.bloom import BloomConfig

        if get("slow_but_exact"):
            raise NotImplementedError(
                "slow_but_exact BLOOM inference reorders the matmul "
                "accumulation; the flax forward is the standard path")
        return BloomConfig(
            vocab_size=get("vocab_size", 250880),
            hidden_size=get("hidden_size", get("n_embed", 1024)),
            num_hidden_layers=get("n_layer", get("num_hidden_layers", 24)),
            num_attention_heads=get("n_head", get("num_attention_heads", 16)),
            layer_norm_epsilon=get("layer_norm_epsilon", 1e-5),
        )
    if family == "gpt_neox":
        from ..models.gpt_neox import GPTNeoXConfig

        act = get("hidden_act", "gelu")
        if act not in _GELU_VARIANTS:
            raise NotImplementedError(
                f"hidden_act {act!r} (supported: {sorted(_GELU_VARIANTS)})")
        if not get("attention_bias", True):
            raise NotImplementedError(
                "attention_bias=False GPT-NeoX variants are not representable "
                "(the flax projections declare biases)")
        return GPTNeoXConfig(
            vocab_size=get("vocab_size", 50432),
            hidden_size=get("hidden_size", 768),
            intermediate_size=get("intermediate_size", 3072),
            num_hidden_layers=get("num_hidden_layers", 12),
            num_attention_heads=get("num_attention_heads", 12),
            max_position_embeddings=get("max_position_embeddings", 2048),
            rotary_pct=get("rotary_pct", 0.25),
            rope_theta=get("rotary_emb_base", get("rope_theta", 10000.0)),
            use_parallel_residual=get("use_parallel_residual", True),
            hidden_act=act,
            layer_norm_eps=get("layer_norm_eps", 1e-5),
        )
    if family == "vit":
        from ..models.vit import ViTConfig

        act = get("hidden_act", "gelu")
        if act != "gelu":
            raise NotImplementedError(
                f"hidden_act {act!r}: the flax ViT MLP is exact gelu")
        if not get("qkv_bias", True):
            raise NotImplementedError(
                "qkv_bias=False ViT variants are not representable (the flax "
                "attention projections carry biases)")
        return ViTConfig(
            image_size=get("image_size", 224),
            patch_size=get("patch_size", 16),
            num_channels=get("num_channels", 3),
            hidden_size=get("hidden_size", 768),
            num_hidden_layers=get("num_hidden_layers", 12),
            num_attention_heads=get("num_attention_heads", 12),
            intermediate_size=get("intermediate_size", 3072),
            layer_norm_eps=get("layer_norm_eps", 1e-12),
            hidden_dropout_prob=get("hidden_dropout_prob", 0.0),
            attention_probs_dropout_prob=get("attention_probs_dropout_prob", 0.0),
            num_labels=len(get("id2label", {i: i for i in range(1000)})),
        )
    if family == "bert":
        from ..models.bert import BertConfig

        return BertConfig(
            vocab_size=get("vocab_size", 30522),
            hidden_size=get("hidden_size", 768),
            num_hidden_layers=get("num_hidden_layers", 12),
            num_attention_heads=get("num_attention_heads", 12),
            intermediate_size=get("intermediate_size", 3072),
            max_position_embeddings=get("max_position_embeddings", 512),
            type_vocab_size=get("type_vocab_size", 2),
            layer_norm_eps=get("layer_norm_eps", 1e-12),
            num_labels=len(get("id2label", {0: 0, 1: 1})),
        )
    if family == "t5":
        from ..models.t5 import T5Config

        return T5Config(
            vocab_size=get("vocab_size", 32128),
            hidden_size=get("d_model", 512),
            intermediate_size=get("d_ff", 2048),
            num_layers=get("num_layers", 6),
            num_heads=get("num_heads", 8),
            head_dim=get("d_kv", 64),
            relative_attention_num_buckets=get("relative_attention_num_buckets", 32),
            relative_attention_max_distance=get("relative_attention_max_distance", 128),
            layer_norm_eps=get("layer_norm_epsilon", 1e-6),
            dropout_rate=get("dropout_rate", 0.1),
            feed_forward_proj=get("feed_forward_proj", "relu"),
            tie_word_embeddings=get("tie_word_embeddings", True),
        )
    raise ValueError(f"unsupported family {family!r}")


def open_hf_checkpoint(checkpoint_dir: str, config=None):
    """Shared HF-dir preamble: read ``config.json``, detect the family,
    build (or accept) the config, and instantiate the flax module.
    Returns ``(family, config, module)`` — used by the streamed dispatch
    (big_modeling), the quantized loader, and anything else that consumes a
    checkpoint directory."""
    config_path = os.path.join(checkpoint_dir, "config.json")
    if not os.path.exists(config_path):
        # No family escape hatch here (unlike load_hf_checkpoint's family=
        # argument), so a weights-only dir must fail with the real reason,
        # not a misleading "unsupported model_type ''".
        raise FileNotFoundError(
            f"{checkpoint_dir} has no config.json; family detection needs it")
    with open(config_path) as f:
        hf_config = json.load(f)
    family = detect_family(hf_config)
    if config is None:
        config = config_from_hf(hf_config, family)
    return family, config, model_from_config(config, family)


def model_from_config(config, family: str):
    """Instantiate the flax module matching a converted config — the single
    family→model-class switch shared by the streamed HF dispatch
    (big_modeling) and the memory estimator (commands/estimate)."""
    if family in ("llama", "mistral", "qwen2", "gemma", "gemma2"):
        from ..models.llama import LlamaForCausalLM

        return LlamaForCausalLM(config)
    if family in ("mixtral", "qwen2_moe"):
        from ..models.mixtral import MixtralForCausalLM

        return MixtralForCausalLM(config)
    if family == "cohere2_moe":
        from ..models.cohere2_moe import Cohere2MoeForCausalLM

        return Cohere2MoeForCausalLM(config)
    if family == "pangu_ultra_moe":
        from ..models.pangu_ultra_moe import PanguUltraMoeForCausalLM

        return PanguUltraMoeForCausalLM(config)
    if family == "gpt2":
        from ..models.gpt2 import GPT2LMHeadModel

        return GPT2LMHeadModel(config)
    if family == "gptj":
        from ..models.gptj import GPTJForCausalLM

        return GPTJForCausalLM(config)
    if family == "gpt_neox":
        from ..models.gpt_neox import GPTNeoXForCausalLM

        return GPTNeoXForCausalLM(config)
    if family == "bloom":
        from ..models.bloom import BloomForCausalLM

        return BloomForCausalLM(config)
    if family == "opt":
        from ..models.opt import OPTForCausalLM

        return OPTForCausalLM(config)
    if family == "phi":
        from ..models.phi import PhiForCausalLM

        return PhiForCausalLM(config)
    if family == "bert":
        from ..models.bert import BertForSequenceClassification

        return BertForSequenceClassification(config)
    if family == "vit":
        from ..models.vit import ViTForImageClassification

        return ViTForImageClassification(config)
    if family == "t5":
        from ..models.t5 import T5ForConditionalGeneration

        return T5ForConditionalGeneration(config)
    raise ValueError(f"unsupported family {family!r}; supported: {sorted(_FAMILY_RULES)}")


def map_hf_key(key: str, family: str) -> Optional[tuple[str, str]]:
    """Translate one HF tensor name to ``(our_dotted_name, op)``.

    Returns None for rule-less keys (tied heads, buffers). This is the
    per-tensor streaming interface used by the big-model loader
    (big_modeling.load_checkpoint_in_model) so HF shards can be mapped
    lazily without materializing the whole state dict. Ops: "t" transposes
    on read; "stack:<e>:t" (mixtral experts) marks the tensor as member
    ``e`` of a stacked (E, in, out) param, transposed — the loader
    aggregates all members before placing the name.
    """
    if family not in _COMPILED:
        raise ValueError(f"unsupported family {family!r}; supported: {sorted(_COMPILED)}")
    key = _strip_prefix(key, family)
    member = _match_expert(key, family)
    if member:
        return member[0].replace("/", "."), f"stack:{member[1]}:t"
    for hf_re, _, _, ours_t, op in _COMPILED[family]:
        match = hf_re.match(key)
        if match:
            return _fill(ours_t, match).replace("/", "."), op
    return None


def _strip_prefix(key: str, family: str) -> str:
    for prefix in _STRIP_PREFIXES.get(family, ()):
        if key.startswith(prefix):
            return key[len(prefix):]
    return key


def convert_hf_state_dict(
    state_dict: dict, family: str, *, strict: bool = False,
    to_numpy: Optional[Callable] = None,
) -> dict:
    """HF state dict -> our nested param pytree (numpy leaves).

    ``state_dict`` values may be numpy arrays or anything with ``.numpy()``
    (torch CPU tensors). Unmatched HF keys are skipped (tied heads, buffers)
    unless ``strict``.
    """
    if family not in _COMPILED:
        raise ValueError(f"unsupported family {family!r}; supported: {sorted(_COMPILED)}")
    rules = _COMPILED[family]
    flat: dict[str, np.ndarray] = {}
    expert_parts: dict[str, dict[int, np.ndarray]] = {}
    drop_keys: set[str] = set()

    def as_np(v):
        if to_numpy is not None:
            return to_numpy(v)
        if hasattr(v, "detach"):  # torch tensor without importing torch
            return v.detach().cpu().numpy()
        return np.asarray(v)

    def drop_tied_duplicate(head_key: str, ref_key: str) -> None:
        # Tied checkpoints carry the head as a duplicate of the embedding;
        # the tied flax model has no lm_head param, so drop it. A genuinely
        # *untied* head converts via the lm_head rule and requires
        # config.tie_word_embeddings=False. First-row precheck so untied
        # loads (e.g. a 1 GB 70B head) don't pay a full elementwise compare.
        head, ref = state_dict.get(head_key), state_dict.get(ref_key)
        if head is None or ref is None:
            return
        h, r = as_np(head), as_np(ref)
        if h.shape == r.shape and np.array_equal(h[:1], r[:1]) and np.array_equal(h, r):
            drop_keys.add(head_key)

    if family == "t5":
        drop_tied_duplicate("lm_head.weight", "shared.weight")
    if family in ("llama", "mistral", "qwen2", "gemma", "gemma2"):
        # gemma always ties; small qwen2/llama variants often do.
        drop_tied_duplicate("lm_head.weight", "model.embed_tokens.weight")

    for raw_key, raw_value in state_dict.items():
        if raw_key in drop_keys:
            continue
        key = _strip_prefix(raw_key, family)
        member = _match_expert(key, family)
        if member:
            # HF per-expert Linear is (out, in); batched einsum wants
            # (in, out) per expert -> transpose, then stack on E below.
            expert_parts.setdefault(member[0], {})[member[1]] = as_np(raw_value).T
            continue
        for hf_re, _, _, ours_t, op in rules:
            match = hf_re.match(key)
            if match:
                flat[_fill(ours_t, match)] = _apply_op(as_np(raw_value), op)
                break
        else:
            if strict and not _SKIPPABLE.search(key):
                raise KeyError(f"no conversion rule for HF key {raw_key!r} ({family})")
    for ours, parts in expert_parts.items():
        # The router's output width is the authoritative expert count — a
        # truncated shard set missing the *tail* experts would otherwise
        # stack a silently-too-small tensor.
        router_key = ours.rsplit("/experts/", 1)[0] + "/router"
        n_experts = flat[router_key].shape[1] if router_key in flat else max(parts) + 1
        missing = set(range(n_experts)) - set(parts)
        if missing:
            raise KeyError(f"missing experts {sorted(missing)} for {ours}")
        flat[ours] = np.stack([parts[e] for e in sorted(parts)])
    return _nest(flat)


def export_hf_state_dict(params: dict, family: str, *, prefix: str = "",
                         config=None, dtype=None) -> dict:
    """Our param pytree -> flat HF-named state dict (numpy, torch layouts).

    Inverse of :func:`convert_hf_state_dict`; raises on any param with no
    rule so checkpoints cannot silently lose weights. ``prefix`` lets callers
    re-add a wrapper scope (e.g. ``"transformer."`` for GPT-2). ``config``
    is required for families whose export is shape-ambiguous (vit: the conv
    kernel's (channels, patch, patch) factorization). ``dtype`` downcasts
    every floating tensor at export time (the reference's
    ``zero3_save_16bit_model`` capability: train in full precision, publish
    bf16/fp16 weights)."""
    if family not in _COMPILED:
        raise ValueError(f"unsupported family {family!r}; supported: {sorted(_COMPILED)}")
    rules = _COMPILED[family]
    out: dict[str, np.ndarray] = {}
    flat_params = _flatten(params)
    # Gated T5 trees (intermediate_gate present) must export the activated
    # projection as wi_0, not v1.0's wi — the first-match rule can't know.
    t5_gated = family == "t5" and any("intermediate_gate" in k for k in flat_params)
    for key, value in flat_params.items():
        stacked = re.match(r"^layers_(\d+)/mlp/(\w+)/(\w+)$", key)
        convention = stacked and next(
            (c for c in _EXPERT_CONVENTIONS.get(family, ()) if c[3] == stacked.group(2)), None)
        if convention:
            _, tok_to_name, hf_key_for, _ = convention
            w = {v: k for k, v in tok_to_name.items()}[stacked.group(3)]
            for e in range(value.shape[0]):
                out[prefix + hf_key_for(stacked.group(1), e, w)] = \
                    np.ascontiguousarray(value[e].T)
            continue
        for _, ours_re, hf_t, _, op in rules:
            match = ours_re.match(key)
            if match:
                hf_key = _fill(hf_t, match)
                if t5_gated and hf_key.endswith(".DenseReluDense.wi.weight"):
                    hf_key = hf_key.replace(".wi.weight", ".wi_0.weight")
                if op == "cf":
                    # [in*p*p, out] -> [out, in, p, p]: the factorization
                    # needs the config (shape alone is ambiguous).
                    if config is None:
                        raise ValueError(
                            f"exporting {key!r} needs config= (conv kernel "
                            "channel/patch factorization)")
                    c, p = config.num_channels, config.patch_size
                    out[prefix + hf_key] = np.ascontiguousarray(
                        value.T.reshape(value.shape[1], c, p, p))
                else:
                    out[prefix + hf_key] = _apply_op(value, op)
                break
        else:
            raise KeyError(f"no export rule for param {key!r} ({family})")
    if dtype is not None:
        dt = np.dtype(dtype)  # accepts "bfloat16" via ml_dtypes

        def is_float(v):
            return (np.issubdtype(v.dtype, np.floating)
                    or v.dtype.name == "bfloat16")

        out = {k: (v.astype(dt) if is_float(v) else v) for k, v in out.items()}
    return out


def load_hf_checkpoint(
    checkpoint_dir: str, family: Optional[str] = None, config=None, dtype=None,
):
    """Load an HF-format checkpoint directory into (config, params).

    Reads ``config.json`` (family autodetection + config build) and the
    safetensors weights (single file, or sharded via
    ``model.safetensors.index.json``) — no torch involved.
    """
    from safetensors import safe_open

    from ..big_modeling import _checkpoint_shards

    config_path = os.path.join(checkpoint_dir, "config.json")
    hf_config = {}
    if os.path.exists(config_path):
        with open(config_path) as f:
            hf_config = json.load(f)
    if family is None:
        family = detect_family(hf_config)
    if config is None:
        config = config_from_hf(hf_config, family)
    state_dict = {}
    for shard_path, keys in _checkpoint_shards(checkpoint_dir):
        with safe_open(shard_path, framework="numpy") as f:
            for key in keys:
                tensor = f.get_tensor(key)
                # Cast at read time: casting after conversion would hold
                # three full-size copies of the model in host RAM at peak.
                state_dict[key] = tensor if dtype is None else tensor.astype(dtype)
    return config, convert_hf_state_dict(state_dict, family)
