"""HF Transformers weight-bridge parity tests.

For each family: build a *tiny* randomly-initialized HF torch model (no
downloads), convert its state dict with ``convert_hf_state_dict``, run both
models on the same inputs, and compare logits. This is the strongest
possible check of the name/layout mapping — any transposed kernel, swapped
norm, or misrouted projection shows up as a numeric mismatch.

Round-trip (export_hf_state_dict) is checked to be lossless.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

import jax.numpy as jnp  # noqa: E402

from accelerate_tpu.utils.hf_interop import (  # noqa: E402
    config_from_hf,
    convert_hf_state_dict,
    detect_family,
    export_hf_state_dict,
    load_hf_checkpoint,
)

TOL = dict(atol=2e-4, rtol=2e-3)


def _logits_close(ours, theirs, **overrides):
    tol = {**TOL, **overrides}
    np.testing.assert_allclose(
        np.asarray(ours, np.float32), theirs.detach().numpy().astype(np.float32), **tol)


def _roundtrip(params, family, hf_sd, prefix=""):
    """export o convert must reproduce every converted param exactly."""
    exported = export_hf_state_dict(params, family, prefix=prefix)
    back = convert_hf_state_dict(exported, family)
    from accelerate_tpu.utils.hf_interop import _flatten

    flat, flat_back = _flatten(params), _flatten(back)
    assert set(flat) == set(flat_back)
    for key in flat:
        np.testing.assert_array_equal(flat[key], flat_back[key], err_msg=key)
    # dtype= publishes downcast weights (zero3_save_16bit_model parity):
    # every float tensor converts, nothing else changes.
    half = export_hf_state_dict(params, family, prefix=prefix, dtype="bfloat16")
    assert set(half) == set(exported)
    for key, v in half.items():
        full = np.asarray(exported[key])
        if np.issubdtype(full.dtype, np.floating) or full.dtype.name == "bfloat16":
            assert np.asarray(v).dtype.name == "bfloat16", key
        else:
            assert np.asarray(v).dtype == full.dtype, key


class TestLlama:
    def _pair(self):
        hf_cfg = transformers.LlamaConfig(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=64, rms_norm_eps=1e-5, tie_word_embeddings=False)
        torch.manual_seed(0)
        with torch.no_grad():
            hf = transformers.LlamaForCausalLM(hf_cfg).eval()
        cfg = config_from_hf(hf_cfg.to_dict())
        assert cfg.num_key_value_heads == 2 and cfg.hidden_size == 32
        from accelerate_tpu.models.llama import LlamaForCausalLM

        cfg.use_flash_attention = False
        params = convert_hf_state_dict(hf.state_dict(), "llama", strict=True)
        return hf, LlamaForCausalLM(cfg), params

    def test_forward_parity(self):
        hf, model, params = self._pair()
        ids = np.arange(24, dtype=np.int64).reshape(2, 12) % 128
        ours = model.apply({"params": params}, jnp.asarray(ids, jnp.int32))
        with torch.no_grad():
            theirs = hf(torch.from_numpy(ids)).logits
        _logits_close(ours, theirs)

    def test_roundtrip(self):
        hf, _, params = self._pair()
        _roundtrip(params, "llama", hf.state_dict())

    def test_repetition_penalty_matches_hf(self):
        """CTRL-rule penalty over prompt+generated tokens, greedy — must
        change the output AND match transformers exactly."""
        from accelerate_tpu.generation import generate

        hf, model, params = self._pair()
        ids = (np.arange(10, dtype=np.int64)[None] * 3) % 128
        plain = np.asarray(generate(model, params, jnp.asarray(ids, jnp.int32),
                                    max_new_tokens=8, cache_dtype=jnp.float32))
        for penalty in (1.8, 0.05):  # suppress repeats / strongly boost them
            ours = np.asarray(generate(model, params, jnp.asarray(ids, jnp.int32),
                                       max_new_tokens=8, repetition_penalty=penalty,
                                       cache_dtype=jnp.float32))
            with torch.no_grad():
                theirs = hf.generate(torch.from_numpy(ids).long(), max_new_tokens=8,
                                     do_sample=False, repetition_penalty=penalty)
            np.testing.assert_array_equal(ours, theirs.numpy(), err_msg=str(penalty))
        # The boosting penalty must force repeated tokens != plain greedy.
        assert not np.array_equal(ours, plain)

    def test_llama3_rope_scaling_parity(self):
        """Llama-3.1-style checkpoints carry rope_scaling; logits must match
        HF's scaled-RoPE implementation, not silently use vanilla RoPE."""
        rope_scaling = {"rope_type": "llama3", "factor": 8.0,
                        "low_freq_factor": 1.0, "high_freq_factor": 4.0,
                        "original_max_position_embeddings": 32}
        hf_cfg = transformers.LlamaConfig(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=64, rope_theta=10000.0,
            rope_scaling=rope_scaling, tie_word_embeddings=False)
        torch.manual_seed(0)
        with torch.no_grad():
            hf = transformers.LlamaForCausalLM(hf_cfg).eval()
        cfg = config_from_hf(hf_cfg.to_dict())
        assert cfg.rope_scaling is not None
        cfg.use_flash_attention = False
        from accelerate_tpu.models.llama import LlamaForCausalLM

        params = convert_hf_state_dict(hf.state_dict(), "llama", strict=True)
        ids = np.arange(40, dtype=np.int64).reshape(2, 20) % 128
        ours = LlamaForCausalLM(cfg).apply({"params": params}, jnp.asarray(ids, jnp.int32))
        with torch.no_grad():
            theirs = hf(torch.from_numpy(ids)).logits
        _logits_close(ours, theirs)

    def test_generate_with_rope_scaling_config(self):
        """Dict-valued config fields (rope_scaling) must not break the
        generate executable cache (hashability)."""
        from accelerate_tpu.generation import generate
        from accelerate_tpu.models.llama import LlamaConfig, LlamaForCausalLM

        cfg = LlamaConfig.tiny(use_flash_attention=False,
                               rope_scaling={"rope_type": "linear", "factor": 2.0})
        model = LlamaForCausalLM(cfg)
        params = model.init_params(jax.random.PRNGKey(0))
        out = generate(model, params, jnp.zeros((1, 4), jnp.int32), max_new_tokens=3)
        assert out.shape == (1, 7)

    def test_unsupported_rope_type_rejected(self):
        with pytest.raises(NotImplementedError, match="rope_scaling"):
            config_from_hf({"model_type": "llama",
                            "rope_scaling": {"rope_type": "yarn", "factor": 4.0}})

    def test_unsupported_hidden_act_rejected(self):
        with pytest.raises(NotImplementedError, match="hidden_act"):
            config_from_hf({"model_type": "llama", "hidden_act": "gelu"})

    def test_checkpoint_dir_load(self, tmp_path):
        import json

        from safetensors.numpy import save_file

        hf, model, params = self._pair()
        sd = {k: v.numpy() for k, v in hf.state_dict().items()}
        save_file(sd, str(tmp_path / "model.safetensors"))
        (tmp_path / "config.json").write_text(json.dumps(hf.config.to_dict()))
        cfg2, params2 = load_hf_checkpoint(str(tmp_path))
        assert cfg2.num_hidden_layers == 2
        from accelerate_tpu.utils.hf_interop import _flatten

        for key, val in _flatten(params).items():
            np.testing.assert_array_equal(val, _flatten(params2)[key], err_msg=key)


class TestGPT2:
    def _pair(self):
        hf_cfg = transformers.GPT2Config(
            vocab_size=96, n_embd=32, n_layer=2, n_head=4, n_positions=64,
            resid_pdrop=0.0, embd_pdrop=0.0, attn_pdrop=0.0)
        torch.manual_seed(0)
        with torch.no_grad():
            hf = transformers.GPT2LMHeadModel(hf_cfg).eval()
        cfg = config_from_hf(hf_cfg.to_dict())
        cfg.use_flash_attention = False
        from accelerate_tpu.models.gpt2 import GPT2LMHeadModel

        params = convert_hf_state_dict(hf.state_dict(), "gpt2", strict=True)
        return hf, GPT2LMHeadModel(cfg), params

    def test_forward_parity(self):
        hf, model, params = self._pair()
        ids = (np.arange(20, dtype=np.int64).reshape(2, 10) * 3) % 96
        ours = model.apply({"params": params}, jnp.asarray(ids, jnp.int32))
        with torch.no_grad():
            theirs = hf(torch.from_numpy(ids)).logits
        _logits_close(ours, theirs)

    @pytest.mark.nightly  # llama/t5 roundtrips stay default
    def test_roundtrip(self):
        hf, _, params = self._pair()
        _roundtrip(params, "gpt2", hf.state_dict(), prefix="transformer.")


class TestGPTJ:
    """GPT-J: interleaved partial rope + single-LN parallel residual +
    untied biased head (one of the reference's benchmark families)."""

    def _pair(self):
        hf_cfg = transformers.GPTJConfig(
            vocab_size=96, n_embd=32, n_layer=2, n_head=4, n_positions=64,
            rotary_dim=4, resid_pdrop=0.0, embd_pdrop=0.0, attn_pdrop=0.0)
        torch.manual_seed(0)
        with torch.no_grad():
            hf = transformers.GPTJForCausalLM(hf_cfg).eval()
        assert detect_family(hf_cfg.to_dict()) == "gptj"
        cfg = config_from_hf(hf_cfg.to_dict())
        assert cfg.rotary_dim == 4
        cfg.use_flash_attention = False
        from accelerate_tpu.models.gptj import GPTJForCausalLM

        params = convert_hf_state_dict(hf.state_dict(), "gptj", strict=True)
        return hf, GPTJForCausalLM(cfg), params

    def test_forward_parity(self):
        hf, model, params = self._pair()
        ids = (np.arange(20, dtype=np.int64).reshape(2, 10) * 3) % 96
        ours = model.apply({"params": params}, jnp.asarray(ids, jnp.int32))
        with torch.no_grad():
            theirs = hf(torch.from_numpy(ids)).logits
        _logits_close(ours, theirs)

    def test_greedy_decode_parity(self):
        hf, model, params = self._pair()
        from accelerate_tpu.generation import generate

        ids = np.array([[5, 17, 3, 29, 11]], dtype=np.int64)
        ours = generate(model, params, jnp.asarray(ids, jnp.int32), max_new_tokens=8,
                        cache_dtype=jnp.float32)
        with torch.no_grad():
            theirs = hf.generate(torch.from_numpy(ids), max_new_tokens=8,
                                 do_sample=False)
        np.testing.assert_array_equal(np.asarray(ours), theirs.numpy())

    @pytest.mark.nightly  # llama/t5 roundtrips stay default
    def test_roundtrip(self):
        hf, _, params = self._pair()
        _roundtrip(params, "gptj", hf.state_dict(), prefix="transformer.")


class TestBloom:
    """BLOOM: ALiBi position bias (no position embeddings at all), fused
    per-head QKV with biases, embedding LayerNorm, tanh-gelu MLP, tied head
    — the ALiBi architecture class of the HF bridge."""

    def _pair(self):
        hf_cfg = transformers.BloomConfig(
            vocab_size=96, hidden_size=32, n_layer=2, n_head=4,
            hidden_dropout=0.0, attention_dropout=0.0)
        torch.manual_seed(0)
        with torch.no_grad():
            hf = transformers.BloomForCausalLM(hf_cfg).eval()
        assert detect_family(hf_cfg.to_dict()) == "bloom"
        cfg = config_from_hf(hf_cfg.to_dict())
        assert cfg.num_attention_heads == 4 and cfg.hidden_size == 32
        from accelerate_tpu.models.bloom import BloomForCausalLM

        params = convert_hf_state_dict(hf.state_dict(), "bloom", strict=True)
        return hf, BloomForCausalLM(cfg), params

    def test_forward_parity(self):
        hf, model, params = self._pair()
        ids = (np.arange(20, dtype=np.int64).reshape(2, 10) * 3) % 96
        ours = model.apply({"params": params}, jnp.asarray(ids, jnp.int32))
        with torch.no_grad():
            theirs = hf(torch.from_numpy(ids)).logits
        _logits_close(ours, theirs)

    def test_greedy_decode_parity(self):
        hf, model, params = self._pair()
        from accelerate_tpu.generation import generate

        ids = np.array([[5, 17, 3, 29, 11]], dtype=np.int64)
        ours = generate(model, params, jnp.asarray(ids, jnp.int32), max_new_tokens=8,
                        cache_dtype=jnp.float32)
        with torch.no_grad():
            theirs = hf.generate(torch.from_numpy(ids), max_new_tokens=8,
                                 do_sample=False)
        np.testing.assert_array_equal(np.asarray(ours), theirs.numpy())

    def test_alibi_slopes_match_hf(self):
        from transformers.models.bloom.modeling_bloom import build_alibi_tensor

        from accelerate_tpu.models.bloom import alibi_slopes

        for n in (4, 6, 16):  # incl. a non-power-of-two head count
            mask = torch.ones((1, 5))
            hf_alibi = build_alibi_tensor(mask, n, torch.float32)  # [n, 1, 5]
            # HF's tensor is slopes x position; position 1 column = slopes.
            np.testing.assert_allclose(
                np.asarray(alibi_slopes(n)), hf_alibi[:, 0, 1].numpy(), rtol=1e-6)

    @pytest.mark.nightly  # llama/t5 roundtrips stay default
    def test_roundtrip(self):
        hf, _, params = self._pair()
        _roundtrip(params, "bloom", hf.state_dict(), prefix="transformer.")


class TestGPTNeoX:
    """GPT-NeoX: fused per-head QKV + partial split-half rope + parallel
    residual + untied head (one of the reference's benchmark families)."""

    def _pair(self, parallel=True):
        hf_cfg = transformers.GPTNeoXConfig(
            vocab_size=96, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4,
            max_position_embeddings=64, rotary_pct=0.5,
            use_parallel_residual=parallel,
            hidden_dropout=0.0, attention_dropout=0.0)
        torch.manual_seed(0)
        with torch.no_grad():
            hf = transformers.GPTNeoXForCausalLM(hf_cfg).eval()
        assert detect_family(hf_cfg.to_dict()) == "gpt_neox"
        cfg = config_from_hf(hf_cfg.to_dict())
        assert cfg.rotary_ndims == 4 and cfg.use_parallel_residual is parallel
        cfg.use_flash_attention = False
        from accelerate_tpu.models.gpt_neox import GPTNeoXForCausalLM

        params = convert_hf_state_dict(hf.state_dict(), "gpt_neox", strict=True)
        return hf, GPTNeoXForCausalLM(cfg), params

    @pytest.mark.parametrize("parallel", [
        pytest.param(True, marks=pytest.mark.nightly), False,
    ])
    def test_forward_parity(self, parallel):
        hf, model, params = self._pair(parallel)
        ids = (np.arange(20, dtype=np.int64).reshape(2, 10) * 3) % 96
        ours = model.apply({"params": params}, jnp.asarray(ids, jnp.int32))
        with torch.no_grad():
            theirs = hf(torch.from_numpy(ids)).logits
        _logits_close(ours, theirs)

    def test_greedy_decode_parity(self):
        hf, model, params = self._pair()
        from accelerate_tpu.generation import generate

        ids = np.array([[5, 17, 3, 29, 11]], dtype=np.int64)
        ours = generate(model, params, jnp.asarray(ids, jnp.int32), max_new_tokens=8,
                        cache_dtype=jnp.float32)
        with torch.no_grad():
            theirs = hf.generate(torch.from_numpy(ids), max_new_tokens=8,
                                 do_sample=False)
        np.testing.assert_array_equal(np.asarray(ours), theirs.numpy())

    @pytest.mark.nightly  # llama/t5 roundtrips stay default
    def test_roundtrip(self):
        hf, _, params = self._pair()
        _roundtrip(params, "gpt_neox", hf.state_dict(), prefix="gpt_neox.")


class TestOPT:
    """OPT: offset learned positions + ReLU pre-LN decoder (one of the
    reference's benchmark families)."""

    def _pair(self):
        hf_cfg = transformers.OPTConfig(
            vocab_size=96, hidden_size=32, ffn_dim=64, num_hidden_layers=2,
            num_attention_heads=4, max_position_embeddings=64,
            do_layer_norm_before=True, dropout=0.0, attention_dropout=0.0,
            word_embed_proj_dim=32)
        torch.manual_seed(0)
        with torch.no_grad():
            hf = transformers.OPTForCausalLM(hf_cfg).eval()
        assert detect_family(hf_cfg.to_dict()) == "opt"
        cfg = config_from_hf(hf_cfg.to_dict())
        assert cfg.intermediate_size == 64 and cfg.activation == "relu"
        cfg.use_flash_attention = False
        from accelerate_tpu.models.opt import OPTForCausalLM

        params = convert_hf_state_dict(hf.state_dict(), "opt", strict=True)
        return hf, OPTForCausalLM(cfg), params

    def test_forward_parity(self):
        hf, model, params = self._pair()
        ids = (np.arange(20, dtype=np.int64).reshape(2, 10) * 3) % 96
        ours = model.apply({"params": params}, jnp.asarray(ids, jnp.int32))
        with torch.no_grad():
            theirs = hf(torch.from_numpy(ids)).logits
        _logits_close(ours, theirs)

    def test_greedy_decode_parity(self):
        """OPT's config carries eos_token_id=2; compare up to and including
        HF's first EOS (past it HF stops, ours repeats EOS — static shapes)."""
        hf, model, params = self._pair()
        from accelerate_tpu.generation import generate

        ids = np.array([[5, 17, 3, 29, 11]], dtype=np.int64)
        ours = np.asarray(generate(model, params, jnp.asarray(ids, jnp.int32),
                                   max_new_tokens=8, eos_token_id=2,
                                   cache_dtype=jnp.float32))
        with torch.no_grad():
            theirs = hf.generate(torch.from_numpy(ids), max_new_tokens=8,
                                 do_sample=False).numpy()
        for row_ours, row_hf in zip(ours, theirs):
            hf_eos = np.where(row_hf == 2)[0]
            stop = (hf_eos[0] + 1) if hf_eos.size else len(row_hf)
            np.testing.assert_array_equal(row_ours[:stop], row_hf[:stop])
            if hf_eos.size:
                assert (row_ours[hf_eos[0]:] == 2).all()

    @pytest.mark.nightly  # llama/t5 roundtrips stay default
    def test_roundtrip(self):
        hf, _, params = self._pair()
        _roundtrip(params, "opt", hf.state_dict(), prefix="model.decoder.")

    def test_post_ln_variant_rejected(self):
        with pytest.raises(NotImplementedError, match="post-LN"):
            config_from_hf({"model_type": "opt", "do_layer_norm_before": False})


class TestPhi:
    """Phi: single-LN parallel residual + partial split-half rope + GQA +
    untied biased head (the reference's distributed-inference example
    family)."""

    def _pair(self):
        hf_cfg = transformers.PhiConfig(
            vocab_size=96, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=64, partial_rotary_factor=0.5,
            resid_pdrop=0.0, embd_pdrop=0.0, attention_dropout=0.0)
        torch.manual_seed(0)
        with torch.no_grad():
            hf = transformers.PhiForCausalLM(hf_cfg).eval()
        assert detect_family(hf_cfg.to_dict()) == "phi"
        cfg = config_from_hf(hf_cfg.to_dict())
        assert cfg.rotary_ndims == 4 and cfg.num_key_value_heads == 2
        cfg.use_flash_attention = False
        from accelerate_tpu.models.phi import PhiForCausalLM

        params = convert_hf_state_dict(hf.state_dict(), "phi", strict=True)
        return hf, PhiForCausalLM(cfg), params

    def test_forward_parity(self):
        hf, model, params = self._pair()
        ids = (np.arange(20, dtype=np.int64).reshape(2, 10) * 3) % 96
        ours = model.apply({"params": params}, jnp.asarray(ids, jnp.int32))
        with torch.no_grad():
            theirs = hf(torch.from_numpy(ids)).logits
        _logits_close(ours, theirs)

    def test_greedy_decode_parity(self):
        hf, model, params = self._pair()
        from accelerate_tpu.generation import generate

        ids = np.array([[5, 17, 3, 29, 11]], dtype=np.int64)
        ours = generate(model, params, jnp.asarray(ids, jnp.int32), max_new_tokens=8,
                        cache_dtype=jnp.float32)
        with torch.no_grad():
            theirs = hf.generate(torch.from_numpy(ids), max_new_tokens=8,
                                 do_sample=False)
        np.testing.assert_array_equal(np.asarray(ours), theirs.numpy())

    @pytest.mark.nightly  # llama/t5 roundtrips stay default
    def test_roundtrip(self):
        hf, _, params = self._pair()
        _roundtrip(params, "phi", hf.state_dict(), prefix="model.")

    def test_qk_layernorm_rejected(self):
        with pytest.raises(NotImplementedError, match="qk_layernorm"):
            config_from_hf({"model_type": "phi", "qk_layernorm": True})


class TestBert:
    def _pair(self):
        hf_cfg = transformers.BertConfig(
            vocab_size=120, hidden_size=32, num_hidden_layers=2,
            num_attention_heads=4, intermediate_size=64,
            max_position_embeddings=64, type_vocab_size=2,
            hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
            num_labels=3)
        torch.manual_seed(0)
        with torch.no_grad():
            hf = transformers.BertForSequenceClassification(hf_cfg).eval()
        cfg = config_from_hf(hf_cfg.to_dict())
        cfg.num_labels = 3
        cfg.hidden_dropout_prob = 0.0
        cfg.use_flash_attention = False
        from accelerate_tpu.models.bert import BertForSequenceClassification

        params = convert_hf_state_dict(hf.state_dict(), "bert", strict=True)
        return hf, BertForSequenceClassification(cfg), params

    def test_forward_parity(self):
        hf, model, params = self._pair()
        ids = (np.arange(16, dtype=np.int64).reshape(2, 8) * 5) % 120
        mask = np.ones((2, 8), np.int64)
        mask[1, 5:] = 0
        ours = model.apply({"params": params}, jnp.asarray(ids, jnp.int32),
                           attention_mask=jnp.asarray(mask, jnp.int32))
        with torch.no_grad():
            theirs = hf(torch.from_numpy(ids), attention_mask=torch.from_numpy(mask)).logits
        _logits_close(ours, theirs)

    @pytest.mark.nightly  # llama/t5 roundtrips stay default
    def test_roundtrip(self):
        hf, _, params = self._pair()
        _roundtrip(params, "bert", hf.state_dict(), prefix="bert.")


class TestT5:
    def _pair(self):
        hf_cfg = transformers.T5Config(
            vocab_size=100, d_model=32, d_ff=64, d_kv=8, num_layers=2,
            num_heads=4, relative_attention_num_buckets=8,
            relative_attention_max_distance=20, dropout_rate=0.0,
            feed_forward_proj="relu", tie_word_embeddings=True)
        torch.manual_seed(0)
        with torch.no_grad():
            hf = transformers.T5ForConditionalGeneration(hf_cfg).eval()
        cfg = config_from_hf(hf_cfg.to_dict())
        cfg.dropout_rate = 0.0
        from accelerate_tpu.models.t5 import T5ForConditionalGeneration

        params = convert_hf_state_dict(hf.state_dict(), "t5", strict=True)
        return hf, T5ForConditionalGeneration(cfg), params

    def test_forward_parity(self):
        hf, model, params = self._pair()
        src = (np.arange(16, dtype=np.int64).reshape(2, 8) * 7) % 100
        tgt = (np.arange(12, dtype=np.int64).reshape(2, 6) * 3) % 100
        ours = model.apply({"params": params}, jnp.asarray(src, jnp.int32),
                           jnp.asarray(tgt, jnp.int32))
        with torch.no_grad():
            theirs = hf(input_ids=torch.from_numpy(src),
                        decoder_input_ids=torch.from_numpy(tgt)).logits
        _logits_close(ours, theirs)

    def test_roundtrip(self):
        hf, _, params = self._pair()
        _roundtrip(params, "t5", hf.state_dict())

    def test_flan_style_gated_untied_parity(self):
        """t5-v1.1/flan: gated-gelu MLP + untied lm_head, no 1/sqrt(d)
        head rescale."""
        hf_cfg = transformers.T5Config(
            vocab_size=100, d_model=32, d_ff=64, d_kv=8, num_layers=2,
            num_heads=4, relative_attention_num_buckets=8,
            relative_attention_max_distance=20, dropout_rate=0.0,
            feed_forward_proj="gated-gelu", tie_word_embeddings=False)
        torch.manual_seed(0)
        with torch.no_grad():
            hf = transformers.T5ForConditionalGeneration(hf_cfg).eval()
        cfg = config_from_hf(hf_cfg.to_dict())
        assert cfg.feed_forward_proj == "gated-gelu" and not cfg.tie_word_embeddings
        cfg.dropout_rate = 0.0
        from accelerate_tpu.models.t5 import T5ForConditionalGeneration

        params = convert_hf_state_dict(hf.state_dict(), "t5", strict=True)
        src = (np.arange(16, dtype=np.int64).reshape(2, 8) * 7) % 100
        tgt = (np.arange(12, dtype=np.int64).reshape(2, 6) * 3) % 100
        ours = T5ForConditionalGeneration(cfg).apply(
            {"params": params}, jnp.asarray(src, jnp.int32), jnp.asarray(tgt, jnp.int32))
        with torch.no_grad():
            theirs = hf(input_ids=torch.from_numpy(src),
                        decoder_input_ids=torch.from_numpy(tgt)).logits
        _logits_close(ours, theirs)
        _roundtrip(params, "t5", hf.state_dict())


class TestMixtral:
    def _pair(self):
        hf_cfg = transformers.MixtralConfig(
            vocab_size=96, hidden_size=32, intermediate_size=48,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            num_local_experts=4, num_experts_per_tok=2,
            max_position_embeddings=64, rms_norm_eps=1e-5,
            router_jitter_noise=0.0, attention_dropout=0.0,
            tie_word_embeddings=False)
        torch.manual_seed(0)
        with torch.no_grad():
            hf = transformers.MixtralForCausalLM(hf_cfg).eval()
        cfg = config_from_hf(hf_cfg.to_dict())
        assert detect_family(hf_cfg.to_dict()) == "mixtral"
        assert cfg.num_experts == 4 and cfg.top_k == 2
        # No-drop capacity so sparse dispatch is exact (matches HF's dense
        # gather over selected experts).
        cfg.capacity_factor = float(cfg.num_experts)
        cfg.use_flash_attention = False
        from accelerate_tpu.models.mixtral import MixtralForCausalLM

        params = convert_hf_state_dict(hf.state_dict(), "mixtral", strict=True)
        return hf, MixtralForCausalLM(cfg), params

    def test_forward_parity(self):
        hf, model, params = self._pair()
        ids = (np.arange(16, dtype=np.int64).reshape(2, 8) * 5) % 96
        out = model.apply({"params": params}, jnp.asarray(ids, jnp.int32))
        ours = out[0] if isinstance(out, tuple) else out
        with torch.no_grad():
            theirs = hf(torch.from_numpy(ids)).logits
        _logits_close(ours, theirs, atol=5e-4)

    @pytest.mark.nightly  # llama/t5 roundtrips stay default
    def test_roundtrip(self):
        hf, _, params = self._pair()
        _roundtrip(params, "mixtral", hf.state_dict())


class TestViT:
    def _pair(self):
        hf_cfg = transformers.ViTConfig(
            image_size=32, patch_size=8, num_channels=3, hidden_size=32,
            num_hidden_layers=2, num_attention_heads=4, intermediate_size=64,
            hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0)
        hf_cfg.id2label = {0: "a", 1: "b", 2: "c"}
        torch.manual_seed(0)
        with torch.no_grad():
            hf = transformers.ViTForImageClassification(hf_cfg).eval()
        cfg = config_from_hf({**hf_cfg.to_dict(), "model_type": "vit"})
        assert cfg.num_labels == 3 and cfg.patch_size == 8
        from accelerate_tpu.models.vit import ViTForImageClassification

        params = convert_hf_state_dict(hf.state_dict(), "vit", strict=True)
        return hf, ViTForImageClassification(cfg), params, cfg

    def test_forward_parity(self):
        hf, model, params, _ = self._pair()
        images = np.random.RandomState(0).randn(2, 3, 32, 32).astype(np.float32)
        # ours: NHWC, HF: NCHW
        ours = model.apply({"params": params},
                           jnp.asarray(images.transpose(0, 2, 3, 1)))
        with torch.no_grad():
            theirs = hf(torch.from_numpy(images)).logits
        _logits_close(ours, theirs)

    def test_roundtrip(self):
        # Stays DEFAULT (unlike the other family roundtrips): the only
        # test of export_hf_state_dict's config= success path.
        hf, _, params, cfg = self._pair()
        exported = export_hf_state_dict(params, "vit", prefix="", config=cfg)
        back = convert_hf_state_dict(exported, "vit")
        from accelerate_tpu.utils.hf_interop import _flatten

        flat, flat_back = _flatten(params), _flatten(back)
        assert set(flat) == set(flat_back)
        for key in flat:
            np.testing.assert_array_equal(flat[key], flat_back[key], err_msg=key)

    def test_export_without_config_rejected(self):
        _, _, params, _ = self._pair()
        with pytest.raises(ValueError, match="needs config"):
            export_hf_state_dict(params, "vit")


class TestBeamSearch:
    def _pair(self):
        hf_cfg = transformers.LlamaConfig(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=64, tie_word_embeddings=False)
        torch.manual_seed(3)
        with torch.no_grad():
            hf = transformers.LlamaForCausalLM(hf_cfg).eval()
        cfg = config_from_hf(hf_cfg.to_dict())
        cfg.use_flash_attention = False
        from accelerate_tpu.models.llama import LlamaForCausalLM

        params = convert_hf_state_dict(hf.state_dict(), "llama", strict=True)
        return hf, LlamaForCausalLM(cfg), params

    def test_matches_hf_beam_search(self):
        from accelerate_tpu.generation import beam_search_generate

        hf, model, params = self._pair()
        ids = (np.arange(12, dtype=np.int64).reshape(2, 6) * 11) % 128
        ours = beam_search_generate(model, params, jnp.asarray(ids, jnp.int32),
                                    max_new_tokens=6, num_beams=4,
                                    cache_dtype=jnp.float32)
        with torch.no_grad():
            theirs = hf.generate(torch.from_numpy(ids), max_new_tokens=6,
                                 num_beams=4, do_sample=False,
                                 min_new_tokens=6, length_penalty=1.0)
        np.testing.assert_array_equal(np.asarray(ours), theirs.numpy())

    def test_beam_bucket_shares_one_executable_across_lengths(self):
        """Beam search shares its ONE compiled run per 128-bucket: nearby
        prompt lengths must not retrace, and each stays HF-identical."""
        from accelerate_tpu.generation import _compiled_beam, beam_search_generate

        hf, model, params = self._pair()
        sizes = None
        for S in (3, 6, 10):
            ids = (np.arange(2 * S, dtype=np.int64).reshape(2, S) * 11 + 2) % 128
            ours = beam_search_generate(model, params, jnp.asarray(ids, jnp.int32),
                                        max_new_tokens=5, num_beams=3,
                                        cache_dtype=jnp.float32)
            with torch.no_grad():
                theirs = hf.generate(torch.from_numpy(ids), max_new_tokens=5,
                                     num_beams=3, do_sample=False,
                                     min_new_tokens=5, length_penalty=1.0)
            np.testing.assert_array_equal(np.asarray(ours), theirs.numpy())
            run = _compiled_beam(model, 5, 3, None, 1.0, jnp.float32)
            now = run._cache_size()
            if sizes is None:
                sizes = now
            else:
                assert now == sizes, f"beam retraced across lengths: {sizes} -> {now}"

    def test_single_beam_equals_greedy(self):
        from accelerate_tpu.generation import beam_search_generate, generate

        hf, model, params = self._pair()
        ids = jnp.asarray((np.arange(8)[None] * 7) % 128, jnp.int32)
        beam = beam_search_generate(model, params, ids, max_new_tokens=5,
                                    num_beams=1, cache_dtype=jnp.float32)
        greedy = generate(model, params, ids, max_new_tokens=5,
                          cache_dtype=jnp.float32)
        np.testing.assert_array_equal(np.asarray(beam), np.asarray(greedy))

    def test_eos_freezes_beams(self):
        """With eos = the argmax first token, the best beam stops and pads
        with eos; shape stays static."""
        from accelerate_tpu.generation import beam_search_generate, generate

        hf, model, params = self._pair()
        ids = jnp.asarray((np.arange(8)[None] * 7) % 128, jnp.int32)
        greedy = np.asarray(generate(model, params, ids, max_new_tokens=5,
                                     cache_dtype=jnp.float32))
        eos = int(greedy[0, 8])  # force the greedy continuation to be eos
        out = np.asarray(beam_search_generate(
            model, params, ids, max_new_tokens=5, num_beams=3,
            eos_token_id=eos, cache_dtype=jnp.float32))
        assert out.shape == (1, 13)
        row = out[0, 8:]
        eos_positions = np.where(row == eos)[0]
        assert eos_positions.size > 0  # some beam finished
        first = eos_positions[0]
        # frozen: everything after the first eos is eos
        assert (row[first:] == eos).all()


class TestT5Generate:
    """Cached encoder-decoder decode vs HF greedy generate — validates the
    decoder self-attention cache, the absolute-position relative bias, and
    the precomputed cross K/V in one shot."""

    def _make(self, **cfg_over):
        base = dict(
            vocab_size=100, d_model=32, d_ff=64, d_kv=8, num_layers=2,
            num_heads=4, relative_attention_num_buckets=8,
            relative_attention_max_distance=20, dropout_rate=0.0,
            feed_forward_proj="relu", tie_word_embeddings=True,
            decoder_start_token_id=0, eos_token_id=1, pad_token_id=0)
        base.update(cfg_over)
        hf_cfg = transformers.T5Config(**base)
        torch.manual_seed(0)
        with torch.no_grad():
            hf = transformers.T5ForConditionalGeneration(hf_cfg).eval()
        cfg = config_from_hf(hf_cfg.to_dict())
        cfg.dropout_rate = 0.0
        from accelerate_tpu.models.t5 import T5ForConditionalGeneration

        params = convert_hf_state_dict(hf.state_dict(), "t5", strict=True)
        return hf, T5ForConditionalGeneration(cfg), params

    def test_encoder_bucket_shares_executables_across_src_lengths(self):
        """Nearby ENCODER lengths share one compiled (encode, prefill,
        decode) triple — the source is padded to its 128-bucket with the
        pads masked via attention_mask (cross-attention would otherwise
        attend them) — while staying token-identical to HF per length."""
        from accelerate_tpu.generation import _compiled_seq2seq, seq2seq_generate

        hf, model, params = self._make()
        sizes = None
        for S in (3, 8, 13):
            src = (np.arange(2 * S, dtype=np.int64).reshape(2, S) * 7) % 100
            ours = np.asarray(seq2seq_generate(
                model, params, jnp.asarray(src, jnp.int32), max_new_tokens=5,
                decoder_start_token_id=0, eos_token_id=1, min_new_tokens=5,
                cache_dtype=jnp.float32))
            with torch.no_grad():
                theirs = hf.generate(
                    torch.from_numpy(src), max_new_tokens=5, min_new_tokens=5,
                    do_sample=False, num_beams=1,
                    attention_mask=torch.ones_like(torch.from_numpy(src))).numpy()
            np.testing.assert_array_equal(ours, theirs)
            triple = _compiled_seq2seq(model, 5, 1, jnp.float32, None, 1.0, 5)
            now = tuple(f._cache_size() for f in triple)
            if sizes is None:
                sizes = now
            else:
                assert now == sizes, f"seq2seq retraced across src lengths: {sizes} -> {now}"

    @pytest.mark.parametrize("variant", [
        pytest.param("tied-relu", marks=pytest.mark.nightly), "flan",
    ])
    def test_cached_generate_matches_hf(self, variant):
        from accelerate_tpu.generation import seq2seq_generate

        over = {} if variant == "tied-relu" else dict(
            feed_forward_proj="gated-gelu", tie_word_embeddings=False)
        hf, model, params = self._make(**over)
        src = (np.arange(16, dtype=np.int64).reshape(2, 8) * 7) % 100
        # min_new_tokens on BOTH sides -> no early EOS anywhere, so the
        # whole [B, 1+T] arrays must be exactly equal (same-length rows).
        ours = np.asarray(seq2seq_generate(
            model, params, jnp.asarray(src, jnp.int32), max_new_tokens=7,
            decoder_start_token_id=0, eos_token_id=1, min_new_tokens=7,
            cache_dtype=jnp.float32))
        with torch.no_grad():
            # Explicit all-ones mask: src contains token 0, which HF's
            # generate would otherwise treat as padding (pad_token_id=0).
            theirs = hf.generate(torch.from_numpy(src),
                                 attention_mask=torch.ones_like(torch.from_numpy(src)),
                                 max_new_tokens=7, min_new_tokens=7,
                                 do_sample=False).numpy()
        np.testing.assert_array_equal(ours, theirs)

    def test_early_eos_parity(self):
        """No min_new_tokens: the EOS stop path itself — rows compare up to
        and including HF's first EOS (past it HF pads, ours repeats EOS)."""
        from accelerate_tpu.generation import seq2seq_generate

        hf, model, params = self._make()
        src = (np.arange(16, dtype=np.int64).reshape(2, 8) * 7) % 100
        ours = np.asarray(seq2seq_generate(
            model, params, jnp.asarray(src, jnp.int32), max_new_tokens=7,
            decoder_start_token_id=0, eos_token_id=1, cache_dtype=jnp.float32))
        with torch.no_grad():
            theirs = hf.generate(torch.from_numpy(src),
                                 attention_mask=torch.ones_like(torch.from_numpy(src)),
                                 max_new_tokens=7, do_sample=False).numpy()
        for row_ours, row_hf in zip(ours, theirs):
            hf_eos = np.where(row_hf == 1)[0]
            stop = (hf_eos[0] + 1) if hf_eos.size else len(row_hf)
            np.testing.assert_array_equal(row_ours[:stop], row_hf[:stop])
        # Stopped rows keep emitting EOS (static shape contract).
        for row_ours, row_hf in zip(ours, theirs):
            hf_eos = np.where(row_hf == 1)[0]
            if hf_eos.size:
                assert (row_ours[hf_eos[0]:] == 1).all()

    def test_min_new_tokens_boundary_decoder_only(self):
        """min_new < max on the decoder-only path: EOS must be allowed from
        exactly new token min+1 — an off-by-one diverges from HF."""
        from accelerate_tpu.generation import generate
        from accelerate_tpu.models.llama import LlamaForCausalLM

        torch.manual_seed(0)
        hf_cfg = transformers.LlamaConfig(
            vocab_size=64, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=64, tie_word_embeddings=False,
            eos_token_id=1, pad_token_id=0)
        with torch.no_grad():
            hf = transformers.LlamaForCausalLM(hf_cfg).eval()
        cfg = config_from_hf(hf_cfg.to_dict())
        cfg.use_flash_attention = False
        params = convert_hf_state_dict(hf.state_dict(), "llama", strict=True)
        ids = (np.arange(6, dtype=np.int64)[None] * 5) % 64
        for min_new in (1, 3, 5):
            ours = np.asarray(generate(
                LlamaForCausalLM(cfg), params, jnp.asarray(ids, jnp.int32),
                max_new_tokens=8, eos_token_id=1, min_new_tokens=min_new,
                cache_dtype=jnp.float32))
            with torch.no_grad():
                theirs = hf.generate(torch.from_numpy(ids).long(),
                                     attention_mask=torch.ones(1, 6).long(),
                                     max_new_tokens=8, min_new_tokens=min_new,
                                     do_sample=False).numpy()
            for row_ours, row_hf in zip(ours, theirs):
                hf_eos = np.where(row_hf == 1)[0]
                stop = (hf_eos[0] + 1) if hf_eos.size else len(row_hf)
                np.testing.assert_array_equal(row_ours[:stop], row_hf[:stop],
                                              err_msg=f"min_new={min_new}")

    def test_generate_routes_seq2seq(self):
        """supports_kv_cache(t5) is True, so generate() must work on it —
        it delegates to the seq2seq mechanics."""
        from accelerate_tpu.generation import generate, supports_kv_cache

        hf, model, params = self._make()
        assert supports_kv_cache(model)
        src = jnp.asarray((np.arange(8)[None] * 5) % 100, jnp.int32)
        out = generate(model, params, src, max_new_tokens=4)
        assert out.shape == (1, 5)  # start token + 4 generated

    def test_repetition_penalty_seq2seq_matches_hf(self):
        from accelerate_tpu.generation import seq2seq_generate

        hf, model, params = self._make()
        src = (np.arange(16, dtype=np.int64).reshape(2, 8) * 7) % 100
        ours = np.asarray(seq2seq_generate(
            model, params, jnp.asarray(src, jnp.int32), max_new_tokens=7,
            decoder_start_token_id=0, eos_token_id=1, repetition_penalty=1.7,
            cache_dtype=jnp.float32))
        with torch.no_grad():
            theirs = hf.generate(torch.from_numpy(src),
                                 attention_mask=torch.ones_like(torch.from_numpy(src)),
                                 max_new_tokens=7, do_sample=False,
                                 repetition_penalty=1.7).numpy()
        for row_ours, row_hf in zip(ours, theirs):
            hf_eos = np.where(row_hf == 1)[0]
            stop = (hf_eos[0] + 1) if hf_eos.size else len(row_hf)
            np.testing.assert_array_equal(row_ours[:stop], row_hf[:stop])

    def test_cached_matches_full_forward(self):
        """Per-step cached logits == teacher-forced full forward logits."""
        hf, model, params = self._make()
        src = jnp.asarray((np.arange(8)[None] * 5) % 100, jnp.int32)
        dec = jnp.asarray([[0, 42, 17, 63]], jnp.int32)
        full = model.apply({"params": params}, src, dec)
        enc = model.apply({"params": params}, src, mode="encode")
        cache = model.init_decode_cache(1, 4, jnp.float32)
        logits0, cache, ckv = model.apply(
            {"params": params}, decoder_input_ids=dec[:, :1], mode="decode",
            encoder_out=enc, cache=cache, cache_pos=0)
        steps = [logits0]
        for t in range(1, 4):
            lt, cache, _ = model.apply(
                {"params": params}, decoder_input_ids=dec[:, t:t + 1], mode="decode",
                encoder_out=enc, cache=cache, cache_pos=t, cross_kv=ckv)
            steps.append(lt)
        stepwise = jnp.concatenate(steps, axis=1)
        np.testing.assert_allclose(np.asarray(stepwise), np.asarray(full),
                                   atol=2e-4, rtol=2e-3)


class TestMistral:
    """Mistral = llama naming + sliding-window attention. The window (4) is
    narrower than the test sequence, so any implementation that silently
    computes full causal attention fails the comparison."""

    def _pair(self, window=4):
        hf_cfg = transformers.MistralConfig(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=64, sliding_window=window,
            attention_dropout=0.0, tie_word_embeddings=False)
        torch.manual_seed(0)
        with torch.no_grad():
            hf = transformers.MistralForCausalLM(hf_cfg).eval()
        cfg = config_from_hf(hf_cfg.to_dict())
        assert detect_family(hf_cfg.to_dict()) == "mistral"
        assert cfg.sliding_window == window
        from accelerate_tpu.models.llama import LlamaForCausalLM

        params = convert_hf_state_dict(hf.state_dict(), "mistral", strict=True)
        return hf, LlamaForCausalLM(cfg), params

    def test_forward_parity_window_narrower_than_seq(self):
        hf, model, params = self._pair(window=4)
        ids = (np.arange(24, dtype=np.int64).reshape(2, 12) * 3) % 128
        ours = model.apply({"params": params}, jnp.asarray(ids, jnp.int32))
        with torch.no_grad():
            theirs = hf(torch.from_numpy(ids)).logits
        _logits_close(ours, theirs)

    def test_window_changes_logits(self):
        """Sanity: the window actually masks something on this input."""
        hf, model, params = self._pair(window=4)
        import dataclasses

        wide = dataclasses.replace(model.config, sliding_window=None)
        ids = jnp.asarray((np.arange(24).reshape(2, 12) * 3) % 128, jnp.int32)
        narrow_out = model.apply({"params": params}, ids)
        wide_out = type(model)(wide).apply({"params": params}, ids)
        assert not np.allclose(np.asarray(narrow_out), np.asarray(wide_out), atol=1e-5)

    def test_cached_generate_parity(self):
        """KV-cached decode must apply the same window as prefill."""
        from accelerate_tpu.generation import generate

        hf, model, params = self._pair(window=4)
        ids = np.arange(10, dtype=np.int64)[None] % 128
        # fp32 cache: HF decodes in fp32, and bf16 KV rounding can flip
        # greedy ties on a random tiny model.
        ours = generate(model, params, jnp.asarray(ids, jnp.int32), max_new_tokens=6,
                        cache_dtype=jnp.float32)
        with torch.no_grad():
            theirs = hf.generate(torch.from_numpy(ids).long(), max_new_tokens=6,
                                 do_sample=False)
        assert np.asarray(ours)[0, 10:].tolist() == theirs[0, 10:].tolist()


class TestStreamedDispatch:
    """HF checkpoint dir -> per-tensor lazy translation -> block-streaming
    executor, against the torch model's logits."""

    def _hf_dir(self, tmp_path):
        import json

        from safetensors.numpy import save_file

        hf_cfg = transformers.LlamaConfig(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=64, tie_word_embeddings=False)
        torch.manual_seed(0)
        with torch.no_grad():
            hf = transformers.LlamaForCausalLM(hf_cfg).eval()
        save_file({k: v.numpy() for k, v in hf.state_dict().items()},
                  str(tmp_path / "model.safetensors"))
        (tmp_path / "config.json").write_text(json.dumps(hf_cfg.to_dict()))
        return hf

    @pytest.mark.parametrize("tier", [
        pytest.param("device", marks=pytest.mark.nightly),
        pytest.param("cpu", marks=pytest.mark.nightly),
        "disk",  # hardest tier (offload folder + reload) stays default
    ])
    def test_llama_parity_per_tier(self, tmp_path, tier):
        from accelerate_tpu.big_modeling import load_hf_checkpoint_and_dispatch

        hf = self._hf_dir(tmp_path)
        device_map = {"": {"device": 0, "cpu": "cpu", "disk": "disk"}[tier]}
        streamed, module = load_hf_checkpoint_and_dispatch(
            str(tmp_path), device_map=device_map)
        module.config.use_flash_attention = False
        ids = np.arange(16, dtype=np.int64).reshape(2, 8) % 128
        ours = streamed(jnp.asarray(ids, jnp.int32))
        with torch.no_grad():
            theirs = hf(torch.from_numpy(ids)).logits
        _logits_close(ours, theirs)

    @pytest.mark.parametrize("family", [
        "gptj",  # representative; the full family sweep runs nightly
        pytest.param("gpt_neox", marks=pytest.mark.nightly),
        pytest.param("opt", marks=pytest.mark.nightly),
        pytest.param("phi", marks=pytest.mark.nightly),
        pytest.param("bloom", marks=pytest.mark.nightly),
    ])
    def test_benchmark_families_stream_and_decode(self, tmp_path, family):
        """The reference's benchmark families (GPT-J / GPT-NeoX / OPT) run
        through the block-streaming executor off a raw HF dir: forward
        logits parity at the disk tier + KV-cached streamed greedy decode
        matching the full-forward argmax path."""
        import json

        from safetensors.numpy import save_file

        from accelerate_tpu.big_modeling import load_hf_checkpoint_and_dispatch

        mk = {
            "gptj": lambda: transformers.GPTJForCausalLM(transformers.GPTJConfig(
                vocab_size=96, n_embd=32, n_layer=2, n_head=4, n_positions=64,
                rotary_dim=4, resid_pdrop=0.0, embd_pdrop=0.0, attn_pdrop=0.0)),
            "gpt_neox": lambda: transformers.GPTNeoXForCausalLM(transformers.GPTNeoXConfig(
                vocab_size=96, hidden_size=32, intermediate_size=64,
                num_hidden_layers=2, num_attention_heads=4,
                max_position_embeddings=64, rotary_pct=0.5,
                hidden_dropout=0.0, attention_dropout=0.0)),
            "opt": lambda: transformers.OPTForCausalLM(transformers.OPTConfig(
                vocab_size=96, hidden_size=32, ffn_dim=64, num_hidden_layers=2,
                num_attention_heads=4, max_position_embeddings=64,
                do_layer_norm_before=True, dropout=0.0, attention_dropout=0.0,
                word_embed_proj_dim=32)),
            "phi": lambda: transformers.PhiForCausalLM(transformers.PhiConfig(
                vocab_size=96, hidden_size=32, intermediate_size=64,
                num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
                max_position_embeddings=64, partial_rotary_factor=0.5,
                resid_pdrop=0.0, embd_pdrop=0.0, attention_dropout=0.0)),
            "bloom": lambda: transformers.BloomForCausalLM(transformers.BloomConfig(
                vocab_size=96, hidden_size=32, n_layer=2, n_head=4,
                hidden_dropout=0.0, attention_dropout=0.0)),
        }
        torch.manual_seed(0)
        with torch.no_grad():
            hf = mk[family]().eval()
        save_file({k: v.numpy() for k, v in hf.state_dict().items()},
                  str(tmp_path / "model.safetensors"))
        (tmp_path / "config.json").write_text(json.dumps(hf.config.to_dict()))

        streamed, module = load_hf_checkpoint_and_dispatch(
            str(tmp_path), device_map={"": "disk"})
        module.config.use_flash_attention = False
        ids = np.arange(16, dtype=np.int64).reshape(2, 8) % 96
        ours = streamed(jnp.asarray(ids, jnp.int32))
        with torch.no_grad():
            theirs = hf(torch.from_numpy(ids)).logits
        _logits_close(ours, theirs)

        prompt = jnp.asarray([[5, 17, 3, 29, 11]], jnp.int32)
        toks = np.asarray(streamed.generate(prompt, max_new_tokens=4))
        with torch.no_grad():
            hf_toks = hf.generate(torch.tensor([[5, 17, 3, 29, 11]]),
                                  max_new_tokens=4, do_sample=False,
                                  eos_token_id=None).numpy()
        np.testing.assert_array_equal(toks, hf_toks)

    def test_mistral_sliding_window_through_block_executor(self, tmp_path):
        """The streamed executor must thread sliding_window into the cached
        block passes — full causal attention here would silently widen the
        receptive field (window 4 < prompt 10)."""
        import json

        from safetensors.numpy import save_file

        from accelerate_tpu.big_modeling import load_hf_checkpoint_and_dispatch

        hf_cfg = transformers.MistralConfig(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=64, sliding_window=4,
            attention_dropout=0.0, tie_word_embeddings=False)
        torch.manual_seed(0)
        with torch.no_grad():
            hf = transformers.MistralForCausalLM(hf_cfg).eval()
        save_file({k: v.numpy() for k, v in hf.state_dict().items()},
                  str(tmp_path / "model.safetensors"))
        (tmp_path / "config.json").write_text(json.dumps(hf_cfg.to_dict()))
        streamed, module = load_hf_checkpoint_and_dispatch(
            str(tmp_path), device_map={"": "cpu"})
        ids = np.arange(10, dtype=np.int64)[None] % 128
        ours = streamed.generate(jnp.asarray(ids, jnp.int32), max_new_tokens=6)
        with torch.no_grad():
            theirs = hf.generate(torch.from_numpy(ids).long(), max_new_tokens=6,
                                 do_sample=False)
        assert np.asarray(ours)[0, 10:].tolist() == theirs[0, 10:].tolist()

    def test_quantized_hf_load(self, tmp_path):
        """HF dir -> stream-quantized int8 params: close logits, smaller
        footprint, head kept full precision."""
        from accelerate_tpu.utils import (
            QuantizationConfig,
            QuantizedTensor,
            load_and_quantize_hf_checkpoint,
            load_hf_checkpoint,
        )

        self._hf_dir(tmp_path)
        qcfg = QuantizationConfig(load_in_8bit=True, min_weight_size=64)
        cfg, module, qparams, apply_fn = load_and_quantize_hf_checkpoint(
            str(tmp_path), qcfg)
        cfg.use_flash_attention = False
        _, full_params = load_hf_checkpoint(str(tmp_path))
        ids = jnp.asarray(np.arange(8)[None] % 128, jnp.int32)
        q_out = apply_fn(qparams, ids)
        full_out = module.apply({"params": full_params}, ids)
        np.testing.assert_allclose(np.asarray(q_out, np.float32),
                                   np.asarray(full_out, np.float32),
                                   atol=0.35, rtol=0.35)
        # Projections quantized, head skipped.
        assert isinstance(
            qparams["model"]["layers_0"]["self_attn"]["q_proj"]["kernel"], QuantizedTensor)
        assert not isinstance(qparams["lm_head"]["kernel"], QuantizedTensor)

    def test_quantized_hf_load_rejects_truncated_checkpoint(self, tmp_path):
        from safetensors.numpy import load_file, save_file

        from accelerate_tpu.utils import QuantizationConfig, load_and_quantize_hf_checkpoint

        self._hf_dir(tmp_path)
        sd = load_file(str(tmp_path / "model.safetensors"))
        sd.pop("model.layers.1.mlp.down_proj.weight")
        save_file(sd, str(tmp_path / "model.safetensors"))
        with pytest.raises(ValueError, match="missing keys"):
            load_and_quantize_hf_checkpoint(
                str(tmp_path), QuantizationConfig(load_in_8bit=True, min_weight_size=64))

    def test_rejects_unsupported_family(self, tmp_path):
        import json

        (tmp_path / "config.json").write_text(json.dumps({"model_type": "bert"}))
        from accelerate_tpu.big_modeling import load_hf_checkpoint_and_dispatch

        with pytest.raises(ValueError, match="streamed dispatch supports"):
            load_hf_checkpoint_and_dispatch(str(tmp_path))


class TestStreamedMixtral:
    """Per-expert HF shards aggregate into stacked expert tensors lazily
    (LazyStack) — the streamed executor runs MoE checkpoints from any tier."""

    def _hf_dir(self, tmp_path):
        import json

        from safetensors.numpy import save_file

        hf_cfg = transformers.MixtralConfig(
            vocab_size=96, hidden_size=32, intermediate_size=48,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            num_local_experts=4, num_experts_per_tok=2,
            max_position_embeddings=64, router_jitter_noise=0.0,
            attention_dropout=0.0, tie_word_embeddings=False)
        torch.manual_seed(0)
        with torch.no_grad():
            hf = transformers.MixtralForCausalLM(hf_cfg).eval()
        save_file({k: v.numpy() for k, v in hf.state_dict().items()},
                  str(tmp_path / "model.safetensors"))
        (tmp_path / "config.json").write_text(json.dumps(hf_cfg.to_dict()))
        return hf

    @pytest.mark.parametrize("tier", [
        pytest.param("cpu", marks=pytest.mark.nightly), "disk",
    ])
    def test_streamed_forward_parity(self, tmp_path, tier):
        from accelerate_tpu.big_modeling import load_hf_checkpoint_and_dispatch

        hf = self._hf_dir(tmp_path)
        streamed, module = load_hf_checkpoint_and_dispatch(
            str(tmp_path), device_map={"": tier})
        # exact sparse dispatch (no capacity drops) for the comparison
        module.config.capacity_factor = float(module.config.num_experts)
        module.config.use_flash_attention = False
        ids = (np.arange(16, dtype=np.int64).reshape(2, 8) * 5) % 96
        out = streamed(jnp.asarray(ids, jnp.int32))
        ours = out[0] if isinstance(out, tuple) else out
        with torch.no_grad():
            theirs = hf(torch.from_numpy(ids)).logits
        _logits_close(ours, theirs, atol=5e-4)

    def test_streamed_cached_generate(self, tmp_path):
        from accelerate_tpu.big_modeling import load_hf_checkpoint_and_dispatch

        hf = self._hf_dir(tmp_path)
        streamed, module = load_hf_checkpoint_and_dispatch(
            str(tmp_path), device_map={"": "cpu"})
        module.config.use_flash_attention = False
        ids = np.arange(8, dtype=np.int64)[None] % 96
        out = streamed.generate(jnp.asarray(ids, jnp.int32), max_new_tokens=5)
        with torch.no_grad():
            ref = hf.generate(torch.from_numpy(ids).long(), max_new_tokens=5,
                              do_sample=False)
        assert np.asarray(out)[0, 8:].tolist() == ref[0, 8:].tolist()

    def test_truncated_expert_shards_rejected(self, tmp_path):
        from safetensors.numpy import load_file, save_file

        from accelerate_tpu.big_modeling import load_hf_checkpoint_and_dispatch

        self._hf_dir(tmp_path)
        sd = load_file(str(tmp_path / "model.safetensors"))
        for w in ("w1", "w2", "w3"):
            sd.pop(f"model.layers.1.block_sparse_moe.experts.3.{w}.weight")
        save_file(sd, str(tmp_path / "model.safetensors"))
        with pytest.raises(ValueError, match="missing stacked members"):
            load_hf_checkpoint_and_dispatch(str(tmp_path), device_map={"": "cpu"})


class TestStreamedT5:
    """Encoder-decoder streaming: the reference's T0pp-11B benchmark shape.
    Encoder blocks run once; the decoder loops with self-KV + cross-KV
    carried across steps while weights stream per block."""

    def _hf_dir(self, tmp_path):
        import json

        from safetensors.numpy import save_file

        hf_cfg = transformers.T5Config(
            vocab_size=100, d_model=32, d_ff=64, d_kv=8, num_layers=2,
            num_heads=4, relative_attention_num_buckets=8,
            relative_attention_max_distance=20, dropout_rate=0.0,
            feed_forward_proj="relu", tie_word_embeddings=True,
            decoder_start_token_id=0, eos_token_id=1, pad_token_id=0)
        torch.manual_seed(0)
        with torch.no_grad():
            hf = transformers.T5ForConditionalGeneration(hf_cfg).eval()
        save_file({k: v.numpy() for k, v in hf.state_dict().items()},
                  str(tmp_path / "model.safetensors"))
        (tmp_path / "config.json").write_text(json.dumps(hf_cfg.to_dict()))
        return hf

    @pytest.mark.parametrize("tier", [
        pytest.param("cpu", marks=pytest.mark.nightly), "disk",
    ])
    def test_streamed_forward_parity(self, tmp_path, tier):
        from accelerate_tpu.big_modeling import load_hf_checkpoint_and_dispatch

        hf = self._hf_dir(tmp_path)
        streamed, module = load_hf_checkpoint_and_dispatch(
            str(tmp_path), device_map={"": tier})
        src = (np.arange(16, dtype=np.int64).reshape(2, 8) * 7) % 100
        tgt = (np.arange(12, dtype=np.int64).reshape(2, 6) * 3) % 100
        ours = streamed(jnp.asarray(src, jnp.int32), jnp.asarray(tgt, jnp.int32))
        with torch.no_grad():
            theirs = hf(input_ids=torch.from_numpy(src),
                        decoder_input_ids=torch.from_numpy(tgt)).logits
        _logits_close(ours, theirs)

    def test_streamed_cached_generate_matches_hf(self, tmp_path):
        from accelerate_tpu.big_modeling import load_hf_checkpoint_and_dispatch

        hf = self._hf_dir(tmp_path)
        streamed, module = load_hf_checkpoint_and_dispatch(
            str(tmp_path), device_map={"": "cpu"})
        src = (np.arange(16, dtype=np.int64).reshape(2, 8) * 7) % 100
        out = np.asarray(streamed.seq2seq_generate(
            jnp.asarray(src, jnp.int32), max_new_tokens=6,
            cache_dtype=jnp.float32))
        with torch.no_grad():
            ref = hf.generate(torch.from_numpy(src),
                              attention_mask=torch.ones(2, 8).long(),
                              max_new_tokens=6, do_sample=False).numpy()
        for row_ours, row_hf in zip(out, ref):
            hf_eos = np.where(row_hf == 1)[0]
            stop = (hf_eos[0] + 1) if hf_eos.size else len(row_hf)
            np.testing.assert_array_equal(row_ours[:stop], row_hf[:stop])

    def test_streamed_cached_default_dtype(self, tmp_path):
        """The default bf16 cache must work: prefill computes cross K/V in
        the activation dtype while decode reads the cache dtype — the cond
        branches have to agree."""
        from accelerate_tpu.big_modeling import load_hf_checkpoint_and_dispatch

        self._hf_dir(tmp_path)
        streamed, _ = load_hf_checkpoint_and_dispatch(str(tmp_path),
                                                      device_map={"": "cpu"})
        src = jnp.asarray((np.arange(8)[None] * 5) % 100, jnp.int32)
        out = streamed.seq2seq_generate(src, max_new_tokens=4)
        assert out.shape == (1, 5)

    def test_streamed_cached_matches_uncached(self, tmp_path):
        from accelerate_tpu.big_modeling import load_hf_checkpoint_and_dispatch

        self._hf_dir(tmp_path)
        streamed, _ = load_hf_checkpoint_and_dispatch(
            str(tmp_path), device_map={"": "cpu"})
        src = jnp.asarray((np.arange(8)[None] * 5) % 100, jnp.int32)
        cached = streamed.seq2seq_generate(src, max_new_tokens=5,
                                           cache_dtype=jnp.float32)
        uncached = streamed.seq2seq_generate(src, max_new_tokens=5, use_cache=False)
        np.testing.assert_array_equal(np.asarray(cached), np.asarray(uncached))

    def test_decoder_only_generate_refuses_seq2seq(self, tmp_path):
        from accelerate_tpu.big_modeling import load_hf_checkpoint_and_dispatch

        self._hf_dir(tmp_path)
        streamed, _ = load_hf_checkpoint_and_dispatch(str(tmp_path),
                                                      device_map={"": "cpu"})
        with pytest.raises(TypeError, match="seq2seq_generate"):
            streamed.generate(jnp.zeros((1, 4), jnp.int32))


class TestErrors:
    def test_unknown_family(self):
        with pytest.raises(ValueError, match="unsupported"):
            convert_hf_state_dict({}, "gpt17")

    def test_strict_unknown_key(self):
        with pytest.raises(KeyError, match="no conversion rule"):
            convert_hf_state_dict(
                {"model.mystery.weight": np.ones((2, 2), np.float32)},
                "llama", strict=True)

    def test_tied_head_skipped_non_strict(self):
        params = convert_hf_state_dict(
            {"lm_head.weight": np.ones((4, 2), np.float32),
             "model.norm.weight": np.ones((2,), np.float32)}, "llama")
        assert "lm_head" in params and "model" in params

    def test_export_refuses_unknown_param(self):
        with pytest.raises(KeyError, match="no export rule"):
            export_hf_state_dict({"mystery": {"kernel": np.ones((2, 2))}}, "llama")

    def test_untied_t5_head_converts_to_lm_head(self):
        sd = {"shared.weight": np.ones((8, 4), np.float32),
              "lm_head.weight": np.full((8, 4), 2.0, np.float32)}
        params = convert_hf_state_dict(sd, "t5")
        assert params["lm_head"]["kernel"].shape == (4, 8)

    def test_tied_t5_head_dropped(self):
        shared = np.ones((8, 4), np.float32)
        params = convert_hf_state_dict(
            {"shared.weight": shared, "lm_head.weight": shared.copy()}, "t5")
        assert "shared_embedding" in params and "lm_head" not in params

    def test_missing_tail_expert_detected(self):
        # Router says 4 experts; only experts 0-2 present (truncated shards).
        sd = {"model.layers.0.block_sparse_moe.gate.weight": np.ones((4, 6), np.float32)}
        for e in range(3):
            for w in ("w1", "w2", "w3"):
                shape = (6, 5) if w == "w2" else (5, 6)
                sd[f"model.layers.0.block_sparse_moe.experts.{e}.{w}.weight"] = (
                    np.ones(shape, np.float32))
        with pytest.raises(KeyError, match=r"missing experts \[3\]"):
            convert_hf_state_dict(sd, "mixtral")


class TestQwen2:
    """Qwen2 = llama skeleton + q/k/v projection biases."""

    def _pair(self, tie=False):
        hf_cfg = transformers.Qwen2Config(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=64, rms_norm_eps=1e-5,
            tie_word_embeddings=tie, use_sliding_window=False)
        torch.manual_seed(0)
        with torch.no_grad():
            hf = transformers.Qwen2ForCausalLM(hf_cfg).eval()
        cfg = config_from_hf(hf_cfg.to_dict())
        assert cfg.attention_qkv_bias and not cfg.attention_out_bias
        assert cfg.sliding_window is None
        from accelerate_tpu.models.llama import LlamaForCausalLM

        cfg.use_flash_attention = False
        params = convert_hf_state_dict(hf.state_dict(), "qwen2", strict=True)
        return hf, LlamaForCausalLM(cfg), params

    def test_forward_parity(self):
        hf, model, params = self._pair()
        ids = np.arange(24, dtype=np.int64).reshape(2, 12) % 128
        ours = model.apply({"params": params}, jnp.asarray(ids, jnp.int32))
        with torch.no_grad():
            theirs = hf(torch.from_numpy(ids)).logits
        _logits_close(ours, theirs)

    def test_greedy_decode_parity(self):
        from accelerate_tpu.generation import generate

        hf, model, params = self._pair()
        ids = (np.arange(8, dtype=np.int64)[None] * 5) % 128
        ours = np.asarray(generate(model, params, jnp.asarray(ids, jnp.int32),
                                   max_new_tokens=8, cache_dtype=jnp.float32))
        with torch.no_grad():
            theirs = hf.generate(torch.from_numpy(ids).long(), max_new_tokens=8,
                                 do_sample=False)
        np.testing.assert_array_equal(ours, theirs.numpy())

    def test_tied_head_duplicate_dropped(self):
        hf, model, params = self._pair(tie=True)
        assert "lm_head" not in params
        ids = np.arange(12, dtype=np.int64).reshape(1, 12) % 128
        ours = model.apply({"params": params}, jnp.asarray(ids, jnp.int32))
        with torch.no_grad():
            theirs = hf(torch.from_numpy(ids)).logits
        _logits_close(ours, theirs)

    @pytest.mark.nightly  # llama/t5 roundtrips stay default
    def test_roundtrip(self):
        hf, _, params = self._pair()
        _roundtrip(params, "qwen2", hf.state_dict())


class TestGemma:
    """Gemma = llama skeleton + GeGLU, (1+w) norms, sqrt(hidden) embedding
    scaling, decoupled head_dim, always-tied head."""

    def _pair(self):
        hf_cfg = transformers.GemmaConfig(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            head_dim=16, max_position_embeddings=64, rms_norm_eps=1e-6,
            hidden_activation="gelu_pytorch_tanh")
        torch.manual_seed(0)
        with torch.no_grad():
            hf = transformers.GemmaForCausalLM(hf_cfg).eval()
        cfg = config_from_hf(hf_cfg.to_dict())
        assert cfg.rms_norm_unit_offset and cfg.scale_embeddings
        assert cfg.mlp_activation == "gelu_tanh"
        assert cfg.head_dim == 16 and cfg.tie_word_embeddings
        from accelerate_tpu.models.llama import LlamaForCausalLM

        cfg.use_flash_attention = False
        params = convert_hf_state_dict(hf.state_dict(), "gemma", strict=True)
        assert "lm_head" not in params  # tied duplicate dropped
        return hf, LlamaForCausalLM(cfg), params

    def test_forward_parity(self):
        hf, model, params = self._pair()
        ids = np.arange(24, dtype=np.int64).reshape(2, 12) % 128
        ours = model.apply({"params": params}, jnp.asarray(ids, jnp.int32))
        with torch.no_grad():
            theirs = hf(torch.from_numpy(ids)).logits
        _logits_close(ours, theirs)

    def test_greedy_decode_parity(self):
        from accelerate_tpu.generation import generate

        hf, model, params = self._pair()
        ids = (np.arange(8, dtype=np.int64)[None] * 7) % 128
        ours = np.asarray(generate(model, params, jnp.asarray(ids, jnp.int32),
                                   max_new_tokens=8, cache_dtype=jnp.float32))
        with torch.no_grad():
            theirs = hf.generate(torch.from_numpy(ids).long(), max_new_tokens=8,
                                 do_sample=False)
        np.testing.assert_array_equal(ours, theirs.numpy())

    @pytest.mark.nightly  # llama/t5 roundtrips stay default
    def test_roundtrip(self):
        hf, _, params = self._pair()
        _roundtrip(params, "gemma", hf.state_dict())

    def test_explicit_exact_gelu_honored(self):
        # An EXPLICIT hidden_activation="gelu" means the exact erf form in
        # transformers; parity must hold (not be coerced to tanh).
        hf_cfg = transformers.GemmaConfig(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            head_dim=16, max_position_embeddings=64, rms_norm_eps=1e-6,
            hidden_activation="gelu")
        torch.manual_seed(1)
        with torch.no_grad():
            hf = transformers.GemmaForCausalLM(hf_cfg).eval()
        cfg = config_from_hf(hf_cfg.to_dict())
        assert cfg.mlp_activation == "gelu_exact"
        from accelerate_tpu.models.llama import LlamaForCausalLM

        cfg.use_flash_attention = False
        params = convert_hf_state_dict(hf.state_dict(), "gemma", strict=True)
        ids = np.arange(12, dtype=np.int64).reshape(1, 12) % 128
        ours = LlamaForCausalLM(cfg).apply({"params": params}, jnp.asarray(ids, jnp.int32))
        with torch.no_grad():
            theirs = hf(torch.from_numpy(ids)).logits
        _logits_close(ours, theirs)

    def test_streamed_dispatch(self, tmp_path):
        # The big-model executor must honor gemma's embedding scaling,
        # (1+w) final norm, and tied head block-by-block.
        import json as _json

        from safetensors.numpy import save_file

        from accelerate_tpu import load_hf_checkpoint_and_dispatch

        hf, model, params = self._pair()
        d = tmp_path / "gemma"
        d.mkdir()
        save_file({k: v.numpy() for k, v in hf.state_dict().items()},
                  str(d / "model.safetensors"))
        _json.dump(hf.config.to_dict(), open(d / "config.json", "w"))
        streamed, module = load_hf_checkpoint_and_dispatch(
            str(d), device_map={"": "disk"}, dtype=jnp.float32)
        ids = np.arange(1, 9, dtype=np.int32)[None]
        ours = np.asarray(streamed.generate(ids, max_new_tokens=5))
        with torch.no_grad():
            theirs = hf.generate(torch.from_numpy(ids).long(), max_new_tokens=5,
                                 do_sample=False)
        np.testing.assert_array_equal(ours, theirs.numpy())


class TestQwen2WindowMixture:
    def test_partial_window_layers_become_layer_windows(self):
        # HF: the first max_window_layers layers are full-attention, the
        # rest slide — represented as a per-layer mixture.
        cfg = dict(model_type="qwen2", vocab_size=128, hidden_size=32,
                   intermediate_size=64, num_hidden_layers=4,
                   num_attention_heads=4, num_key_value_heads=2,
                   use_sliding_window=True, sliding_window=16,
                   max_window_layers=2)
        out = config_from_hf(cfg)
        assert out.sliding_window is None
        assert out.layer_windows == (None, None, 16, 16)

    def test_full_window_layers_stay_uniform(self):
        cfg = dict(model_type="qwen2", vocab_size=128, hidden_size=32,
                   intermediate_size=64, num_hidden_layers=4,
                   num_attention_heads=4, num_key_value_heads=2,
                   use_sliding_window=True, sliding_window=16,
                   max_window_layers=0)
        out = config_from_hf(cfg)
        assert out.sliding_window == 16 and out.layer_windows is None

    def test_window_mixture_forward_parity(self):
        hf_cfg = transformers.Qwen2Config(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=64, rms_norm_eps=1e-5,
            tie_word_embeddings=False, use_sliding_window=True,
            sliding_window=8, max_window_layers=2, attn_implementation="eager")
        torch.manual_seed(0)
        with torch.no_grad():
            hf = transformers.Qwen2ForCausalLM(hf_cfg).eval()
        cfg = config_from_hf(hf_cfg.to_dict())
        assert cfg.layer_windows == (None, None, 8, 8)
        from accelerate_tpu.models.llama import LlamaForCausalLM

        cfg.use_flash_attention = False
        params = convert_hf_state_dict(hf.state_dict(), "qwen2", strict=True)
        ids = np.arange(24, dtype=np.int64).reshape(2, 12) % 128
        ours = LlamaForCausalLM(cfg).apply({"params": params}, jnp.asarray(ids, jnp.int32))
        with torch.no_grad():
            theirs = hf(torch.from_numpy(ids)).logits
        _logits_close(ours, theirs)


class TestGemma2:
    """Gemma2 = gemma + sandwich norms, logit softcaps, query_pre_attn_scalar,
    and the alternating local/global attention mixture (layer_types)."""

    def _pair(self):
        hf_cfg = transformers.Gemma2Config(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
            head_dim=16, max_position_embeddings=64, rms_norm_eps=1e-6,
            sliding_window=8, attn_logit_softcapping=50.0,
            final_logit_softcapping=30.0, query_pre_attn_scalar=32,
            hidden_activation="gelu_pytorch_tanh", attn_implementation="eager")
        torch.manual_seed(0)
        with torch.no_grad():
            hf = transformers.Gemma2ForCausalLM(hf_cfg).eval()
        cfg = config_from_hf(hf_cfg.to_dict())
        assert cfg.post_norms and cfg.attn_logit_softcapping == 50.0
        assert cfg.final_logit_softcapping == 30.0
        # layer_types alternate: even layers slide, odd are global.
        assert cfg.layer_windows == (8, None, 8, None)
        from accelerate_tpu.models.llama import LlamaForCausalLM

        cfg.use_flash_attention = False
        params = convert_hf_state_dict(hf.state_dict(), "gemma2", strict=True)
        assert "lm_head" not in params
        return hf, LlamaForCausalLM(cfg), params

    def test_forward_parity(self):
        # seq 12 > window 8, so the local/global mixture actually masks.
        hf, model, params = self._pair()
        ids = np.arange(24, dtype=np.int64).reshape(2, 12) % 128
        ours = model.apply({"params": params}, jnp.asarray(ids, jnp.int32))
        with torch.no_grad():
            theirs = hf(torch.from_numpy(ids)).logits
        _logits_close(ours, theirs)

    def test_greedy_decode_parity(self):
        from accelerate_tpu.generation import generate

        hf, model, params = self._pair()
        ids = (np.arange(10, dtype=np.int64)[None] * 3) % 128
        ours = np.asarray(generate(model, params, jnp.asarray(ids, jnp.int32),
                                   max_new_tokens=8, cache_dtype=jnp.float32))
        with torch.no_grad():
            theirs = hf.generate(torch.from_numpy(ids).long(), max_new_tokens=8,
                                 do_sample=False)
        np.testing.assert_array_equal(ours, theirs.numpy())

    @pytest.mark.nightly  # llama/t5 roundtrips stay default
    def test_roundtrip(self):
        hf, _, params = self._pair()
        _roundtrip(params, "gemma2", hf.state_dict())

    def test_streamed_dispatch(self, tmp_path):
        import json as _json

        from safetensors.numpy import save_file

        from accelerate_tpu import load_hf_checkpoint_and_dispatch

        hf, model, params = self._pair()
        d = tmp_path / "gemma2"
        d.mkdir()
        save_file({k: v.numpy() for k, v in hf.state_dict().items()},
                  str(d / "model.safetensors"))
        _json.dump(hf.config.to_dict(), open(d / "config.json", "w"))
        streamed, module = load_hf_checkpoint_and_dispatch(
            str(d), device_map={"": "disk"}, dtype=jnp.float32)
        ids = np.arange(1, 11, dtype=np.int32)[None]
        ours = np.asarray(streamed.generate(ids, max_new_tokens=5))
        with torch.no_grad():
            theirs = hf.generate(torch.from_numpy(ids).long(), max_new_tokens=5,
                                 do_sample=False)
        np.testing.assert_array_equal(ours, theirs.numpy())

    def test_pipelined_rejects_window_mixture(self):
        from accelerate_tpu.models.llama import LlamaConfig, PipelinedLlamaForCausalLM

        cfg = LlamaConfig.tiny(layer_windows=(8, None))
        with pytest.raises(NotImplementedError, match="heterogeneous"):
            PipelinedLlamaForCausalLM(cfg)

    def test_fused_loss_applies_final_softcap(self):
        # The chunked head must softcap per chunk — loss AND grads equal
        # the materialized softcapped-logits CE.
        from accelerate_tpu.models.llama import (
            LlamaConfig,
            LlamaForCausalLM,
            causal_lm_loss,
            fused_causal_lm_loss,
        )

        cfg = LlamaConfig.tiny(use_flash_attention=False, final_logit_softcapping=5.0)
        model = LlamaForCausalLM(cfg)
        params = model.init_params(jax.random.PRNGKey(0), batch_size=2, seq_len=16)
        ids = np.arange(32, dtype=np.int32).reshape(2, 16) % cfg.vocab_size
        batch = {"input_ids": jnp.asarray(ids)}
        ref, g_ref = jax.value_and_grad(causal_lm_loss(model.apply))(params, batch)
        got, g_got = jax.value_and_grad(fused_causal_lm_loss(model, num_chunks=4))(params, batch)
        np.testing.assert_allclose(float(got), float(ref), rtol=2e-5)
        for (pa, a), (pb, b) in zip(
            jax.tree_util.tree_leaves_with_path(g_got),
            jax.tree_util.tree_leaves_with_path(g_ref),
        ):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-3,
                                       err_msg=jax.tree_util.keystr(pa))


class TestQwen2Moe:
    """Qwen2-MoE = qwen2 attention (qkv biases) + routed experts +
    sigmoid-gated shared expert (+ optional dense mlp_only layers)."""

    def _pair(self, mlp_only_layers=(), norm_topk=False):
        hf_cfg = transformers.Qwen2MoeConfig(
            vocab_size=96, hidden_size=32, intermediate_size=80,
            moe_intermediate_size=48, shared_expert_intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            num_experts=4, num_experts_per_tok=2, norm_topk_prob=norm_topk,
            decoder_sparse_step=1, mlp_only_layers=list(mlp_only_layers),
            max_position_embeddings=64, rms_norm_eps=1e-5,
            use_sliding_window=False, tie_word_embeddings=False,
            router_jitter_noise=0.0, attention_dropout=0.0)
        torch.manual_seed(0)
        with torch.no_grad():
            hf = transformers.Qwen2MoeForCausalLM(hf_cfg).eval()
        cfg = config_from_hf(hf_cfg.to_dict())
        assert detect_family(hf_cfg.to_dict()) == "qwen2_moe"
        assert cfg.attention_qkv_bias and cfg.intermediate_size == 48
        assert cfg.shared_expert_intermediate_size == 64
        assert cfg.dense_intermediate_size == 80
        assert cfg.mlp_only_layers == tuple(mlp_only_layers)
        assert cfg.norm_topk_prob is norm_topk
        # No-drop capacity so sparse dispatch is exact (matches HF's dense
        # gather over selected experts).
        cfg.capacity_factor = float(cfg.num_experts)
        cfg.use_flash_attention = False
        from accelerate_tpu.models.mixtral import MixtralForCausalLM

        params = convert_hf_state_dict(hf.state_dict(), "qwen2_moe", strict=True)
        return hf, MixtralForCausalLM(cfg), params

    @pytest.mark.parametrize("norm_topk", [False, True])
    def test_forward_parity(self, norm_topk):
        hf, model, params = self._pair(norm_topk=norm_topk)
        ids = (np.arange(16, dtype=np.int64).reshape(2, 8) * 5) % 96
        out = model.apply({"params": params}, jnp.asarray(ids, jnp.int32))
        ours = out[0] if isinstance(out, tuple) else out
        with torch.no_grad():
            theirs = hf(torch.from_numpy(ids)).logits
        _logits_close(ours, theirs, atol=5e-4)

    def test_dense_mlp_only_layer_parity(self):
        hf, model, params = self._pair(mlp_only_layers=(1,))
        ids = (np.arange(16, dtype=np.int64).reshape(2, 8) * 7) % 96
        out = model.apply({"params": params}, jnp.asarray(ids, jnp.int32))
        ours = out[0] if isinstance(out, tuple) else out
        with torch.no_grad():
            theirs = hf(torch.from_numpy(ids)).logits
        _logits_close(ours, theirs, atol=5e-4)

    def test_greedy_decode_parity(self):
        from accelerate_tpu.generation import generate

        hf, model, params = self._pair()
        ids = (np.arange(8, dtype=np.int64)[None] * 3) % 96
        ours = np.asarray(generate(model, params, jnp.asarray(ids, jnp.int32),
                                   max_new_tokens=6, cache_dtype=jnp.float32))
        with torch.no_grad():
            theirs = hf.generate(torch.from_numpy(ids).long(), max_new_tokens=6,
                                 do_sample=False)
        np.testing.assert_array_equal(ours, theirs.numpy())

    @pytest.mark.nightly  # llama/t5 roundtrips stay default
    def test_roundtrip(self):
        hf, _, params = self._pair()
        _roundtrip(params, "qwen2_moe", hf.state_dict())

    def test_streamed_dispatch(self, tmp_path):
        import json as _json

        from safetensors.numpy import save_file

        from accelerate_tpu import load_hf_checkpoint_and_dispatch

        hf, model, params = self._pair()
        d = tmp_path / "qwen2moe"
        d.mkdir()
        save_file({k: v.numpy() for k, v in hf.state_dict().items()},
                  str(d / "model.safetensors"))
        _json.dump(hf.config.to_dict(), open(d / "config.json", "w"))
        streamed, module = load_hf_checkpoint_and_dispatch(
            str(d), device_map={"": "disk"}, dtype=jnp.float32)
        ids = np.arange(1, 9, dtype=np.int32)[None]
        ours = np.asarray(streamed.generate(ids, max_new_tokens=5))
        with torch.no_grad():
            theirs = hf.generate(torch.from_numpy(ids).long(), max_new_tokens=5,
                                 do_sample=False)
        np.testing.assert_array_equal(ours, theirs.numpy())

    def test_sliding_window_parity(self):
        # Uniform window (max_window_layers=0: every layer slides) — the one
        # configuration transformers' EAGER path implements faithfully (its
        # eager mask applies the window to all layers, ignoring
        # max_window_layers; only its flash path is per-layer, matching our
        # layer_windows semantics).
        hf_cfg = transformers.Qwen2MoeConfig(
            vocab_size=96, hidden_size=32, intermediate_size=80,
            moe_intermediate_size=48, shared_expert_intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            num_experts=4, num_experts_per_tok=2, norm_topk_prob=False,
            max_position_embeddings=64, rms_norm_eps=1e-5,
            use_sliding_window=True, sliding_window=8, max_window_layers=0,
            tie_word_embeddings=False, router_jitter_noise=0.0,
            attention_dropout=0.0, attn_implementation="eager")
        torch.manual_seed(0)
        with torch.no_grad():
            hf = transformers.Qwen2MoeForCausalLM(hf_cfg).eval()
        cfg = config_from_hf(hf_cfg.to_dict())
        assert cfg.sliding_window == 8 and cfg.layer_windows is None
        cfg.capacity_factor = float(cfg.num_experts)
        cfg.use_flash_attention = False
        from accelerate_tpu.models.mixtral import MixtralForCausalLM

        params = convert_hf_state_dict(hf.state_dict(), "qwen2_moe", strict=True)
        ids = (np.arange(24, dtype=np.int64).reshape(2, 12) * 5) % 96
        out = MixtralForCausalLM(cfg).apply({"params": params}, jnp.asarray(ids, jnp.int32))
        ours = out[0] if isinstance(out, tuple) else out
        with torch.no_grad():
            theirs = hf(torch.from_numpy(ids)).logits
        _logits_close(ours, theirs, atol=5e-4)

    def test_window_mixture_conversion(self):
        # Per-layer mixture (intended max_window_layers semantics; HF honors
        # it only on the flash path, so no eager parity comparison here).
        cfg = config_from_hf(dict(
            model_type="qwen2_moe", vocab_size=96, hidden_size=32,
            intermediate_size=80, moe_intermediate_size=48,
            num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
            num_experts=4, num_experts_per_tok=2,
            use_sliding_window=True, sliding_window=8, max_window_layers=2))
        assert cfg.sliding_window is None
        assert cfg.layer_windows == (None, None, 8, 8)


class TestCohere2Moe:
    """No transformers class of this family is at hand: the round trip is
    between our tree and the assumed HF names, and the config comes from the
    published ``config.json`` keys."""

    PUBLISHED = dict(
        model_type="cohere2_moe", vocab_size=96, hidden_size=32, intermediate_size=16,
        num_hidden_layers=4, num_attention_heads=8, num_key_value_heads=2, head_dim=8,
        max_position_embeddings=256, layer_norm_eps=1e-5, rope_theta=50000, sliding_window=8,
        layer_types=["sliding_attention"] * 3 + ["full_attention"], layer_switch=4,
        num_experts=8, num_experts_per_tok=2, num_shared_experts=2,
        expert_selection_fn="sigmoid", norm_topk_prob=True, logit_scale=1,
        shared_expert_combination_strategy="average", position_embedding_type="rope_gptj",
        rotary_pct=1, use_parallel_block=True, use_qk_norm=False, first_k_dense_replace=0,
        tie_word_embeddings=True)

    def test_config_and_round_trip(self):
        import jax

        from accelerate_tpu.models.cohere2_moe import Cohere2MoeConfig
        from accelerate_tpu.utils.hf_interop import model_from_config

        assert detect_family(self.PUBLISHED) == "cohere2_moe"
        cfg = config_from_hf(self.PUBLISHED)
        assert isinstance(cfg, Cohere2MoeConfig)
        assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.num_shared_experts) == (8, 2, 2)
        assert cfg.held == (0, 8) and cfg.window_for(0) == 8 and cfg.window_for(3) is None
        cfg.use_flash_attention = False
        model = model_from_config(cfg, "cohere2_moe")
        params = jax.tree.map(np.asarray, model.init_params(jax.random.PRNGKey(0)))
        exported = export_hf_state_dict(params, "cohere2_moe")
        assert exported["model.layers.2.mlp.experts.5.down_proj.weight"].shape == (32, 16)
        assert exported["model.layers.0.mlp.shared_experts.1.gate_proj.weight"].shape == (16, 32)
        assert exported["model.layers.3.mlp.gate.weight"].shape == (8, 32)
        assert "lm_head.weight" not in exported          # tied
        _roundtrip(params, "cohere2_moe", exported)
        back = convert_hf_state_dict(exported, "cohere2_moe", strict=True)
        ids = jnp.asarray((np.arange(20).reshape(1, 20) * 7) % 96, jnp.int32)
        np.testing.assert_array_equal(model.apply({"params": params}, ids),
                                      model.apply({"params": back}, ids))

    def test_what_is_not_implemented_is_refused(self):
        for key, value in (("use_qk_norm", True), ("first_k_dense_replace", 2),
                           ("use_parallel_block", False),
                           ("shared_expert_combination_strategy", "sum"),
                           ("tie_word_embeddings", False)):
            with pytest.raises(NotImplementedError):
                config_from_hf(dict(self.PUBLISHED, **{key: value}))
