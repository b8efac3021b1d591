"""Big-model inference latency benchmark (reference parity:
benchmarks/big_model_inference/measures_util.py + README.md:26-45 — model
load time, per-token generation latency, memory placement).

Builds a Llama (or, with ``--family t5``, an encoder-decoder — the
reference table's T0pp-11B shape), exports it to sharded safetensors, then
for each placement tier (all-HBM / host-offload / disk-offload) measures:

* load time  — checkpoint -> WeightStore via load_checkpoint_and_dispatch
* first call — generate end-to-end including XLA compiles
* decode     — KV-cached per-token latency (the reference table's
               "generation time per token")
* no-cache   — full re-forward per token, for contrast

Run: ``python benchmarks/big_model_inference.py [--size tiny|small|1b]
[--tiers device,cpu,disk] [--tokens N]``. Prints a markdown table and one
JSON line. Runs on the device jax gives it and names that device in every
output; ``JAX_PLATFORMS=cpu`` asks for the CPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


SIZES = {
    # hidden, inter, layers, heads, kv_heads, vocab
    "tiny": (256, 512, 4, 4, 2, 2048),
    "small": (1024, 2816, 8, 16, 8, 32000),
    "1b": (2048, 5632, 22, 32, 4, 32000),
}


def build_and_save(size: str, ckpt_dir: str, family: str = "llama"):
    import types

    import jax

    from accelerate_tpu.checkpointing import save_model

    h, inter, layers, heads, kv, vocab = SIZES[size]
    if family == "t5":
        # Encoder-decoder tier rows (reference table's T0pp-11B shape).
        from accelerate_tpu.models.t5 import T5Config, T5ForConditionalGeneration

        cfg = T5Config(vocab_size=vocab, hidden_size=h, intermediate_size=inter,
                       num_layers=layers, num_heads=heads,
                       head_dim=max(h // heads, 8), dropout_rate=0.0)
        module = T5ForConditionalGeneration(cfg)
        params = module.init_params(jax.random.PRNGKey(0))
    elif family == "gptj":
        # Reference table rows :31-32 (GPT-J-6B) use this architecture.
        from accelerate_tpu.models.gptj import GPTJConfig, GPTJForCausalLM

        cfg = GPTJConfig(vocab_size=vocab, hidden_size=h, intermediate_size=inter,
                         num_hidden_layers=layers, num_attention_heads=heads,
                         max_position_embeddings=2048,
                         rotary_dim=min(64, h // heads), use_flash_attention=False)
        module = GPTJForCausalLM(cfg)
        params = module.init_params(jax.random.PRNGKey(0), batch_size=1, seq_len=8)
    elif family == "gpt_neox":
        # Reference table rows :33-34 (GPT-NeoX-20B).
        from accelerate_tpu.models.gpt_neox import GPTNeoXConfig, GPTNeoXForCausalLM

        cfg = GPTNeoXConfig(vocab_size=vocab, hidden_size=h, intermediate_size=inter,
                            num_hidden_layers=layers, num_attention_heads=heads,
                            max_position_embeddings=2048, use_flash_attention=False)
        module = GPTNeoXForCausalLM(cfg)
        params = module.init_params(jax.random.PRNGKey(0), batch_size=1, seq_len=8)
    elif family == "bloom":
        # ALiBi family: no position table at all.
        from accelerate_tpu.models.bloom import BloomConfig, BloomForCausalLM

        cfg = BloomConfig(vocab_size=vocab, hidden_size=h,
                          num_hidden_layers=layers, num_attention_heads=heads)
        module = BloomForCausalLM(cfg)
        params = module.init_params(jax.random.PRNGKey(0), batch_size=1, seq_len=8)
    elif family == "opt":
        # Reference table rows :36-37 (OPT-30B, cpu/disk offload).
        from accelerate_tpu.models.opt import OPTConfig, OPTForCausalLM

        cfg = OPTConfig(vocab_size=vocab, hidden_size=h, intermediate_size=inter,
                        num_hidden_layers=layers, num_attention_heads=heads,
                        max_position_embeddings=2048, use_flash_attention=False)
        module = OPTForCausalLM(cfg)
        params = module.init_params(jax.random.PRNGKey(0), batch_size=1, seq_len=8)
    else:
        from accelerate_tpu.models.llama import LlamaConfig, LlamaForCausalLM

        cfg = LlamaConfig(
            vocab_size=vocab, hidden_size=h, intermediate_size=inter,
            num_hidden_layers=layers, num_attention_heads=heads,
            num_key_value_heads=kv, max_position_embeddings=2048,
            use_flash_attention=False,
        )
        module = LlamaForCausalLM(cfg)
        params = module.init_params(jax.random.PRNGKey(0), batch_size=1, seq_len=8)
    single = types.SimpleNamespace(is_main_process=True, wait_for_everyone=lambda: None)
    save_model(single, params, ckpt_dir, max_shard_size="512MB")
    n_params = sum(int(p.size) for p in jax.tree_util.tree_leaves(params))
    del params
    return module, n_params


def bench_tier(module, ckpt_dir: str, tier: str, prompt_len: int, tokens: int,
               offload_folder=None, prompt_lookup: int = 0, assisted: int = 0):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from accelerate_tpu.big_modeling import load_checkpoint_and_dispatch

    device_map = {"": {"device": 0, "cpu": "cpu", "disk": "disk"}[tier]}
    ex = jnp.zeros((1, 8), jnp.int32)
    is_t5 = type(module).__name__ == "T5ForConditionalGeneration"
    t0 = time.perf_counter()
    streamed = load_checkpoint_and_dispatch(
        module, ckpt_dir, device_map=device_map, offload_folder=offload_folder,
        example_args=(ex, ex) if is_t5 else (ex,),
    )
    load_s = time.perf_counter() - t0

    rng = np.random.default_rng(0)
    ids = jnp.asarray(
        rng.integers(0, module.config.vocab_size, size=(1, prompt_len)), jnp.int32
    )

    def gen(n=None, **kw):
        n = tokens if n is None else n
        if is_t5:
            return streamed.seq2seq_generate(ids, max_new_tokens=n, **kw)
        return streamed.generate(ids, max_new_tokens=n, **kw)

    # First call compiles one executable per block kind for THIS cache
    # length (cache shape is part of the jit key, so the warm-up must use
    # the same max_new_tokens as the timed run).
    t0 = time.perf_counter()
    out = gen()
    first_token_s = time.perf_counter() - t0  # includes compile

    t0 = time.perf_counter()
    out = gen()
    kv_per_token = (time.perf_counter() - t0) / tokens  # prefill amortized in

    nocache_per_token = None
    if tokens >= 2:
        gen(n=2, use_cache=False)  # compile warm-up
        t0 = time.perf_counter()
        gen(n=2, use_cache=False)
        nocache_per_token = (time.perf_counter() - t0) / 2

    lookup_per_token = None
    if prompt_lookup and not is_t5:
        # Prompt-lookup speculation: a REPETITIVE prompt so acceptance is
        # realistic for the self-repetitive texts the technique targets.
        rep = jnp.asarray(np.tile(rng.integers(0, module.config.vocab_size,
                                               size=(1, 4)), (1, prompt_len // 4)),
                          jnp.int32)
        kw = dict(max_new_tokens=tokens, prompt_lookup_num_tokens=prompt_lookup)
        streamed.generate(rep, **kw)  # compile warm-up
        t0 = time.perf_counter()
        streamed.generate(rep, **kw)
        lookup_per_token = (time.perf_counter() - t0) / tokens

    assisted_per_token = None
    if assisted and not is_t5:
        # Self-speculation upper bound: the draft is the SAME weights
        # rebuilt device-resident (the checkpoint came from this seed), so
        # acceptance is 1.0 and the row shows the ceiling of what a good
        # draft buys — streamed passes divided by the full run length.
        try:
            draft_params = module.init_params(jax.random.PRNGKey(0),
                                              batch_size=1, seq_len=8)
        except TypeError:
            draft_params = module.init_params(jax.random.PRNGKey(0))
        kw = dict(max_new_tokens=tokens, assistant_module=module,
                  assistant_params=draft_params, num_draft=assisted)
        streamed.generate(ids, **kw)  # compile warm-up
        t0 = time.perf_counter()
        streamed.generate(ids, **kw)
        assisted_per_token = (time.perf_counter() - t0) / tokens

    result = {
        "tier": tier,
        "load_s": round(load_s, 2),
        "first_call_s": round(first_token_s, 2),
        "kv_s_per_token": round(kv_per_token, 4),
        "nocache_s_per_token": round(nocache_per_token, 4) if nocache_per_token else None,
        "lookup_s_per_token": round(lookup_per_token, 4) if lookup_per_token else None,
        "assisted_s_per_token": round(assisted_per_token, 4) if assisted_per_token else None,
        "hbm_resident_bytes": streamed.hbm_resident_bytes,
        "n_new_tokens": int(out.shape[1] - (1 if is_t5 else prompt_len)),
    }
    streamed.close()
    return result


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--size", default="tiny", choices=sorted(SIZES))
    ap.add_argument("--family", default="llama",
                choices=["llama", "t5", "gptj", "gpt_neox", "bloom", "opt"])
    ap.add_argument("--tiers", default="device,cpu")
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--prompt-lookup", type=int, default=0,
                    help="also time prompt-lookup speculation with K drafts "
                         "(decoder-only families)")
    ap.add_argument("--assisted", type=int, default=0,
                    help="also time draft-model speculation with K drafts; "
                         "the draft is the same weights device-resident "
                         "(acceptance-1.0 upper bound; decoder-only)")
    ap.add_argument("--emit-markdown", action="store_true",
                    help="also print rows in EXACTLY the reference table's "
                         "column shape (reference: benchmarks/"
                         "big_model_inference/README.md:26-37) plus a "
                         "Backend column, ready to append to "
                         "benchmarks/README.md")
    args = ap.parse_args()

    import jax

    from accelerate_tpu.utils.platforms import device_kind

    platform = jax.default_backend()
    print(f"platform: {platform} ({device_kind()})", file=sys.stderr)

    rows = []
    with tempfile.TemporaryDirectory() as tmp:
        ckpt = f"{tmp}/ckpt"
        module, n_params = build_and_save(args.size, ckpt, family=args.family)
        for tier in args.tiers.split(","):
            offload = f"{tmp}/offload_{tier}" if tier == "disk" else None
            rows.append(
                bench_tier(module, ckpt, tier.strip(), args.prompt_len, args.tokens,
                           offload_folder=offload, prompt_lookup=args.prompt_lookup,
                           assisted=args.assisted)
            )

    print(f"\n{args.family}-{args.size} ({n_params/1e6:.0f}M params), "
          f"prompt={args.prompt_len}, platform={platform}\n")
    with_lookup = any(r.get("lookup_s_per_token") for r in rows)
    with_assist = any(r.get("assisted_s_per_token") for r in rows)
    lk_head = " Prompt-lookup /token |" if with_lookup else ""
    lk_sep = ":---:|" if with_lookup else ""
    as_head = " Assisted /token |" if with_assist else ""
    as_sep = ":---:|" if with_assist else ""
    print("| Placement | Load time | First call (compile) | KV decode /token "
          f"| No-cache /token | HBM resident |{lk_head}{as_head}")
    print(f"|:---------:|:---------:|:-----------:|:----------------:|:---------------:|:------------:|{lk_sep}{as_sep}")
    for r in rows:
        nc = f"{r['nocache_s_per_token']:.3f}s" if r["nocache_s_per_token"] else "-"

        def spec_cell(key, on):
            if not on:
                return ""
            v = r.get(key)
            return f" {v*1000:.1f}ms |" if v else " - |"

        lk = spec_cell("lookup_s_per_token", with_lookup)
        asst = spec_cell("assisted_s_per_token", with_assist)
        print(f"| {r['tier']} | {r['load_s']:.1f}s | {r['first_call_s']:.2f}s "
              f"| {r['kv_s_per_token']*1000:.1f}ms | {nc} "
              f"| {r['hbm_resident_bytes']/2**30:.2f}GiB |{lk}{asst}")
    print()
    if args.emit_markdown:
        # The reference's own column shape (Model | load | s-per-token |
        # dtype | memory placement | disk), plus Backend so TPU rows can be
        # appended next to CPU rows without a new table. save_model writes
        # the fp32 init params and load_checkpoint_and_dispatch applies no
        # cast here, so dtype is float32 throughout.
        backend = platform if platform == "cpu" else f"{platform} ({device_kind()})"
        total_gib = n_params * 4 / 2**30
        name = f"{args.family}-{args.size} ({n_params/1e6:.0f}M)"
        print("| Model | Backend | Model load time | Generation time | dtype "
              "| HBM use | Host RAM use | Disk offload |")
        print("|:-----:|:-------:|:---------------:|:---------------:|:-----:"
              "|:-------:|:------------:|:------------:|")
        for r in rows:
            host = total_gib if r["tier"] == "cpu" else 0.0
            print(f"| {name} | {backend} | {r['load_s']:.1f}s "
                  f"| {r['kv_s_per_token']:.2f}s per token | float32 "
                  f"| {r['hbm_resident_bytes']/2**30:.2f}GB | {host:.2f}GB "
                  f"| {'yes' if r['tier'] == 'disk' else 'no'} |")
        print()
    print(json.dumps({"metric": "big_model_kv_decode_s_per_token",
                      "size": args.size, "family": args.family,
                      "platform": platform, "device_kind": device_kind(),
                      "tiers": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
