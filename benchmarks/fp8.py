"""FP8 training parity benchmark (reference: benchmarks/fp8/
{non_distributed,ddp,fsdp,distrib_deepspeed}.py — verifies fp8-through-
Accelerator trains at the same level as the raw fp8 engine).

The TPU-native fp8 engine is ops/quant.py (delayed-scaling e4m3/e5m2
matmuls with amax history, TransformerEngine semantics); there is no
separate "raw" engine to diff against, so parity is measured the way the
reference's assertions do: fp8 training must track the bf16 baseline's
loss trajectory within tolerance, across the same four layouts
(single-device / DP / FSDP / DeepSpeed-translated ZeRO-2).

Run: ``python benchmarks/fp8.py`` (CPU mesh or TPU). Prints one row per
layout and a JSON line.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REL_TOL = 0.12  # max allowed relative gap in final loss, fp8 vs bf16


def _reset():
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

    for s in (AcceleratorState, GradientState, PartialState):
        s._reset_state()


def make_accelerator(layout: str):
    import jax

    from accelerate_tpu import Accelerator, MeshConfig
    from accelerate_tpu.utils import DeepSpeedPlugin, FullyShardedDataParallelPlugin

    n = len(jax.devices())
    if layout == "single":
        return Accelerator(mesh_config=MeshConfig(devices=jax.devices()[:1]))
    if layout == "dp":
        return Accelerator(mesh_config=MeshConfig(dp=n))
    if layout == "fsdp":
        return Accelerator(
            mesh_config=MeshConfig(fsdp=n),
            fsdp_plugin=FullyShardedDataParallelPlugin(min_weight_size_to_shard=1),
        )
    if layout == "deepspeed":
        return Accelerator(
            mesh_config=MeshConfig(fsdp=n),
            deepspeed_plugin=DeepSpeedPlugin(zero_stage=2),
        )
    raise ValueError(layout)


def train(layout: str, use_fp8: bool, steps: int = 12):
    import jax
    import numpy as np
    import optax

    from accelerate_tpu import Accelerator, Model
    from accelerate_tpu.data_loader import make_global_batch
    from accelerate_tpu.models.llama import LlamaConfig, LlamaForCausalLM, causal_lm_loss
    from accelerate_tpu.utils import set_seed

    _reset()
    set_seed(42)
    acc = make_accelerator(layout)
    cfg = LlamaConfig.tiny(
        hidden_size=128, intermediate_size=256, use_flash_attention=False, use_fp8=use_fp8
    )
    model_def = LlamaForCausalLM(cfg)
    params = model_def.init_params(jax.random.PRNGKey(42), batch_size=2, seq_len=32)
    model, opt = acc.prepare(Model(model_def, params), optax.adamw(3e-3))
    step = acc.compile_train_step(causal_lm_loss(model_def.apply), max_grad_norm=1.0)
    rng = np.random.default_rng(42)
    batch_size = max(8, len(jax.devices()))
    losses = []
    for _ in range(steps):
        ids = rng.integers(0, cfg.vocab_size, (batch_size, 32)).astype(np.int32)
        with acc.mesh:
            metrics = step(make_global_batch({"input_ids": ids}, acc.mesh))
        losses.append(float(metrics["loss"]))
    return losses


def main() -> int:
    from accelerate_tpu.utils.platforms import device_kind, request_virtual_cpu_devices

    if os.environ.get("JAX_PLATFORMS", "").split(",")[0].strip().lower() == "cpu":
        # Asked for the CPU: the dp/fsdp layouts need more than one device.
        request_virtual_cpu_devices(8)
    import jax

    platform = f"{jax.default_backend()} ({device_kind()})"
    rows, ok = [], True
    print(f"fp8 vs bf16 training parity ({platform})\n")
    print("| layout | bf16 final loss | fp8 final loss | rel gap | pass |")
    print("|---|---|---|---|---|")
    for layout in ("single", "dp", "fsdp", "deepspeed"):
        bf16 = train(layout, use_fp8=False)
        fp8 = train(layout, use_fp8=True)
        gap = abs(fp8[-1] - bf16[-1]) / max(abs(bf16[-1]), 1e-9)
        passed = gap < REL_TOL and fp8[-1] < fp8[0]
        ok &= passed
        rows.append({"layout": layout, "bf16_final": round(bf16[-1], 4),
                     "fp8_final": round(fp8[-1], 4), "rel_gap": round(gap, 4),
                     "pass": passed})
        print(f"| {layout} | {bf16[-1]:.4f} | {fp8[-1]:.4f} | {gap:.3f} | "
              f"{'yes' if passed else 'NO'} |")
    print()
    print(json.dumps({"metric": "fp8_bf16_final_loss_rel_gap", "platform": platform,
                      "tolerance": REL_TOL, "rows": rows, "ok": ok}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
