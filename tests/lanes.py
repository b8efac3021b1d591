"""Test-lane partition: the single source of truth for CI/dev test splits.

Mirrors the reference's budgeted lanes (reference: Makefile:26-58 and
.github/workflows/test.yml:22-38) adapted to this box: one alphabetical
25-minute run hides a failure behind 20 minutes of unrelated tests, so the
suite splits into four lanes a developer can run by cost.

    make test-fast          # unit core            (~5 min budget)
    make test-models        # model zoo + HF parity (~12 min)
    make test-subproc       # CLI + example scripts (~12 min)
    make test-multiprocess  # real jax.distributed worlds (~8 min)
    make test-all           # everything, no -x

Usage as a module:  python tests/lanes.py <lane>  prints the file list.
``test_lanes_partition`` (in test_state.py's fast lane) asserts every
``tests/test_*.py`` belongs to exactly one lane, so new files must be
assigned here or the fast lane fails immediately.
"""

from __future__ import annotations

import os
import sys

#: lane -> (budget_minutes, [test files])
LANES: dict[str, tuple[int, list[str]]] = {
    "fast": (5, [
        "test_accelerator.py",
        "test_bench.py",
        "test_bringup.py",
        "test_checkpointing.py",
        "test_data_loader.py",
        "test_env_memory_utils.py",
        "test_flash_attention.py",
        "test_fused_loss.py",
        "test_lanes.py",
        "test_local_sgd_inference.py",
        "test_menu.py",
        "test_moe.py",
        "test_native.py",
        "test_operations.py",
        "test_other_utils.py",
        "test_packing.py",
        "test_perf_guards.py",
        "test_precision.py",
        "test_ring_attention.py",
        "test_state.py",
        "test_tpu_compile.py",
        "test_tracking.py",
        "test_zero_sharding.py",
    ]),
    "models": (12, [
        "test_adapters.py",
        "test_big_modeling.py",
        "test_cached_attention_bounded.py",
        "test_cohere2_moe.py",
        "test_pangu_ultra_moe.py",
        "test_phi4flash.py",
        "test_fp8.py",
        "test_generation.py",
        "test_hf_interop.py",
        "test_host_offload.py",
        "test_loadgen.py",
        "test_longcat_flash.py",
        "test_glm_moe_dsa.py",
        "test_moe_held.py",
        "test_loadtest_smoke.py",
        "test_memory_properties.py",
        "test_models.py",
        "test_observability.py",
        "test_op_scopes.py",
        "test_paged_attention_kernel.py",
        "test_paged_tick_attention.py",
        "test_selective_scan_kernel.py",
        "test_pipeline.py",
        "test_program_parts.py",
        "test_quantization.py",
        "test_serving.py",
        "test_serving_async.py",
        "test_serving_control.py",
        "test_serving_gateway.py",
        "test_serving_mesh.py",
        "test_serving_paged.py",
        "test_serving_quantized.py",
        "test_serving_supervisor.py",
    ]),
    "subproc": (12, [
        "test_chip_smoke.py",
        "test_cli.py",
        "test_cli_hostonly.py",
        "test_examples.py",
    ]),
    "multiprocess": (8, [
        "test_multiprocess.py",
    ]),
}


def lane_files(lane: str) -> list[str]:
    here = os.path.dirname(os.path.abspath(__file__))
    _, files = LANES[lane]
    return [os.path.join("tests", f) for f in files if os.path.exists(os.path.join(here, f))]


def all_assigned() -> set[str]:
    return {f for _, files in LANES.values() for f in files}


def main() -> int:
    if len(sys.argv) != 2 or sys.argv[1] not in LANES:
        print(f"usage: python tests/lanes.py {{{','.join(LANES)}}}", file=sys.stderr)
        return 2
    print(" ".join(lane_files(sys.argv[1])))
    return 0


if __name__ == "__main__":
    sys.exit(main())
