"""Generate docs/package_reference/*.md from the package's docstrings.

Usage:  python docs/gen_api_reference.py

Pure introspection — imports the package on a pinned CPU platform, walks a
curated module list (mirroring the reference's package_reference/ layout),
and emits one markdown file per group: every public class with its public
methods, every public function, each with its signature and the first
paragraph of its docstring. Items without docstrings are listed bare, so
gaps are visible rather than hidden.
"""

from __future__ import annotations

import importlib
import inspect
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from accelerate_tpu.utils.platforms import force_cpu_platform  # noqa: E402

force_cpu_platform()

OUT_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "package_reference")

#: (output file stem, page title, [module paths], optional intro line)
GROUPS = [
    ("accelerator", "Accelerator", ["accelerate_tpu.accelerator"],
     "The main orchestrator: `prepare`, the fused train step, collectives, checkpoint hooks."),
    ("state", "State singletons", ["accelerate_tpu.state"],
     "Process topology, mesh, precision, and accumulation state shared framework-wide."),
    ("big_modeling", "Big-model inference", ["accelerate_tpu.big_modeling"],
     "Meta-init, device maps, weight streaming, the block-streaming executor."),
    ("generation", "Generation", ["accelerate_tpu.generation"],
     "Fused KV-cached decoding: greedy/sampling, beam search, encoder-decoder."),
    ("inference", "Pipelined inference", ["accelerate_tpu.inference"],
     "PiPPy-parity staged inference over the pp axis."),
    ("serving", "Serving",
     ["accelerate_tpu.serving.engine", "accelerate_tpu.serving.request",
      "accelerate_tpu.serving.scheduler", "accelerate_tpu.serving.metrics",
      "accelerate_tpu.serving.mesh_exec",
      "accelerate_tpu.serving.router", "accelerate_tpu.serving.gateway",
      "accelerate_tpu.serving.gateway_aio",
      "accelerate_tpu.serving.supervisor", "accelerate_tpu.serving.chaos",
      "accelerate_tpu.serving.control"],
     "Continuous-batching decode service: slot scheduler, fixed-shape "
     "prefill/decode programs, request handles, serving counters — plus "
     "mesh-sliced tensor-parallel execution (one replica = a multi-chip "
     "slice), the multi-replica router (health states, fault-tolerant "
     "failover), the stdlib HTTP gateway in front of it, and the "
     "self-healing layer: the fleet supervisor (hang watchdog, "
     "auto-restart, crash-loop circuit breaker) with its deterministic "
     "chaos-injection harness. The gateway has two wire front ends: the "
     "threading handler in `gateway` and the single-event-loop asyncio "
     "front end in `gateway_aio` that multiplexes thousands of SSE "
     "streams on one thread. `control` is the SLO policy layer over all "
     "of it: priority classes (queue ordering + preemption victim "
     "selection), per-tenant rate limits and weighted fair share at the "
     "gateway, and the supervisor-driven autoscaler that unparks/parks "
     "replicas against queue and page pressure."),
    ("loadgen", "Load generation",
     ["accelerate_tpu.loadgen.generator", "accelerate_tpu.loadgen.report"],
     "Open-loop serving load: seeded heavy-tailed arrival schedules and "
     "traffic profiles, the single-event-loop SSE driver that measures "
     "TTFT/ITL from *scheduled* arrival, and the goodput / overload-"
     "conformance report behind `accelerate-tpu loadtest` and the "
     "`extra.serving.open_loop` bench."),
    ("observability", "Observability",
     ["accelerate_tpu.observability.tracing",
      "accelerate_tpu.observability.flight_recorder",
      "accelerate_tpu.observability.promlint",
      "accelerate_tpu.observability.program_parts"],
     "Request-scoped tracing (trace ids, per-thread span rings, "
     "Chrome-trace export), the per-replica flight recorder behind "
     "failover postmortems, the Prometheus exposition linter, and the "
     "vocabulary of named parts every compiled program carries into a "
     "device trace."),
    ("adapters", "LoRA adapters",
     ["accelerate_tpu.adapters.lora", "accelerate_tpu.adapters.registry"],
     "Multi-tenant LoRA: config/init/merge and the frozen-base training "
     "split, plus the device-resident adapter bank the serving engine "
     "gathers from per slot — many tenants over one base model with "
     "zero recompiles."),
    ("data_loader", "Data loading", ["accelerate_tpu.data_loader"],
     "Sharded/dispatched loaders, global-batch assembly, skip/resume, packing."),
    ("optimizer_scheduler", "Optimizer & scheduler",
     ["accelerate_tpu.optimizer", "accelerate_tpu.scheduler"], None),
    ("checkpointing", "Checkpointing", ["accelerate_tpu.checkpointing"], None),
    ("tracking_logging", "Tracking & logging",
     ["accelerate_tpu.tracking", "accelerate_tpu.logging"], None),
    ("launchers", "Launchers & LocalSGD",
     ["accelerate_tpu.launchers", "accelerate_tpu.local_sgd"], None),
    ("parallel", "Parallelism",
     ["accelerate_tpu.parallel.mesh", "accelerate_tpu.parallel.sharding",
      "accelerate_tpu.parallel.pipeline", "accelerate_tpu.parallel.host_offload"],
     "The mesh, sharding rules, the pipeline scan, and host offload."),
    ("ops", "Ops & kernels",
     ["accelerate_tpu.ops.attention", "accelerate_tpu.ops.flash_pallas",
      "accelerate_tpu.ops.paged_attention",
      "accelerate_tpu.ops.ring_attention", "accelerate_tpu.ops.moe",
      "accelerate_tpu.ops.quant", "accelerate_tpu.ops.fused_loss"],
     "Pallas flash attention, the decode tick's paged attention, ring/Ulysses attention, MoE "
     "dispatch, fp8 matmul."),
    ("models", "Model zoo",
     ["accelerate_tpu.models.llama", "accelerate_tpu.models.mixtral",
      "accelerate_tpu.models.gpt2", "accelerate_tpu.models.gptj",
      "accelerate_tpu.models.gpt_neox", "accelerate_tpu.models.bloom",
      "accelerate_tpu.models.opt",
      "accelerate_tpu.models.phi",
      "accelerate_tpu.models.bert", "accelerate_tpu.models.t5",
      "accelerate_tpu.models.vit", "accelerate_tpu.models.resnet"],
     "Flax model families, all shardable by the same mesh rules and loadable "
     "from HF checkpoints."),
    ("kwargs", "Plugins & kwargs handlers", ["accelerate_tpu.utils.dataclasses"],
     "Every plugin/config dataclass `Accelerator` accepts."),
    ("precision", "Precision policies", ["accelerate_tpu.precision"], None),
    ("utilities", "Utilities",
     ["accelerate_tpu.utils.operations", "accelerate_tpu.utils.modeling",
      "accelerate_tpu.utils.memory", "accelerate_tpu.utils.random",
      "accelerate_tpu.utils.quantization", "accelerate_tpu.utils.environment",
      "accelerate_tpu.utils.platforms", "accelerate_tpu.utils.hf_interop",
      "accelerate_tpu.utils.profiling"], None),
    ("native", "Native IO", ["accelerate_tpu.native.io"],
     "The C++ parallel safetensors reader and token-bin prefetch ring."),
]


#: modules whose docstring is rendered whole (a table the reference should
#: carry), the rest of it as preformatted text under the first paragraph
WHOLE_DOCSTRING = {"accelerate_tpu.observability.program_parts"}


def first_paragraph(obj) -> str:
    import re

    doc = inspect.getdoc(obj)
    if not doc:
        return "*(no docstring)*"
    para = doc.split("\n\n")[0].replace("\n", " ").strip()
    # Dataclass reprs in docstrings can embed memory addresses; scrub them
    # so regeneration is deterministic (same policy as signature_of).
    return re.sub(r" at 0x[0-9a-f]+", "", para)


def signature_of(obj) -> str:
    import re

    try:
        sig = str(inspect.signature(obj))
    except (ValueError, TypeError):
        return "(...)"
    # Default-value reprs can embed memory addresses, and a module's its
    # install path; strip them so regeneration is deterministic (no
    # address-only or machine-only doc churn).
    sig = re.sub(r"<module '([\w.]+)' from '[^']*'>", r"\1", sig)
    return re.sub(r" at 0x[0-9a-f]+", "", sig)


def public_members(mod):
    """Classes and functions defined in (not imported into) the module."""
    classes, functions = [], []
    for name, obj in vars(mod).items():
        if name.startswith("_") or getattr(obj, "__module__", None) != mod.__name__:
            continue
        if inspect.isclass(obj):
            classes.append((name, obj))
        elif inspect.isfunction(obj):
            functions.append((name, obj))
    return classes, functions


def _doc_with_mro(cls, mname: str, obj) -> str:
    """Docstring of a member, falling back to base classes (an override
    without its own docstring inherits the interface's contract)."""
    target = obj.fget if isinstance(obj, property) else obj
    if inspect.getdoc(target):
        return first_paragraph(target)
    for base in cls.__mro__[1:]:
        parent = base.__dict__.get(mname)
        if parent is not None:
            ptarget = parent.fget if isinstance(parent, property) else parent
            if inspect.getdoc(ptarget):
                return first_paragraph(ptarget)
    return "*(no docstring)*"


def render_class(name: str, cls) -> list[str]:
    lines = [f"### `{name}{signature_of(cls)}`", "", first_paragraph(cls), ""]
    for mname, meth in sorted(vars(cls).items()):
        if mname.startswith("_") or not (inspect.isfunction(meth) or isinstance(meth, property)):
            continue
        doc = _doc_with_mro(cls, mname, meth)
        if isinstance(meth, property):
            lines.append(f"- **`.{mname}`** (property) — {doc}")
        else:
            lines.append(f"- **`.{mname}{signature_of(meth)}`** — {doc}")
    lines.append("")
    return lines


def render_module(path: str) -> list[str]:
    mod = importlib.import_module(path)
    classes, functions = public_members(mod)
    if not classes and not functions:
        return []
    lines = [f"## `{path}`", "", first_paragraph(mod), ""]
    if path in WHOLE_DOCSTRING:
        rest = inspect.getdoc(mod).split("\n\n", 1)[1:]
        lines += ["```text", *rest, "```", ""]
    for name, cls in classes:
        lines += render_class(name, cls)
    for name, fn in functions:
        lines += [f"### `{name}{signature_of(fn)}`", "", first_paragraph(fn), ""]
    return lines


def main() -> int:
    os.makedirs(OUT_DIR, exist_ok=True)
    index = ["# API reference", "",
             "Generated from docstrings by `python docs/gen_api_reference.py` — do not edit by hand.", ""]
    for stem, title, modules, intro in GROUPS:
        lines = [f"# {title}", ""]
        if intro:
            lines += [intro, ""]
        for path in modules:
            lines += render_module(path)
        with open(os.path.join(OUT_DIR, f"{stem}.md"), "w") as f:
            f.write("\n".join(lines).rstrip() + "\n")
        index.append(f"- [{title}]({stem}.md)")
        print(f"wrote package_reference/{stem}.md")
    index += ["", "CLI commands are documented in "
              "[Launching scripts](../basic_tutorials/launch.md); run "
              "`accelerate-tpu <command> --help` for flag-level detail."]
    with open(os.path.join(OUT_DIR, "index.md"), "w") as f:
        f.write("\n".join(index) + "\n")
    print("wrote package_reference/index.md")
    return 0


if __name__ == "__main__":
    sys.exit(main())
