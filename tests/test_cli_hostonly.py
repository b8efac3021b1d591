"""Host-only CLI subcommands never initialise a non-CPU backend.

A chip belongs to one process at a time, so a command that only reads
shapes or files (``estimate-memory``, ``merge-weights``, ``config
--default``) — or that only forks the real worker (``launch``'s parent) —
must not open the accelerator: run beside a trainer or a server that holds
the chip it would fail, and run first it would take the chip away. Each
command runs in a child with no platform pin in its environment; after it
returns, the child reports which jax backends it created.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WRAPPER = """
import json, sys
from accelerate_tpu.commands.accelerate_cli import main
sys.argv = ["accelerate-tpu"] + json.loads(sys.argv[1])
try:
    rc = main() or 0
except SystemExit as e:
    rc = e.code or 0
from jax._src import xla_bridge
print("ATPU_BACKENDS=" + json.dumps({"rc": rc, "backends": sorted(xla_bridge._backends)}))
"""


def _ckpt(tmp_path):
    from safetensors.numpy import save_file

    src = tmp_path / "ckpt"
    src.mkdir()
    save_file({"w": np.ones((4, 4), np.float32)}, str(src / "model.safetensors"))
    return [str(src), str(tmp_path / "merged.safetensors")]


def _noop_script(tmp_path):
    script = tmp_path / "noop.py"
    script.write_text("print('LAUNCHED_OK')\n")
    return [str(script)]


@pytest.mark.parametrize("argv,extra,expect", [
    (["estimate-memory", "llama-tiny", "--dtypes", "bfloat16"], None, "bfloat16"),
    (["merge-weights"], _ckpt, None),
    (["config", "--default"], None, None),
    (["launch"], _noop_script, "LAUNCHED_OK"),
], ids=["estimate-memory", "merge-weights", "config-default", "launch-parent"])
def test_command_leaves_the_accelerator_alone(argv, extra, expect, tmp_path):
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)  # an unpinned user shell
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["ACCELERATE_TPU_CONFIG_DIR"] = str(tmp_path / "cfg")
    if extra is not None:
        argv = argv + extra(tmp_path)
    r = subprocess.run([sys.executable, "-c", WRAPPER, json.dumps(argv)],
                       capture_output=True, text=True, env=env, timeout=300, cwd=REPO)
    assert r.returncode == 0, r.stderr[-2000:]
    marker = [l for l in r.stdout.splitlines() if l.startswith("ATPU_BACKENDS=")]
    assert marker, r.stdout[-2000:]
    report = json.loads(marker[-1].split("=", 1)[1])
    assert report["rc"] == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert set(report["backends"]) <= {"cpu"}, (
        f"{argv[0]} initialised {report['backends']}: it would take (or fail "
        "to get) a chip that a running trainer or server holds")
    if expect is not None:
        assert expect in r.stdout
