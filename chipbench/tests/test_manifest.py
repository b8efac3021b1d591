"""Manifest hygiene: names, units, arrows and files, for BENCHMARK.json and
for the toy manifest the CPU rehearsals use."""

import json
import re

import pytest

from chipbench import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
MANIFESTS = [harness.ROOT / "BENCHMARK.json",
             harness.PACKAGE / "tests" / "toy" / "BENCHMARK.json"]


@pytest.fixture(params=MANIFESTS, ids=["real", "toy"])
def manifest(request):
    return json.loads(request.param.read_text())


def test_names_and_units(manifest):
    names = ([c["name"] for c in manifest["configs"]] + [w["name"] for w in manifest["workloads"]]
             + [w["traffic"] for w in manifest["workloads"]]
             + [m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]])
    assert all(NAME.match(n) for n in names), names
    metrics = manifest["end_to_end"] + manifest["per_layer"]
    assert all(UNIT.match(m["unit"]) for m in metrics)
    assert all(m["better"] in ("lower", "higher") for m in metrics)
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert all(m["source"] in ("host_clock", "device_trace") for m in manifest["end_to_end"])
    assert all(0 < m["bound"] <= 0.1 for m in manifest["end_to_end"])
    assert any(m["name"] == "setup_s" for m in manifest["end_to_end"])
    for text in [w["why"] for w in manifest["workloads"]] + [c["why"] for c in manifest["configs"]]:
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_every_arrow_points_at_a_metric_its_cells_report(manifest):
    cells = {w["name"] for w in manifest["workloads"]}
    e2e = {m["name"]: set(m.get("workloads", cells)) for m in manifest["end_to_end"]}
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e, m
        assert m["workloads"] and set(m["workloads"]) <= cells, m
        assert set(m["workloads"]) <= e2e[m["moves"]], m
    for cell in cells:                       # setup_s, another end-to-end metric, a layer metric
        assert len([n for n, ws in e2e.items() if cell in ws]) >= 2
        assert any(cell in m["workloads"] for m in manifest["per_layer"])
    assert any("mfu" in m["name"] for m in manifest["per_layer"])


def test_every_file_named_exists(manifest):
    data_dir = harness.ROOT / manifest["paths"][0]
    used = {w["config"] for w in manifest["workloads"]}
    assert used == {c["name"] for c in manifest["configs"]}
    files = [c["file"] for c in manifest["configs"]]
    assert len(set(files)) == len(files)
    for c in manifest["configs"]:
        cfg = json.loads((harness.ROOT / c["file"]).read_text())
        assert cfg["reduced"] == c["reduced"]
        assert (harness.PACKAGE / "models" / f"{cfg['family']}.py").is_file()
    for w in manifest["workloads"]:
        mix = json.loads((data_dir / "traffic" / f"{w['traffic']}.json").read_text())
        assert (harness.PACKAGE / "drivers" / f"{mix['driver']}.py").is_file()
        limits = json.loads((data_dir / "limits" / f"{w['name']}.json").read_text())
        assert limits["limits"]
        harness.load_cell(harness.ROOT / "BENCHMARK.json" if data_dir == harness.PACKAGE
                          else data_dir / "BENCHMARK.json", w["name"])
    for m in manifest["per_layer"]:
        assert (harness.PACKAGE / "layer_metrics" / f"{m['name']}.py").is_file(), m["name"]


def test_the_harness_names_no_cell():
    manifest = json.loads(MANIFESTS[0].read_text())
    names = ({c["name"] for c in manifest["configs"]} | {w["name"] for w in manifest["workloads"]}
             | {w["traffic"] for w in manifest["workloads"]}
             | {m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]})
    names.discard("setup_s")                 # the one metric every driver reports by contract
    for source in ("run.py", "harness.py", "trace_reduce.py", "traffic.py"):
        text = (harness.PACKAGE / source).read_text()
        assert not [n for n in names if re.search(rf"[\"']{re.escape(n)}[\"']", text)], source


def test_published_widths_are_untouched():
    cfg = json.loads((harness.PACKAGE / "configs" / "mistral-7b-v0.1-d2.json").read_text())
    assert (cfg["hidden_size"], cfg["intermediate_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["vocab_size"], cfg["sliding_window"]) == \
        (4096, 14336, 32, 8, 32000, 4096)
    moe = json.loads((harness.PACKAGE / "configs" / "mixtral-8x7b-v0.1-d3.json").read_text())
    assert (moe["hidden_size"], moe["intermediate_size"], moe["num_local_experts"],
            moe["num_experts_per_tok"], moe["num_key_value_heads"]) == (4096, 14336, 8, 2, 8)
