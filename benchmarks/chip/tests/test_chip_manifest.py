"""BENCHMARK.json against the benchmark's files and the manifest rules."""
import json
import os
import re
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import manifest  # noqa: E402

M = manifest.load_manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in M["workloads"]]


def test_top_level_keys():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert M["paths"] == ["benchmarks/chip"]
    assert M["command"][1] == "benchmarks/chip/run_cell.py"
    assert 1 <= M["run_seconds"] <= 51


def test_names_units_and_keys():
    names = []
    for c in M["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        names.append(c["name"])
        assert all(NAME.match(k) for k in c["reduced"])
    for w in M["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] == 1 and len(w["why"]) <= 200
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        names.append(w["name"])
    for m in M["end_to_end"] + M["per_layer"]:
        names.append(m["name"])
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in M["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in M["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    assert all(NAME.match(n) for n in names)
    assert len(names) == len(set(names))
    assert len(json.dumps(M)) < 64 * 1024


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_are_found_by_name(cell):
    c = manifest.cell(cell)
    driver = os.path.join(HERE, "drivers", f"{c.config['kind']}.py")
    ref = os.path.join(HERE, "refs", f"{c.config['reference']}.py")
    assert os.path.isfile(driver) and os.path.isfile(ref)
    reported = {m["name"] for m in c.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert c.per_layer
    for m in c.per_layer:
        assert m["moves"] in reported
    for m in c.end_to_end + c.per_layer:
        assert callable(manifest.load_module("metrics", m["name"]).read)


def test_metric_readers_state_no_unit_layer_or_moves():
    """Only BENCHMARK.json says a metric's unit, layer and moves; each
    reader under metrics/ is a ``read`` and nothing that could drift."""
    names = {m["name"] for m in M["end_to_end"] + M["per_layer"]}
    files = {f[:-3] for f in os.listdir(os.path.join(HERE, "metrics"))
             if f.endswith(".py")}
    assert files == names
    for n in names:
        mod = manifest.load_module("metrics", n)
        assert not {"UNIT", "LAYER", "MOVES"} & set(vars(mod))


def test_every_config_is_used_and_its_file_matches():
    used = {w["config"] for w in M["workloads"]}
    for c in M["configs"]:
        assert c["name"] in used
        assert c["file"].startswith("benchmarks/chip/configs/")
        with open(os.path.join(manifest.ROOT, c["file"])) as f:
            body = json.load(f)
        assert body["name"] == c["name"] and body["source"] == c["source"]
        assert sorted(body["reduced"]) == sorted(c["reduced"])
        assert all(v is not None for v in body["limits"].values())


def _hashes(root):
    out = {}
    for dp, _, files in os.walk(root):
        for f in files:
            p = os.path.join(dp, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hash(fh.read())
    return out


def test_a_new_cell_and_metric_need_no_edit(tmp_path, monkeypatch):
    """Add a cell, a traffic mix and a per-layer metric to a copy of the
    benchmark as new files and manifest entries only: the harness finds
    them, and no file that was there changes."""
    bench = tmp_path / "benchmarks" / "chip"
    shutil.copytree(HERE, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "tests", "testdata"))
    before = _hashes(bench)
    (bench / "traffic" / "chat_slow.json").write_text(json.dumps(
        dict(manifest.cell("qwen3-chat").traffic, rate_rps=0.25)))
    (bench / "metrics" / "tokens_per_step.py").write_text(
        "def read(ctx):\n    return None\n")
    m = json.loads(json.dumps(M))
    m["workloads"].append({"name": "qwen3-chat-slow", "config": "qwen3-1.7b",
                           "traffic": "chat_slow", "chips": 1, "why": "x"})
    for e in m["end_to_end"] + m["per_layer"]:
        if "qwen3-chat" in e.get("workloads", []):
            e["workloads"].append("qwen3-chat-slow")
    m["per_layer"].append({"name": "tokens_per_step", "unit": "tokens",
                           "better": "higher", "source": "host_clock",
                           "layer": "engine", "moves": "decode_tok_s",
                           "workloads": ["qwen3-chat-slow"]})
    monkeypatch.setattr(manifest, "HERE", str(bench))
    monkeypatch.setattr(manifest, "ROOT", str(tmp_path))
    for f in M["configs"]:
        dst = tmp_path / f["file"]
        assert dst.exists()
    c = manifest.cell("qwen3-chat-slow", m)
    assert c.traffic["rate_rps"] == 0.25
    assert "tokens_per_step" in [p["name"] for p in c.per_layer]
    assert {e["name"] for e in c.end_to_end} == {
        e["name"] for e in manifest.cell("qwen3-chat", m).end_to_end}
    assert "tokens_per_step" not in [
        p["name"] for p in manifest.cell("vgg16-cifar-b256", m).per_layer]
    after = _hashes(bench)
    assert {k: v for k, v in after.items() if k in before} == before
