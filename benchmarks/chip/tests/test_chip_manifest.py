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


STUB_REF = '''"""Stand-in reference of a DeepSeekMoE decoder: the fields only."""


def program_fields(c):
    return {"n_layers": c["num_hidden_layers"], "d_model": c["hidden_size"],
            "vocab": c["vocab_size"], "n_heads": c["num_attention_heads"],
            "n_kv_heads": c["num_key_value_heads"],
            "d_head": c["hidden_size"] // c["num_attention_heads"],
            "d_ff": c["intermediate_size"], "rope_theta": c["rope_theta"],
            "qk_norm": c["qk_norm"], "activation": "silu", "family": "moe"}
'''


def _decode_trace():
    """A traced window of 0-10 s: one decode program holding a named
    kernel of each class."""
    from trace_reduce import Event, Reduced
    ms = 1e6
    ops = [Event(f"%bitserial_matmul_{c}.{i} = s32[8,128]{{1,0}} "
                 "custom-call()", (2 + i) * ms, (3 + i) * ms)
           for i, c in enumerate(("attn_q", "ffn_up", "lm_head"))]
    return Reduced(w0=0.0, w1=10e9, modules=[[Event("jit_decode_fn(1)",
                                                     ms, 9 * ms)]],
                   ops=[ops], spans=[])


def test_a_second_lm_architecture_needs_no_edit(tmp_path, monkeypatch):
    """Add a DeepSeekMoE configuration (no head_dim, qk-norm off), its
    stand-in reference, a traffic mix and a cell as new files and manifest
    entries only: the LM driver checks the file against the program's
    config through the reference's ``program_fields``, the readers of
    decode work read None where the reference counts none, and no file
    that was there changes."""
    from types import SimpleNamespace

    import work
    bench = tmp_path / "benchmarks" / "chip"
    shutil.copytree(HERE, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "tests", "testdata"))
    before = _hashes(bench)
    cfg = {"name": "deepseek-moe-smoke", "kind": "lm",
           "source": "https://huggingface.co/deepseek-ai/deepseek-moe-16b-base",
           "program_config": "deepseek-moe-16b", "program_smoke": True,
           "reference": "deepseek_moe_stub", "hidden_size": 64,
           "intermediate_size": 128, "moe_intermediate_size": 32,
           "num_hidden_layers": 2, "num_attention_heads": 4,
           "num_key_value_heads": 4, "n_routed_experts": 8,
           "num_experts_per_tok": 3, "n_shared_experts": 1,
           "vocab_size": 256, "max_position_embeddings": 128,
           "rope_theta": 500000.0, "qk_norm": False, "reduced": {},
           "limits": {"served_gap": 2.0}}
    (bench / "configs" / "deepseek-moe-smoke.json").write_text(
        json.dumps(cfg))
    (bench / "refs" / "deepseek_moe_stub.py").write_text(STUB_REF)
    (bench / "traffic" / "chat_short.json").write_text(json.dumps(dict(
        manifest.cell("qwen3-chat").traffic, prompt_lens=[16, 32],
        prompt_p=[0.5, 0.5], max_batch=2, max_seq=128,
        output={"dist": "uniform", "min": 1, "max": 64})))
    m = json.loads(json.dumps(M))
    m["configs"].append({"name": cfg["name"], "source": cfg["source"],
                         "file": "benchmarks/chip/configs/"
                                 "deepseek-moe-smoke.json",
                         "reduced": [], "why": "x"})
    m["workloads"].append({"name": "deepseek-chat", "config": cfg["name"],
                           "traffic": "chat_short", "chips": 1, "why": "x"})
    for e in m["end_to_end"] + m["per_layer"]:
        if "qwen3-chat" in e.get("workloads", []):
            e["workloads"].append("deepseek-chat")
    monkeypatch.setattr(manifest, "HERE", str(bench))
    monkeypatch.setattr(manifest, "ROOT", str(tmp_path))

    c = manifest.cell("deepseek-chat", m)
    assert "head_dim" not in c.config and c.config["qk_norm"] is False
    lm = manifest.load_module("drivers", c.config["kind"])
    pc = lm.program_config(c.config, c.traffic)
    assert (pc.family, pc.qk_norm, pc.max_seq) == ("moe", False, 128)
    with pytest.raises(ValueError, match="deepseek-moe-16b"):
        lm.program_config(dict(c.config, num_attention_heads=8), c.traffic)

    steps = [SimpleNamespace(t0=1.0, contexts=[40, 50]),
             SimpleNamespace(t0=2.0, contexts=[41])]
    decode = [p["name"] for p in c.per_layer
              if p["name"] == "decode_mfu_pct"
              or p["name"].startswith("bitserial_matmul_roofline.")]
    assert len(decode) == 5
    for config, reads in ((c.config, False),
                          (manifest.cell("qwen3-chat", m).config, True)):
        ctx = {"trace": _decode_trace(), "peaks": work.peaks("TPU v5 lite"),
               "rec": {"kind": "lm", "config": config, "steps": steps,
                       "t_w0": 0.0, "t_w1": 10.0}}
        for name in decode:
            got = manifest.load_module("metrics", name).read(ctx)
            assert (got is not None) == reads, (config["name"], name)

    after = _hashes(bench)
    assert {k: v for k, v in after.items() if k in before} == before
