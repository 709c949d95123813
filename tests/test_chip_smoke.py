"""The chip smoke script off the chip: it refuses to run without a TPU,
and its phases pass on the smoke configs with the Pallas interpreter
against the XLA route (the CPU rehearsal of the chip run)."""
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from repro import configs  # noqa: E402


def _ok_lines(stdout: str) -> list:
    out = []
    for line in stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if isinstance(obj, dict) and "ok" in obj:
            out.append(obj)
    return out


def test_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert not _ok_lines(r.stdout), r.stdout
    assert "no TPU" in r.stderr


def test_lm_phase_interpret_matches_xla():
    out = chip_smoke.lm_phase(configs.get("qwen3-1.7b", smoke=True),
                              backend="pallas_interpret", ref_backend="xla")
    assert out["streams_equal"] == chip_smoke.N_REQUESTS
    assert out["tokens"] == chip_smoke.N_REQUESTS * chip_smoke.GEN_LEN
    assert out["max_dlogits"] <= out["logit_tol"]
    for be in ("pallas_interpret", "xla"):
        assert {"convert_s", "fingerprint_s", "decode_compile_s",
                "serve_s"} <= set(out[be])


def test_cnn_phase_interpret_matches_xla():
    out = chip_smoke.cnn_phase(configs.get("paper-cnn", smoke=True),
                               backend="pallas_interpret", ref_backend="xla",
                               batch=4)
    assert out["predictions_equal"] == 4

