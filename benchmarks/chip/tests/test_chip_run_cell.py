"""run_cell.py refuses without a TPU and prints no result."""
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(HERE))


@pytest.mark.parametrize("cell", ["qwen3-chat", "vgg16-cifar-b256"])
def test_refuses_on_cpu(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "run_cell.py"), "--workload",
         cell, "--seed", str(2**31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "refusing" in p.stderr
    for name in ("decode_tok_s", "ttft_p95_ms", "images_s", "setup_s"):
        assert name not in p.stdout + p.stderr
