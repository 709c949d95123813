"""CLI integration: the launchers run end-to-end as a user would invoke
them (subprocesses, CPU-scale smoke configs)."""
import os
import subprocess
import sys

import pytest

ENV = {"PYTHONPATH": "src", "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
       "HOME": os.environ.get("HOME", "/root")}


def _run(args, timeout=560):
    return subprocess.run([sys.executable] + args, capture_output=True,
                          text=True, cwd=".", env=ENV, timeout=timeout)


def test_train_cli_smoke(tmp_path):
    r = _run(["-m", "repro.launch.train", "--arch", "qwen3-1.7b",
              "--steps", "6", "--batch", "2", "--seq", "32",
              "--ckpt-dir", str(tmp_path), "--ckpt-every", "3"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "done" in r.stdout
    assert "loss" in r.stdout


def test_train_cli_qat_mode():
    r = _run(["-m", "repro.launch.train", "--arch", "qwen3-1.7b",
              "--steps", "3", "--batch", "2", "--seq", "32",
              "--mode", "fake_quant", "--a-bits", "8", "--w-bits", "8"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "done" in r.stdout


def test_serve_cli_int8():
    r = _run(["-m", "repro.launch.serve", "--arch", "qwen3-1.7b",
              "--mode", "serve_int8", "--batch", "2", "--prompt-len", "8",
              "--gen-len", "4"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "generated" in r.stdout and "done" in r.stdout


def test_serve_cli_packed():
    r = _run(["-m", "repro.launch.serve", "--arch", "mixtral-8x7b",
              "--mode", "serve_packed", "--batch", "2", "--prompt-len", "8",
              "--gen-len", "3"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "done" in r.stdout


@pytest.mark.parametrize("max_seq,unfinished", [("16", 0), ("13", 1)])
def test_serve_server_exit_code_counts_unfinished(tmp_path, max_seq,
                                                  unfinished):
    """Server mode exits non-zero when a request did not finish. Request j
    has prompt 8+j and 4 new tokens, so a 13-token cache fails the third
    request (10 + 4 > 13) and serves the other two. The run keeps its
    compilation cache where JAX_COMPILATION_CACHE_DIR says."""
    env = dict(ENV, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch", "qwen3-1.7b",
         "--mode", "serve_packed", "--server", "3", "--batch", "2",
         "--prompt-len", "8", "--gen-len", "4", "--max-seq", max_seq],
        capture_output=True, text=True, cwd=".", env=env, timeout=560)
    assert f"{3 - unfinished}/3 requests done unfinished={unfinished}" \
        in r.stdout, r.stdout[-2000:] + r.stderr[-2000:]
    if unfinished:
        assert r.returncode != 0
        assert "did not finish" in r.stderr
        assert "done\n" not in r.stdout
    else:
        assert r.returncode == 0, r.stderr[-2000:]
        assert r.stdout.endswith("done\n")
    assert any(tmp_path.iterdir()), "no compilation cache entries written"
