"""The runnable examples must actually run (subprocess smoke)."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_example(args, timeout=600):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable] + args,
        cwd=REPO, env=env, capture_output=True, text=True, timeout=timeout,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


class TestExamples:
    def test_quickstart(self):
        out = run_example(["examples/quickstart.py"])
        assert "BRCR exact: True" in out
        assert "BSTC lossless: True" in out

    def test_train_llm_short(self):
        out = run_example([
            "examples/train_llm.py", "--steps", "25", "--d-model", "64",
            "--layers", "2", "--seq-len", "64", "--batch", "2",
            "--vocab", "512", "--ckpt-every", "10",
        ])
        assert "improved" in out and "NOT improved" not in out

    def test_serve_llm_short(self):
        out = run_example([
            "examples/serve_llm.py", "--steps", "6", "--batch", "2",
            "--prompt-len", "16",
        ])
        assert "admission=chunked" in out
        assert "ttft_s p50=" in out  # serving metrics are always reported

    def test_bgpp_example(self):
        out = run_example(["examples/bgpp_sparse_attention.py"])
        assert "per-round alive counts" in out


class TestLaunchers:
    def test_train_launcher(self, tmp_path):
        out = run_example([
            "-m", "repro.launch.train", "--smoke", "--steps", "20",
            "--batch", "2",
            "--seq-len", "32", "--ckpt-every", "10",
            "--ckpt-dir", str(tmp_path / "ck"),
            "--heartbeat", str(tmp_path / "hb.json"),
        ])
        assert "done (0 failures survived)" in out

    def test_serve_launcher(self):
        out = run_example([
            "-m", "repro.launch.serve", "--smoke", "--requests", "2",
            "--slots", "2", "--max-new", "4",
        ])
        assert "2/2 requests" in out
