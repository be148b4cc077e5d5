"""Launcher entrypoints must run end-to-end on a 1-device mesh: train with
checkpoint/restart + straggler watchdog, and serve with batched requests."""
import subprocess
import sys
from pathlib import Path

import pytest

pytestmark = pytest.mark.slow  # subprocess e2e launchers: minutes, not tier-1

ROOT = Path(__file__).resolve().parent.parent


def _run(args, timeout=600):
    return subprocess.run(
        [sys.executable, "-m"] + args, cwd=ROOT, timeout=timeout,
        capture_output=True, text=True,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin:/usr/local/bin",
             "HOME": "/root"})


def test_train_launcher(tmp_path):
    r = _run(["repro.launch.train", "--arch", "minicpm-2b", "--reduced",
              "--dp", "1", "--tp", "1", "--batch", "4", "--seq", "32",
              "--steps", "6", "--ckpt-every", "3",
              "--ckpt-dir", str(tmp_path / "ck")])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "done: 6 steps" in r.stdout
    # restart resumes from the published checkpoint
    r2 = _run(["repro.launch.train", "--arch", "minicpm-2b", "--reduced",
               "--dp", "1", "--tp", "1", "--batch", "4", "--seq", "32",
               "--steps", "8", "--ckpt-every", "3",
               "--ckpt-dir", str(tmp_path / "ck")])
    assert r2.returncode == 0, r2.stderr[-2000:]
    assert "[restart] resumed from step 6" in r2.stdout


def test_serve_launcher():
    r = _run(["repro.launch.serve", "--arch", "qwen2.5-3b", "--reduced",
              "--requests",
              "4", "--slots", "2", "--max-new", "3", "--prompt-len", "8",
              "--max-seq", "32"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "served 4 requests" in r.stdout
    assert "SimFA-TPU decode prediction" in r.stdout
