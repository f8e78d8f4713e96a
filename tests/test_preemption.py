"""Preemption fault-tolerance: SIGTERM mid-run -> clean checkpoint ->
resumed run completes with no lost steps."""

import os
import signal
import subprocess
import sys
import time

from repro.train import checkpoint
import pytest

# full XLA compiles: quick tier skips with -m "not slow"
pytestmark = pytest.mark.slow


def _launch(ckpt_dir: str, steps: int):
    return subprocess.Popen(
        [sys.executable, "-m", "repro.launch.train",
         "--arch", "qwen1.5-0.5b", "--reduced", "--d-model", "32",
         "--n-layers", "2", "--steps", str(steps), "--batch", "2",
         "--seq", "32", "--ckpt-dir", ckpt_dir, "--ckpt-every", "5",
         "--log-every", "5"],
        env={"PYTHONPATH": "src", "JAX_PLATFORMS": "cpu",
             "PATH": os.environ.get("PATH", ""),
             "HOME": os.environ.get("HOME", "/root")},
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)


def test_sigterm_checkpoints_and_resumes(tmp_path):
    ckpt = str(tmp_path)
    proc = _launch(ckpt, steps=2000)   # would run ~forever
    # wait for training to actually start making progress
    deadline = time.time() + 300
    while time.time() < deadline:
        if checkpoint.latest_step(ckpt):
            break
        time.sleep(1.0)
    assert checkpoint.latest_step(ckpt), "no checkpoint before preemption"
    proc.send_signal(signal.SIGTERM)
    out, _ = proc.communicate(timeout=180)
    assert proc.returncode == 0, out
    assert "preempted at step" in out, out[-800:]
    step = checkpoint.latest_step(ckpt)
    assert step and step >= 5

    # relaunch: resumes from the preemption checkpoint and finishes
    proc2 = _launch(ckpt, steps=step + 5)
    out2, _ = proc2.communicate(timeout=300)
    assert proc2.returncode == 0, out2
    assert f"resumed from step {step}" in out2, out2[-800:]
    assert "final loss" in out2
