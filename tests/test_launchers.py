"""Launcher smoke tests (subprocess): the end-to-end train driver with
checkpoint resume, and the example scripts' entry points."""
import os
import subprocess
import sys

import pytest

from util import SRC


def run_py(args, timeout=540):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    # the launchers' mains place JAX's persistent compile cache in the
    # checkout; a test run must neither fill it nor read programs back
    env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    return subprocess.run([sys.executable] + args, env=env,
                          capture_output=True, text=True, timeout=timeout)


@pytest.mark.slow
def test_train_launcher_runs_and_resumes(tmp_path):
    base = ["-m", "repro.launch.train", "--arch", "llama3.2-3b",
            "--reduced", "--batch", "4", "--seq", "32", "--accum", "2",
            "--ckpt-dir", str(tmp_path), "--ckpt-every", "5",
            "--log-every", "5"]
    p1 = run_py(base + ["--steps", "8"])
    assert p1.returncode == 0, p1.stderr[-2000:]
    assert "final: step 8" in p1.stdout
    # resume: continues from the checkpoint, not from scratch
    p2 = run_py(base + ["--steps", "12"])
    assert p2.returncode == 0, p2.stderr[-2000:]
    assert "final: step 12" in p2.stdout


@pytest.mark.slow
def test_quickstart_example():
    p = run_py([os.path.join(os.path.dirname(__file__), "..", "examples",
                             "quickstart.py")])
    assert p.returncode == 0, p.stderr[-2000:]
    assert "OK" in p.stdout
