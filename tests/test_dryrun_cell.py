"""One real dry-run cell in a subprocess (512 virtual devices need a fresh
jax), proving the launch path end-to-end inside the test suite."""
import json
import os
import subprocess
import sys

import pytest


@pytest.mark.slow
def test_dryrun_single_cell(tmp_path):
    out = tmp_path / "cell.json"
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--arch", "smollm_360m", "--shape", "decode_32k",
         "--mesh", "single", "--out", str(out)],
        env=env, capture_output=True, text=True, timeout=900,
        cwd=os.path.join(os.path.dirname(__file__), ".."),
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    row = json.loads(out.read_text().splitlines()[0])
    assert row["status"] == "OK"
    assert row["chips"] == 256
    assert row["roofline"]["memory_s"] > 0
    assert row["collectives"]["total_bytes"] > 0


@pytest.mark.slow
def test_dryrun_skip_rule(tmp_path):
    """long_500k must SKIP for full-attention archs without compiling."""
    out = tmp_path / "skip.json"
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--arch", "codeqwen1_5_7b", "--shape", "long_500k",
         "--mesh", "single", "--out", str(out)],
        env=env, capture_output=True, text=True, timeout=300,
        cwd=os.path.join(os.path.dirname(__file__), ".."),
    )
    assert proc.returncode == 0
    row = json.loads(out.read_text().splitlines()[0])
    assert row["status"] == "SKIP"
    assert "sub-quadratic" in row["reason"]
