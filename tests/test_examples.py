"""Examples must stay runnable (they are the public-API contract)."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script, timeout=600, extra=()):
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    return subprocess.run([sys.executable, script, *extra],
                          capture_output=True, text=True, env=env,
                          cwd=ROOT, timeout=timeout)


def test_quickstart_runs():
    r = _run("examples/quickstart.py")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "correct=True" in r.stdout
    assert "=== hvx ===" in r.stdout and "=== dnnweaver ===" in r.stdout


def test_train_lm_learns(tmp_path):
    # 30 jax training steps with simulated stragglers run ~14 min on a
    # loaded CI host; 900s flaked right at the margin
    r = _run("examples/train_lm.py", timeout=1800,
             extra=("--steps", "30", "--ckpt-dir", str(tmp_path)))
    assert r.returncode == 0, r.stderr[-2000:]
    assert "loss" in r.stdout


@pytest.mark.slow
def test_compile_layers_sweep():
    r = _run("examples/compile_layers.py", timeout=1200)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "BERT-LG-GEMM1" in r.stdout


@pytest.mark.slow
def test_sweep_variants_example(tmp_path):
    r = _run("examples/sweep_variants.py", timeout=1200,
             extra=("--workers", "2", "--store", str(tmp_path / "store")))
    assert r.returncode == 0, r.stderr[-2000:]
    assert "each compiled exactly once" in r.stdout
    warm = [l for l in r.stdout.splitlines() if l.startswith("[warm]")]
    assert warm and ", 0 pipeline stages run" in warm[0]


@pytest.mark.search
def test_warm_start_search_example(tmp_path):
    r = _run("examples/warm_start_search.py", timeout=1200,
             extra=("--store", str(tmp_path / "store")))
    assert r.returncode == 0, r.stderr[-2000:]
    assert "3 units via serial" in r.stdout
    assert "seed(s) injected" in r.stdout
    assert "warm-start index:" in r.stdout
