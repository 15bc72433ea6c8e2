"""Multi-device tests (8 host devices) run in subprocesses so the main
pytest process keeps its single-device view (XLA fixes the device count at
first init)."""
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(__file__)
SCRIPT = os.path.join(HERE, "_dist_checks.py")


def run_check(name: str, timeout: int = 420) -> str:
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, SCRIPT, name],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert proc.returncode == 0, f"{name} failed:\n{proc.stdout}\n{proc.stderr[-3000:]}"
    assert f"CHECK {name} OK" in proc.stdout
    return proc.stdout


@pytest.mark.slow
def test_moe_expert_parallel_matches_local():
    run_check("moe_ep")


@pytest.mark.slow
def test_pipeline_parallel_forward_and_grad():
    run_check("pipeline_parallel")


@pytest.mark.slow
def test_crosspod_gradient_compression():
    run_check("compression")


@pytest.mark.slow
def test_elastic_remesh_8_to_4_devices():
    run_check("elastic_remesh")


def test_tp_serving_repeats_kv_heads_below_mesh():
    run_check("tp_kv_repeat")
