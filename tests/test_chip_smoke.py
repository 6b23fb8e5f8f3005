"""chip_smoke.py's phases on the CPU at tiny sizes.

The script itself refuses to run off a TPU; these tests drive its phase
functions directly (the toy graph, ``powerlaw_graph(200, 1500)``, the
CSR kernel in interpret mode, the sharded phase on four fake devices) so its
control flow and checks are exercised on every run.
"""
import importlib.util
import json
import os
import pathlib
import subprocess
import sys

import pytest

import jax

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def graph200():
    from repro.graph import powerlaw_graph

    return powerlaw_graph(200, 1500, seed=3)


def test_device_phase_refuses_the_cpu(smoke):
    with pytest.raises(AssertionError, match="not a TPU"):
        smoke.phase_device()


def test_reference_phase_on_toy(smoke):
    from repro.graph import toy_graph

    out = smoke.phase_reference(*toy_graph(), queries=4, budget_walks=1024)
    assert out["n"] == 8 and out["queries"] == 4
    assert out["max_abs_err"] <= out["error_bound"]


def test_reference_phase_on_powerlaw(smoke, graph200):
    out = smoke.phase_reference(*graph200, budget_walks=512)
    assert out["walks"] == 512 and len(out["per_query_err"]) == 16
    assert out["max_abs_err"] <= out["error_bound"]


def test_serving_phase(smoke, graph200):
    out = smoke.phase_serving(*graph200, budget_walks=256)
    assert out["epoch_vs_rebuild_max_abs"] == 0.0  # bitwise on the CPU
    assert out["versions"][1] > out["versions"][0]


def test_service_phase(smoke, graph200):
    out = smoke.phase_service(*graph200, budget_walks=128)
    assert out["statuses"] == [200] and out["requests"] == 32
    assert max(out["batch_hist"]) > 1
    assert out["version_after"] > out["version_before"]


def test_kernel_phase_interpret(smoke):
    assert jax.default_backend() == "cpu"
    out = smoke.phase_kernel(64, 512, 16)
    assert not out["native"]
    assert out["max_abs_diff_scores"] <= smoke.KERNEL_TOL
    assert out["max_abs_diff_total"] == 0.0  # the deposit is one add


_SHARDED_SCRIPT = r"""
import importlib.util, json, sys
spec = importlib.util.spec_from_file_location("chip_smoke", "chip_smoke.py")
smoke = importlib.util.module_from_spec(spec)
spec.loader.exec_module(smoke)
from repro.graph import powerlaw_graph
out = smoke.phase_sharded(*powerlaw_graph(400, 3000, seed=3), budget_walks=256)
print(json.dumps({p: out[p]["max_abs_diff"] for p in ("spmd", "ring", "epoch")}))
"""


def test_sharded_phase_on_four_fake_devices():
    """The --chips 4 phase on 4 fake XLA host devices (a subprocess: the
    device count is fixed when jax initializes)."""
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, "-c", _SHARDED_SCRIPT], capture_output=True,
        text=True, env=env, cwd=str(ROOT), timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    diffs = json.loads(out.stdout.splitlines()[-1])
    assert max(diffs.values()) <= 1e-4
