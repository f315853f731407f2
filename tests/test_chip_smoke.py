"""chip_smoke.py off the chip: its phases at tiny sizes, with the
kernel interpreted, and its refusal to run without a TPU."""
import importlib.util
import os
import pathlib
import shutil
import subprocess
import sys
import textwrap

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def tpu_selection(monkeypatch):
    """Auto-selection as on a TPU; the kernel still runs interpreted,
    since JAX's own backend stays the CPU."""
    from repro.backends import registry
    monkeypatch.setattr(registry, "_device_default", lambda: "tpu")


def test_batch_phase(smoke, tpu_selection):
    ref, q = smoke.batch_data(np.random.default_rng(0), 16, 24, 700)
    out = smoke.phase_batch(ref, q)
    assert out["cost"].shape == (16,) and out["end"].max() < 700
    assert out["kernel_in_hlo"] is False      # interpreted off the chip


def test_search_phase(smoke):
    refs, q = smoke.search_data(np.random.default_rng(1), 3, 600, 8, 32)
    assert len(refs) == 3 and q.shape == (8, 32)
    smoke.phase_search(refs, q)


def test_grad_phase(smoke):
    rng = np.random.default_rng(2)
    ref, pred = smoke.batch_data(rng, 8, 32, 500)
    small_ref, small_pred = smoke.batch_data(rng, 8, 16, 256)
    smoke.phase_grad(ref, pred, small_ref, small_pred)


def test_distributed_phase_on_four_cpu_devices():
    code = textwrap.dedent("""
        import importlib.util, sys
        import numpy as np, jax
        spec = importlib.util.spec_from_file_location("chip_smoke",
                                                      sys.argv[1])
        smoke = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(smoke)
        ref, q = smoke.batch_data(np.random.default_rng(3), 8, 24, 512)
        smoke.phase_distributed(ref, q, jax.devices()[:4], row_block=8)
        print("DIST-SMOKE-OK")
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    out = subprocess.run([sys.executable, "-c", code,
                          str(ROOT / "chip_smoke.py")], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "DIST-SMOKE-OK" in out.stdout


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_exits_nonzero_without_tpu(tmp_path, where):
    """Under JAX_PLATFORMS=cpu, or copied out of the repository, the
    script fails and prints no result line."""
    script = ROOT / "chip_smoke.py"
    if where == "alone":
        script = pathlib.Path(shutil.copy(script, tmp_path))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    out = subprocess.run([sys.executable, str(script)], env=env,
                         cwd=tmp_path, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    if where == "repo":
        assert "no TPU found" in out.stderr
