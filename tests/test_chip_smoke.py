"""chip_smoke.py on the CPU: its phases at RESNET_SMOKE, its refusal to
run without a TPU, and the compile-cache placement it relies on."""
import importlib.util
import os
import pathlib
import subprocess
import sys

import pytest

from repro.configs import RESNET_SMOKE
from repro.runtime import compile_cache

ROOT = pathlib.Path(__file__).resolve().parent.parent
SCRIPT = ROOT / "chip_smoke.py"


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _env(**extra):
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"), **extra)
    env.pop("XLA_FLAGS", None)
    return env


def test_phase_serving_passes_its_checks_at_smoke(chip_smoke):
    lines = []
    out = chip_smoke.phase_serving(RESNET_SMOKE, 0, lines.append)
    assert out["hb_rounds"] > 0 and out["exact_rounds"] > out["hb_rounds"]
    assert 0 < out["input_scale"] <= 1.0
    assert any("SimComm" in line for line in lines)
    assert any("bit-identical to core.gmw_ref" in line for line in lines)
    # default batch policy: the 3-request mix is one fused micro-batch
    assert any("micro-batch of 3 requests" in line for line in lines)


def test_phase_kernels_passes_its_checks_at_smoke(chip_smoke):
    out = chip_smoke.phase_kernels(RESNET_SMOKE, 0, lambda _: None)
    assert out["rounds_k64_m0"] > out["rounds_k21_m13"] > 0


def test_logit_error_bound_is_tight_on_an_exact_forward(chip_smoke):
    """With nothing to lose (exact ring) the bound is the fixed-point
    term alone, far below the 8-bit window's."""
    import jax
    from repro.core.hummingbird import HBLayer
    from repro.models import resnet

    params = chip_smoke.fixed_point_params(
        resnet.init(jax.random.PRNGKey(0), RESNET_SMOKE))
    x = chip_smoke.on_grid(jax.random.normal(jax.random.PRNGKey(1),
                                             (1, 3, 16, 16)) * 0.5)
    n = resnet.n_relu_groups(RESNET_SMOKE)
    _, exact = chip_smoke.logit_error_bound(params, RESNET_SMOKE, x,
                                            (HBLayer(),) * n)
    _, window = chip_smoke.logit_error_bound(params, RESNET_SMOKE, x,
                                             (HBLayer(21, 13),) * n)
    assert float(exact.max()) < 0.01 < float(window.min())


def test_four_chip_phase_on_forced_host_devices():
    """--four-chips' mesh phase on four forced CPU devices: bit-identical
    to the one-device replay, census == schedule."""
    code = (
        "import os, sys\n"
        "os.environ['XLA_FLAGS'] = "
        "'--xla_force_host_platform_device_count=4'\n"
        f"sys.path.insert(0, {str(ROOT)!r})\n"
        "import chip_smoke\n"
        "from repro.configs import RESNET_SMOKE\n"
        "from repro.launch.mesh import make_mpc_mesh\n"
        "print(chip_smoke.phase_mesh(RESNET_SMOKE, 0, make_mpc_mesh(2), "
        "print))\n")
    out = subprocess.run([sys.executable, "-c", code], env=_env(),
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, (out.stdout[-3000:], out.stderr[-3000:])
    assert "bit-identical" in out.stdout


@pytest.mark.parametrize("extra", [{}, {"HB_PALLAS_INTERPRET": "1"}])
def test_script_refuses_without_a_tpu(extra):
    out = subprocess.run([sys.executable, str(SCRIPT)],
                         env=_env(JAX_PLATFORMS="cpu", **extra),
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout


def test_cache_dir_honours_env_else_fixed_checkout_path(monkeypatch,
                                                         tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.cache_dir() == str(tmp_path)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert compile_cache.cache_dir() == str(ROOT / ".jax_cache")


def test_cache_files_land_in_env_dir_and_hit_on_rerun(tmp_path):
    """Two processes with JAX_COMPILATION_CACHE_DIR set: the first writes
    its entries there, the second reads them back."""
    code = ("import jax, jax.numpy as jnp\n"
            "from repro.runtime.compile_cache import cache_stats, "
            "enable_compile_cache\n"
            "print(enable_compile_cache())\n"
            "jax.jit(lambda x: jnp.cumsum(x) @ x)(jnp.ones(64))"
            ".block_until_ready()\n"
            "print(cache_stats()['hits'])\n")
    env = _env(JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PLATFORMS="cpu")
    runs = [subprocess.run([sys.executable, "-c", code], env=env,
                           capture_output=True, text=True, timeout=300)
            for _ in range(2)]
    for r in runs:
        assert r.returncode == 0, r.stderr[-2000:]
    assert runs[0].stdout.split()[0] == str(tmp_path / "cache")
    assert any((tmp_path / "cache").iterdir())
    assert int(runs[0].stdout.split()[-1]) == 0
    assert int(runs[1].stdout.split()[-1]) > 0


def test_entry_point_imports_touch_no_device():
    """Importing the entry points must not initialise a JAX backend: a
    parent that did would hold the accelerator its children need."""
    code = ("import repro.launch.party_host, repro.serve.frontend, "
            "repro.launch.serve, repro.transport\n"
            "from jax._src import xla_bridge\n"
            "print(sorted(xla_bridge._backends))\n")
    out = subprocess.run([sys.executable, "-c", code], env=_env(),
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip() == "[]"
