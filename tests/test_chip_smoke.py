"""chip_smoke.py and the device-facing guards it relies on, on the CPU.

The smoke's check functions run here at ``reduced=True`` (Pallas kernels
in interpret mode); the script itself must refuse to run without a TPU.
"""
from __future__ import annotations

import importlib.util
import os
import pathlib
import shutil
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import pytest

from repro.core import hardware
from repro.core.measure import _block, time_callable
from repro.launch import compile_cache

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod          # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def _env(**extra):
    env = {"PATH": os.environ.get("PATH", "/usr/bin:/bin"),
           "JAX_PLATFORMS": "cpu"}
    env.update(extra)
    return env


# --- the smoke's checks, reduced, in interpret mode -------------------------

def test_smoke_kernels_reduced(smoke, capsys):
    smoke.check_kernels(reduced=True)
    lines = capsys.readouterr().out.splitlines()
    assert len(lines) == 7
    assert all("max_abs_err=" in line and "tol=" in line for line in lines)


def test_smoke_serve_reduced(smoke, capsys):
    out = smoke.serve(reduced=True)
    size = smoke.REDUCED
    assert out["tokens"] == 3 * size.requests * size.tokens
    text = capsys.readouterr().out
    assert "repeat_new_step_cache_entries=0 repeat_executables=0" in text


def test_smoke_kernel_vs_xla_reduced(smoke, capsys):
    smoke.kernel_vs_xla(reduced=True)
    assert "xla decode logits" in capsys.readouterr().out


def test_smoke_four_chips_on_virtual_devices(tmp_path):
    code = (f"import sys; sys.path.insert(0, {str(ROOT)!r})\n"
            "import chip_smoke\n"
            "chip_smoke.four_chips(reduced=True)\n")
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=600, cwd=tmp_path,
        env=_env(XLA_FLAGS="--xla_force_host_platform_device_count=4",
                 JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache")))
    assert r.returncode == 0, r.stderr[-2000:]
    assert "mesh={'data': 1, 'model': 4}" in r.stdout
    assert "four_chips: max |loss_4 - loss_1|" in r.stdout


# --- the script refuses to run off the chip ---------------------------------

def test_smoke_main_fails_on_cpu(tmp_path):
    r = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py")], capture_output=True,
        text=True, timeout=300, cwd=ROOT,
        env=_env(JAX_COMPILATION_CACHE_DIR=str(tmp_path)))
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr


def test_smoke_alone_fails(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    r = subprocess.run([sys.executable, "chip_smoke.py"], capture_output=True,
                       text=True, timeout=300, cwd=tmp_path, env=_env())
    assert r.returncode != 0
    assert r.stdout == ""


# --- compile cache ------------------------------------------------------------

def test_compile_cache_env_is_left_alone(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable_compile_cache()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_entries_land_in_env_dir(tmp_path):
    code = ("import jax, jax.numpy as jnp\n"
            "from repro.launch.compile_cache import enable_compile_cache\n"
            "enable_compile_cache()\n"
            "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
            "jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(8)).block_until_ready()\n"
            "print(jax.config.jax_compilation_cache_dir)\n")
    cache = tmp_path / "cache"
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=300, cwd=tmp_path,
        env=_env(PYTHONPATH=str(ROOT / "src"),
                 JAX_COMPILATION_CACHE_DIR=str(cache)))
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip() == str(cache)
    assert any(cache.iterdir())


# --- no device-hiding fallbacks -----------------------------------------------

class _FailingSync:
    def block_until_ready(self):
        raise RuntimeError("device sync failed")


def test_block_reraises_failed_sync():
    with pytest.raises(RuntimeError, match="device sync failed"):
        _block(_FailingSync())
    with pytest.raises(RuntimeError, match="device sync failed"):
        time_callable(_FailingSync, warmup=1, rounds=1, iters=1)
    _block({"a": jnp.ones(3), "b": 1.0})          # non-array leaves pass


def _fake_tpu(monkeypatch, kind):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices",
                        lambda *a: [types.SimpleNamespace(device_kind=kind)])


def test_get_hardware_raises_on_unknown_device_kind(monkeypatch):
    _fake_tpu(monkeypatch, "TPU v99 hypothetical")
    with pytest.raises(ValueError, match="no hardware spec"):
        hardware.get_hardware()


def test_get_hardware_resolves_the_attached_chip(monkeypatch):
    _fake_tpu(monkeypatch, "TPU v5 lite")
    assert hardware.get_hardware() is hardware.TPU_V5E
    assert hardware.get_hardware("tpu_v5e") is hardware.TPU_V5E
    with pytest.raises(ValueError, match="attached device"):
        hardware.get_hardware("h20")


def test_get_hardware_off_tpu_is_the_named_target():
    assert hardware.get_hardware() is hardware.TPU_V5E
    assert hardware.get_hardware("h20") is hardware.H20
