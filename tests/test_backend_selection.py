"""Decisions taken from the platform rather than from flags: interpret mode
from the JAX backend, the tuner's chip from the device kind, the compile
cache from the environment or a fixed in-checkout path — and a chip smoke
run that refuses to run anywhere but a TPU."""

import os
import subprocess
import sys

import jax
import pytest

from repro.core import hardware
from repro.core.tuner import Autotuner
from repro.kernels.mode import resolve_interpret
from repro.launch import compile_cache

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))


def test_interpret_resolves_from_backend_and_explicit_wins():
    assert jax.default_backend() == "cpu"
    assert resolve_interpret() is True
    assert resolve_interpret(None) is True
    assert resolve_interpret(False) is False
    assert resolve_interpret(True) is True


def test_device_kind_table_maps_v5e():
    assert hardware.chip_for_device_kind("TPU v5 lite").name == "tpu_v5e"
    for kind, chip in hardware.DEVICE_KINDS.items():
        assert hardware.chip_for_device_kind(kind) is hardware.CHIPS[chip]


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no ChipSpec"):
        hardware.chip_for_device_kind("TPU v9 imaginary")


def test_cpu_host_tunes_for_target_chip(monkeypatch):
    monkeypatch.delenv("REPRO_TARGET_CHIP", raising=False)
    assert hardware.default_chip().name == "tpu_v5e"
    monkeypatch.setenv("REPRO_TARGET_CHIP", "tpu_v6e")
    assert hardware.default_chip().name == "tpu_v6e"
    assert Autotuner().backend.chip.name == "tpu_v6e"


def test_compile_cache_honours_env(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.compile_cache_dir() == str(tmp_path)
    # JAX reads the variable itself; the helper sets no other directory.
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_one_ignored_path_in_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    first = compile_cache.compile_cache_dir()
    assert first == compile_cache.compile_cache_dir()
    assert first == os.path.join(ROOT, ".jax_cache")
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def _run_smoke(**env):
    full = dict(os.environ, JAX_PLATFORMS="cpu", **env)
    return subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                          env=full, capture_output=True, text=True,
                          timeout=120)


def test_chip_smoke_refuses_cpu():
    out = _run_smoke()
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "needs a TPU" in out.stderr


def test_chip_smoke_refuses_kernel_guard():
    out = _run_smoke(REPRO_KERNEL_GUARD="1")
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "REPRO_KERNEL_GUARD" in out.stderr
