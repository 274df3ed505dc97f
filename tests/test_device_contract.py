"""What a run may say about the device it ran on: measured utilization
only from a known chip's published peaks ("not measured" off the chip),
and a persistent compile cache at a fixed, externally placeable path."""
import os
import pathlib
import subprocess
import sys
import types

import jax
import pytest

from repro.launch import compile_cache
from repro.launch.serve import format_mfu
from repro.perf.roofline_model import DEVICE_PEAKS, device_peaks

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _device(platform, kind):
    return types.SimpleNamespace(platform=platform, device_kind=kind)


class TestDevicePeaks:
    def test_v5e_published_peaks(self):
        p = device_peaks(_device("tpu", "TPU v5 lite"))
        assert (p.bf16_flops, p.int8_ops, p.hbm_bytes_per_s) == (
            197e12, 393e12, 819e9)

    def test_unknown_tpu_kind_is_an_error(self):
        assert "TPU v9 imaginary" not in DEVICE_PEAKS
        with pytest.raises(ValueError, match="TPU v9 imaginary"):
            device_peaks(_device("tpu", "TPU v9 imaginary"))

    def test_cpu_is_not_measured(self):
        assert device_peaks(jax.devices()[0]) is None
        assert format_mfu(None) == "not measured"
        assert format_mfu(0.25) == "2.50e-01"


class TestCompileCache:
    def test_env_placed_dir_is_kept(self, monkeypatch):
        before = jax.config.jax_compilation_cache_dir
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert compile_cache.use_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == before

    def test_default_is_checkout_root(self, monkeypatch):
        before = jax.config.jax_compilation_cache_dir
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        try:
            got = compile_cache.use_compile_cache()
            assert got == str(ROOT / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
        finally:
            jax.config.update("jax_compilation_cache_dir", before)

    def test_env_placed_dir_receives_entries(self, tmp_path):
        """A fresh process with the variable set writes its compiles
        there (the program sets no other directory)."""
        code = ("import jax\n"
                "from repro.launch.compile_cache import use_compile_cache\n"
                "use_compile_cache()\n"
                "jax.config.update("
                "'jax_persistent_cache_min_compile_time_secs', 0)\n"
                "jax.jit(lambda x: x * 2 + 1)(3.0).block_until_ready()\n")
        env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
                   PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu")
        subprocess.run([sys.executable, "-c", code], env=env, check=True,
                       timeout=120)
        assert any(tmp_path.iterdir())
