"""Host-side parts of the entry points: which device spec a run reports,
where the compile cache lives, the serving CLI's engine, and the chip
smoke's refusal to run off a TPU."""

import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import jax
import pytest

from repro.core.hardware import (
    TPU_V4, TPU_V5E, TPU_V5P, device_hardware, hardware_for_kind,
)
from repro.launch import jax_cache
from repro.launch import serve
from repro.serving import ContinuousServeEngine

ROOT = Path(__file__).resolve().parents[1]


class TestDeviceKind:
    @pytest.mark.parametrize("kind,spec", [
        ("TPU v5 lite", TPU_V5E), ("TPU v5", TPU_V5P), ("TPU v4", TPU_V4)])
    def test_known_kinds(self, kind, spec):
        assert hardware_for_kind(kind) is spec
        dev = SimpleNamespace(platform="tpu", device_kind=kind)
        assert device_hardware(dev) is spec

    def test_unknown_tpu_kind_raises(self):
        with pytest.raises(KeyError, match="TPU v9"):
            device_hardware(SimpleNamespace(platform="tpu",
                                            device_kind="TPU v9"))

    def test_cpu_has_no_spec(self):
        assert device_hardware() is None        # tests run on the CPU
        assert device_hardware(SimpleNamespace(
            platform="cpu", device_kind="cpu")) is None

    def test_cpu_plans_for_the_explicit_target(self):
        assert serve.planning_hardware() is TPU_V5E


class TestCompileCacheDir:
    def test_env_var_is_respected(self, monkeypatch, tmp_path):
        monkeypatch.setenv(jax_cache.ENV_VAR, str(tmp_path))
        before = jax.config.jax_compilation_cache_dir
        assert jax_cache.compile_cache_dir() == str(tmp_path)
        assert jax_cache.enable_compile_cache() == str(tmp_path)
        # JAX reads the variable itself: nothing is set over it
        assert jax.config.jax_compilation_cache_dir == before

    def test_default_is_fixed_in_checkout(self, monkeypatch):
        monkeypatch.delenv(jax_cache.ENV_VAR, raising=False)
        assert jax_cache.DEFAULT_DIR == ROOT / ".jax_cache"
        assert jax_cache.compile_cache_dir() == str(ROOT / ".jax_cache")
        before = jax.config.jax_compilation_cache_dir
        try:
            assert jax_cache.enable_compile_cache() == str(
                ROOT / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == str(
                ROOT / ".jax_cache")
        finally:
            jax.config.update("jax_compilation_cache_dir", before)
        ignored = (ROOT / ".gitignore").read_text().split()
        assert ".jax_cache/" in ignored


def test_serve_cli_reduced_runs_the_continuous_engine(monkeypatch,
                                                      tmp_path, capsys):
    monkeypatch.setenv(jax_cache.ENV_VAR, str(tmp_path))
    engine, results = serve.main(
        ["--reduced", "--requests", "3", "--prompt-len", "12",
         "--new-tokens", "5", "--batch-slots", "2", "--prefill-chunk", "8"])
    assert isinstance(engine, ContinuousServeEngine)
    assert engine.compile_cache is not None and engine.swapper is not None
    assert engine.planner is not None and engine.prefill_chunk == 8
    assert [len(r.tokens) for r in results] == [5, 5, 5]
    led = engine.ledger()
    assert led.complete and led.finished == 3
    assert "traces after warm-up 0" in capsys.readouterr().out


def test_chip_smoke_refuses_the_cpu(tmp_path):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "needs a TPU" in out.stderr
