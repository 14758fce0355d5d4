"""Fixtures for the benchmark's CPU tests (see ``tinybench.py``)."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from tinybench import make_tiny  # noqa: E402


@pytest.fixture
def tiny_bench(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE", "0")
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "autotune.json"))
    monkeypatch.setenv("REPRO_COSTMODEL_CACHE", str(tmp_path / "cm.json"))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))
    monkeypatch.setenv("TPU_LOG_DIR", str(tmp_path / "tpu_logs"))
    return make_tiny(tmp_path / "bench")
