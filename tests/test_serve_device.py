"""serve.py names its device and kernel mode, and keeps the compile cache
at one fixed place.

Runs ``serve.main`` in-process (the entry point ``chip_smoke.py`` drives)
at a tiny size on whatever backend the tests see.
"""

import json
import os

import jax
import pytest

from repro.launch import serve
from repro.launch.compile_cache import REPO_CACHE_DIR, enable_compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = ["--devices", "1", "--corpus-per-device", "4096", "--dim", "64",
        "--batch", "16", "--requests", "2"]


@pytest.fixture
def isolated_env(monkeypatch, tmp_path):
    """serve.main may set XLA_FLAGS and picks a compile cache: keep both
    out of the rest of the session (an env cache dir makes it set none)."""
    monkeypatch.setenv("XLA_FLAGS", os.environ.get("XLA_FLAGS", ""))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    return tmp_path


def test_compile_cache_fixed_path_when_env_unset(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert enable_compile_cache() == REPO_CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == REPO_CACHE_DIR
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert REPO_CACHE_DIR == os.path.join(ROOT, ".jax_cache")
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_compile_cache_env_dir_sets_nothing(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


@pytest.mark.parametrize("route", [
    ["--quant", "int8", "--fused", "on"],
    ["--index", "graph", "--corpus-per-device", "300", "--dim", "32"],
])
def test_serve_reports_device_and_kernel_mode(isolated_env, capsys, route):
    mpath = isolated_env / "m.json"
    report = serve.main(TINY + route + ["--metrics-json", str(mpath)])
    dev = jax.devices()[0]
    compiled = dev.platform == "tpu"
    assert report["platform"] == dev.platform
    assert report["device_kind"] == dev.device_kind
    assert report["devices"] == 1
    assert report["kernels"] == ("compiled" if compiled else "interpret")
    out = capsys.readouterr().out
    assert f"kernels={report['kernels']}" in out.splitlines()[0]
    doc = json.loads(mpath.read_text())
    assert doc["report"]["kernels"] == report["kernels"]
    assert doc["config"]["delta_d"] == (128 if compiled else 32)
    assert doc["config"]["block_q"] == (32 if compiled else 8)
    assert doc["provenance"]["platform"] == dev.platform
    assert doc["provenance"]["device_count"] == len(jax.devices())


def test_serve_refuses_more_devices_than_visible(isolated_env):
    n = len(jax.devices())
    with pytest.raises(SystemExit, match=f"JAX sees {n} "):
        serve.main(TINY[2:] + ["--devices", str(n + 1)])
