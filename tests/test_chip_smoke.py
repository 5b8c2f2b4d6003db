"""chip_smoke.py, the chip bring-up check, rehearsed on the CPU.

The script drives the served path (``serve.build_service`` ->
``RequestPipeline``) and compares every answer with a numpy reference over
the live edge set; here it runs with ``--allow-cpu`` at a tiny scale.
"""
import importlib.util
import json
import pathlib

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.launch import compile_cache

ROOT = pathlib.Path(__file__).resolve().parent.parent


@pytest.fixture
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    # main() turns on the persistent compile cache; keep that out of the
    # tests that run after this one in the same process
    cache_dir = jax.config.jax_compilation_cache_dir
    yield mod
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    compilation_cache.reset_cache()


def test_cpu_rehearsal_passes_every_reference_check(chip_smoke, capsys):
    rc = chip_smoke.main(["--allow-cpu", "--scale", "10", "--batch", "512"])
    out = capsys.readouterr().out.splitlines()
    assert rc == 0, "\n".join(out)
    checks = [line for line in out if line.startswith("[smoke] check")]
    for what in ("update 5", "membership 5", "pagerank", "bfs levels",
                 "wcc partition"):
        assert any(what in line for line in checks), what
    assert all(" ok " in line for line in checks), checks
    dev = jax.devices()[0]
    assert json.loads(out[-1]) == {"ok": True, "device": {
        "platform": "cpu", "kind": dev.device_kind, "count": 1}}


def test_refuses_to_run_without_a_tpu(chip_smoke, capsys):
    assert chip_smoke.main(["--scale", "10"]) == 2
    assert "{" not in capsys.readouterr().out      # no result printed


def test_compile_cache_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.enable_compile_cache() == str(
            ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(
            ROOT / ".jax_cache")
        # a directory set in the environment is JAX's own to read
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        jax.config.update("jax_compilation_cache_dir", before)
        assert compile_cache.enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        compilation_cache.reset_cache()
