"""The chip smoke script's CPU rehearsal, run in this process, and the
compile-cache placement helper its entry points share."""
import json
import os

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def chip_smoke(monkeypatch, tmp_path):
    # JAX read the variable when it was imported, so setting it now
    # changes nothing — except that the helper then leaves the cache
    # alone, which keeps this test from enabling it for the worker
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.syspath_prepend(ROOT)
    import chip_smoke
    return chip_smoke


def test_tiny_rehearsal_prints_the_result_line(chip_smoke, capsys):
    result = chip_smoke.main(["--tiny"])
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == result
    assert result == {"ok": True, "device": {
        "platform": "cpu", "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices())}}


def test_refuses_a_backend_that_is_not_tpu(chip_smoke, capsys):
    with pytest.raises(SystemExit) as e:
        chip_smoke.main([])
    assert e.value.code not in (0, None)
    assert "ok" not in capsys.readouterr().out


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        path = compile_cache.enable_compile_cache()
        assert path == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_env_is_left_to_jax(monkeypatch, tmp_path):
    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
