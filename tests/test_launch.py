"""Launcher plumbing: where the compile cache goes, and meshes over the
devices jax reports."""
import jax
import pytest
from jax.sharding import AxisType

from repro.launch import cache
from repro.launch.mesh import parse_mesh


def _cache_dir_set_by(monkeypatch):
    seen = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: seen.__setitem__(k, v))
    return seen


def test_compile_cache_follows_env(monkeypatch, tmp_path):
    seen = _cache_dir_set_by(monkeypatch)
    monkeypatch.setenv(cache.ENV_CACHE_DIR, str(tmp_path))
    assert cache.enable_compile_cache() == str(tmp_path)
    assert seen == {}                  # jax reads the variable itself


def test_compile_cache_defaults_to_checkout_root(monkeypatch):
    seen = _cache_dir_set_by(monkeypatch)
    monkeypatch.delenv(cache.ENV_CACHE_DIR, raising=False)
    where = cache.enable_compile_cache()
    root = cache.CHECKOUT_CACHE.parent
    assert where == str(root / ".jax_cache")
    assert (root / "src" / "repro").is_dir()
    assert seen == {"jax_compilation_cache_dir": where}


def test_parse_mesh_uses_the_devices_as_they_are():
    n = len(jax.devices())
    mesh = parse_mesh(f"1x{n}")
    assert dict(mesh.shape) == {"data": 1, "model": n}
    assert mesh.axis_types == (AxisType.Auto, AxisType.Auto)
    with pytest.raises(ValueError, match="DATAxMODEL"):
        parse_mesh(f"2x{n}")
