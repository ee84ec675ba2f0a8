"""Device identity in records, the chip table, and the compile cache."""
import jax
import pytest

from repro.core import device
from repro.core.manifest import build_manifest


def test_manifest_names_the_device():
    m = build_manifest()
    devs = jax.devices()
    assert m["platform"] == devs[0].platform == "cpu"
    assert m["device_kind"] == devs[0].device_kind
    assert m["device_count"] == len(devs)


def test_chip_table_has_v5e_and_rejects_unknown_kinds():
    v5e = device.chip("TPU v5 lite")
    assert v5e.peak_bf16_flops == 197e12 and v5e.hbm_bw == 819e9
    assert v5e.source
    with pytest.raises(KeyError, match="cpu"):
        device.chip("cpu")


@pytest.fixture
def cache_dir_config():
    """Restore the process-wide cache directory after a test sets it."""
    was = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", None)
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_defaults_to_fixed_repo_path(monkeypatch,
                                                   cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = device.enable_compile_cache()
    assert got == str(device.REPO_ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == got
    ignored = (device.REPO_ROOT / ".gitignore").read_text().splitlines()
    assert ".jax_cache/" in ignored


def test_compile_cache_env_dir_is_left_to_jax(monkeypatch, tmp_path,
                                              cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert device.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir is None   # nothing set
