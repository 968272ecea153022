"""Launch-layer helpers: the keyed peaks table and the compile cache."""
import ast
from pathlib import Path

import jax
import pytest

from repro.core import cost_model
from repro.launch import compile_cache
from repro.hw import PEAKS, TARGET, TARGET_KIND, peaks


def test_peaks_are_the_published_v5e_figures():
    p = peaks("TPU v5 lite")
    assert (p.bf16_flops, p.hbm_bw) == (197e12, 819e9)
    assert p.ici_bw == 1600e9 / 8            # 1,600 Gbit/s, in bytes/s
    assert "TPU v5e" in p.source
    assert TARGET is PEAKS[TARGET_KIND]


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="TPU v9"):
        peaks("TPU v9")


def test_peaks_module_imports_nothing_from_the_repo():
    import repro.hw
    tree = ast.parse(Path(repro.hw.__file__).read_text())
    names = [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
             for a in n.names]
    names += [n.module or "" for n in ast.walk(tree)
              if isinstance(n, ast.ImportFrom)]
    assert not [m for m in names if m.split(".")[0] == "repro"]


def test_cost_model_seeds_read_the_table():
    assert cost_model.ACCEL_FLOPS == TARGET.bf16_flops
    assert cost_model.ACCEL_BW == TARGET.hbm_bw


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_defaults_to_the_repo_dir(monkeypatch,
                                                restore_cache_dir):
    monkeypatch.delenv(compile_cache.CACHE_ENV, raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(compile_cache.REPO_ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path


def test_compile_cache_follows_the_env_var(monkeypatch, tmp_path,
                                           restore_cache_dir):
    monkeypatch.setenv(compile_cache.CACHE_ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
