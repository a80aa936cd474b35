"""Guards that keep a CPU run from passing for a chip run: where the
compile cache goes, the roofline's per-device peaks, the bench setup
cache's device key, and the interpret-mode notice."""
import logging
import os
import pickle

import jax
import pytest

from repro.kernels import common
from repro.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_dir_config():
    """Restores ``jax_compilation_cache_dir`` after the test."""
    old = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", old)


def test_compile_cache_env_var_wins(monkeypatch, tmp_path, cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_ignored_checkout_dir(monkeypatch,
                                                        cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_roofline_peaks_are_keyed_by_device_kind():
    from benchmarks import roofline
    v5e = roofline.chip_peaks("TPU v5 lite")
    assert (v5e["flops"], v5e["hbm_bw"]) == (197e12, 819e9)
    assert "TPU v5e" in v5e["source"]
    with pytest.raises(ValueError, match="no published peaks"):
        roofline.chip_peaks("cpu")


def test_bench_setup_cache_is_keyed_by_device(monkeypatch, tmp_path):
    from benchmarks import common as bench
    monkeypatch.setattr(bench, "CACHE", str(tmp_path))
    # a setup pickled on another backend is never loaded here
    with open(tmp_path / "setup_tpu_TPU-v5-lite.pkl", "wb") as f:
        pickle.dump("built on the chip", f)
    assert bench.cached("setup", lambda: "built here") == "built here"
    dev = jax.devices()[0]
    here = f"setup_{dev.platform}_{dev.device_kind}.pkl".replace(" ", "-")
    assert here in os.listdir(tmp_path)
    assert bench.cached("setup", lambda: "rebuilt") == "built here"


def test_interpret_mode_is_announced_off_tpu(caplog):
    common._warn_interpreted.cache_clear()
    with caplog.at_level(logging.WARNING, logger=common.__name__):
        assert common.interpret_mode() is True
        assert common.interpret_mode() is True
    notes = [r for r in caplog.records if "interpret mode" in r.getMessage()]
    assert len(notes) == 1 and "'cpu'" in notes[0].getMessage()
