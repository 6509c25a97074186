"""The serve path's view of the platform: the chip-constants table keyed
by ``device_kind``, and the persistent compilation cache's location."""

import pytest

from repro.core import hardware
from repro.launch import compile_cache


def test_chip_table_knows_the_v5e():
    chip = hardware.chip_for("TPU v5 lite")
    assert chip is hardware.TPU_V5E
    assert chip.peak_flops == 197e12 and chip.hbm_bw == 819e9


def test_unknown_device_kind_raises():
    with pytest.raises(ValueError, match="no hardware constants"):
        hardware.chip_for("TPU v99")


def test_compile_cache_honours_the_environment(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path / "xla"))
    assert compile_cache.cache_dir() == tmp_path / "xla"


def test_compile_cache_defaults_to_one_path_in_the_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    first = compile_cache.cache_dir()
    assert first == compile_cache.cache_dir()
    assert first.name == ".jax_cache"
    assert (first.parent / "src" / "repro").is_dir()
