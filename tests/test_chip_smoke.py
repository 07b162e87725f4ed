"""chip_smoke.py's phases at a tiny size on the CPU, its refusal to run
without a TPU, and where the launcher puts the compile cache."""

import importlib.util
from pathlib import Path

import jax

from repro.launch import train

ROOT = Path(__file__).resolve().parents[1]
MIB = 1 << 20


def _smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_refuses_without_tpu(capsys):
    assert jax.default_backend() != "tpu"
    assert _smoke().main([]) == 1
    assert '"ok"' not in capsys.readouterr().out


def test_smoke_log_phase_tiny():
    out = _smoke().log_phase(0, ring=12 * MIB, payload=6 * MIB, batch=2)
    assert out["bytes"] >= 6 * MIB and out["replicas_checked"] == 3


def test_smoke_journal_phase_tiny():
    out = _smoke().journal_phase(0, batch=2, seq=32, reduced=True)
    assert out["restored_step"] == 3 and len(out["losses"]) == 6


def test_compile_cache_dir(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
    assert train.use_compile_cache(tmp_path) == str(tmp_path / "env")
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        assert train.use_compile_cache(tmp_path) == \
            str(tmp_path / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == \
            str(tmp_path / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
