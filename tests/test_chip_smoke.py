"""``chip_smoke.py`` off the chip: it refuses to run, and its two phases
pass their checks at a small size on the CPU (the kernels in interpret
mode, the ``modeled`` backend in place of ``pinned_host``)."""
import sys
from pathlib import Path

import jax
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import chip_smoke  # noqa: E402


def test_refuses_to_run_off_a_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main() == 1
    captured = capsys.readouterr()
    assert captured.out == ""
    assert "needs a TPU" in captured.err


def test_serve_phase_tiny(monkeypatch, tmp_path):
    # serve.main turns on the persistent compile cache: with the variable
    # set it leaves JAX's configuration, and this worker, as they are.
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    checks, info = chip_smoke.serve_phase(tiny=True)
    assert all(checks.values()), checks
    assert info["tokens_differing"] == 0
    assert info["tokens_per_placement"] == info["requests"] * info["new_tokens"]


def test_embedding_phase_small():
    checks, info = chip_smoke.embedding_phase(rows=1 << 14, backend="modeled")
    # interpret mode is no compiled kernel; every other check must hold
    assert checks.pop("kernel_compiled") is False
    assert all(checks.values()), checks
    assert info["max_abs_diff"] == [0.0, 0.0]
    assert info["full_shard_copies"] == 0


def test_embedding_phase_needs_pinned_host():
    """The chip's configuration is never quietly modeled on the CPU."""
    with pytest.raises(ValueError, match="pinned_host"):
        chip_smoke.embedding_phase(rows=1 << 14)
