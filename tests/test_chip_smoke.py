"""chip_smoke.py off the chip: it refuses to run without a TPU, and its
phases and host references hold at tiny sizes on the CPU."""
import dataclasses
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax.numpy as jnp
import pytest

from repro.algorithms import MSParams, RMATParams, UTSParams, uts_sequential
from repro.kernels.mandelbrot.ops import mandelbrot

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
import chip_smoke  # noqa: E402


def _run_script(path: Path, cwd: Path) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, str(path)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)


def test_refuses_without_tpu():
    proc = _run_script(ROOT / "chip_smoke.py", ROOT)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_refuses_without_the_repository(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    proc = _run_script(tmp_path / "chip_smoke.py", tmp_path)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


@pytest.mark.parametrize("depth", [0, 1, 7])
def test_uts_host_count_matches_sequential(depth):
    params = UTSParams(seed=19, b0=4.0, max_depth=depth, chunk=2048)
    assert chip_smoke.uts_host_count(params, threads=3) == \
        uts_sequential(params)


def test_uts_phase(capsys):
    chip_smoke.run_uts(UTSParams(seed=19, b0=4.0, max_depth=6),
                       warmup_depth=4, workers=2)
    assert "uts check ok=True" in capsys.readouterr().out


def test_ms_phase(capsys):
    ms = MSParams(width=64, height=64, max_dwell=64,
                  initial_subdivision=4, max_depth=3, split=2)
    chip_smoke.run_ms(ms, dataclasses.replace(ms, width=32, height=32,
                                              initial_subdivision=2),
                      workers=2, kernel_backend="interpret",
                      kernel_plane=dataclasses.replace(ms, width=128,
                                                       height=128))
    out = capsys.readouterr().out
    assert "kernel_differing_px=0 kernel_plane=128x128" in out
    assert "ms check ok=True" in out


def test_bc_phase(capsys):
    chip_smoke.run_bc(RMATParams(scale=6, seed=2), n_tasks=8, workers=2)
    assert "bc check ok=True" in capsys.readouterr().out


def test_backend_check_rejects_ref():
    # a kernel that ran as ref, or never ran, fails the check
    mandelbrot(jnp.zeros((1, 4)), jnp.zeros((1, 4)), 4, backend="ref")
    with pytest.raises(chip_smoke.SmokeFailure, match="not only as"):
        chip_smoke.check_kernel_backends()
