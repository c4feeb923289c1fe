"""``chip_smoke.py`` on the CPU: it refuses to start without a TPU, and
its phases run end to end at a tiny size with the real kernels in
interpret mode (the chip runs them compiled, at full size)."""
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


@pytest.mark.parametrize("alone", [False, True])
def test_chip_smoke_refuses_without_tpu(tmp_path, alone):
    """Under ``JAX_PLATFORMS=cpu`` the script exits non-zero, names the
    missing TPU and prints no result line — also when it is copied out
    of the repo on its own."""
    script = os.path.join(ROOT, "chip_smoke.py")
    if alone:
        script = shutil.copy(script, tmp_path / "chip_smoke.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, str(script)], cwd=tmp_path,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert res.returncode != 0
    assert "needs a TPU" in res.stderr
    assert '"ok"' not in res.stdout


def test_chip_smoke_tabular_phase_tiny():
    clock = chip_smoke.CompileClock()
    chip_smoke.phase_tabular(clock, cells=16, steps=6, parity_cells=16,
                             parity_steps=4, impl="pallas_interpret")


def test_chip_smoke_dqn_and_served_phases_tiny():
    from repro.configs import get_config, reduced
    from repro.launch.serve import build_engines
    clock = chip_smoke.CompileClock()
    dqn = chip_smoke.phase_dqn(clock, cells=16, steps=6,
                               impl="pallas_interpret", hidden=16,
                               replay_capacity=256)
    engines = build_engines(reduced(get_config("edge-ladder")),
                            variants=("d0",), max_len=48)
    chip_smoke.phase_served(clock, dqn, cells=4, waves=2, max_batch=2,
                            engines=engines)
