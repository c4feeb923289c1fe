"""The check that decides ``correct`` has to fail what is wrong, in every
cell of ``BENCHMARK.json``, each at its configuration's ``test_size``.

* A sound run at a test's size is correct.
* The control, the reference itself in bfloat16 in the program's place,
  fails the check.
* A whole run, with the timed path broken underneath, reads
  ``correct: false`` for each fault of ``faults.py``: a step that returns
  its state unchanged, half of the fleet left out of the update, an
  update written to the wrong column, and a stale greedy action handed
  back.
* The numbers compared see where a write lands, not only how large the
  table's change is.
"""
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import control  # noqa: E402
import run as bench_run  # noqa: E402
import spec  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import faults  # noqa: E402

CELLS = [w["name"] for w in spec.load_benchmark()["workloads"]]
#: link states in the Q-table's state: what a data file alone could add
#: as a cell once the kernel compiles for it
LINKS = {"cells": 64, "track_links": True}


def small(workload):
    bench = spec.load_benchmark()
    return spec.load_config(spec.cell(bench, workload)["config"])[
        "test_size"]


@pytest.fixture
def no_cache(monkeypatch):
    """Runs here leave JAX's persistent cache off."""
    monkeypatch.setattr(bench_run, "enable_compile_cache",
                        lambda jax: "off")


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(no_cache, workload):
    r = bench_run.run_cell(workload, 5, 0.3, False, require_chip=False,
                           overrides=small(workload))
    assert r["correct"] is True, r["checks"]


def test_sound_run_with_link_states(no_cache):
    r = bench_run.run_cell("tabular_train", 5, 0.3, False,
                           require_chip=False, overrides=LINKS)
    assert r["correct"] is True, r["checks"]


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("seed", [11, 2 ** 32 + 1, 987654321987])
def test_control_in_bfloat16_fails(workload, seed):
    checks = control.control_checks(workload, seed,
                                    overrides=small(workload))
    assert not all(c["value"] <= c["limit"] for c in checks), checks


def test_control_fails_with_link_states():
    checks = control.control_checks("tabular_train", 9, overrides=LINKS)
    assert not all(c["value"] <= c["limit"] for c in checks), checks


@pytest.mark.parametrize("how", faults.FAULTS)
def test_broken_step_is_not_correct(no_cache, monkeypatch, how):
    faults.plant(monkeypatch, how)
    r = bench_run.run_cell("tabular_train", 5, 0.3, False,
                           require_chip=False,
                           overrides=small("tabular_train"))
    assert r["correct"] is False
    if how in ("unchanged", "half_fleet"):
        gap = r["checks"]["q_norm_gap"]["value"]
        want = 1.0 if how == "unchanged" else 1 - np.sqrt(0.5)
        assert gap == pytest.approx(want, abs=0.1)
    else:
        assert r["checks"]["cell_gap_p90"]["value"] > 0.5


def _record(q):
    return {"ms": np.ones(3), "q_norm": float(np.sqrt((q ** 2).sum())),
            "q_sample": q}


def test_a_write_in_the_wrong_place_is_seen():
    """The same values in other columns leave the norm as it is; the
    sampled tables' gap reads it."""
    kind = spec.load_module("kinds", "fleet_qlearning")
    rng = np.random.default_rng(0)
    q = -rng.random((8, 36, 243)).astype(np.float32)
    moved = np.roll(q, 1, axis=2)
    r = kind.readings(_record(moved), _record(q))
    assert r["q_norm_gap"] < 1e-6
    assert r["cell_gap_p90"] > 0.5
    assert kind.readings(_record(q), _record(q))["cell_gap_p90"] == 0.0


def test_cell_sample_is_drawn_from_the_seed():
    import fleets
    config = spec.load_config("table5-tabular")
    a = fleets.sample_cells(config, 7)
    assert len(set(a.tolist())) == config["check_cells"]
    assert (a == fleets.sample_cells(config, 7)).all()
    assert (a != fleets.sample_cells(config, 8)).any()
    assert a.max() < config["cells"]
