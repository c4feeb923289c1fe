"""Required work is counted from shapes, the same for every
implementation of an op, and is what the algorithm needs."""
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import peaks  # noqa: E402
import spec  # noqa: E402

W = spec.load_module("work", "fleet_qlearning")
CELLS, USERS, ACTIONS = 131072, 5, 243


def test_same_step_count_for_every_implementation():
    counts = [W.work(CELLS, USERS, ACTIONS, impl)["step"]
              for impl in ("pallas", "ref", "xla")]
    assert counts[0] == counts[1] == counts[2]


def test_kernel_count_only_where_the_kernel_runs():
    assert "tabular_rl" in W.work(CELLS, USERS, ACTIONS, "pallas")
    assert "tabular_rl" not in W.work(CELLS, USERS, ACTIONS, "ref")


def test_update_reads_one_row_and_one_entry_per_cell():
    """Row ``s2`` for the TD target (its argmax after the update is the
    next greedy action) and entry ``(s, a)`` read and written; 4-byte
    indices, reward, greedy action and TD error."""
    w = W.tabular_update(CELLS, ACTIONS)
    assert w["bytes"] == CELLS * (4 * ACTIONS + 4 * 2 + 4 * 6)
    assert w["bytes"] < CELLS * 2 * 4 * ACTIONS
    assert w["bytes"] / CELLS < 1.1e3               # about 1 KB a cell
    assert w["flops"] == CELLS * (2 * ACTIONS + 4)


def test_step_adds_state_and_response_model():
    step = W.step(CELLS, USERS, ACTIONS)
    upd = W.tabular_update(CELLS, ACTIONS)
    assert step["bytes"] > upd["bytes"] and step["flops"] > upd["flops"]


def test_count_scales_with_cells():
    a = W.work(CELLS, USERS, ACTIONS, "pallas")
    half = W.work(CELLS // 2, USERS, ACTIONS, "pallas")
    assert half["step"]["bytes"] * 2 == a["step"]["bytes"]
    assert half["tabular_rl"]["flops"] * 2 == a["tabular_rl"]["flops"]


def test_least_seconds_is_the_binding_bound():
    w = W.step(CELLS, USERS, ACTIONS)
    p = peaks.peaks("TPU v5 lite")
    t = peaks.least_seconds(w, "TPU v5 lite")
    assert t == pytest.approx(w["bytes"] / p["bytes_per_s"])
    assert peaks.least_seconds(w, "TPU v5 lite", chips=4) == \
        pytest.approx(t / 4)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks("TPU v99")
