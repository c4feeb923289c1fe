"""repro.obs.prof (ISSUE-7): compiled-cost profiling + the regression
gate.

Covers: CostProfile flops sanity on a known matmul (compiler count
within 2x of the analytic 2mnk), determinism across recompiles,
roofline terms and backend-peak fallback, stage_costs for both fleet
agents (stage sets, fractions summing to ~1, determinism of the flop
fractions, spans recorded), scaling_sweep report schema + JSON
round-trip, tools/benchgate.py via subprocess (pass / regression /
manifest mismatch / --force / structural on the tracked baseline and
on a broken JSON), obsview --fail-on-move and --history, and the
save_json history.jsonl append.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, ROOT)  # for the benchmarks package

from repro.fleet import (FleetConfig, FleetDQN, FleetDQNConfig,
                         FleetQConfig, FleetQLearning, SyntheticSource)
from repro.obs import SpanRecorder, attach_manifest
from repro.obs.prof import (PEAKS, device_peaks, profile_fn,
                            scaling_sweep, stage_costs)
from repro.obs.report import flatten, rel_diff


# ------------------------------------------------------- CostProfile -----
def _matmul_profile(m=64, k=128, n=32):
    a = jnp.ones((m, k), jnp.float32)
    b = jnp.ones((k, n), jnp.float32)
    return profile_fn(jnp.dot, a, b, name="mm"), 2 * m * k * n


def test_costprofile_matmul_flops_within_2x_of_analytic():
    prof, analytic = _matmul_profile()
    assert prof.name == "mm"
    assert analytic / 2 <= prof.flops <= analytic * 2
    assert prof.bytes_accessed > 0
    assert prof.arithmetic_intensity == pytest.approx(
        prof.flops / prof.bytes_accessed)
    assert prof.dominant in ("compute", "memory")


def test_costprofile_dict_is_jsonable_and_derived():
    prof, _ = _matmul_profile()
    d = prof.as_dict()
    json.dumps(d)  # must round-trip
    for key in ("flops", "bytes_accessed", "arithmetic_intensity",
                "ridge_intensity", "compute_s", "memory_s", "dominant",
                "backend", "temp_bytes"):
        assert key in d
    assert d["ridge_intensity"] == pytest.approx(
        prof.peak_flops_per_s / prof.peak_bytes_per_s)
    # dominant consistent with the roofline terms
    expect = "compute" if d["compute_s"] >= d["memory_s"] else "memory"
    assert d["dominant"] == expect


def test_costprofile_deterministic_across_recompiles():
    p1, _ = _matmul_profile()
    p2, _ = _matmul_profile()
    assert p1.flops == p2.flops
    assert p1.bytes_accessed == p2.bytes_accessed
    assert p1.temp_bytes == p2.temp_bytes


def test_backend_peaks_known_rows_and_fallback():
    """Peaks are keyed by device_kind, cite their source, and an unknown
    kind raises instead of falling back to another row."""
    v5e = device_peaks("TPU v5 lite")       # what a v5e reports
    assert v5e.flops_per_s == pytest.approx(197e12)
    assert v5e.bytes_per_s == pytest.approx(819e9)
    assert "TPU v5e" in v5e.source
    with pytest.raises(KeyError, match="no_such_device"):
        device_peaks("no_such_device")
    # default resolves the live device's kind without raising
    assert device_peaks() is PEAKS[jax.devices()[0].device_kind]


def test_profile_fn_never_executes():
    calls = []

    def f(x):
        calls.append(1)  # traced once at lower time, never executed
        return x * 2.0

    profile_fn(f, jnp.ones((4,)))
    assert len(calls) == 1  # tracing only; no second call from execution


# -------------------------------------------------------- stage_costs ----
def _source(cells=8):
    return SyntheticSource(FleetConfig(cells=cells, users=2,
                                       arrival_rate=1.0))


def test_stage_costs_dqn_stages_and_fractions():
    spans = SpanRecorder()
    agent = FleetDQN(_source(), cfg=FleetDQNConfig(replay_capacity=256,
                                                   batch_size=16))
    rep = stage_costs(agent, reps=2, spans=spans)
    assert rep["kind"] == "dqn"
    # default impl routes the act stage through the fused head
    assert set(rep["stages"]) == {"fused_encode_act", "env_step",
                                  "replay", "update"}
    for fr in ("flop_fracs", "byte_fracs", "wall_fracs"):
        assert sum(rep[fr].values()) == pytest.approx(1.0)
        assert all(v >= 0 for v in rep[fr].values())
    assert rep["dominant_stage_flops"] in rep["stages"]
    assert rep["dominant_stage_wall"] in rep["stages"]
    # wall was measured through the span recorder
    assert len(spans.durations_ms("prof.stage.update")) == 2
    json.dumps(rep)


def test_stage_costs_tabular_stages_and_fractions():
    agent = FleetQLearning(_source(), cfg=FleetQConfig())
    rep = stage_costs(agent, reps=2)
    assert rep["kind"] == "tabular"
    # default impl: TD update + next-step act fused into one stage
    assert set(rep["stages"]) == {"encode_act", "env_step",
                                  "fused_update_act"}
    assert sum(rep["flop_fracs"].values()) == pytest.approx(1.0)
    assert rep["cells"] == 8 and rep["users"] == 2
    json.dumps(rep)


def test_stage_costs_xla_impl_keeps_legacy_stage_names():
    rep = stage_costs(FleetQLearning(_source(), cfg=FleetQConfig(),
                                     impl="xla"), reps=1)
    assert set(rep["stages"]) == {"encode_act", "env_step", "update"}
    rep = stage_costs(FleetDQN(_source(),
                               cfg=FleetDQNConfig(replay_capacity=256,
                                                  batch_size=16),
                               impl="xla"), reps=1)
    assert set(rep["stages"]) == {"encode_act", "env_step", "replay",
                                  "update"}


def test_stage_flop_fractions_deterministic_across_recompiles():
    agent = FleetDQN(_source(), cfg=FleetDQNConfig(replay_capacity=256,
                                                   batch_size=16))
    r1 = stage_costs(agent, reps=1)
    r2 = stage_costs(agent, reps=1)
    assert r1["flop_fracs"] == r2["flop_fracs"]
    assert r1["byte_fracs"] == r2["byte_fracs"]


# ------------------------------------------------------ scaling_sweep ----
def test_scaling_sweep_schema_and_classification():
    rep = scaling_sweep([8, 16], users=2, steps=20, chunk=5)
    assert rep["grid"] == [8, 16]
    assert rep["devices"] == 1 and rep["sharded"] is False
    for key in ("flops_per_cell", "us_device_per_cell_step",
                "per_device_cell_steps_per_s"):
        assert set(rep[key]) == {"8", "16"}
        assert all(v > 0 for v in rep[key].values())
    assert 0 < rep["flatness"] <= 1.0
    assert rep["classification"] in ("flat", "runtime", "algorithmic")
    if rep["classification"] == "flat":
        assert rep["cliff_cells"] is None
    else:
        assert rep["cliff_cells"] in rep["grid"]
        assert str(rep["cliff_cells"]) in rep["summary"]
    json.dumps(rep)


# ---------------------------------------------------------- benchgate ----
GATE = os.path.join(ROOT, "tools", "benchgate.py")
BASELINE = os.path.join(ROOT, "results", "BENCH_fleet.json")


def _gate(*args):
    return subprocess.run([sys.executable, GATE, *args],
                          capture_output=True, text=True, timeout=60)


def _bench_payload(**overrides):
    metrics = {
        "env_steps_per_s": 1e6, "rl_steps_per_s": 4e5,
        "dqn_rl_steps_per_s": 4e4, "converged_cells_per_s": 100.0,
        "trace_env_steps_per_s": 5e5, "sharded_env_steps_per_s": 2e5,
        "dqn_holdout_reward_ratio": 1.0, "dqn_obs_overhead_x": 1.0,
        "trace_serving_gap_x": 7.0,
        "slo_attainment_measured": 0.9, "slo_attainment_predicted": 1.0,
        "p99_ms": 2000.0, "windowed_overhead_x": 1.0,
        "rl_fused_tabular_steps_per_s": 8e5,
        "rl_unfused_tabular_steps_per_s": 4e5,
        "rl_fused_tabular_speedup_x": 2.0,
        "rl_fused_dqn_steps_per_s": 9e4,
        "rl_unfused_dqn_steps_per_s": 8e4,
        "rl_fused_dqn_speedup_x": 1.15,
    }
    metrics.update(overrides)
    return attach_manifest(metrics)


def _write(path, payload):
    path.write_text(json.dumps(payload, default=str))
    return str(path)


def test_benchgate_identical_passes(tmp_path):
    p = _write(tmp_path / "base.json", _bench_payload())
    res = _gate(p, p)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "0 regression(s)" in res.stdout


def test_benchgate_regression_fails(tmp_path):
    base = _write(tmp_path / "base.json", _bench_payload())
    bad = _write(tmp_path / "bad.json", _bench_payload(
        env_steps_per_s=1e5,            # -90% throughput (tol 40%)
        dqn_holdout_reward_ratio=0.8,   # below the 0.95 floor
        trace_serving_gap_x=20.0))      # gap blew up (lower-better)
    res = _gate(base, bad)
    assert res.returncode == 1, res.stdout + res.stderr
    assert "3 regression(s)" in res.stdout
    assert "REGR" in res.stdout


def test_benchgate_degraded_slo_attainment_fails(tmp_path):
    """ISSUE-8 satellite: a copy whose SLO metrics degraded exits 1 —
    attainment gates on an absolute floor (0.50), p99 and the windowed
    overhead on lower-better bands."""
    base = _write(tmp_path / "base.json", _bench_payload())
    bad = _write(tmp_path / "bad.json", _bench_payload(
        slo_attainment_measured=0.3,    # below the 0.50 floor
        p99_ms=5000.0,                  # 2.5x the baseline tail (tol 60%)
        windowed_overhead_x=1.5))       # windows suddenly cost 50%
    res = _gate(base, bad)
    assert res.returncode == 1, res.stdout + res.stderr
    assert "3 regression(s)" in res.stdout
    for key in ("slo_attainment_measured", "p99_ms",
                "windowed_overhead_x"):
        assert key in res.stdout
    # attainment at the floor still passes even if below baseline
    ok = _write(tmp_path / "ok.json", _bench_payload(
        slo_attainment_measured=0.55))
    assert _gate(base, ok).returncode == 0


def test_benchgate_fused_speedup_floor(tmp_path):
    """ISSUE-10: a run whose fused/unfused ratio collapses below the
    absolute floor exits 1 — fused regressing to parity with the legacy
    path must fail the build even if raw throughput looks fine."""
    base = _write(tmp_path / "base.json", _bench_payload())
    bad = _write(tmp_path / "bad.json", _bench_payload(
        rl_fused_tabular_speedup_x=1.1,   # below the 1.7 floor
        rl_fused_dqn_speedup_x=0.9))      # fused slower than legacy
    res = _gate(base, bad)
    assert res.returncode == 1, res.stdout + res.stderr
    assert "2 regression(s)" in res.stdout
    assert "rl_fused_tabular_speedup_x" in res.stdout
    # at-floor still passes even below the baseline's recorded ratio
    ok = _write(tmp_path / "ok.json", _bench_payload(
        rl_fused_tabular_speedup_x=1.75, rl_fused_dqn_speedup_x=1.03))
    assert _gate(base, ok).returncode == 0


def test_benchgate_improvement_passes(tmp_path):
    base = _write(tmp_path / "base.json", _bench_payload())
    better = _write(tmp_path / "up.json", _bench_payload(
        env_steps_per_s=5e6, trace_serving_gap_x=2.0))
    res = _gate(base, better)
    assert res.returncode == 0, res.stdout + res.stderr


def test_benchgate_manifest_mismatch_refused_unless_forced(tmp_path):
    base_payload = _bench_payload()
    other = json.loads(json.dumps(base_payload, default=str))
    other["manifest"]["device_count"] = 512
    base = _write(tmp_path / "base.json", base_payload)
    new = _write(tmp_path / "new.json", other)
    res = _gate(base, new)
    assert res.returncode == 2, res.stdout + res.stderr
    assert "NOT COMPARABLE" in res.stdout
    assert "device_count" in res.stdout
    res = _gate(base, new, "--force")
    assert res.returncode == 0, res.stdout + res.stderr


def test_benchgate_tolerance_scale_widens_band(tmp_path):
    base = _write(tmp_path / "base.json", _bench_payload())
    down = _write(tmp_path / "down.json", _bench_payload(
        env_steps_per_s=5e5))  # -50%: outside tol 40%, inside 40%*2
    assert _gate(base, down).returncode == 1
    assert _gate(base, down, "--tolerance-scale", "2.0").returncode == 0


def test_benchgate_structural_on_tracked_baseline():
    res = _gate("--structural", BASELINE)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "0 structural problem(s)" in res.stdout


def test_benchgate_structural_rejects_broken_json(tmp_path):
    broken = _bench_payload()
    del broken["env_steps_per_s"]
    broken["dqn_holdout_reward_ratio"] = None
    del broken["manifest"]
    p = _write(tmp_path / "broken.json", broken)
    res = _gate("--structural", p)
    assert res.returncode == 2, res.stdout + res.stderr
    assert "no manifest" in res.stdout
    assert "env_steps_per_s" in res.stdout


# ------------------------------------------------- obsview satellites ----
OBSVIEW = os.path.join(ROOT, "tools", "obsview.py")


def _obsview(*args):
    return subprocess.run([sys.executable, OBSVIEW, *args],
                          capture_output=True, text=True, timeout=60)


def test_obsview_fail_on_move(tmp_path):
    a = _write(tmp_path / "a.json", _bench_payload())
    b = _write(tmp_path / "b.json", _bench_payload(env_steps_per_s=2e6))
    assert _obsview("--diff", a, b).returncode == 0  # informational
    res = _obsview("--diff", a, b, "--fail-on-move")
    assert res.returncode == 1, res.stdout + res.stderr
    res = _obsview("--diff", a, a, "--fail-on-move")
    assert res.returncode == 0, res.stdout + res.stderr


def test_obsview_history_renders_trajectory(tmp_path):
    hist = tmp_path / "history.jsonl"
    rows = [
        {"_name": "BENCH_fleet", "_created_utc": f"2026-08-0{i}T00:00:00",
         "_git_sha": "abc", "env_steps_per_s": 1e6 * (1 + i),
         "suites.fleet.detail": 1.0}
        for i in range(3)
    ]
    hist.write_text("\n".join(json.dumps(r) for r in rows) + "\n")
    res = _obsview("--history", str(hist))
    assert res.returncode == 0, res.stdout + res.stderr
    assert "3 run(s)" in res.stdout
    assert "->" in res.stdout and "env_steps_per_s" in res.stdout
    assert "overall" in res.stdout
    assert "suites.fleet.detail" not in res.stdout  # hidden by default
    res = _obsview("--history", str(hist), "--filter", "detail")
    assert "suites.fleet.detail" in res.stdout
    res = _obsview("--history", str(hist), "--name", "no_such_bench")
    assert res.returncode == 0 and "no rows" in res.stdout


def test_save_json_appends_history_row(tmp_path, monkeypatch):
    from benchmarks import common
    monkeypatch.setattr(common, "RESULTS_DIR", str(tmp_path))
    common.save_json("histtest", {"a": 1.5, "nested": {"b": 2}})
    common.save_json("histtest", {"a": 2.5, "nested": {"b": 2}})
    rows = [json.loads(line) for line in
            (tmp_path / "history.jsonl").read_text().splitlines()]
    assert len(rows) == 2
    assert rows[0]["_name"] == "histtest"
    assert rows[0]["a"] == 1.5 and rows[1]["a"] == 2.5
    assert rows[0]["nested.b"] == 2
    assert rows[0]["_created_utc"]
    # the main JSON is still written, manifest attached
    payload = json.loads((tmp_path / "histtest.json").read_text())
    assert payload["manifest"]["jax_version"]


# ----------------------------------------------------- shared helpers ----
def test_flatten_and_rel_diff_shared_semantics():
    flat = flatten({"a": 1, "b": {"c": 2.0}, "manifest": {"skip": 1},
                    "s": "x"})
    assert flat == {"a": 1, "b.c": 2.0, "s": "x"}
    assert rel_diff(100.0, 50.0) == pytest.approx(-0.5)
    assert rel_diff(0.0, 1.0) == pytest.approx(1.0)  # zero-base guard
