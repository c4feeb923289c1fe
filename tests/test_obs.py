"""repro.obs (ISSUE-6 + ISSUE-8): host-sync-free fleet telemetry.

Covers: MetricsAccumulator correctness against numpy, chunked-merge
equality (exact on integer leaves and extrema, ULP-tolerant on float
sums), histogram merge == concat-then-bin, both agents carrying the
accumulator inside their jitted scans (counts, epsilon decay, the
metrics=False escape hatch, and metrics-on/off training bit-identity),
SpanRecorder + Chrome trace-event schema validation, run manifests,
hot_edges in RouteResult.summary(), the end-to-end gap_breakdown
acceptance (both exact sum identities against a real ServingEngine
batch), and tools/obsview.py via subprocess.

ISSUE-8 (time-resolved telemetry): windowed ring leaves (slot
arithmetic, wrap, windows-on/off update equality on the shared
leaves), explicit underflow/overflow counters + the clipped-quantile
UserWarning regression, the two-source quantile agreement bound
(exact order statistics vs histogram midpoints within one bin width),
SLO attainment identities end-to-end against a real ServingEngine
(attained + violated == dispatched at every granularity, request.e2e
spans reproducing the served e2e stream, the slo.attainment counter
track), and obsview --timeline rendering.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.fleet import (FleetConfig, FleetDQN, FleetDQNConfig,
                        FleetOrchestrator, FleetQLearning, SyntheticSource,
                        TraceSource, fleet_metrics, mixed_table5_fleet,
                        topology, train_against_oracle, with_topology)
from repro.obs import (MetricDef, MetricsAccumulator, SpanRecorder,
                       attach_manifest, config_hash, run_manifest, span,
                       validate_chrome_trace)

DATA = os.path.join(os.path.dirname(__file__), "data")
FIXTURE = os.path.join(DATA, "trace_small.npz")
ROOT = os.path.join(os.path.dirname(__file__), "..")


# ------------------------------------------------------------ metrics -----
def _acc():
    return MetricsAccumulator.create({
        "r": MetricDef(lo=-2.0, hi=0.0, bins=8, lanes=4),
        "eps": MetricDef(lo=0.0, hi=1.0, bins=4, lanes=1),
    })


def test_metrics_moments_match_numpy():
    rng = np.random.default_rng(0)
    acc = _acc()
    samples = []
    for _ in range(7):
        x = rng.uniform(-2.5, 0.5, size=(4,)).astype(np.float32)
        samples.append(x)
        acc = acc.update({"r": jnp.asarray(x)})
    flat = np.concatenate(samples).astype(np.float64)
    s = acc.summary()["r"]
    assert s["count"] == flat.size and s["lanes"] == 4
    assert s["mean"] == pytest.approx(flat.mean(), rel=1e-6)
    assert s["std"] == pytest.approx(flat.std(), rel=1e-5)
    assert s["min"] == pytest.approx(flat.min(), rel=1e-6)
    assert s["max"] == pytest.approx(flat.max(), rel=1e-6)
    # out-of-range values clipped into edge bins, mass conserved
    assert sum(s["hist"]) == s["count"]
    assert len(s["edges"]) == 8 + 1


def test_metrics_histogram_merge_equals_concat_then_bin():
    rng = np.random.default_rng(1)
    xs = rng.uniform(-2.2, 0.2, size=(10, 4)).astype(np.float32)
    a, b = _acc(), _acc()
    for x in xs[:6]:
        a = a.update({"r": jnp.asarray(x)})
    for x in xs[6:]:
        b = b.update({"r": jnp.asarray(x)})
    merged = a.merge(b).summary()["r"]
    ref, _ = np.histogram(np.clip(xs.ravel(), -2.0, np.nextafter(0.0, -1)),
                          bins=8, range=(-2.0, 0.0))
    np.testing.assert_array_equal(merged["hist"], ref)


@pytest.mark.parametrize("bins", [1, 32, 64])
@pytest.mark.parametrize("lanes", [1, 1024])
@pytest.mark.parametrize("k", [1, 3])
def test_metrics_hist_counts_equal_bincount_and_scatter(bins, lanes, k):
    """``hist`` after two updates is the bincount of the clipped bin
    indices, and equals the scatter-add formulation ``.at[idx].add(1)``,
    for samples inside ``[lo, hi)`` (bin edges included), below ``lo``,
    at ``hi`` and above it. ``hi - lo`` is 2 and ``bins`` a power of two
    (or 1), so the bin arithmetic is exact in float32 on either side."""
    lo, hi = -2.0, 0.0
    df = MetricDef(lo=lo, hi=hi, bins=bins, lanes=lanes)
    rng = np.random.default_rng(bins * 10 + lanes + k)
    special = np.array([lo, hi, -3.0, 0.5, lo + 2.0 / bins, hi - 2.0**-20],
                       np.float32)
    xs = []
    for _ in range(2):
        x = rng.uniform(lo - 0.5, hi + 0.5,
                        size=(lanes, k)).astype(np.float32)
        x.ravel()[:special.size] = special[:x.size]
        xs.append(x)
    acc = MetricsAccumulator.create({"m": df})
    update = jax.jit(lambda a, x: a.update({"m": x}))
    for x in xs:
        acc = update(acc, jnp.asarray(x))
    hist = np.asarray(acc.data["m"]["hist"])
    assert hist.dtype == np.int32

    flat = np.concatenate([x.ravel() for x in xs])
    scale = np.float32(bins / (hi - lo))
    idx = np.clip(((flat - np.float32(lo)) * scale).astype(np.int32),
                  0, bins - 1)
    np.testing.assert_array_equal(hist, np.bincount(idx, minlength=bins))

    jidx = jnp.clip(((jnp.asarray(flat) - lo) * (bins / (hi - lo)))
                    .astype(jnp.int32), 0, bins - 1)
    scatter = jnp.zeros((bins,), jnp.int32).at[jidx].add(1)
    np.testing.assert_array_equal(hist, np.asarray(scatter))
    s = acc.summary()["m"]
    assert s["underflow"] == int((flat < lo).sum())
    assert s["overflow"] == int((flat >= hi).sum())


def test_metrics_chunked_merge_matches_single_stream():
    """merge(chunk1, chunk2) == one stream: exact on count/hist/extrema,
    reassociation-ULP close on the float sums (the CHANGES.md caveat)."""
    rng = np.random.default_rng(2)
    xs = [rng.uniform(-2.0, 0.0, size=(4,)).astype(np.float32)
          for _ in range(9)]
    one = _acc()
    for x in xs:
        one = one.update({"r": jnp.asarray(x)})
    a, b = _acc(), _acc()
    for x in xs[:4]:
        a = a.update({"r": jnp.asarray(x)})
    for x in xs[4:]:
        b = b.update({"r": jnp.asarray(x)})
    m, o = a.merge(b).data["r"], one.data["r"]
    for leaf in ("count", "hist", "mn", "mx"):
        np.testing.assert_array_equal(np.asarray(m[leaf]),
                                      np.asarray(o[leaf]))
    for leaf in ("total", "sumsq"):
        np.testing.assert_allclose(np.asarray(m[leaf]),
                                   np.asarray(o[leaf]), rtol=1e-6)


def test_metrics_jit_update_matches_eager():
    """The scan-carry usage: updates inside jit produce the same leaves
    as eager updates — including donation-friendly structure stability
    when only a subset of metrics is named."""
    x = jnp.asarray([-0.5, -1.0, -1.5, -0.25], jnp.float32)

    def once(acc):
        return acc.update({"r": x})        # 'eps' passes through

    eager = once(_acc())
    jitted = jax.jit(once)(_acc())
    for leaf in ("count", "total", "sumsq", "mn", "mx", "hist"):
        np.testing.assert_array_equal(np.asarray(eager.data["r"][leaf]),
                                      np.asarray(jitted.data["r"][leaf]))
    # untouched metric is bit-identical to the fresh one
    np.testing.assert_array_equal(np.asarray(jitted.data["eps"]["count"]),
                                  np.zeros(1, np.int32))


def test_metrics_lane_means_and_empty_summary():
    acc = _acc()
    s = acc.summary()["r"]
    assert s["count"] == 0 and s["mean"] is None and s["min"] is None
    acc = acc.update({"r": jnp.asarray([1.0, 2.0, 3.0, 4.0])})
    lm = acc.lane_means("r")
    np.testing.assert_allclose(lm, [1.0, 2.0, 3.0, 4.0])
    assert np.isnan(acc.lane_means("eps")).all()


def test_metrics_errors():
    with pytest.raises(ValueError, match="hi > lo"):
        MetricDef(lo=1.0, hi=1.0)
    with pytest.raises(ValueError, match="bins"):
        MetricDef(bins=0)
    acc = _acc()
    with pytest.raises(KeyError, match="unknown metric"):
        acc.update({"nope": jnp.zeros(4)})
    with pytest.raises(ValueError, match="lanes"):
        acc.update({"r": jnp.zeros(3)})     # 3 does not split into 4 lanes
    other = MetricsAccumulator.create({"r": MetricDef(lanes=4)})
    with pytest.raises(ValueError, match="different specs"):
        acc.merge(other)


# ----------------------------------------------- agents carry metrics -----
def test_qlearning_records_metrics_in_scan():
    src = TraceSource.load(FIXTURE)
    agent = FleetQLearning(src, seed=0)
    steps = 2 * src.horizon
    agent.run(steps)
    s = agent.metrics_summary()
    assert s["reward"]["count"] == src.cells * steps
    assert s["epsilon"]["count"] == steps            # one lane, one obs/step
    assert -2.5 <= s["reward"]["min"] <= s["reward"]["max"] <= 0.0
    # epsilon decays monotonically: max is the first value, min the last
    assert s["epsilon"]["max"] > s["epsilon"]["min"]
    assert sum(s["reward"]["hist"]) == s["reward"]["count"]
    assert agent.metrics.lane_means("reward").shape == (src.cells,)


def test_dqn_records_metrics_including_replay_fill():
    cfg = FleetConfig(cells=8, users=2, arrival_rate=1.0)
    agent = FleetDQN(SyntheticSource(cfg), cfg=FleetDQNConfig(), seed=0)
    agent.run(30)
    s = agent.metrics_summary()
    assert s["reward"]["count"] == 8 * 30
    assert s["loss"]["count"] == 30
    assert 0.0 < s["replay_fill"]["max"] <= 1.0
    assert s["replay_fill"]["min"] <= s["replay_fill"]["max"]  # fills up


def test_metrics_off_is_bit_identical_training():
    """The accumulator consumes no RNG and feeds nothing back: training
    with metrics=False is bit-identical, and metrics_summary is None."""
    src = SyntheticSource(FleetConfig(cells=8, users=2, arrival_rate=1.0))
    a = FleetQLearning(src, seed=4)
    b = FleetQLearning(src, seed=4, metrics=False)
    a.run(30)
    b.run(30)
    assert b.metrics is None and b.metrics_summary() is None
    np.testing.assert_array_equal(np.asarray(a.q), np.asarray(b.q))
    np.testing.assert_array_equal(np.asarray(a.counts),
                                  np.asarray(b.counts))
    da = FleetDQN(src, cfg=FleetDQNConfig(), seed=4)
    db = FleetDQN(src, cfg=FleetDQNConfig(), seed=4, metrics=False)
    da.run(25)
    db.run(25)
    assert db.metrics_summary() is None
    for la, lb in zip(jax.tree_util.tree_leaves(da.params),
                      jax.tree_util.tree_leaves(db.params)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def test_fleet_metrics_factory_shapes():
    m = fleet_metrics(16, "tabular")
    assert m.defs["reward"].lanes == 16 and "loss" not in m.defs
    m = fleet_metrics(16, "dqn")
    assert {"loss", "replay_fill"} <= set(m.defs)
    with pytest.raises(ValueError, match="kind"):
        fleet_metrics(16, "nope")


def test_train_against_oracle_attaches_manifest():
    src = TraceSource.load(FIXTURE)
    agent = FleetQLearning(src, seed=0)
    res = train_against_oracle(agent, max_steps=src.horizon,
                               check_every=src.horizon)
    m = res.manifest
    assert m["schema"] == "repro.obs/manifest-v1"
    assert m["jax_version"] == jax.__version__
    assert m["steps"] == agent.steps > 0
    assert m["wall_seconds"] == pytest.approx(res.wall_seconds)


# -------------------------------------------------------------- spans -----
def test_span_recorder_nesting_and_durations():
    rec = SpanRecorder()
    with rec.span("outer", kind="test"):
        with rec.span("inner"):
            pass
    rec.instant("marker", note="hi")
    rec.counter("queue", depth=3)
    names = [e["name"] for e in rec.events]
    assert names == ["inner", "outer", "marker", "queue"]  # close order
    outer = next(e for e in rec.events if e["name"] == "outer")
    inner = next(e for e in rec.events if e["name"] == "inner")
    assert outer["ts"] <= inner["ts"]
    assert outer["dur"] >= inner["dur"]
    assert outer["args"] == {"kind": "test"}
    assert rec.durations_ms("outer") and rec.durations_ms("nope") == []


def test_span_module_helper_none_recorder_is_noop():
    with span(None, "anything", x=1):
        pass                                         # no recorder, no-op
    rec = SpanRecorder()
    with span(rec, "real"):
        pass
    assert [e["name"] for e in rec.events] == ["real"]


def test_chrome_trace_save_validate_roundtrip(tmp_path):
    rec = SpanRecorder()
    with rec.span("a", obj=object()):                # non-json arg -> str
        pass
    path = rec.save(str(tmp_path / "t.json"), manifest=run_manifest())
    with open(path) as f:
        trace = json.load(f)
    validate_chrome_trace(trace)
    assert trace["displayTimeUnit"] == "ms"
    assert trace["otherData"]["schema"] == "repro.obs/manifest-v1"
    e = trace["traceEvents"][0]
    assert e["ph"] == "X" and e["ts"] >= 0 and e["dur"] >= 0
    assert isinstance(e["args"]["obj"], str)


def test_validate_chrome_trace_rejections():
    ok = {"traceEvents": [{"name": "x", "ph": "X", "ts": 0.0, "dur": 1.0,
                           "pid": 1, "tid": 1}]}
    validate_chrome_trace(ok)
    with pytest.raises(ValueError, match="must be a dict"):
        validate_chrome_trace([])
    with pytest.raises(ValueError, match="must be a list"):
        validate_chrome_trace({"traceEvents": {}})
    bad = {"traceEvents": [{"ph": "X", "ts": 0.0}]}
    with pytest.raises(ValueError, match="name"):
        validate_chrome_trace(bad)
    bad = {"traceEvents": [{"name": "x", "ph": "Z", "ts": 0.0,
                            "pid": 1, "tid": 1}]}
    with pytest.raises(ValueError, match="bad phase"):
        validate_chrome_trace(bad)
    bad = {"traceEvents": [{"name": "x", "ph": "X", "ts": -1.0, "dur": 1.0,
                            "pid": 1, "tid": 1}]}
    with pytest.raises(ValueError, match="ts"):
        validate_chrome_trace(bad)
    bad = {"traceEvents": [{"name": "x", "ph": "X", "ts": 0.0,
                            "pid": 1, "tid": 1}]}
    with pytest.raises(ValueError, match="dur"):
        validate_chrome_trace(bad)


# ----------------------------------------------------------- manifest -----
def test_run_manifest_keys_and_config_hash():
    m = run_manifest(config=FleetConfig(cells=4, users=2), extra_key=7)
    assert m["schema"] == "repro.obs/manifest-v1"
    assert m["backend"] == jax.default_backend()
    assert m["device_count"] == jax.device_count()
    assert m["extra_key"] == 7
    assert len(m["config_hash"]) == 16
    # hash is deterministic and config-sensitive
    assert config_hash(FleetConfig(cells=4, users=2)) == m["config_hash"]
    assert config_hash(FleetConfig(cells=5, users=2)) != m["config_hash"]
    assert config_hash({"b": 1, "a": 2}) == config_hash({"a": 2, "b": 1})


def test_attach_manifest_does_not_mutate():
    payload = {"x": 1}
    out = attach_manifest(payload, wall_seconds=1.0)
    assert "manifest" not in payload
    assert out["x"] == 1 and out["manifest"]["wall_seconds"] == 1.0


# ------------------------------------------- hot edges + gap breakdown ----
def _trained_topo_agent():
    scen = with_topology(mixed_table5_fleet(jax.random.PRNGKey(0), 12, 2),
                         topology.hot_edge_topology(12, 4))
    cfg = FleetConfig(cells=12, users=2, arrival_rate=1.5, n_edges=4)
    agent = FleetQLearning(SyntheticSource(cfg, scen=scen), seed=0)
    agent.run(40)
    return agent


def test_hot_edges_in_summary():
    """Satellite: route everything to the edge tier over a
    hot_edge_topology — half the fleet shares edge 0, so edge 0 is the
    unique utilization peak; the hot set follows the threshold."""
    from repro.fleet.api import StaticPolicy
    scen = with_topology(mixed_table5_fleet(jax.random.PRNGKey(0), 12, 2),
                         topology.hot_edge_topology(12, 4))
    orch = FleetOrchestrator(StaticPolicy(users=2, strategy="edge"))
    res = orch.route(scen=scen, with_edge_util=True, as_result=True,
                     hot_edge_util=0.5)
    util = np.asarray(res.edge_util)
    assert util.argmax() == 0                        # the hot edge
    s = res.summary()
    assert s["hot_edge_util"] == 0.5
    assert s["hot_edges"] == res.hot_edges
    assert res.hot_edges == [int(i) for i in np.nonzero(util >= 0.5)[0]]
    assert 0 in res.hot_edges
    # threshold above the peak -> empty hot set
    res2 = orch.route(scen=scen, with_edge_util=True, as_result=True,
                      hot_edge_util=float(util.max()) + 0.01)
    assert res2.summary()["hot_edges"] == []
    # a trained agent keeps the tuple contract, util values matching
    agent_orch = FleetOrchestrator(_trained_topo_agent())
    r3 = agent_orch.route(with_edge_util=True, as_result=True)
    dec, ids, util3 = agent_orch.route(with_edge_util=True)
    np.testing.assert_allclose(np.asarray(util3),
                               np.asarray(r3.edge_util))
    assert "hot_edges" not in agent_orch.route(as_result=True).summary()


def test_gap_breakdown_end_to_end_with_real_engines():
    """ISSUE-6 acceptance: gap_breakdown components sum to the measured
    wall time of a real engine batch — both identities exact."""
    from repro.launch.serve import build_engines, get_config
    src = TraceSource.load(FIXTURE)
    agent = FleetQLearning(src, seed=0)
    agent.run(src.horizon)
    engines = build_engines(get_config("edge-ladder"), variants=("d0",),
                            max_len=48)
    rec = SpanRecorder()
    res = FleetOrchestrator(agent).route(
        dispatch=engines, max_new_tokens=2, batch_size=4, prompt_len=8,
        spans=rec)
    gb = res.gap_breakdown()
    w = gb["wall_ms"]
    assert w["total"] == pytest.approx(
        w["batching"] + w["compute"] + w["dispatch"], abs=1e-6)
    assert w["dispatch"] >= 0.0
    pr = gb["per_request_ms"]
    assert pr["e2e"] == pytest.approx(pr["queueing"] + pr["compute"],
                                      abs=1e-6)
    assert gb["gap_x"] > 0.0
    assert gb["gap_components_x"]["e2e"] == pytest.approx(
        gb["gap_components_x"]["queueing"]
        + gb["gap_components_x"]["compute"], abs=1e-9)
    for tv in gb["per_tier_variant"].values():
        assert tv["gap_x"] > 0.0
    # per-request identity holds request by request, not just in the mean
    for r in res.served:
        assert r.queue_ms >= 0.0
        assert r.measured_ms >= 0.0
    # the spans cover the dispatch path
    names = {e["name"] for e in rec.events}
    assert {"route.decide", "route.dispatch", "dispatch.batch_build",
            "engine.generate", "engine.prefill",
            "engine.decode"} <= names
    assert any(n.startswith("dispatch.drain.") for n in names)
    validate_chrome_trace(rec.chrome_trace(run_manifest()))
    # summary carries the breakdown
    assert res.summary()["gap_breakdown"]["gap_x"] == gb["gap_x"]


def test_gap_breakdown_none_without_dispatch():
    orch = FleetOrchestrator(_trained_topo_agent())
    res = orch.route(as_result=True)
    assert res.gap_breakdown() is None
    assert "gap_breakdown" not in res.summary()


# ------------------------------------------------------------ obsview ----
def _run_obsview(*args):
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "obsview.py"), *args],
        capture_output=True, text=True, timeout=60)


def test_obsview_show_and_diff(tmp_path):
    a = tmp_path / "a.json"
    b = tmp_path / "b.json"
    a.write_text(json.dumps(attach_manifest(
        {"x": 1.0, "nested": {"y": 2.0}}, wall_seconds=1.0)))
    b.write_text(json.dumps(attach_manifest(
        {"x": 1.5, "nested": {"y": 2.0}}, wall_seconds=2.0)))
    res = _run_obsview(str(a))
    assert res.returncode == 0, res.stderr
    assert "nested.y" in res.stdout and "jax" in res.stdout
    res = _run_obsview("--diff", str(a), str(b))
    assert res.returncode == 0, res.stderr
    assert "+50.0%" in res.stdout and "<--" in res.stdout
    assert "1 metric(s) moved" in res.stdout


# ------------------------------------------ ISSUE-8: windowed metrics -----
def test_windowed_ring_slots_wrap_and_series():
    """Slot arithmetic: updates land in (step // window_len) %
    n_windows; the ring wraps and summary()/window_series flag it."""
    acc = MetricsAccumulator.create(
        {"m": MetricDef(lo=0.0, hi=10.0, bins=4, lanes=2,
                        n_windows=3, window_len=2)})
    # 8 updates of a 3x2-step ring -> slots 0,0,1,1,2,2,0,0 (wrapped)
    for t in range(8):
        acc = acc.update({"m": jnp.asarray([float(t), float(t)])})
    d = acc.data["m"]
    np.testing.assert_array_equal(np.asarray(d["wcount"]),
                                  [[4, 4], [2, 2], [2, 2]])
    # slot 0 holds steps {0,1,6,7}: total 14, min 0, max 7 per lane
    np.testing.assert_allclose(np.asarray(d["wtotal"])[0], [14.0, 14.0])
    np.testing.assert_allclose(np.asarray(d["wmn"])[0], [0.0, 0.0])
    np.testing.assert_allclose(np.asarray(d["wmx"])[0], [7.0, 7.0])
    s = acc.summary()["m"]
    w = s["windows"]
    assert w["n_windows"] == 3 and w["window_len"] == 2
    assert w["count"] == [8, 4, 4]
    assert sum(w["count"]) == s["count"]
    assert w["wrapped"] is True
    assert w["last_slot"] == 0                       # step 7 -> slot 0
    from repro.obs import window_series
    rows = window_series(s)
    assert [r[0] for r in rows] == [0, 1, 2]
    assert rows[1] == (1, 4, pytest.approx(2.5), pytest.approx(2.0),
                       pytest.approx(3.0))
    # un-windowed stream: no windows block, empty series
    plain = _acc().summary()["r"]
    assert "windows" not in plain and window_series(plain) == []


def test_windowed_empty_slots_and_def_validation():
    acc = MetricsAccumulator.create(
        {"m": MetricDef(n_windows=4, window_len=5)})
    acc = acc.update({"m": jnp.asarray([0.5])})      # only slot 0 touched
    w = acc.summary()["m"]["windows"]
    assert w["count"] == [1, 0, 0, 0]
    assert w["mean"][1] is None and w["min"][1] is None
    assert w["wrapped"] is False and w["last_slot"] == 0
    with pytest.raises(ValueError, match="n_windows"):
        MetricDef(n_windows=-1)
    with pytest.raises(ValueError, match="n_windows"):
        MetricDef(n_windows=2, window_len=0)


def test_windowed_merge_and_chunked_step_clock():
    """Positional window merge (shard semantics) + the self-clock:
    chunked scans resume the SAME accumulator, so the step counter —
    and hence slot assignment — continues across chunks."""
    mk = lambda: MetricsAccumulator.create(  # noqa: E731
        {"m": MetricDef(lo=0.0, hi=1.0, bins=4, lanes=2,
                        n_windows=2, window_len=2)})

    @jax.jit
    def chunk(acc, xs):
        def body(c, x):
            return c.update({"m": x}), None
        acc, _ = jax.lax.scan(body, acc, xs)
        return acc

    xs = jnp.linspace(0.0, 1.0, 16).reshape(8, 2)
    whole = chunk(mk(), xs)
    split = chunk(chunk(mk(), xs[:3]), xs[3:])       # uneven chunks
    for la, lb in zip(jax.tree_util.tree_leaves(whole),
                      jax.tree_util.tree_leaves(split)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    assert int(whole.step) == 8
    # positional merge: counts add slot-by-slot, extrema min/max
    m = whole.merge(whole)
    np.testing.assert_array_equal(np.asarray(m.data["m"]["wcount"]),
                                  2 * np.asarray(whole.data["m"]["wcount"]))
    assert int(m.step) == 8                          # max, not sum


# ------------------- ISSUE-8: underflow/overflow + quantile agreement -----
def test_underflow_overflow_counts_and_quantile_warns():
    """Regression (edge-bin fix): out-of-range mass is COUNTED, not
    silently folded — and quantiles() warns when the bound is void."""
    acc = MetricsAccumulator.create(
        {"m": MetricDef(lo=0.0, hi=1.0, bins=4, lanes=1)})
    acc = acc.update({"m": jnp.asarray([-5.0, -1.0, 0.5, 2.0])})
    s = acc.summary()["m"]
    assert s["underflow"] == 2 and s["overflow"] == 1
    assert sum(s["hist"]) == s["count"] == 4         # mass still conserved
    with pytest.warns(UserWarning, match="underflow"):
        q = acc.quantiles("m")
    assert q["clipped"] and q["underflow"] == 2 and q["overflow"] == 1
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("error")               # warn=False is silent
        q2 = acc.quantiles("m", warn=False)
    assert q2["p50"] == q["p50"]
    # in-range stream: no counts, no warning, clipped False
    clean = MetricsAccumulator.create(
        {"m": MetricDef(lo=0.0, hi=1.0, bins=4, lanes=1)})
    clean = clean.update({"m": jnp.asarray([0.1, 0.6])})
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        qc = clean.quantiles("m")
    assert not qc["clipped"]
    assert clean.summary()["m"]["underflow"] == 0


def test_exact_vs_hist_quantiles_within_bin_width():
    from repro.obs import timeline
    rng = np.random.default_rng(3)
    vals = rng.gamma(2.0, 200.0, size=500)           # skewed, latency-like
    acc = MetricsAccumulator.create(
        {"ms": MetricDef(lo=0.0, hi=float(vals.max()) + 1.0, bins=64)})
    for v in vals:
        acc = acc.update({"ms": jnp.asarray([v], jnp.float32)})
    exact = timeline.exact_quantiles(vals)
    hist = acc.quantiles("ms")
    assert not hist["clipped"] and hist["n"] == 500
    for k in ("p50", "p90", "p95", "p99"):
        assert abs(exact[k] - hist[k]) <= hist["bin_width"] + 1e-9, k
        # and the exact source really is an order statistic
        assert exact[k] in vals
    # empty + malformed inputs
    assert timeline.exact_quantiles([]) == {}
    assert timeline.hist_quantiles([0, 0], [0.0, 0.5, 1.0])["n"] == 0
    with pytest.raises(ValueError, match="len\\(hist\\)\\+1"):
        timeline.hist_quantiles([1, 2], [0.0, 1.0])
    att = timeline.attainment([1.0, 2.0, 3.0], 2.0)
    assert att == (2, 1) and sum(att) == 3


# --------------------------------- ISSUE-8: SLO through the bridge --------
def test_slo_end_to_end_with_real_engines():
    """ISSUE-8 acceptance: RouteResult.slo() satisfies attained +
    violated == dispatched per (tier, variant) against a REAL
    ServingEngine, the request.e2e spans reproduce the served e2e
    stream, and both quantile sources agree within one bin width."""
    from repro.launch.serve import build_engines, get_config
    src = TraceSource.load(FIXTURE)
    agent = FleetQLearning(src, seed=0)
    agent.run(src.horizon)
    engines = build_engines(get_config("edge-ladder"), variants=("d0",),
                            max_len=48)
    rec = SpanRecorder()
    res = FleetOrchestrator(agent).route(
        dispatch=engines, max_new_tokens=2, batch_size=4, prompt_len=8,
        spans=rec)
    slo = res.slo()
    n = slo["requests"]
    assert n == len(res.served) > 0
    from repro.fleet.dynamics import MAX_RESPONSE_MS
    assert slo["deadline_ms"] == MAX_RESPONSE_MS     # the QoS default
    for side in ("measured", "predicted"):
        assert slo[side]["attained"] + slo[side]["violated"] == n
        assert slo[side]["attainment"] == slo[side]["attained"] / n
    assert sum(tv["dispatched"]
               for tv in slo["per_tier_variant"].values()) == n
    for tv in slo["per_tier_variant"].values():
        assert tv["measured_attained"] + tv["measured_violated"] \
            == tv["dispatched"]
        assert tv["predicted_attained"] + tv["predicted_violated"] \
            == tv["dispatched"]
    # per-request stamps are scored, and e2e = queue + compute
    for r in res.served:
        assert r.deadline_met is not None
        assert r.deadline_met == (r.e2e_ms <= r.deadline_ms)
        assert r.e2e_ms == pytest.approx(r.queue_ms + r.measured_ms)
    # the request.e2e spans ARE the host-exact quantile source
    durs = np.sort(np.asarray(rec.durations_ms("request.e2e")))
    e2e = np.sort(np.asarray([r.e2e_ms for r in res.served]))
    assert durs.size == n
    np.testing.assert_allclose(durs, e2e, rtol=1e-6)
    from repro.obs import timeline
    assert slo["quantiles"]["exact_ms"] == timeline.exact_quantiles(e2e)
    # two-source agreement (guarded by the explicit clipped flag)
    hist = slo["quantiles"]["hist_ms"]
    if not hist["clipped"]:
        for k in ("p50", "p90", "p95", "p99"):
            assert abs(slo["quantiles"]["exact_ms"][k] - hist[k]) \
                <= hist["bin_width"] + 1e-9, k
    # the counter track rides the trace and ends at the final split
    cnt = [e for e in rec.events
           if e["ph"] == "C" and e["name"] == "slo.attainment"]
    assert cnt and cnt[-1]["args"]["attained"] == slo["measured"]["attained"]
    assert cnt[-1]["args"]["violated"] == slo["measured"]["violated"]
    validate_chrome_trace(rec.chrome_trace())
    # summary carries it
    assert res.summary()["slo"]["requests"] == n


def test_slo_deadline_override_forces_violations():
    """An impossible deadline violates every request — the identity
    holds in the all-violated regime and predicted tracks the same
    deadline."""
    from repro.launch.serve import build_engines, get_config
    src = TraceSource.load(FIXTURE)
    agent = FleetQLearning(src, seed=0)
    agent.run(4)
    engines = build_engines(get_config("edge-ladder"), variants=("d0",),
                            max_len=48)
    res = FleetOrchestrator(agent).route(
        dispatch=engines, max_new_tokens=2, batch_size=4, prompt_len=8,
        deadline_ms=1e-3)
    slo = res.slo()
    n = slo["requests"]
    assert slo["deadline_ms"] == pytest.approx(1e-3)
    assert slo["measured"] == {"attained": 0, "violated": n,
                               "attainment": 0.0}
    assert slo["predicted"]["attained"] + slo["predicted"]["violated"] == n
    assert all(r.deadline_met is False for r in res.served)
    # lat_acc sized off the deadline: everything overflows, flagged
    hist = slo["quantiles"]["hist_ms"]
    assert hist["clipped"] and hist["overflow"] == n


def test_slo_none_without_dispatch():
    orch = FleetOrchestrator(_trained_topo_agent())
    res = orch.route(as_result=True)
    assert res.slo() is None
    assert res.lat_acc is None
    assert "slo" not in res.summary()


def test_obsview_timeline(tmp_path):
    """--timeline renders windows + SLO blocks from a stamped JSON."""
    acc = MetricsAccumulator.create(
        {"reward": MetricDef(lo=-2.5, hi=0.0, bins=8, lanes=2,
                             n_windows=2, window_len=2)})
    for v in (-0.5, -1.5, -0.25, -2.0):
        acc = acc.update({"reward": jnp.asarray([v, v])})
    payload = attach_manifest({
        "training": acc.summary(),
        "slo": {
            "deadline_ms": 2500.0, "requests": 4,
            "measured": {"attained": 3, "violated": 1,
                         "attainment": 0.75},
            "predicted": {"attained": 4, "violated": 0,
                          "attainment": 1.0},
            "attainment_gap": 0.25,
            "per_tier_variant": {"E/d0": {
                "dispatched": 4, "attainment_measured": 0.75,
                "attainment_predicted": 1.0}},
            "quantiles": {"exact_ms": {"p50": 100.0, "p99": 400.0},
                          "hist_ms": {"p50": 110.0, "p99": 390.0,
                                      "bin_width": 50.0,
                                      "clipped": False}},
        }})
    p = tmp_path / "run.json"
    p.write_text(json.dumps(payload))
    res = _run_obsview("--timeline", str(p))
    assert res.returncode == 0, res.stderr
    out = res.stdout
    assert "windows  training.reward" in out and "<- last" in out
    assert "slo  slo" in out and "75.0%" in out and "+25.0%" in out
    assert "E/d0" in out and "bin_width = 50" in out
    # plain show / diff untouched by the new mode; exclusivity enforced
    bad = _run_obsview("--timeline", "--diff", str(p), str(p))
    assert bad.returncode != 0
    # a run without any time-resolved blocks says so instead of failing
    q = tmp_path / "plain.json"
    q.write_text(json.dumps(attach_manifest({"x": 1.0})))
    res2 = _run_obsview("--timeline", str(q))
    assert res2.returncode == 0, res2.stderr
    assert "no windowed metrics" in res2.stdout
