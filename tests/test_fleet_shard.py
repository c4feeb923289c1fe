"""repro.fleet.shard (ISSUE-5): device-sharded fleet execution.

The acceptance claims: the sharded fleet step and training are
BIT-identical (``assert_array_equal``) to the single-device path — the
comparisons below run the same jitted programs on sharded vs unsharded
inputs, which is exactly the GSPMD guarantee being claimed — the
shard-local topology generator never lets an edge span device blocks,
and the ``shard_map`` local-aggregation path matches the global
segment-sum path. At one device every helper degenerates to a no-op
placement and the tests still pin the code paths;
``test_forced_8_device_parity`` re-runs this file under a forced
8-device host platform (the CI fleet-subset step uses 2).
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.fleet import (FleetConfig, FleetDQN, FleetDQNConfig,
                         FleetOrchestrator, FleetQConfig, FleetQLearning,
                         SyntheticSource, TraceSource, holdout_reward_ratio,
                         init_fleet, record_trace, shard, step_fleet,
                         topology)

NDEV = jax.device_count()


def _mesh():
    return shard.fleet_mesh()


def _full_cfg(cells, users=2, shard_local=False):
    """Every scenario dynamic at once: Markov links, Poisson arrivals,
    churn, a shared-edge topology with cloud queueing and edge
    failures — the hardest case for placement to preserve. A
    shard-local topology has no edge failures, whose reroutes would
    cross device blocks (``make_topology`` refuses the pair)."""
    return FleetConfig(cells=cells, users=users, p_r2w=0.1, p_w2r=0.2,
                       arrival_rate=1.0, p_join=0.02, p_leave=0.02,
                       n_edges=2 * NDEV, cloud_servers=8.0,
                       capacity_tiers=(1.0, 2.0),
                       p_edge_fail=0.0 if shard_local else 0.1,
                       shard_local=shard_local, n_shards=NDEV)


def _assert_scen_equal(a, b):
    np.testing.assert_array_equal(np.asarray(a.end_b), np.asarray(b.end_b))
    np.testing.assert_array_equal(np.asarray(a.edge_b),
                                  np.asarray(b.edge_b))
    np.testing.assert_array_equal(np.asarray(a.member),
                                  np.asarray(b.member))
    np.testing.assert_array_equal(np.asarray(a.active),
                                  np.asarray(b.active))
    if a.topo is not None:
        np.testing.assert_array_equal(np.asarray(a.topo.cell_edge),
                                      np.asarray(b.topo.cell_edge))


# ------------------------------------------------------------ placement ---
def test_fleet_spec_shards_divisible_cells():
    mesh = _mesh()
    spec = shard.fleet_spec(mesh, (8 * NDEV, 3), axis=0)
    assert spec[0] == "fleet"
    x = shard.shard_array(jnp.zeros((8 * NDEV, 3)), mesh)
    assert x.sharding.spec[0] == "fleet"


@pytest.mark.skipif(NDEV < 2, reason="needs a real multi-device mesh")
def test_fleet_spec_indivisible_falls_back_to_replication():
    mesh = _mesh()
    spec = shard.fleet_spec(mesh, (8 * NDEV + 1, 3), axis=0)
    assert spec[0] is None          # graceful fallback, never an error


def test_helpers_are_identity_without_mesh():
    scen = init_fleet(jax.random.PRNGKey(0), _full_cfg(4 * NDEV))
    assert shard.shard_scenario(scen, None) is scen
    assert shard.constrain_array(scen.end_b, None) is scen.end_b
    assert shard.replicate(scen, None) is scen


# ------------------------------------------- bit-parity: scenario step ----
def test_step_fleet_sharded_bit_parity():
    """Same jitted step, sharded vs unsharded inputs: bit-identical
    through 5 chained steps of every scenario dynamic at once."""
    mesh = _mesh()
    cfg = _full_cfg(8 * NDEV)
    scen = init_fleet(jax.random.PRNGKey(0), cfg)
    step = jax.jit(lambda k, s: step_fleet(k, s, cfg))
    a, b = scen, shard.shard_scenario(scen, mesh)
    for i in range(5):
        k = jax.random.PRNGKey(10 + i)
        a, b = step(k, a), step(k, b)
        _assert_scen_equal(a, b)
    if NDEV > 1:
        assert b.end_b.sharding.spec[0] == "fleet"   # layout survives


# --------------------------------------- bit-parity: tabular training -----
def _trained_pair(steps=40):
    cfg = _full_cfg(8 * NDEV)
    a = FleetQLearning(SyntheticSource(cfg), cfg=FleetQConfig(), seed=3)
    b = FleetQLearning(SyntheticSource(cfg), cfg=FleetQConfig(), seed=3,
                       mesh=_mesh())
    a.run(steps)
    b.run(steps)
    return a, b


def test_qlearning_training_bit_parity():
    a, b = _trained_pair()
    np.testing.assert_array_equal(np.asarray(a.q), np.asarray(b.q))
    np.testing.assert_array_equal(np.asarray(a.counts),
                                  np.asarray(b.counts))
    _assert_scen_equal(a.scen, b.scen)
    np.testing.assert_array_equal(np.asarray(a.greedy_decisions()),
                                  np.asarray(b.greedy_decisions()))
    # the in-scan metrics accumulator (ISSUE-6) rides the same carry.
    # The accumulator itself adds no cross-lane float ops (see the
    # standalone test below for its own bit-parity), so integer leaves,
    # extrema, and histograms are exact; the float total/sumsq record
    # values like the per-cell mean_ms whose masked-mean arithmetic can
    # contract (FMA) differently under partitioning — ULP-level, the
    # same compilation-context caveat CHANGES.md documents for
    # eager-vs-jit, while the Q-table stays bit-identical above
    for name, da in a.metrics.data.items():
        db = b.metrics.data[name]
        for leaf in ("count", "hist", "mn", "mx"):
            np.testing.assert_array_equal(np.asarray(da[leaf]),
                                          np.asarray(db[leaf]))
        for leaf in ("total", "sumsq"):
            np.testing.assert_allclose(np.asarray(da[leaf]),
                                       np.asarray(db[leaf]), rtol=1e-6)
    sa, sb = a.metrics_summary(), b.metrics_summary()
    for name in sa:
        assert sa[name]["count"] == sb[name]["count"]
        assert sa[name]["hist"] == sb[name]["hist"]
        assert sa[name]["mean"] == pytest.approx(sb[name]["mean"],
                                                 rel=1e-6)
    if NDEV > 1:
        assert b.q.sharding.spec[0] == "fleet"       # donation kept layout


def test_fused_impl_sharded_training_bit_parity():
    """ISSUE-10: the fused hot path under a mesh. ``impl='pallas'``
    resolves to the fused-jnp formulation when a mesh is attached
    (GSPMD cannot partition ``pallas_call``; see
    ``kernels.ops.resolve_rl_impl``) — per-cell elementwise + reduces
    along the unsharded action axis, so a sharded fused run is
    bit-identical to the single-device fused run."""
    from repro.kernels import ops
    cfg = _full_cfg(8 * NDEV)
    single = FleetQLearning(SyntheticSource(cfg), cfg=FleetQConfig(),
                            seed=3, impl="pallas")
    meshed = FleetQLearning(SyntheticSource(cfg), cfg=FleetQConfig(),
                            seed=3, impl="pallas", mesh=_mesh())
    assert ops.resolve_rl_impl("pallas", meshed.mesh) == "ref"
    assert meshed._op_impl == "ref"
    for ag in (single, meshed):
        ag.run(40)
    np.testing.assert_array_equal(np.asarray(single.q),
                                  np.asarray(meshed.q))
    np.testing.assert_array_equal(np.asarray(single.counts),
                                  np.asarray(meshed.counts))
    np.testing.assert_array_equal(
        np.asarray(single.greedy_decisions()),
        np.asarray(meshed.greedy_decisions()))
    if NDEV > 1:
        assert meshed.q.sharding.spec[0] == "fleet"


def _count_compiles(fn):
    """``fn()``, and the number of backend compiles it made."""
    compiles = []

    def count(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(duration)

    jax.monitoring.register_event_duration_secs_listener(count)
    try:
        fn()
    finally:
        jax.monitoring.unregister_event_duration_listener(count)
    return len(compiles)


def test_kernel_per_shard_training_bit_parity():
    """The kernel path under a mesh: the real ``tabular_rl`` kernel
    (interpret mode) runs once per device on its own block of cells
    under ``shard_map``, and so do the table's layout changes around
    the scan. 13 cells a device, not a multiple of the kernel's block,
    over 40 steps in two calls and one single ``step``: the table,
    counts and greedy decisions are bit-equal to the single-device
    kernel, and counts and decisions to the single-device ``ref``
    formulation, whose table the kernel matches up to its
    fma-contraction ulp (as on one device, ``test_fleet_fused``). The
    table keeps its layout, and the second call compiles nothing."""
    cfg = _full_cfg(13 * NDEV, shard_local=True)
    meshed = FleetQLearning(SyntheticSource(cfg), cfg=FleetQConfig(),
                            seed=3, impl="pallas_interpret", mesh=_mesh())
    single = FleetQLearning(SyntheticSource(cfg), cfg=FleetQConfig(),
                            seed=3, impl="pallas_interpret")
    ref = FleetQLearning(SyntheticSource(cfg), cfg=FleetQConfig(),
                         seed=3, impl="ref")
    assert meshed.update_path == "pallas_interpret_per_shard"
    assert single.update_path == "pallas_interpret"
    for ag in (single, ref):
        ag.run(20)
        ag.run(20)
    meshed.run(20)
    assert _count_compiles(lambda: meshed.run(20)) == 0
    for ag in (single, ref, meshed):
        ag.step()
    np.testing.assert_array_equal(np.asarray(single.q),
                                  np.asarray(meshed.q))
    np.testing.assert_allclose(np.asarray(ref.q), np.asarray(meshed.q),
                               rtol=1e-6, atol=1e-6)
    for other in (single, ref):
        np.testing.assert_array_equal(np.asarray(other.counts),
                                      np.asarray(meshed.counts))
        np.testing.assert_array_equal(
            np.asarray(other.greedy_decisions()),
            np.asarray(meshed.greedy_decisions()))
    if NDEV > 1:
        assert meshed.q.sharding.spec[0] == "fleet"


def test_tpu_mesh_resolves_the_kernel_for_tabular_only(monkeypatch):
    """On a TPU backend ``impl='pallas'`` under a mesh runs the kernel
    per shard for ``FleetQLearning``, whose table the mesh splits along
    its cells; ``FleetDQN``, whose head is not wrapped, keeps ``ref``.
    Nothing is compiled here: the backend is only named."""
    from repro.kernels import ops
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = _mesh()
    assert ops.resolve_rl_impl("pallas", mesh, per_shard=True) == "pallas"
    assert ops.resolve_rl_impl("pallas", mesh) == "ref"
    tab = FleetQLearning(SyntheticSource(_full_cfg(8 * NDEV,
                                                   shard_local=True)),
                         cfg=FleetQConfig(), seed=3, mesh=mesh)
    assert tab._op_impl == "pallas"
    assert not tab.path.interpret and tab.path.mesh is mesh
    assert tab.update_path == "pallas_per_shard"
    dqn = FleetDQN(SyntheticSource(FleetConfig(cells=8 * NDEV, users=2)),
                   cfg=FleetDQNConfig(), seed=5, mesh=mesh)
    assert dqn._op_impl == "ref"


def test_zeros_are_made_on_each_device():
    """``shard.zeros`` makes each device's block where it lives, with no
    transfer: the agent's Q-table under a mesh is made so, and is never
    whole on one device."""
    with jax.transfer_guard("disallow"):
        z = shard.zeros((8 * NDEV, 3, 5), jnp.float32, _mesh())
    assert [s.data.shape for s in z.addressable_shards] == [(8, 3, 5)] * NDEV
    assert not np.asarray(z).any()
    agent = FleetQLearning(SyntheticSource(_full_cfg(8 * NDEV)),
                           cfg=FleetQConfig(), seed=3, mesh=_mesh())
    assert agent.q.sharding == z.sharding
    assert not np.asarray(agent.q).any()


@pytest.mark.parametrize("impl", ["pallas", "pallas_interpret"])
def test_second_run_under_the_mesh_compiles_nothing(impl):
    """Every leaf of the placed scenario, its step counter too, comes
    back from the scan in the layout it went in with, so the next
    ``run`` call under the mesh reuses the compiled scan."""
    cfg = FleetConfig(cells=8 * NDEV, users=2, p_r2w=0.05, p_w2r=0.05,
                      n_edges=2 * NDEV, shard_local=True, n_shards=NDEV)
    agent = FleetQLearning(SyntheticSource(cfg), cfg=FleetQConfig(),
                           seed=3, impl=impl, mesh=_mesh())
    compiles = []

    def count(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(duration)

    jax.monitoring.register_event_duration_secs_listener(count)
    try:
        agent.run(4)
        first = len(compiles)
        agent.run(4)
        agent.run(4)
    finally:
        jax.monitoring.unregister_event_duration_listener(count)
    assert first > 0 and len(compiles) == first


def test_metrics_accumulator_sharded_update_bit_parity():
    """Standalone obs satellite: the same jitted update on a placed
    accumulator (lane leaves sharded along the fleet axis, histograms
    replicated) is bit-identical to the unplaced one — per-lane
    elementwise work plus an integer scatter, the op classes the fleet
    parity discipline allows."""
    from repro.obs import MetricDef, MetricsAccumulator
    mesh = _mesh()
    lanes = 8 * NDEV
    defs = {"r": MetricDef(lo=-2.5, hi=0.0, bins=16, lanes=lanes),
            "eps": MetricDef(lo=0.0, hi=1.0, bins=8)}
    plain = MetricsAccumulator.create(defs)
    placed = plain.place(lambda x, axis=0: shard.shard_array(x, mesh,
                                                             axis=axis),
                         lambda x: shard.replicate(x, mesh))
    if NDEV > 1:
        assert placed.data["r"]["total"].sharding.spec[0] == "fleet"
        assert placed.data["r"]["hist"].sharding.is_fully_replicated

    @jax.jit
    def roll(acc, key):
        def body(carry, k):
            x = -2.5 * jax.random.uniform(k, (lanes,))
            e = jax.random.uniform(jax.random.fold_in(k, 1), (1,))
            return carry.update({"r": x, "eps": e}), None
        acc, _ = jax.lax.scan(body, acc, jax.random.split(key, 10))
        return acc

    key = jax.random.PRNGKey(0)
    a, b = roll(plain, key), roll(placed, key)
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    if NDEV > 1:                                     # layout survived scan
        assert b.data["r"]["count"].sharding.spec[0] == "fleet"
    # and merging the two reduces exactly (integer + extrema leaves)
    m = a.merge(b).summary()["r"]
    assert m["count"] == 2 * a.summary()["r"]["count"]


def test_windowed_metrics_sharded_update_bit_parity():
    """ISSUE-8: the ``(n_windows, lanes)`` ring — integer slot index on
    the replicated window axis, elementwise along the sharded lane
    axis — is the permitted op class, so windowed leaves stay
    bit-identical under placement too."""
    from repro.obs import MetricDef, MetricsAccumulator
    mesh = _mesh()
    lanes = 8 * NDEV
    defs = {"r": MetricDef(lo=-2.5, hi=0.0, bins=16, lanes=lanes,
                           n_windows=4, window_len=3)}
    plain = MetricsAccumulator.create(defs)
    placed = plain.place(lambda x, axis=0: shard.shard_array(x, mesh,
                                                             axis=axis),
                         lambda x: shard.replicate(x, mesh))
    if NDEV > 1:
        assert placed.data["r"]["wtotal"].sharding.spec[1] == "fleet"
        assert placed.data["r"]["hist"].sharding.is_fully_replicated

    @jax.jit
    def roll(acc, key):
        def body(carry, k):
            return carry.update(
                {"r": -2.5 * jax.random.uniform(k, (lanes,))}), None
        acc, _ = jax.lax.scan(body, acc, jax.random.split(key, 10))
        return acc

    a, b = (roll(acc, jax.random.PRNGKey(4)) for acc in (plain, placed))
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    if NDEV > 1:                                     # layout survived scan
        assert b.data["r"]["wcount"].sharding.spec[1] == "fleet"


def test_windowed_training_bit_parity_and_matches_unwindowed():
    """ISSUE-8 acceptance: a windowed FleetQLearning run is (a)
    bit-identical sharded vs single-device on every leaf including the
    ring, and (b) bit-identical on the shared (un-windowed) leaves and
    the Q-table to a run with windows off — windows only ADD telemetry,
    they never perturb training."""
    cfg = _full_cfg(8 * NDEV)
    w = dict(n_windows=4, window_len=10)
    a = FleetQLearning(SyntheticSource(cfg), cfg=FleetQConfig(), seed=3,
                       **w)
    b = FleetQLearning(SyntheticSource(cfg), cfg=FleetQConfig(), seed=3,
                       mesh=_mesh(), **w)
    off = FleetQLearning(SyntheticSource(cfg), cfg=FleetQConfig(), seed=3)
    for agent in (a, b, off):
        agent.run(40)
    np.testing.assert_array_equal(np.asarray(a.q), np.asarray(b.q))
    for name, da in a.metrics.data.items():
        db = b.metrics.data[name]
        for leaf in ("count", "hist", "underflow", "overflow",
                     "wcount", "wmn", "wmx"):
            np.testing.assert_array_equal(np.asarray(da[leaf]),
                                          np.asarray(db[leaf]))
        np.testing.assert_allclose(np.asarray(da["wtotal"]),
                                   np.asarray(db["wtotal"]), rtol=1e-6)
    # (b) windows on vs off: training stream untouched
    np.testing.assert_array_equal(np.asarray(a.q), np.asarray(off.q))
    _assert_scen_equal(a.scen, off.scen)
    for name, da in a.metrics.data.items():
        do = off.metrics.data[name]
        for leaf in do:                              # shared leaves only
            np.testing.assert_array_equal(np.asarray(da[leaf]),
                                          np.asarray(do[leaf]))
    # and the ring is self-consistent: per-window counts sum to totals
    s = a.metrics_summary()["reward"]
    assert sum(s["windows"]["count"]) == s["count"]


def test_windowed_ring_sums_property():
    """Hypothesis property (ISSUE-8): for any update stream, per-window
    counts sum EXACTLY to the whole-run count (integer leaves), and the
    float window totals sum to the run total within reassociation ULPs."""
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")
    from repro.obs import MetricDef, MetricsAccumulator

    @hyp.given(st.data())
    @hyp.settings(max_examples=20, deadline=None)
    def run(data):
        lanes = data.draw(st.integers(1, 4), label="lanes")
        n_windows = data.draw(st.integers(1, 5), label="n_windows")
        window_len = data.draw(st.integers(1, 4), label="window_len")
        steps = data.draw(st.integers(0, 24), label="steps")
        vals = data.draw(st.lists(
            st.lists(st.floats(-1e3, 1e3, allow_nan=False, width=32),
                     min_size=lanes, max_size=lanes),
            min_size=steps, max_size=steps), label="vals")
        acc = MetricsAccumulator.create(
            {"m": MetricDef(lo=-10.0, hi=10.0, bins=8, lanes=lanes,
                            n_windows=n_windows, window_len=window_len)})
        for row in vals:
            acc = acc.update({"m": jnp.asarray(row, jnp.float32)})
        d = acc.data["m"]
        np.testing.assert_array_equal(
            np.asarray(d["wcount"]).sum(0), np.asarray(d["count"]))
        np.testing.assert_allclose(
            np.asarray(d["wtotal"], np.float64).sum(0),
            np.asarray(d["total"], np.float64), rtol=1e-5, atol=1e-4)
        assert int(acc.step) == steps

    run()


def test_holdout_reward_ratio_bit_parity():
    a, b = _trained_pair()
    ha = holdout_reward_ratio(a, a.scen)
    hb = holdout_reward_ratio(b, b.scen)
    assert ha.ratio == hb.ratio
    np.testing.assert_array_equal(ha.achieved, hb.achieved)
    np.testing.assert_array_equal(ha.optimal, hb.optimal)
    np.testing.assert_array_equal(ha.feasible, hb.feasible)


def test_orchestrator_routes_sharded_fleet():
    _, b = _trained_pair(steps=20)
    orch = FleetOrchestrator(b)
    assert orch.mesh is b.mesh                       # inherited knob
    dec, ids = orch.route()
    assert np.asarray(dec).shape == (8 * NDEV, 2)
    assert np.asarray(ids).shape == (8 * NDEV,)


# ------------------------------------------------ DQN data parallelism ----
def test_dqn_sharded_cold_decisions_match_and_training_runs():
    cfg = FleetConfig(cells=8 * NDEV, users=2, arrival_rate=1.0)
    a = FleetDQN(SyntheticSource(cfg), cfg=FleetDQNConfig(), seed=5)
    b = FleetDQN(SyntheticSource(cfg), cfg=FleetDQNConfig(), seed=5,
                 mesh=_mesh())
    # same seed -> identical replicated params; the cold greedy pass is
    # per-cell, so sharding the fleet cannot change any decision
    scen = init_fleet(jax.random.PRNGKey(1), cfg)
    counts = jnp.zeros((cfg.cells, 2), jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(a.policy_decisions(counts, scen)[0]),
        np.asarray(b.policy_decisions(
            shard.shard_array(counts, b.mesh),
            shard.shard_scenario(scen, b.mesh))[0]))
    b.run(30)                                        # trains sharded
    if NDEV > 1:
        assert b.buffer.s.sharding.spec[0] == "fleet"
        leaf = jax.tree_util.tree_leaves(b.params)[0]
        assert leaf.sharding.spec == jax.sharding.PartitionSpec()
    h = holdout_reward_ratio(b, b.scen)
    assert 0.0 < h.ratio <= 1.0 + 1e-6


# ---------------------------------------------- trace replay placement ----
def test_tracesource_mesh_training_bit_parity():
    base = SyntheticSource(FleetConfig(cells=4 * NDEV, users=2,
                                       arrival_rate=1.0, p_r2w=0.1,
                                       p_w2r=0.2))
    trace = record_trace(base, jax.random.PRNGKey(0), 12)
    a = FleetQLearning(TraceSource(trace), seed=7)
    b = FleetQLearning(TraceSource(trace, mesh=_mesh()), seed=7)
    assert b.mesh is not None                        # inherited from source
    a.run(24)
    b.run(24)
    np.testing.assert_array_equal(np.asarray(a.q), np.asarray(b.q))
    _assert_scen_equal(a.scen, b.scen)


def test_synthetic_source_mesh_reset_is_value_identical():
    cfg = _full_cfg(4 * NDEV)
    plain, _ = SyntheticSource(cfg).reset(jax.random.PRNGKey(2))
    placed, _ = SyntheticSource(cfg, mesh=_mesh()).reset(
        jax.random.PRNGKey(2))
    _assert_scen_equal(plain, placed)


# ------------------------------------------------- shard-local topology ---
def test_shard_local_generator_invariant():
    """Satellite: no edge spans shards when shard_local=True — for the
    generator AND through FleetConfig/init_fleet."""
    n_shards = max(NDEV, 4)
    topo = topology.random_topology(jax.random.PRNGKey(0), 8 * n_shards,
                                    2 * n_shards, shard_local=True,
                                    n_shards=n_shards)
    assert topology.is_shard_local(topo, n_shards)
    cpb, epb = topology.shard_blocks(topo.cells, topo.n_edges, n_shards)
    ce = np.asarray(topo.cell_edge)
    for e in range(topo.n_edges):                    # edge-wise statement
        owners = np.nonzero(ce == e)[0]
        assert len(np.unique(owners // cpb)) <= 1
        assert (owners // cpb == e // epb).all()
    # the unconstrained generator does cross blocks (same sizes)
    free = topology.random_topology(jax.random.PRNGKey(0), 8 * n_shards,
                                    2 * n_shards)
    assert not topology.is_shard_local(free, n_shards)


def test_shard_local_divisibility_and_assignment_errors():
    with pytest.raises(ValueError, match="divisible"):
        topology.random_topology(jax.random.PRNGKey(0), 10, 4,
                                 shard_local=True, n_shards=4)
    from repro.fleet.scenarios import make_topology
    with pytest.raises(ValueError, match="random"):
        make_topology(jax.random.PRNGKey(0),
                      FleetConfig(cells=8, users=2, n_edges=4,
                                  assignment="skewed", shard_local=True,
                                  n_shards=2))
    # edge failures reroute across device blocks — they would break the
    # locality invariant mid-run where jit cannot detect it, so the
    # combination is rejected up front
    with pytest.raises(ValueError, match="p_edge_fail"):
        make_topology(jax.random.PRNGKey(0),
                      FleetConfig(cells=8, users=2, n_edges=4,
                                  p_edge_fail=0.1, shard_local=True,
                                  n_shards=2))


def test_local_contention_matches_global_bit_exact():
    """Mode (a) vs mode (b): the shard_map local aggregation equals the
    global segment-sum path — exactly, since the per-edge totals are
    integer sums and the cloud multiplier sees the same psum'd total."""
    mesh = _mesh()
    cells, n_edges = 8 * NDEV, 2 * NDEV
    topo = topology.random_topology(jax.random.PRNGKey(1), cells, n_edges,
                                    shard_local=True, n_shards=NDEV,
                                    capacity_tiers=(1.0, 2.0),
                                    cloud_servers=16.0)
    scen = init_fleet(jax.random.PRNGKey(2),
                      FleetConfig(cells=cells, users=3, arrival_rate=1.0))
    pu = jnp.asarray(np.random.default_rng(0).integers(0, 10, (cells, 3)),
                     jnp.int32)
    ref = topology.shared_contention(pu, topo, active=scen.active)
    topo_s = shard.shard_topology(topo, mesh)
    scen_s = shard.shard_scenario(scen, mesh)
    got = shard.local_contention(shard.shard_array(pu, mesh), topo_s, mesh,
                                 active=scen_s.active)
    for r, g in zip(ref, got):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(g))
    # the jitted seam agrees too (what the benchmark times)
    jit_got = jax.jit(lambda p, t, m: shard.local_contention(
        p, t, mesh, active=m))(shard.shard_array(pu, mesh), topo_s,
                               scen_s.active)
    for r, g in zip(ref, jit_got):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(g))
    # and the full eager response path is bit-identical end to end
    r_ms, r_acc = topology.topology_expected_response(
        pu, scen.end_b, scen.edge_b, topo, active=scen.active)
    l_ms, l_acc = shard.local_expected_response(
        shard.shard_array(pu, mesh), scen_s.end_b, scen_s.edge_b, topo_s,
        mesh, active=scen_s.active)
    np.testing.assert_array_equal(np.asarray(r_ms), np.asarray(l_ms))
    np.testing.assert_array_equal(np.asarray(r_acc), np.asarray(l_acc))


def test_local_contention_rejects_cross_shard_topology():
    mesh = _mesh()
    if NDEV < 2:
        pytest.skip("locality is unfalsifiable on one device")
    bad = topology.hot_edge_topology(8 * NDEV, 2 * NDEV)   # spans blocks
    pu = jnp.zeros((8 * NDEV, 2), jnp.int32)
    with pytest.raises(ValueError, match="shard-local"):
        shard.local_contention(pu, shard.shard_topology(bad, mesh), mesh)


# --------------------------------------------------- forced 8 devices -----
@pytest.mark.skipif(NDEV >= 8 or os.environ.get("REPRO_SHARD_SUBPROCESS"),
                    reason="already on a multi-device host platform")
def test_forced_8_device_parity():
    """The acceptance run: this whole file under a forced 8-device CPU
    host platform (jax locks the device count at first init, so it must
    be a fresh process)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8")
    env["REPRO_SHARD_SUBPROCESS"] = "1"
    env["PYTHONPATH"] = (os.path.join(os.path.dirname(__file__), "..",
                                      "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    res = subprocess.run(
        [sys.executable, "-m", "pytest", "-x", "-q", __file__],
        env=env, capture_output=True, text=True, timeout=1200)
    assert res.returncode == 0, \
        f"8-device run failed:\n{res.stdout}\n{res.stderr}"
