"""End-to-end training equivalence across the RL impl seam.

The fused hot path (``impl='pallas'`` and friends) must train the SAME
agent as the unfused composition. Every path draws the same random
numbers, so the tabular agent is bit-identical when its update op
(``ops.fused_tabular_update``) is swapped for the unfused
gather/max/scatter/argmax chain, and ``FleetDQN``'s fused head matches
its legacy head (``impl='xla'``) to reduction-order tolerance. Runs entirely on CPU — ``'pallas'``
resolves to the fused-jnp formulation here, and ``'pallas_interpret'``
forces the real kernel through the Pallas interpreter on a tiny fleet.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.fleet import (FleetConfig, FleetDQN, FleetDQNConfig,
                         FleetQConfig, FleetQLearning, SyntheticSource)
from test_kernels import _naive_tabular


def _source(cells=32, users=2, seed=0):
    return SyntheticSource(FleetConfig(cells=cells, users=users,
                                       arrival_rate=1.0, p_r2w=0.05,
                                       p_w2r=0.1))


def _tabular(impl, cells=32, **kw):
    return FleetQLearning(_source(cells), cfg=FleetQConfig(), seed=3,
                          impl=impl, **kw)


def _unfused(monkeypatch):
    """From here on, ``ops.fused_tabular_update`` is the unfused
    gather/max/scatter/argmax composition (``test_kernels``'s oracle),
    the seam the benchmark's faults use; returns the list its calls
    are logged in."""
    from repro.kernels import ops
    calls = []

    def naive(*args, **kw):
        calls.append(kw)
        return _naive_tabular(*args, **kw)

    monkeypatch.setattr(ops, "fused_tabular_update", naive)
    return calls


def test_tabular_fused_training_bit_identical_to_xla(monkeypatch):
    """40 scanned steps: Q-table, counts, and greedy decisions from the
    fused path are bit-identical to the same agent whose update is the
    unfused composition."""
    a = _tabular("pallas")
    a.run(40)
    calls = _unfused(monkeypatch)
    b = _tabular("pallas")
    b.run(40)
    assert calls                     # the seam actually switched ops
    np.testing.assert_array_equal(np.asarray(a.q), np.asarray(b.q))
    np.testing.assert_array_equal(np.asarray(a.counts),
                                  np.asarray(b.counts))
    np.testing.assert_array_equal(np.asarray(a.greedy_decisions()),
                                  np.asarray(b.greedy_decisions()))
    sa, sb = a.metrics_summary(), b.metrics_summary()
    assert sa["reward"]["count"] == sb["reward"]["count"]
    assert sa["reward"]["mean"] == pytest.approx(sb["reward"]["mean"],
                                                 rel=1e-6)


def test_tabular_stepwise_bit_identical_across_impls(monkeypatch):
    """The single-step path (which re-gathers greedy instead of carrying
    it) is also bit-identical to the unfused composition. Stepwise and
    scanned runs differ from EACH OTHER (host-float vs in-carry f32
    epsilon decay, a pre-existing property) — the guarantee is within
    each mode."""
    a = _tabular("pallas", cells=8)
    for _ in range(10):
        a.step()
    calls = _unfused(monkeypatch)
    b = _tabular("pallas", cells=8)
    for _ in range(10):
        b.step()
    assert calls
    np.testing.assert_array_equal(np.asarray(a.q), np.asarray(b.q))
    np.testing.assert_array_equal(np.asarray(a.counts),
                                  np.asarray(b.counts))


def test_tabular_interpret_kernel_training_matches_xla():
    """The real Pallas kernel (interpret mode) on a ragged fleet (13
    cells, not a block's multiple): two scanned ``run`` calls and one
    ``step``, identical trajectories to the fused-jnp ``ref`` up to the
    kernel's fma-contraction ulp. The scan carries the table in the
    kernel's layout, and ``agent.q`` keeps its logical shape between
    calls."""
    a = _tabular("ref", cells=13)
    b = _tabular("pallas_interpret", cells=13)
    shape = (13, b.n_states, b.n_actions)
    assert b.q.shape == shape
    for advance in (lambda ag: ag.run(6), lambda ag: ag.run(6),
                    lambda ag: ag.step()):
        advance(a)
        advance(b)
        assert b.q.shape == shape
        np.testing.assert_allclose(np.asarray(a.q), np.asarray(b.q),
                                   atol=1e-5, rtol=1e-5)
        np.testing.assert_array_equal(np.asarray(a.counts),
                                      np.asarray(b.counts))


@pytest.mark.parametrize("impl", ["cuda", "xla"])
def test_tabular_unknown_impl_raises(impl):
    with pytest.raises(ValueError, match="unknown impl"):
        _tabular(impl)


def _dqn(impl, threshold=85.0, cells=16):
    return FleetDQN(_source(cells), seed=5, impl=impl,
                    cfg=FleetDQNConfig(replay_capacity=512, batch_size=32,
                                       hidden=32,
                                       accuracy_threshold=threshold))


@pytest.mark.parametrize("threshold", [0.0, 85.0])
def test_dqn_fused_training_matches_xla(threshold):
    """30 steps of replay-driven training: fused head vs legacy encode +
    masked argmax. At threshold 0 the paths are bit-identical; with the
    constraint head active the combo scoring reduces in a different
    order, so params match to float tolerance — decisions exactly."""
    a, b = _dqn("xla", threshold), _dqn("pallas", threshold)
    assert b._op_impl != "xla"
    a.run(30)
    b.run(30)
    for pa, pb in zip(a.params, b.params):
        np.testing.assert_allclose(np.asarray(pa["w"]),
                                   np.asarray(pb["w"]),
                                   atol=1e-4, rtol=1e-4)
    np.testing.assert_array_equal(np.asarray(a.counts),
                                  np.asarray(b.counts))
    np.testing.assert_array_equal(np.asarray(a.greedy_decisions()),
                                  np.asarray(b.greedy_decisions()))


def test_dqn_cell_net_falls_back_to_legacy():
    """The fused head only covers the shared per-user net; a 'cell' net
    agent silently keeps the legacy path (impl seam resolves to xla)."""
    agent = FleetDQN(_source(8), seed=1,
                     cfg=FleetDQNConfig(replay_capacity=256, batch_size=16,
                                        hidden=16, net="cell"))
    assert agent._op_impl == "xla"
    agent.run(5)                     # still trains


def test_dqn_fused_greedy_respects_constraint_feasibility():
    """Fused greedy decisions at an active QoS goal stay feasible
    whenever the legacy head's are (same accuracy ladder)."""
    from repro.fleet import dynamics
    a, b = _dqn("xla", 85.0), _dqn("pallas", 85.0)
    a.run(20)
    b.run(20)
    da = np.asarray(a.greedy_decisions())
    db = np.asarray(b.greedy_decisions())
    np.testing.assert_array_equal(da, db)
    member = np.asarray(a.scen.member)
    acc = dynamics.accuracies(db)
    nm = np.maximum(member.sum(-1), 1)
    macc = np.where(member.any(-1),
                    (acc * member).sum(-1) / nm, 100.0)
    feas_frac = dynamics.feasible(macc, 85.0).mean()
    assert feas_frac == dynamics.feasible(
        np.where(member.any(-1),
                 (dynamics.accuracies(da) * member).sum(-1) / nm,
                 100.0), 85.0).mean()
