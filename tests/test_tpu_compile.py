"""Compile-only checks of the RL kernels for a described TPU v5e.

The TPU compiler is installed with JAX, so the fleet agents' Pallas
kernels are lowered and compiled for a ``v5e:2x2`` topology that is
described, not attached: Mosaic refuses here what it would refuse on
the chip (unaligned blocks, scalar stores to VMEM, scoped-VMEM
overflow). Shapes are those of ``chip_smoke.py``. Nothing runs, so
nothing here says anything about results or speed.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and the test workers all
import this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops

TAB_CELLS, STATES, ACTIONS = 65536, 36, 243   # the smoke's 2.29 GB Q-table
BENCH_CELLS = 131072                          # the benchmark's 4.59 GB table
DQN_CELLS, USERS, HIDDEN, TOPK, N_ACT = 16384, 5, 128, 5, 10


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _shape(one_chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def test_tabular_kernel_compiles_for_v5e(one_chip):
    fn = jax.jit(lambda q, s, a, r, s2: ops.fused_tabular_update(
        q, s, a, r, s2, alpha=0.9, gamma=0.1, impl="pallas",
        interpret=False), donate_argnums=0)
    idx = _shape(one_chip, (TAB_CELLS,), jnp.int32)
    compiled = fn.lower(_shape(one_chip, (TAB_CELLS, STATES, ACTIONS)), idx,
                        idx, _shape(one_chip, (TAB_CELLS,)), idx).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_tabular_kernel_small_ragged_block_compiles_for_v5e(one_chip):
    """The parity tests' shapes: an 8-cell block (narrower than a lane
    tile), a ragged last block, 9 states and 10 actions."""
    fn = jax.jit(lambda q, s, a, r, s2: ops.fused_tabular_update(
        q, s, a, r, s2, alpha=0.9, gamma=0.1, impl="pallas", bc=8,
        interpret=False), donate_argnums=0)
    idx = _shape(one_chip, (37,), jnp.int32)
    compiled = fn.lower(_shape(one_chip, (37, 9, 10)), idx, idx,
                        _shape(one_chip, (37,)), idx).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("threshold", [0.0, 85.0])
def test_dqn_head_compiles_for_v5e(one_chip, threshold):
    """The fused head at the agent's defaults (5 users, hidden 128,
    ``topk=5``), with and without the QoS goal, at the block size the
    VMEM budget picks."""
    params = [{"w": _shape(one_chip, (i, o)), "b": _shape(one_chip, (o,))}
              for i, o in ((11, HIDDEN), (HIDDEN, HIDDEN), (HIDDEN, N_ACT))]
    per_user = _shape(one_chip, (DQN_CELLS, USERS))
    fn = jax.jit(lambda act, mem, end, agg, p, allowed, acc: ops.dqn_head(
        act, mem, end, agg, p, allowed, acc, threshold=threshold,
        topk=TOPK, impl="pallas", interpret=False))
    compiled = fn.lower(per_user, per_user, per_user,
                        _shape(one_chip, (DQN_CELLS, 8)), params,
                        _shape(one_chip, (USERS, N_ACT)),
                        _shape(one_chip, (N_ACT,))).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_tabular_scan_keeps_kernel_name_under_update_scope(one_chip):
    """The fleet scan with the compiled kernel, at a v5e: the kernel's
    custom call keeps the name the benchmark's roofline matches
    (``fused_tabular_update.N``) and sits under the ``fleet.update``
    scope; the greedy gather before the scan is the prologue."""
    import re

    from repro.fleet import (FleetConfig, FleetQConfig, FleetQLearning,
                             SyntheticSource)
    agent = FleetQLearning(SyntheticSource(FleetConfig(cells=1024,
                                                       users=USERS)),
                           cfg=FleetQConfig())
    agent.path = ops.TabularPath("pallas", agent.n_states, agent.n_actions)
    run = jax.jit(agent._make_run(), static_argnums=(6,),
                  donate_argnums=(0, 1))
    args = jax.tree.map(
        lambda x: _shape(one_chip, jnp.shape(x), jnp.result_type(x)),
        (agent.q, agent.metrics, agent.counts, agent.scen,
         jnp.float32(agent.eps), agent.key))
    text = run.lower(*args, 8).compile().as_text()
    calls = re.findall(r"%(fused_tabular_update(?:\.\d+)?) = .*? "
                       r"custom-call\(.*op_name=\"([^\"]*)\"", text)
    assert len(calls) == 1
    assert "/fleet.update/" in calls[0][1]
    assert "/while/body/" in calls[0][1]
    assert re.search(r'op_name="jit\(run\)/fleet\.prologue/gather"', text)


def _scan(one_chip, cells, **cfg):
    """The fleet scan with the compiled kernel, compiled for a v5e at
    ``cells`` cells. The agent is built at 3 cells, so that no
    full-size table is ever made here; its per-cell state is described
    at ``cells``, and its telemetry is made at ``cells`` (small)."""
    from repro.fleet import (FleetConfig, FleetQConfig, FleetQLearning,
                             SyntheticSource)
    from repro.fleet.population import fleet_metrics
    agent = FleetQLearning(SyntheticSource(FleetConfig(cells=3,
                                                       users=USERS)),
                           cfg=FleetQConfig(**cfg))
    agent.metrics = fleet_metrics(cells, "tabular")
    agent.path = ops.TabularPath("pallas", agent.n_states, agent.n_actions)
    run = jax.jit(agent._make_run(), static_argnums=(6,),
                  donate_argnums=(0, 1))
    args = jax.tree.map(
        lambda x: _shape(one_chip, (cells,) + jnp.shape(x)[1:]
                         if jnp.shape(x)[:1] == (3,) else jnp.shape(x),
                         jnp.result_type(x)),
        (agent.q, agent.metrics, agent.counts, agent.scen,
         jnp.float32(agent.eps), agent.key))
    return agent, run.lower(*args, 8).compile()


@pytest.fixture(scope="module")
def bench_scan(one_chip):
    """``tabular_train``'s scan, compiled once for the module."""
    return _scan(one_chip, BENCH_CELLS)


def test_tabular_scan_at_benchmark_size_holds_one_padded_table(bench_scan):
    """At the benchmark's 131,072 x 36 x 243 the scan carries the table
    in the kernel's layout, padded to 40 x 256: its temporaries are that
    one table and at most 10% more (the pieces of the layout changes
    around the scan, the step's own arrays)."""
    agent, compiled = bench_scan
    assert (agent.n_states, agent.n_actions) == (STATES, ACTIONS)
    padded = BENCH_CELLS * 40 * 256 * 4
    assert compiled.memory_analysis().temp_size_in_bytes <= 1.1 * padded
    assert "tpu_custom_call" in compiled.as_text()


def test_tabular_scan_compiles_with_link_states(one_chip):
    """``track_links=True``: 2,304 states a cell at 2,048 cells (4.59
    GB). The kernel's VMEM does not grow with the states, so it
    compiles."""
    agent, compiled = _scan(one_chip, 2048, track_links=True)
    assert agent.n_states == 2304
    assert "tpu_custom_call" in compiled.as_text()


#: the ``sharded_train_4chip`` cell: 262,144 cells over four chips, 16
#: cells an edge, each cell's edge within its chip's block
MESH_CELLS, MESH_EDGES, MESH_CHIPS = 262144, 16384, 4


def _sharded_scan(topo, kernel: bool):
    """The fleet scan on the ``('fleet',)`` mesh over the four chips of
    a described v5e 2x2, at the benchmark's 262,144-cell fleet with
    shared edges and Markov links, compiled: the ``ref`` update, or the
    compiled kernel (``kernel``). The agent is built at 8 cells; its
    state is described at full size with the layout
    ``shard.shard_scenario`` and ``place_metrics`` give it."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.fleet import (FleetConfig, FleetQConfig, FleetQLearning,
                             SyntheticSource, shard)
    from repro.fleet.population import fleet_metrics
    from repro.fleet.scenarios import FleetScenario
    from repro.fleet.topology import Topology
    mesh = Mesh(topo.devices[:MESH_CHIPS], (shard.FLEET_AXIS,))

    def described(x, shape=None, spec=P()):
        return jax.ShapeDtypeStruct(shape or jnp.shape(x),
                                    jnp.result_type(x),
                                    sharding=NamedSharding(mesh, spec))

    def per_cell(x, axis=0):
        shape = list(jnp.shape(x))
        shape[axis] = MESH_CELLS
        return described(x, tuple(shape),
                         P(*([None] * axis + [shard.FLEET_AXIS])))

    cfg = FleetConfig(cells=8, users=USERS, p_r2w=0.05, p_w2r=0.05,
                      n_edges=4, shard_local=True, n_shards=MESH_CHIPS)
    agent = FleetQLearning(SyntheticSource(cfg), cfg=FleetQConfig(),
                           impl="ref")
    agent.source.attach_mesh(mesh)
    if kernel:
        agent.path = ops.TabularPath("pallas", agent.n_states,
                                     agent.n_actions, mesh)
    run = jax.jit(agent._make_run(), static_argnums=(6,),
                  donate_argnums=(0, 1))
    s = agent.scen
    scen = FleetScenario(
        per_cell(s.end_b), per_cell(s.edge_b), per_cell(s.member),
        per_cell(s.active), described(s.t),
        Topology(per_cell(s.topo.cell_edge),
                 described(s.topo.edge_capacity, (MESH_EDGES,)),
                 described(s.topo.cloud_servers)))
    mets = fleet_metrics(MESH_CELLS, "tabular").place(per_cell, described)
    return run.lower(per_cell(agent.q), mets, per_cell(agent.counts),
                     scen, described(jnp.float32(agent.eps)),
                     described(agent.key), 8).compile()


def test_sharded_scan_at_benchmark_size_fits_four_chips(topo):
    """The four-chip scan with the ``ref`` update (what a mesh resolves
    to off a TPU, and for ``FleetDQN``): one Q-table shard a chip,
    arguments and temporaries under 14 GB a chip, and no kernel."""
    compiled = _sharded_scan(topo, kernel=False)
    q_in = compiled.input_shardings[0][0]
    assert q_in.shard_shape((MESH_CELLS, STATES, ACTIONS))[0] \
        == MESH_CELLS // MESH_CHIPS
    assert len(q_in.device_set) == MESH_CHIPS
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 14e9
    text = compiled.as_text()
    assert "tpu_custom_call" not in text
    assert "all-reduce" in text


@pytest.fixture(scope="module")
def mesh_kernel_scan(topo):
    """``sharded_train_4chip``'s scan with the kernel, compiled once."""
    return _sharded_scan(topo, kernel=True)


def test_sharded_kernel_scan_at_benchmark_size_runs_one_kernel_a_chip(
        mesh_kernel_scan):
    """The same four-chip scan with the kernel path forced, as a TPU
    backend resolves ``impl="pallas"`` under a mesh: the ``tabular_rl``
    kernel runs once a chip on its 65,536 cells under ``shard_map``,
    one custom call under ``fleet.update`` in the scan's body; the
    contention sums are still all-reduced; and each chip holds the
    table in the kernel's layout, padded to 40 x 256, and at most 10%
    more (no whole-shard relayouts)."""
    import re

    compiled = mesh_kernel_scan
    q_in = compiled.input_shardings[0][0]
    assert q_in.shard_shape((MESH_CELLS, STATES, ACTIONS))[0] \
        == MESH_CELLS // MESH_CHIPS
    text = compiled.as_text()
    calls = re.findall(r"%(fused_tabular_update(?:\.\d+)?) = .*? "
                       r"custom-call\(.*op_name=\"([^\"]*)\"", text)
    assert len(calls) == 1
    assert "/fleet.update/" in calls[0][1]
    assert "/while/body/" in calls[0][1]
    assert "all-reduce" in text
    padded_shard = MESH_CELLS // MESH_CHIPS * 40 * 256 * 4
    assert compiled.memory_analysis().temp_size_in_bytes \
        <= 1.1 * padded_shard


@pytest.mark.parametrize("scan", ["bench_scan", "mesh_kernel_scan"])
def test_benchmark_scans_count_histograms_without_scatter(request, scan):
    """Both benchmark scans count the three per-cell telemetry
    histograms (``reward``, ``mean_ms``, ``td_abs``; 32 bins) as one
    ``s32[32]`` reduction each under ``fleet.telemetry``, and no op
    under that scope is a scatter: a scatter-add over 131,072 indices
    into 32 bins runs one update after another on a TPU. On the mesh
    the partial counts are all-reduced."""
    import re

    compiled = request.getfixturevalue(scan)
    if scan == "bench_scan":
        compiled = compiled[1]
    text = compiled.as_text()
    assert "fleet.telemetry/scatter" not in text
    counts = re.findall(
        r"= s32\[32\]\{[^}]*\} fusion\(.*op_name=\"jit\(run\)/while/body/"
        r"closed_call/fleet\.telemetry/reduce_sum\"", text)
    assert len(counts) == 3
    if scan == "mesh_kernel_scan":
        assert "all-reduce" in text
