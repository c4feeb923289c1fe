"""Device-sharded fleet execution: the cell population over a 1-D mesh.

Everything fleet-shaped so far lives on one device: the ``(cells,
states, actions)`` Q-table, the ``FleetScenario`` arrays, the pooled
replay rows, and the topology segment-sums. The ROADMAP's north star is
millions of users, which means the *fleet axis itself* must span
devices. This module is that layer — MaxText-style logical-axis
data-parallelism (``repro.distributed.sharding``'s ``cells`` / ``edges``
rules) over a 1-D ``('fleet',)`` mesh:

* **Placement** — ``fleet_mesh()`` builds the mesh; ``shard_scenario``
  / ``shard_array`` / ``shard_replay`` place fleet state with
  ``jax.sharding.NamedSharding`` (cells axis split into contiguous
  per-device blocks, everything else replicated), and the
  ``constrain_*`` twins re-assert the layout inside jitted steps. Every
  fleet computation is already pure and jitted, so XLA's SPMD
  partitioner runs each cell's dynamics, TD update, and scenario
  transition on the device that owns the cell — bit-identically to the
  single-device path (asserted in ``tests/test_fleet_shard.py``):
  per-cell work is elementwise along the fleet axis, and the only
  cross-cell reductions (topology job totals) are integer sums, which
  are associative exactly.
* **Cross-shard topologies** — once cells sharing an edge live on
  different devices, the per-edge segment-sum becomes a cross-device
  reduction. Two shipped answers, benchmarked against each other in
  ``benchmarks/bench_fleet_sharded.py``:
  (a) the **locality-capped generator**
  (``topology.random_topology(..., shard_local=True)``) keeps every
  edge's cells inside one device block, so ``local_contention`` — a
  ``shard_map`` over the fleet axis — aggregates entirely on-device
  (the one cross-device term left is a scalar ``psum`` for the cloud
  queue), and
  (b) the **all-to-all path**: any assignment through the unchanged
  ``topology.shared_contention`` under GSPMD, which turns the
  segment-sum into the compiler's cross-device reduction.
* **Training** — ``FleetQLearning(..., mesh=)`` shards the Q-table and
  scenario along cells (the update is per-cell, so it never leaves the
  shard); ``FleetDQN(..., mesh=)`` replicates params and optimizer
  state, shards the scenario stream along cells and the replay ring by
  slot blocks (``shard_replay``), and the mini-batch loss mean becomes
  the partitioner's cross-device grad reduction — standard
  replicate-the-policy / shard-the-population data parallelism.
* **Fused RL ops under the mesh** — the agents' default
  ``impl='pallas'`` hot path (ISSUE-10) gates itself here: GSPMD
  cannot partition a ``pallas_call``, so
  ``kernels.ops.resolve_rl_impl`` resolves ``'pallas'`` to the fused
  *jnp* formulation whenever a mesh is attached. That formulation is
  per-cell elementwise plus reduces along the (replicated) action
  axis — the same op classes as the legacy step — so sharded fused
  training stays bit-identical to single-device fused AND to the
  legacy unfused path (``tests/test_fleet_shard.py::
  test_fused_impl_sharded_training_bit_parity``). Running the compiled
  kernel per shard via ``shard_map`` is the open follow-up; it needs a
  TPU mesh to be worth wiring.

CPU-testable: ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
forces an 8-device host platform (no accelerator needed); with a
single device every helper degenerates to a no-op placement, and with
``mesh=None`` they are exact identities.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.distributed import sharding
from repro.fleet import dynamics, topology
from repro.fleet.scenarios import FleetScenario
from repro.fleet.topology import Topology, shard_blocks

__all__ = [
    "FLEET_AXIS", "fleet_mesh", "fleet_spec", "shard_array",
    "constrain_array", "replicate", "shard_topology", "shard_scenario",
    "constrain_scenario", "shard_replay", "local_contention",
    "local_expected_response", "check_shard_local",
]

#: the one mesh axis of fleet data parallelism (see
#: ``distributed.sharding.RULES['cells'/'edges']``)
FLEET_AXIS = "fleet"


def fleet_mesh(n_devices: Optional[int] = None, devices=None) -> Mesh:
    """A 1-D ``('fleet',)`` mesh over ``devices`` (default: all local
    devices, optionally capped at ``n_devices``)."""
    devices = list(devices if devices is not None else jax.devices())
    if n_devices is not None:
        devices = devices[:n_devices]
    return Mesh(np.asarray(devices), (FLEET_AXIS,))


def fleet_spec(mesh: Mesh, shape, axis: int = 0,
               logical: str = "cells") -> P:
    """`PartitionSpec` sharding dimension ``axis`` of ``shape`` along
    the fleet axis, through the logical-axis rule table (so a dimension
    the mesh does not divide falls back to replication instead of
    erroring, exactly like the model shardings)."""
    axes = (None,) * axis + (logical,) + (None,) * (len(shape) - axis - 1)
    return sharding.spec_for(shape, axes, mesh)


def shard_array(x, mesh: Optional[Mesh], axis: int = 0,
                logical: str = "cells"):
    """Place ``x`` with dimension ``axis`` split over the fleet axis
    (identity when ``mesh`` is None)."""
    if mesh is None:
        return x
    x = jnp.asarray(x)
    return jax.device_put(x, NamedSharding(mesh, fleet_spec(mesh, x.shape,
                                                            axis, logical)))


def constrain_array(x, mesh: Optional[Mesh], axis: int = 0,
                    logical: str = "cells"):
    """`with_sharding_constraint` twin of ``shard_array`` — safe both
    inside jit (a layout constraint for the partitioner) and eagerly (a
    commit). Values are never changed, only placement."""
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, fleet_spec(mesh, x.shape, axis, logical)))


def replicate(tree, mesh: Optional[Mesh]):
    """Replicate every leaf of ``tree`` across the mesh (the placement
    for DQN params / optimizer state; identity when ``mesh`` is None)."""
    if mesh is None:
        return tree
    s = NamedSharding(mesh, P())
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, s), tree)


def _map_topology(topo: Optional[Topology], mesh: Optional[Mesh], place):
    if topo is None or mesh is None:
        return topo
    # capacities replicate: the all-to-all path indexes them by
    # arbitrary cell_edge values; shard-local aggregation re-blocks
    # them itself (``local_contention``)
    return Topology(
        place(topo.cell_edge, mesh, 0, "cells"),
        replicate(topo.edge_capacity, mesh),
        replicate(topo.cloud_servers, mesh))


def shard_topology(topo: Optional[Topology],
                   mesh: Optional[Mesh]) -> Optional[Topology]:
    """``cell_edge`` rides with its cells; capacities and the cloud
    queue size replicate."""
    return _map_topology(topo, mesh, shard_array)


def _constrain_replicated(tree, mesh: Optional[Mesh]):
    """Jit-safe twin of ``replicate``: constrain every leaf to the
    fully-replicated layout (identity when ``mesh`` is None)."""
    if mesh is None:
        return tree
    s = NamedSharding(mesh, P())
    return jax.tree_util.tree_map(
        lambda x: jax.lax.with_sharding_constraint(x, s), tree)


def _map_scenario(s: FleetScenario, mesh: Optional[Mesh], place,
                  place_topo, place_rep) -> FleetScenario:
    if mesh is None:
        return s
    # calib is tier-indexed (3,) metadata, not per-cell: replicate it
    return FleetScenario(
        place(s.end_b, mesh), place(s.edge_b, mesh), place(s.member, mesh),
        place(s.active, mesh), s.t, place_topo(s.topo, mesh),
        None if s.calib is None else place_rep(s.calib, mesh))


def shard_scenario(s: FleetScenario,
                   mesh: Optional[Mesh]) -> FleetScenario:
    """Place a ``FleetScenario`` with every per-cell leaf split along
    the fleet axis (``t``, topology metadata, and any calibration
    replicated)."""
    return _map_scenario(s, mesh, shard_array, shard_topology, replicate)


def constrain_scenario(s: FleetScenario,
                       mesh: Optional[Mesh]) -> FleetScenario:
    """Jit-safe sharding constraint over a whole scenario — what the
    sources' ``step`` applies so the layout survives ``lax.scan``."""
    return _map_scenario(
        s, mesh, constrain_array,
        lambda t, m: _map_topology(t, m, constrain_array),
        _constrain_replicated)


def shard_replay(buf, mesh: Optional[Mesh]):
    """Distribute a ``FleetReplay``'s transition rows across the mesh
    (``ptr``/``full`` replicate).

    The split is along the ring's SLOT axis — contiguous blocks of
    buffer capacity per device — not along cells: the ring is
    slot-major, so a step's ``(cells, ...)`` push lands in one slot
    window and uniform sampling gathers from all devices; the
    partitioner inserts the resharding collectives inside the training
    scan. That trades some per-step communication for an evenly split
    buffer footprint (the capacity no longer has to fit one device).
    Values are bit-identical either way; a cell-major ring that keeps
    pushes device-local is the noted follow-up."""
    if mesh is None:
        return buf
    return dataclasses.replace(
        buf,
        s=shard_array(buf.s, mesh), a=shard_array(buf.a, mesh),
        r=shard_array(buf.r, mesh), s2=shard_array(buf.s2, mesh),
        ptr=replicate(buf.ptr, mesh), full=replicate(buf.full, mesh))


# ---------------------------------------------------------------------------
# shard-local topology aggregation
# ---------------------------------------------------------------------------


def check_shard_local(topo: Topology, mesh: Mesh) -> None:
    """Raise unless ``topo`` satisfies the shard-locality invariant for
    ``mesh``. Skipped under tracing, where values are abstract — which
    is why anything that can SILENTLY break the invariant mid-run is
    rejected up front instead (``FleetConfig`` refuses
    ``shard_local=True`` together with ``p_edge_fail``, whose reroutes
    cross device blocks)."""
    if isinstance(topo.cell_edge, jax.core.Tracer):
        return
    n = mesh.shape[FLEET_AXIS]
    if not topology.is_shard_local(topo, n):
        raise ValueError(
            f"topology is not shard-local over {n} devices: at least one "
            "edge's cells span device blocks — generate it with "
            "random_topology(..., shard_local=True) or use the all-to-all "
            "path (topology.shared_contention) instead")


def local_contention(per_user, topo: Topology, mesh: Mesh, active=None):
    """Shard-local twin of ``topology.shared_contention``: per-edge job
    totals aggregated entirely on the device owning the edge.

    Requires a shard-local topology (every edge's cells inside one
    contiguous device block — ``random_topology(..., shard_local=True)``
    over ``mesh``'s device count). Under ``shard_map`` each device
    segment-sums only its own block of cells into its own block of
    edges with LOCAL edge ids; the sole cross-device term is the scalar
    ``psum`` of the fleet-wide cloud count. Returns the same
    ``(n_edge_eff, n_cloud, cloud_mult)`` seam tuple, bit-identical to
    the global path (integer totals; asserted in
    ``tests/test_fleet_shard.py``).
    """
    check_shard_local(topo, mesh)
    n_shards = mesh.shape[FLEET_AXIS]
    _, epb = shard_blocks(topo.cells, topo.n_edges, n_shards)
    if active is None:
        active = jnp.ones(jnp.asarray(per_user).shape, bool)
    # per-edge capacities enter block-sharded through the 'edges'
    # logical-axis rule (shard_blocks guarantees divisibility, so this
    # always resolves to a real fleet split, never the fallback)
    cap_spec = fleet_spec(mesh, topo.edge_capacity.shape, 0, "edges")

    def block(pu, act, ce, cap, cloud_servers):
        at_edge = (pu == dynamics.A_EDGE) & act
        at_cloud = (pu == dynamics.A_CLOUD) & act
        e_cnt = at_edge.sum(-1)
        c_cnt = at_cloud.sum(-1)
        local = ce % epb                   # block-aligned global -> local id
        edge_tot = jax.ops.segment_sum(e_cnt, local, num_segments=epb)
        n_e_eff = edge_tot[local] / cap[local]
        tot_cloud = jax.lax.psum(c_cnt.sum(), FLEET_AXIS)
        mult = topology.cloud_load_multiplier(tot_cloud, cloud_servers,
                                              xp=jnp)
        return n_e_eff, c_cnt, mult

    f = shard_map(
        block, mesh=mesh,
        in_specs=(P(FLEET_AXIS), P(FLEET_AXIS), P(FLEET_AXIS),
                  cap_spec, P()),
        out_specs=(P(FLEET_AXIS), P(FLEET_AXIS), P()))
    return f(jnp.asarray(per_user), jnp.asarray(active),
             topo.cell_edge, topo.edge_capacity,
             jnp.asarray(topo.cloud_servers))


def local_expected_response(per_user, end_b, edge_b, topo: Topology,
                            mesh: Mesh, active=None):
    """Shard-local twin of ``topology.topology_expected_response``:
    the same ``counts`` / ``cloud_mult`` seam into
    ``dynamics.expected_response``, with the edge aggregation kept
    on-device by ``local_contention``."""
    n_e, n_c, mult = local_contention(per_user, topo, mesh, active=active)
    return dynamics.expected_response(per_user, end_b, edge_b,
                                      active=active, counts=(n_e, n_c),
                                      cloud_mult=mult, xp=jnp)
