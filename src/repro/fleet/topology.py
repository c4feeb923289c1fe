"""Multi-edge-cell topologies: shared edge servers, cross-cell
contention, and cloud queueing over the fleet batch.

The paper's contention model (§3, Table 6) stops at one cell: every
edge/cloud compute term scales with the number of co-located offloaders
*inside* that cell, and PR 1's fleet simulator inherited the
assumption — each cell in the ``(cells, users)`` batch owned a private
edge server and a private slice of cloud, so fleet-scale decisions
never interacted. Real end-edge-cloud deployments are topologies (the
regime DeepEdge, arXiv:2110.01863, and Dai et al., arXiv:2011.08442,
target): one edge server fronts several cells, and the cloud queues
across all of them.

This module is the pure, batch-shaped, jit/vmap-safe layer for that:

* ``Topology`` — a registered pytree holding the cell->edge assignment
  (an index vector over ``n_edges``), per-edge capacity tiers, and an
  M/M/c-style cloud queue size.
* ``shared_contention`` — the generalization of ``fleet.dynamics``'
  per-cell contention counts: edge job counts are aggregated across
  ALL cells sharing an edge (one segment-sum over the assignment) and
  divided by that edge's capacity tier; the fleet-wide cloud total
  drives a queueing multiplier (``cloud_load_multiplier``).
* generators — ``identity_topology`` (the isolated-cell reduction),
  ``random_topology``, ``skewed_topology`` (Zipf-weighted hot edges),
  ``hot_edge_topology`` (deterministic hot edge for benchmarks), and
  ``step_edge_failures`` (reroute a failed edge's cells, the scenario
  event behind ``FleetConfig.p_edge_fail``).

Everything plugs into the existing kernel through the ``counts`` /
``cloud_mult`` seam of ``dynamics.response_times``: a 1:1 assignment
with unit capacities and an unbounded cloud queue produces bit-exactly
the same effective counts (integer totals divided by 1.0) and a
multiplier of exactly 1.0, so the topology path reduces to the
isolated-cell path and every existing parity test keeps pinning the
kernel (tested in ``tests/test_topology.py``).

Layering: like ``dynamics``, this module never imports ``repro.core``
or its sibling fleet modules — ``scenarios`` attaches a ``Topology`` to
``FleetScenario`` and ``population`` builds the coupled oracle on top.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.fleet import dynamics

#: saturation ceiling of the M/M/c-style cloud queueing multiplier:
#: 1/(1-rho) diverges as utilization rho -> 1, so the multiplier is
#: clipped to [1, CLOUD_QUEUE_MAX] (rho >= 1 - 1/MAX pins the ceiling).
CLOUD_QUEUE_MAX = 8.0


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Topology:
    """Edge/cloud infrastructure shared by the cells of a fleet.

    cell_edge     : (cells,)   int32  index of the edge server serving
                                      each cell (values in [0, n_edges))
    edge_capacity : (n_edges,) f32    capacity tier of each edge server,
                                      as a multiple of the paper's
                                      a1.large edge (1.0 = Table 6)
    cloud_servers : ()         f32    M/M/c-style cloud queue size in
                                      concurrent jobs; ``inf`` disables
                                      cross-cell cloud queueing
    """
    cell_edge: jnp.ndarray
    edge_capacity: jnp.ndarray
    cloud_servers: jnp.ndarray

    def tree_flatten(self):
        return ((self.cell_edge, self.edge_capacity, self.cloud_servers),
                None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def cells(self) -> int:
        return self.cell_edge.shape[0]

    @property
    def n_edges(self) -> int:
        return self.edge_capacity.shape[0]


# ---------------------------------------------------------------------------
# generators
# ---------------------------------------------------------------------------


def edge_capacities(n_edges: int, capacity_tiers=(1.0,)) -> jnp.ndarray:
    """(n_edges,) capacities cycling deterministically through the tier
    tuple (edge j gets ``capacity_tiers[j % len(capacity_tiers)]``)."""
    t = jnp.asarray(capacity_tiers, jnp.float32)
    return t[jnp.arange(n_edges) % len(capacity_tiers)]


def identity_topology(cells: int, cloud_servers: float = np.inf) -> Topology:
    """The 1:1 reduction: every cell owns a unit-capacity edge and the
    cloud queue is unbounded — bit-exactly the isolated-cell model."""
    return Topology(jnp.arange(cells, dtype=jnp.int32),
                    jnp.ones((cells,), jnp.float32),
                    jnp.float32(cloud_servers))


def shard_blocks(cells: int, n_edges: int, n_shards: int):
    """Validated block sizes ``(cells_per_shard, edges_per_shard)`` of a
    shard-local layout: the first ``cells_per_shard`` cells and the
    first ``edges_per_shard`` edges belong to shard 0, and so on —
    exactly the contiguous blocks ``NamedSharding`` places on each
    device of a 1-D fleet mesh (``repro.fleet.shard``)."""
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    if cells % n_shards or n_edges % n_shards:
        raise ValueError(
            f"shard-local layout needs cells ({cells}) and n_edges "
            f"({n_edges}) divisible by n_shards ({n_shards}) so the "
            "contiguous device blocks line up")
    return cells // n_shards, n_edges // n_shards


def random_topology(key, cells: int, n_edges: int, capacity_tiers=(1.0,),
                    cloud_servers: float = np.inf,
                    shard_local: bool = False,
                    n_shards: Optional[int] = None) -> Topology:
    """Uniform cell->edge assignment.

    ``shard_local=True`` caps the assignment's locality to the device
    blocks of an ``n_shards``-way fleet mesh (default: every local
    device): cells and edges are split into ``n_shards`` contiguous
    equal blocks, and a cell draws its edge uniformly WITHIN its own
    block — so when both arrays are sharded along the fleet axis, no
    edge is ever shared across devices and the per-edge segment-sum
    aggregation stays entirely shard-local
    (``repro.fleet.shard.local_contention``). The unconstrained
    assignment instead couples arbitrary cells, turning the aggregation
    into a cross-shard reduction (the all-to-all path)."""
    if not shard_local:
        ce = jax.random.randint(key, (cells,), 0, n_edges).astype(jnp.int32)
    else:
        if n_shards is None:
            n_shards = jax.device_count()
        cpb, epb = shard_blocks(cells, n_edges, n_shards)
        block = jnp.arange(cells, dtype=jnp.int32) // cpb
        ce = (block * epb
              + jax.random.randint(key, (cells,), 0, epb)).astype(jnp.int32)
    return Topology(ce, edge_capacities(n_edges, capacity_tiers),
                    jnp.float32(cloud_servers))


def is_shard_local(topo: Topology, n_shards: int) -> bool:
    """Host-side check of the shard-locality invariant: every cell's
    edge lies in the cell's own contiguous shard block (no edge spans
    devices when both arrays are sharded along the fleet axis)."""
    cpb, epb = shard_blocks(topo.cells, topo.n_edges, n_shards)
    ce = np.asarray(topo.cell_edge)
    return bool(((np.arange(topo.cells) // cpb) == (ce // epb)).all())


def skewed_topology(key, cells: int, n_edges: int, skew: float = 1.5,
                    capacity_tiers=(1.0,),
                    cloud_servers: float = np.inf) -> Topology:
    """Zipf-weighted assignment: edge j attracts cells with probability
    proportional to ``(j+1)^-skew``, so edge 0 is the hottest. ``skew=0``
    recovers the uniform assignment."""
    w = (1.0 / jnp.arange(1, n_edges + 1, dtype=jnp.float32)) ** skew
    ce = jax.random.choice(key, n_edges, (cells,), p=w / w.sum())
    return Topology(ce.astype(jnp.int32),
                    edge_capacities(n_edges, capacity_tiers),
                    jnp.float32(cloud_servers))


def hot_edge_topology(cells: int, n_edges: int, hot_fraction: float = 0.5,
                      capacity_tiers=(1.0,),
                      cloud_servers: float = np.inf) -> Topology:
    """Deterministic hot edge (benchmark scenario): the first
    ``round(cells * hot_fraction)`` cells all share edge 0, the rest are
    spread round-robin over the remaining edges (over all edges when
    ``n_edges == 1``)."""
    n_hot = int(round(cells * hot_fraction))
    rest = np.arange(cells - n_hot)
    cold = 1 + rest % (n_edges - 1) if n_edges > 1 else rest % n_edges
    ce = np.concatenate([np.zeros(n_hot, np.int32), cold.astype(np.int32)])
    return Topology(jnp.asarray(ce),
                    edge_capacities(n_edges, capacity_tiers),
                    jnp.float32(cloud_servers))


def step_edge_failures(key, topo: Topology, p_fail: float) -> Topology:
    """One edge-failure scenario event: with probability ``p_fail`` a
    uniformly drawn edge fails and each of its cells is rerouted to a
    uniformly drawn *other* edge (a permanent reassignment — the fleet
    does not fail back). Pure and jit/scan-safe; a single-edge topology
    has nowhere to reroute and is returned unchanged."""
    if topo.n_edges <= 1:
        return topo
    k_ev, k_edge, k_re = jax.random.split(key, 3)
    fail = jax.random.bernoulli(k_ev, p_fail)
    edge = jax.random.randint(k_edge, (), 0, topo.n_edges)
    new = jax.random.randint(k_re, topo.cell_edge.shape, 0,
                             topo.n_edges - 1)
    new = (new + (new >= edge)).astype(jnp.int32)   # skip the failed edge
    ce = jnp.where(fail & (topo.cell_edge == edge), new, topo.cell_edge)
    return Topology(ce, topo.edge_capacity, topo.cloud_servers)


# ---------------------------------------------------------------------------
# shared contention
# ---------------------------------------------------------------------------


def _segment_totals(values, segments, n_segments: int, xp):
    """Per-segment sums, generic over numpy/jax.numpy."""
    if xp is np:
        return np.bincount(np.asarray(segments), weights=np.asarray(values),
                           minlength=n_segments)
    return jax.ops.segment_sum(values, segments, num_segments=n_segments)


def cloud_load_multiplier(n_cloud_total, cloud_servers, xp=jnp):
    """M/M/c-style queueing inflation of cloud latency under fleet-wide
    load: utilization ``rho = n_cloud_total / cloud_servers`` maps to
    ``1 / (1 - rho)`` clipped to ``[1, CLOUD_QUEUE_MAX]`` (the mean
    number-in-system inflation of an M/M/1 queue, saturating instead of
    diverging as rho -> 1). ``cloud_servers = inf`` gives exactly 1.0 —
    the isolated-cell reduction."""
    rho = n_cloud_total / cloud_servers
    m = 1.0 / xp.maximum(1.0 - rho, 1.0 / CLOUD_QUEUE_MAX)
    return xp.clip(m, 1.0, CLOUD_QUEUE_MAX)


def shared_contention(per_user, topo: Topology, active=None, xp=jnp):
    """Topology-aware contention terms for a ``(cells, N)`` decision.

    Edge job counts are summed across ALL cells assigned to the same
    edge (one segment-sum over ``topo.cell_edge``) and divided by that
    edge's capacity tier; the per-cell cloud counts keep the paper's
    processor-sharing semantics while their fleet-wide total drives the
    cloud queueing multiplier.

    Returns ``(n_edge_eff (cells,), n_cloud (cells,), cloud_mult ())``,
    shaped to feed the ``counts`` / ``cloud_mult`` seam of
    ``dynamics.response_times``. Under ``identity_topology`` the
    effective counts equal the isolated per-cell counts bit-exactly and
    the multiplier is exactly 1.0.

    Traced, its ops (and, under a fleet mesh, the cross-device
    reductions the partitioner puts around the sums) sit under the
    device scope ``fleet.contention`` (docs/OBSERVABILITY.md).
    """
    with jax.named_scope("fleet.contention"):
        per_user = xp.asarray(per_user)
        at_edge = per_user == dynamics.A_EDGE
        at_cloud = per_user == dynamics.A_CLOUD
        if active is not None:
            active = xp.asarray(active)
            at_edge = at_edge & active
            at_cloud = at_cloud & active
        e_cnt = at_edge.sum(-1)
        c_cnt = at_cloud.sum(-1)
        edge_tot = _segment_totals(e_cnt, topo.cell_edge, topo.n_edges, xp)
        cap = xp.asarray(topo.edge_capacity)
        n_e_eff = edge_tot[topo.cell_edge] / cap[topo.cell_edge]
        mult = cloud_load_multiplier(c_cnt.sum(), topo.cloud_servers, xp=xp)
    return n_e_eff, c_cnt, mult


def topology_response_times(per_user, end_b, edge_b, topo: Topology,
                            active=None, calib=None, xp=jnp):
    """Per-user response times (ms) under shared edge/cloud contention —
    the topology-aware analogue of ``dynamics.response_times`` for a
    ``(cells, N)`` fleet decision."""
    n_e, n_c, mult = shared_contention(per_user, topo, active=active, xp=xp)
    return dynamics.response_times(per_user, end_b, edge_b,
                                   counts=(n_e, n_c), active=active,
                                   cloud_mult=mult, calib=calib, xp=xp)


def topology_expected_response(per_user, end_b, edge_b, topo: Topology,
                               active=None, calib=None, xp=jnp):
    """((cells,) mean ms, (cells,) mean accuracy) under shared
    contention — the topology-aware ``dynamics.expected_response``."""
    n_e, n_c, mult = shared_contention(per_user, topo, active=active, xp=xp)
    return dynamics.expected_response(per_user, end_b, edge_b,
                                      active=active, counts=(n_e, n_c),
                                      cloud_mult=mult, calib=calib, xp=xp)


@jax.jit
def fleet_topology_expected_response(per_user, end_b, edge_b,
                                     topo: Topology, active=None,
                                     calib=None):
    """Jitted fleet entry point: one call evaluates every cell of the
    fleet under shared edge/cloud contention."""
    return topology_expected_response(per_user, end_b, edge_b, topo,
                                      active=active, calib=calib, xp=jnp)


def edge_utilization(per_user, topo: Topology, active=None, xp=jnp):
    """(n_edges,) edge jobs per unit of capacity under ``per_user`` —
    the load report ``FleetOrchestrator.route`` attaches to a routing
    decision (1.0 = one job per a1.large-equivalent of capacity)."""
    per_user = xp.asarray(per_user)
    at_edge = per_user == dynamics.A_EDGE
    if active is not None:
        at_edge = at_edge & xp.asarray(active)
    edge_tot = _segment_totals(at_edge.sum(-1), topo.cell_edge,
                               topo.n_edges, xp)
    return edge_tot / xp.asarray(topo.edge_capacity)
