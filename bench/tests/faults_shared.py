"""Faults planted in the timed path of the shared-edge, Markov-link cell,
each under the program function it breaks; each has to make the check
read ``correct: false``.

* ``static_links``: the links are never stepped
  (``repro.fleet.scenarios.step_links`` returns its links).
* ``per_cell_contention``: each cell's edge jobs are counted in the cell
  alone, not summed over the cells of its edge
  (``repro.fleet.topology.shared_contention``).
* ``quarter_left_out``: the last quarter of the fleet, one chip's block
  on a four-chip mesh, keeps its old entries
  (``repro.kernels.ops.fused_tabular_update``).
* ``neighbour_edges``: each cell's edge jobs are added into the edge of
  the same place in the next chip's block, while each cell reads the
  total of its own edge (``shared_contention``).
"""
from __future__ import annotations

FAULTS = ("static_links", "per_cell_contention", "quarter_left_out",
          "neighbour_edges")
#: the chips whose blocks ``neighbour_edges`` shifts the jobs between
CHIPS = 4


def plant(monkeypatch, how: str):
    """Plant fault ``how`` for the agents built from now on."""
    import jax
    import jax.numpy as jnp
    from repro.fleet import dynamics, scenarios, topology
    from repro.kernels import ops

    if how == "static_links":
        monkeypatch.setattr(scenarios, "step_links",
                            lambda key, b, *a, **kw: b)
    elif how == "per_cell_contention":
        real = topology.shared_contention

        def per_cell(per_user, topo, active=None, xp=jnp):
            own = topology.identity_topology(topo.cells, jnp.inf)
            return real(per_user, topology.Topology(
                own.cell_edge, own.edge_capacity, topo.cloud_servers),
                active=active, xp=xp)
        monkeypatch.setattr(topology, "shared_contention", per_cell)
    elif how == "quarter_left_out":
        real = ops.fused_tabular_update

        def quarter(q, s, a, r, s2, **kw):
            rows = jnp.arange(q.shape[0])
            old = q[rows, s, a]
            q_new, greedy2, td = real(q, s, a, r, s2, **kw)
            cut = q.shape[0] - q.shape[0] // CHIPS
            return (q_new.at[rows[cut:], s[cut:], a[cut:]].set(old[cut:]),
                    greedy2, td)
        monkeypatch.setattr(ops, "fused_tabular_update", quarter)
    elif how == "neighbour_edges":
        real = topology.shared_contention

        def neighbour(per_user, topo, active=None, xp=jnp):
            n_e, n_c, mult = real(per_user, topo, active=active, xp=xp)
            at_edge = per_user == dynamics.A_EDGE
            if active is not None:
                at_edge = at_edge & active
            shifted = (topo.cell_edge + topo.n_edges // CHIPS) % topo.n_edges
            tot = jax.ops.segment_sum(at_edge.sum(-1), shifted,
                                      num_segments=topo.n_edges)
            return (tot[topo.cell_edge] / topo.edge_capacity[topo.cell_edge],
                    n_c, mult)
        monkeypatch.setattr(topology, "shared_contention", neighbour)
    else:
        raise ValueError(f"unknown fault {how!r}")
