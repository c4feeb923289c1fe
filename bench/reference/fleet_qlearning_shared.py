"""Plain reference of fleet tabular Q-learning over shared edges and
Markov links (arXiv:2202.10541 §4-§5, with the fleet's topology).

Written from the paper's latency model and Table 7's Q-learning, and
from the topology's description: cells share edge servers, and an edge's
upload link and processors are shared by the edge jobs of every cell it
serves, divided by its capacity; each end and edge link is a two-state
Regular/Weak chain (the Gilbert-Elliott channel) that switches with
probability ``p_switch`` a step. It imports nothing of the system under
test and takes nothing it made; the paper's model, the state, the TD
update and the reads are those of the isolated reference
(``bench/reference/fleet_qlearning.py``), which this one imports. The
whole fleet is one table on one device, updated in place as there.

The random draws are the training run's own, from the same keys:
``PRNGKey(seed)`` split once per ``run`` call, once per step, and into
(explore, noise, scenario) per step; the scenario key splits into (end
links, edge links, and two the fleet leaves unused), and a link switches
where a uniform draw of its key falls below ``p_switch``.

``dtype`` is the precision of every value computed; the control runs
this same reference in ``bfloat16``. The random draws themselves are
data and stay ``float32`` in both.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import spec

iso = spec.load_module("reference", "fleet_qlearning")


def edge_jobs(per_user, cell_edge, capacity, dt):
    """(cells, 1) jobs at each cell's edge over its capacity: the edge
    jobs of every cell summed per edge over the whole fleet, read back
    by each cell of the edge."""
    own = (per_user == iso.EDGE).sum(-1)
    total = jnp.zeros(capacity.shape, jnp.int32).at[cell_edge].add(own)
    return (total[cell_edge].astype(dt) / capacity[cell_edge].astype(dt))[
        :, None]


def respond(per_user, end_b, edge_b, n_e, dt):
    """Noise-free (cells,) mean response ms and mean top-5 accuracy of a
    (cells, N) decision, every user requesting, with ``n_e`` jobs at the
    cell's edge; the cloud is shared within the cell only. Also the
    cell's own (edge, cloud) job counts."""
    c = lambda v: jnp.asarray(v, dt)  # noqa: E731
    local = per_user < iso.EDGE
    at_e = per_user == iso.EDGE
    at_c = per_user == iso.CLOUD
    n_c = at_c.sum(-1).astype(dt)[:, None]
    t = jnp.asarray(iso.T_ORCH, dt)[end_b]
    t = t + jnp.where(local, iso.device_ms(jnp.where(local, per_user, 0), dt),
                      c(0))
    up = jnp.asarray(iso.T_UP_EDGE, dt)[end_b]
    d0 = iso.device_ms(0, dt)
    link_e = jnp.maximum(c(1), n_e / c(iso.EDGE_LINK_CAP))
    cpu_e = jnp.maximum(c(1), n_e / c(iso.EDGE_CORES))
    mem_e = jnp.where(n_e > iso.EDGE_MEM_BUSY_AT, c(iso.MEM_BUSY_PENALTY),
                      c(1))
    t = t + jnp.where(at_e, up * link_e
                      + d0 / c(iso.EDGE_CORES) * cpu_e * mem_e, c(0))
    link_c = jnp.maximum(c(1), n_c / c(iso.CLOUD_LINK_CAP))
    cpu_c = jnp.maximum(c(1), n_c / c(iso.CLOUD_CORES))
    mem_c = jnp.where(n_c > iso.CLOUD_MEM_BUSY_AT, c(iso.MEM_BUSY_PENALTY),
                      c(1))
    hop = jnp.asarray(iso.T_HOP_CLOUD, dt)[edge_b][:, None] * link_c
    t = t + jnp.where(at_c, up * link_c + hop
                      + d0 / c(iso.CLOUD_CORES) * cpu_c * mem_c, c(0))
    acc = jnp.asarray(iso.TOP5, dt)[jnp.where(local, per_user, 0)]
    n = per_user.shape[-1]
    counts = jnp.stack([at_e.sum(-1), at_c.sum(-1)], -1).astype(jnp.int32)
    return t.sum(-1) / c(n), acc.sum(-1) / c(n), counts


def switch(key, b, p):
    """One step of every link's two-state chain: it switches where its
    uniform draw falls below ``p``."""
    return jnp.where(jax.random.bernoulli(key, p, b.shape), 1 - b, b)


def _step(q, counts, eps, key, end_b, edge_b, cell_edge, capacity, table, *,
          hp, dt):
    """One training step of every cell: act, respond under the shared
    edges, step the links, update in place. Returns the table, the job
    counts, the links and the fleet-mean response."""
    cells, users = end_b.shape
    k = q.shape[2]
    k_exp, k_noise, k_scen = jax.random.split(key, 3)
    u = jax.random.uniform(k_exp, (cells,))
    z = jax.random.normal(k_noise, (cells,))
    s = iso.state(counts, end_b, edge_b, hp["track_links"])
    q_s = iso.row(q, s)
    greedy = jnp.argmax(q_s, -1).astype(jnp.int32)
    rand = jnp.minimum((u / jnp.maximum(eps, 1e-9) * k).astype(jnp.int32),
                       k - 1)
    a = jnp.where(u < eps, rand, greedy)
    per_user = table[a]
    ms, acc, counts2 = respond(per_user, end_b, edge_b,
                               edge_jobs(per_user, cell_edge, capacity, dt),
                               dt)
    mult = jnp.clip(1.0 + (hp["noise"] / np.sqrt(users)) * z, 0.8, 1.2)
    ms = ms * mult.astype(dt)
    feasible = acc >= jnp.asarray(hp["threshold"] - 1e-9, dt)
    r = jnp.where(feasible, -ms, jnp.asarray(-iso.MAX_RESPONSE_MS, dt)) \
        / jnp.asarray(1000.0, dt)
    k_end, k_edge, _, _ = jax.random.split(k_scen, 4)
    end_b = switch(k_end, end_b, hp["p_switch"])
    edge_b = switch(k_edge, edge_b, hp["p_switch"])
    s2 = iso.state(counts2, end_b, edge_b, hp["track_links"])
    q_sa = jnp.take_along_axis(q_s, a[:, None], 1)[:, 0]
    td = r + jnp.asarray(hp["gamma"], dt) * iso.row(q, s2).max(-1) - q_sa
    at = ((jnp.arange(q.shape[1])[None, :] == s[:, None])[:, :, None]
          & (jnp.arange(k)[None, :] == a[:, None])[:, None, :])
    q = jnp.where(at, q + (jnp.asarray(hp["alpha"], dt) * td)[:, None, None],
                  q)
    return q, counts2, end_b, edge_b, ms.astype(jnp.float32).mean()


def follow(fleet: dict, hp: dict, seed: int, steps: int, sample,
           dtype=jnp.float32) -> dict:
    """Follow ``steps`` training steps of a fresh fleet from ``seed``:
    the first ``run`` call of an agent keyed by ``seed``.

    ``fleet``: ``end_b`` (cells, N) and ``edge_b`` (cells,) initial link
    states (0 Regular, 1 Weak), ``cell_edge`` (cells,) each cell's edge
    and ``edge_capacity`` (n_edges,). ``hp``: alpha, gamma, eps_start,
    eps_decay, eps_min, noise, threshold, states, track_links, p_switch.
    ``sample``: the cells whose whole tables are returned. Returns the
    fleet-mean response (ms) of each step, the Frobenius norm of the
    Q-table after the steps and the tables of the sampled cells."""
    cells, users = fleet["end_b"].shape
    table = jnp.asarray(iso.action_table(users))
    end_b = jnp.asarray(fleet["end_b"], jnp.int32)
    edge_b = jnp.asarray(fleet["edge_b"], jnp.int32)
    cell_edge = jnp.asarray(fleet["cell_edge"], jnp.int32)
    capacity = jnp.asarray(fleet["edge_capacity"], jnp.float32)
    step = jax.jit(functools.partial(_step, hp=hp, dt=dtype),
                   donate_argnums=(0,))
    q = jnp.zeros((cells, hp["states"], table.shape[0]), dtype)
    counts = jnp.zeros((cells, 2), jnp.int32)
    # the key of the agent's first run call
    _, key = jax.random.split(jax.random.PRNGKey(seed))
    ms = []
    for eps in iso.epsilons(hp, steps):
        key, k = jax.random.split(key)
        q, counts, end_b, edge_b, m = step(q, counts, jnp.float32(eps), k,
                                           end_b, edge_b, cell_edge,
                                           capacity, table)
        ms.append(m)
    out = {"ms": np.asarray(jnp.stack(ms), np.float64),
           "q_norm": float(iso._norm(q)),
           "q_sample": iso.take_cells(q, sample)}
    del q
    return out
