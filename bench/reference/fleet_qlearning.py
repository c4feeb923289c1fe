"""Plain reference of fleet tabular Q-learning (arXiv:2202.10541 §4-§5).

Written from the paper's latency model and Table 7's Q-learning, in
straightforward ``jax.numpy``; it imports nothing of the system under
test and takes nothing it made. From the seed and the fleet's inputs it
follows the training run's first steps: epsilon-greedy over the joint
offloading actions {local d0, edge or cloud per user}, the noisy
end-edge-cloud response of every isolated cell under its static links,
and the TD update of one Q-table per cell. The whole fleet is one table,
updated in place: the rows a step reads are picked by masks, not
gathered, so the chip holds the table once and little beside it.

The random draws are the training run's own, from the same keys:
``PRNGKey(seed)`` split once per ``run`` call, once per step, and into
(explore, noise, scenario) per step.

``dtype`` is the precision of every value computed; the control runs
this same reference in ``bfloat16``. The random draws themselves are
data and stay ``float32`` in both.
"""
from __future__ import annotations

import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np

# paper Table 4: million MACs, int8?, top-5 accuracy of d0..d7
MACS = np.array([569, 317, 150, 41, 569, 317, 150, 41], np.float64)
IS_INT8 = np.array([0, 0, 0, 0, 1, 1, 1, 1], bool)
TOP5 = np.array([89.9, 88.2, 84.9, 74.2, 88.9, 87.0, 83.2, 72.8])
# calibrated latency model (ms), paper §5 and Tables 6 and 12
A_FP32, B_FP32, A_INT8, B_INT8 = 50.8, 0.7175, 37.3, 0.326
T_ORCH = (21.4, 141.0)            # link Regular / Weak
T_UP_EDGE = (120.0, 280.0)
T_HOP_CLOUD = (108.0, 230.0)
EDGE_CORES, CLOUD_CORES = 2.0, 4.0     # tier speeds 2x and 4x the device
EDGE_LINK_CAP, CLOUD_LINK_CAP = 1.3, 2.4
MEM_BUSY_PENALTY = 1.15
EDGE_MEM_BUSY_AT, CLOUD_MEM_BUSY_AT = 2, 3
MAX_RESPONSE_MS = 2500.0
EDGE, CLOUD = 8, 9


def action_table(users: int) -> np.ndarray:
    """(3**users, users) per-user actions: local d0, edge or cloud per
    user, in the lexicographic order of the joint action ids."""
    return np.array(list(itertools.product((0, EDGE, CLOUD),
                                           repeat=users)), np.int32)


def device_ms(d, dt):
    d = jnp.asarray(d)
    macs = jnp.asarray(MACS, dt)[d]
    return jnp.where(jnp.asarray(IS_INT8)[d],
                     jnp.asarray(A_INT8, dt) + jnp.asarray(B_INT8, dt) * macs,
                     jnp.asarray(A_FP32, dt) + jnp.asarray(B_FP32, dt) * macs)


def respond(per_user, end_b, edge_b, dt):
    """Noise-free (cells,) mean response ms and mean top-5 accuracy of a
    (cells, N) decision, every user requesting, and the (edge, cloud)
    job counts; each cell contends only with itself."""
    c = lambda v: jnp.asarray(v, dt)  # noqa: E731
    local = per_user < EDGE
    at_e = per_user == EDGE
    at_c = per_user == CLOUD
    n_e = at_e.sum(-1).astype(dt)[:, None]
    n_c = at_c.sum(-1).astype(dt)[:, None]
    t = jnp.asarray(T_ORCH, dt)[end_b]
    t = t + jnp.where(local, device_ms(jnp.where(local, per_user, 0), dt),
                      c(0))
    up = jnp.asarray(T_UP_EDGE, dt)[end_b]
    d0 = device_ms(0, dt)
    link_e = jnp.maximum(c(1), n_e / c(EDGE_LINK_CAP))
    cpu_e = jnp.maximum(c(1), n_e / c(EDGE_CORES))
    mem_e = jnp.where(n_e > EDGE_MEM_BUSY_AT, c(MEM_BUSY_PENALTY), c(1))
    t = t + jnp.where(at_e, up * link_e + d0 / c(EDGE_CORES) * cpu_e * mem_e,
                      c(0))
    link_c = jnp.maximum(c(1), n_c / c(CLOUD_LINK_CAP))
    cpu_c = jnp.maximum(c(1), n_c / c(CLOUD_CORES))
    mem_c = jnp.where(n_c > CLOUD_MEM_BUSY_AT, c(MEM_BUSY_PENALTY), c(1))
    hop = jnp.asarray(T_HOP_CLOUD, dt)[edge_b][:, None] * link_c
    t = t + jnp.where(at_c, up * link_c + hop
                      + d0 / c(CLOUD_CORES) * cpu_c * mem_c, c(0))
    acc = jnp.asarray(TOP5, dt)[jnp.where(local, per_user, 0)]
    n = per_user.shape[-1]
    counts = jnp.stack([at_e.sum(-1), at_c.sum(-1)], -1).astype(jnp.int32)
    return t.sum(-1) / c(n), acc.sum(-1) / c(n), counts


def state(counts, end_b, edge_b, track_links: bool):
    """Q-table row: the previous step's (edge, cloud) job counts and,
    with ``track_links``, the packed link bits of the users and the
    edge."""
    users = end_b.shape[1]
    s = counts[:, 0] * (users + 1) + counts[:, 1]
    if track_links:
        bits = (end_b * (2 ** jnp.arange(users))[None, :]).sum(-1)
        s = s * 2 ** (users + 1) + bits * 2 + edge_b
    return s


def row(q, s):
    """(cells, K) row ``s[c]`` of every cell's table: the table masked to
    that row and summed over rows, which adds only zeros to it. It reads
    the table in place, where a gather would first copy it into another
    layout on the chip."""
    at = jnp.arange(q.shape[1])[None, :] == s[:, None]
    return jnp.where(at[:, :, None], q, jnp.zeros((), q.dtype)).sum(1)


def _step(q, counts, eps, key, end_b, edge_b, table, *, hp, dt):
    """One training step of every cell: act, respond, update in place.
    Returns the table, the job counts and the fleet-mean response."""
    cells, users = end_b.shape
    k = q.shape[2]
    k_exp, k_noise, _ = jax.random.split(key, 3)
    u = jax.random.uniform(k_exp, (cells,))
    z = jax.random.normal(k_noise, (cells,))
    s = state(counts, end_b, edge_b, hp["track_links"])
    q_s = row(q, s)
    greedy = jnp.argmax(q_s, -1).astype(jnp.int32)
    rand = jnp.minimum((u / jnp.maximum(eps, 1e-9) * k).astype(jnp.int32),
                       k - 1)
    a = jnp.where(u < eps, rand, greedy)
    ms, acc, counts2 = respond(table[a], end_b, edge_b, dt)
    mult = jnp.clip(1.0 + (hp["noise"] / np.sqrt(users)) * z, 0.8, 1.2)
    ms = ms * mult.astype(dt)
    feasible = acc >= jnp.asarray(hp["threshold"] - 1e-9, dt)
    r = jnp.where(feasible, -ms, jnp.asarray(-MAX_RESPONSE_MS, dt)) \
        / jnp.asarray(1000.0, dt)
    s2 = state(counts2, end_b, edge_b, hp["track_links"])
    q_sa = jnp.take_along_axis(q_s, a[:, None], 1)[:, 0]
    td = r + jnp.asarray(hp["gamma"], dt) * row(q, s2).max(-1) - q_sa
    at = ((jnp.arange(q.shape[1])[None, :] == s[:, None])[:, :, None]
          & (jnp.arange(k)[None, :] == a[:, None])[:, None, :])
    q = jnp.where(at, q + (jnp.asarray(hp["alpha"], dt) * td)[:, None, None],
                  q)
    return q, counts2, ms.astype(jnp.float32).mean()


def epsilons(hp: dict, steps: int) -> list:
    """The exploration rate of each step, as the float32 scan carries it."""
    eps, out = np.float32(hp["eps_start"]), []
    for _ in range(steps):
        out.append(eps)
        eps = np.maximum(np.float32(hp["eps_min"]),
                         eps * np.float32(1.0 - hp["eps_decay"]))
    return out


def follow(fleet: dict, hp: dict, seed: int, steps: int, sample,
           dtype=jnp.float32) -> dict:
    """Follow ``steps`` training steps of a fresh fleet from ``seed``:
    the first ``run`` call of an agent keyed by ``seed``.

    ``fleet``: ``end_b`` (cells, N) and ``edge_b`` (cells,) static link
    states (0 Regular, 1 Weak). ``hp``: alpha, gamma, eps_start, eps_decay,
    eps_min, noise, threshold, states, track_links. ``sample``: the cells
    whose whole tables are returned. Returns the fleet-mean response (ms)
    of each step, the Frobenius norm of the Q-table after the steps and the
    tables of the sampled cells."""
    cells, users = fleet["end_b"].shape
    table = jnp.asarray(action_table(users))
    end_b = jnp.asarray(fleet["end_b"], jnp.int32)
    edge_b = jnp.asarray(fleet["edge_b"], jnp.int32)
    step = jax.jit(functools.partial(_step, hp=hp, dt=dtype),
                   donate_argnums=(0,))
    q = jnp.zeros((cells, hp["states"], table.shape[0]), dtype)
    counts = jnp.zeros((cells, 2), jnp.int32)
    # the key of the agent's first run call
    _, key = jax.random.split(jax.random.PRNGKey(seed))
    ms = []
    for eps in epsilons(hp, steps):
        key, k = jax.random.split(key)
        q, counts, m = step(q, counts, jnp.float32(eps), k, end_b, edge_b,
                            table)
        ms.append(m)
    out = {"ms": np.asarray(jnp.stack(ms), np.float64),
           "q_norm": float(_norm(q)),
           "q_sample": take_cells(q, sample)}
    del q
    return out


@jax.jit
def _norm(q):
    return jnp.sqrt(jnp.sum(jnp.square(q.astype(jnp.float32))))


#: cells read by one call of ``_take_block``
TAKE_BLOCK = 64


@jax.jit
def _take_block(q, cells):
    return jnp.stack([jax.lax.dynamic_index_in_dim(q, c, 0, False)
                      for c in cells]).astype(jnp.float32)


def take_cells(q, cells) -> np.ndarray:
    """The whole tables of ``cells`` as float32 on the host, read cell by
    cell: a gather of rows would copy the whole table into another
    layout on the chip first."""
    cells = np.asarray(cells, np.int32)
    pad = -len(cells) % TAKE_BLOCK
    idx = np.concatenate([cells, np.repeat(cells[-1:], pad)])
    out = [np.asarray(_take_block(q, jnp.asarray(idx[i:i + TAKE_BLOCK])))
           for i in range(0, len(idx), TAKE_BLOCK)]
    return np.concatenate(out)[:len(cells)]
