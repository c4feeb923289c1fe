"""Device-sharded fleet execution (ISSUE-5 acceptance): per-device
throughput as the cell population scales across a ``('fleet',)`` mesh,
and the cost of the two topology-aggregation modes.

Measurements, on the devices of the process it runs in (a TPU host's
chips, or a multi-device CPU host platform forced by the caller, whose
numbers exercise SPMD partitioning + collectives, not device speed):

* ``sharded_env_steps``     — cell-steps/sec of the jitted fleet env
  step with scenario + Q-state sharded along cells, at fleet sizes
  ``devices * {base, 4*base, 16*base}``; per-device throughput should
  stay ~flat as the fleet grows (weak scaling of the population axis).
* ``sharded_rl_steps``      — the tabular act+env+TD loop, sharded.
* ``topology_local_agg``    — ``shard.local_expected_response`` (the
  shard_map path over a locality-capped ``random_topology(...,
  shard_local=True)``: per-edge aggregation never leaves the device).
* ``topology_alltoall_agg`` — the unchanged global segment-sum path
  under GSPMD on an unconstrained assignment (the compiler's
  cross-device reduction).

It starts no child process: one process holds the devices. On a CPU
host, force several devices from the environment before JAX starts,
e.g. ``XLA_FLAGS=--xla_force_host_platform_device_count=8``; with one
device the mesh has one shard.

``--tiny`` (CLI) shrinks every budget to a few seconds of work — the CI
smoke mode that keeps this script from rotting.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import FAST, Timer, emit, save_json
from repro.fleet import (FleetConfig, FleetQConfig, FleetQLearning,
                         SyntheticSource, init_fleet, make_fleet_env_step,
                         shard, topology)

USERS = 3


def bench_env_scaling(cells_grid, host_steps, chunk):
    """Cell-steps/sec of the sharded fleet env step at each fleet size;
    returns {cells: steps_per_s}."""
    mesh = shard.fleet_mesh()
    out = {}
    for cells in cells_grid:
        cfg = FleetConfig(cells=cells, users=USERS, arrival_rate=1.0,
                          p_r2w=0.05, p_w2r=0.1)
        source = SyntheticSource(cfg, mesh=mesh)
        env_step = make_fleet_env_step(source)

        def run_chunk(key, scen, actions):
            def body(carry, a):
                key, scen = carry
                key, k = jax.random.split(key)
                scen2, _, ms, _, _ = env_step(k, scen, a)
                return (key, scen2), ms.mean()
            (key, scen), ms = jax.lax.scan(body, (key, scen), actions)
            return key, scen, ms

        run_chunk = jax.jit(run_chunk)
        scen, _ = source.reset(jax.random.PRNGKey(0))
        rng = np.random.default_rng(1)
        actions = shard.shard_array(
            jnp.asarray(rng.integers(0, 10, (chunk, cells, USERS)),
                        jnp.int32), mesh, axis=1)
        key = jax.random.PRNGKey(2)
        key, scen, _ = run_chunk(key, scen, actions)         # compile
        jax.block_until_ready(scen.end_b)
        n_chunks = max(1, host_steps // chunk)
        with Timer() as t:
            for _ in range(n_chunks):
                key, scen, ms = run_chunk(key, scen, actions)
                jax.block_until_ready(ms)    # bound the collective queue
        out[cells] = n_chunks * chunk * cells / t.seconds
    return out


def bench_rl_sharded(cells, host_steps, chunk):
    """Sharded tabular RL loop (act + env + TD) cell-steps/sec."""
    mesh = shard.fleet_mesh()
    cfg = FleetConfig(cells=cells, users=USERS, arrival_rate=1.0)
    agent = FleetQLearning(SyntheticSource(cfg), cfg=FleetQConfig(
        eps_decay=0.0), mesh=mesh)
    agent.run(chunk)                                         # compile
    jax.block_until_ready(agent.q)
    n_chunks = max(1, host_steps // chunk)
    with Timer() as t:
        for _ in range(n_chunks):
            agent.run(chunk)
        jax.block_until_ready(agent.q)
    return n_chunks * chunk * cells / t.seconds


def bench_topology_agg(cells, edges_per_dev, iters):
    """us/call of one fleet-wide contention-coupled evaluation under
    mode (a) shard-local aggregation vs mode (b) global all-to-all."""
    mesh = shard.fleet_mesh()
    ndev = jax.device_count()
    n_edges = edges_per_dev * ndev
    scen = init_fleet(jax.random.PRNGKey(0),
                      FleetConfig(cells=cells, users=USERS,
                                  arrival_rate=1.0))
    scen = shard.shard_scenario(scen, mesh)
    pu = shard.shard_array(
        jnp.asarray(np.random.default_rng(0).integers(0, 10, (cells, USERS)),
                    jnp.int32), mesh)
    topo_local = shard.shard_topology(
        topology.random_topology(jax.random.PRNGKey(1), cells, n_edges,
                                 shard_local=True, n_shards=ndev,
                                 cloud_servers=float(cells)), mesh)
    topo_free = shard.shard_topology(
        topology.random_topology(jax.random.PRNGKey(1), cells, n_edges,
                                 cloud_servers=float(cells)), mesh)

    local = jax.jit(lambda p, t, s: shard.local_expected_response(
        p, s.end_b, s.edge_b, t, mesh, active=s.active))
    glob = jax.jit(lambda p, t, s: topology.topology_expected_response(
        p, s.end_b, s.edge_b, t, active=s.active, xp=jnp))

    def time_one(fn, topo):
        jax.block_until_ready(fn(pu, topo, scen))            # compile
        with Timer() as t:
            for _ in range(iters):
                # block every call: a deep queue of collective-bearing
                # executions can deadlock the CPU all-reduce rendezvous
                # on an oversubscribed forced host platform, and the
                # per-eval latency (not pipelined throughput) is the
                # number being compared anyway
                jax.block_until_ready(fn(pu, topo, scen)[0])
        return t.us / iters

    return time_one(local, topo_local), time_one(glob, topo_free)


def _run(tiny: bool) -> dict:
    ndev = jax.device_count()
    base = 32 if tiny else 256
    if tiny:
        env_steps, rl_steps, chunk, agg_iters = 60, 40, 20, 20
    elif FAST:
        env_steps, rl_steps, chunk, agg_iters = 400, 200, 50, 100
    else:
        env_steps, rl_steps, chunk, agg_iters = 2000, 1000, 50, 1000
    grid = [ndev * base, ndev * 4 * base, ndev * 16 * base]

    scaling = bench_env_scaling(grid, env_steps, chunk)
    per_dev = {c: s / ndev for c, s in scaling.items()}
    # flatness over the two LARGEST sizes: small fleets are dispatch-
    # bound (throughput still climbing), the saturated regime is where
    # per-device cell-steps/s must stop moving as the population grows
    top2 = [per_dev[c] for c in grid[-2:]]
    flat = min(top2) / max(top2)
    for c, s in scaling.items():
        emit(f"sharded_env_steps_{c}", 1e6 / s,
             f"steps_per_s={s:.0f} per_device={per_dev[c]:.0f} "
             f"devices={ndev}")
    emit("sharded_env_flatness", flat,
         "min/max per-device cell-steps/s over the two largest fleets "
         "(1.0 = perfectly flat scaling)")

    rl_sps = bench_rl_sharded(grid[1], rl_steps, chunk)
    emit("sharded_rl_steps", 1e6 / rl_sps,
         f"steps_per_s={rl_sps:.0f} cells={grid[1]} (act+env+TD, sharded)")

    local_us, alltoall_us = bench_topology_agg(grid[1], 4, agg_iters)
    emit("topology_local_agg", local_us,
         "us/fleet-eval, shard-local (shard_map, on-device segment-sum)")
    emit("topology_alltoall_agg", alltoall_us,
         "us/fleet-eval, all-to-all (GSPMD global segment-sum); "
         f"local is {alltoall_us / local_us:.2f}x cheaper"
         if alltoall_us >= local_us else
         f"us/fleet-eval, all-to-all; all-to-all is "
         f"{local_us / alltoall_us:.2f}x cheaper here")

    metrics = {
        "devices": ndev,
        "cells_grid": grid,
        "sharded_env_steps_per_s": scaling[grid[-1]],
        "per_device_env_steps_per_s": {str(c): v for c, v in
                                       per_dev.items()},
        "per_device_flatness": flat,
        "sharded_rl_steps_per_s": rl_sps,
        "topology_local_agg_us": local_us,
        "topology_alltoall_agg_us": alltoall_us,
        "local_vs_alltoall_x": alltoall_us / local_us,
    }
    save_json("fleet_sharded", metrics)
    return metrics


def main(tiny: bool = False) -> dict:
    return _run(tiny)


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="seconds-scale budgets (CI smoke)")
    main(tiny=ap.parse_args().tiny)
