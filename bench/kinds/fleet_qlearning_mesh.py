"""Fleet tabular Q-learning over a fleet mesh (``repro.fleet.FleetQLearning``
with ``mesh=``), with shared edges and Markov links, under test.

The fleet comes from ``fleets_shared``: its cells share edges, their
links switch from step to step, and the agent's Q-table, job counts and
scenario are split along the cells over a ``('fleet',)`` mesh of the
run's chips, one contiguous block a chip. Set-up and the window are
those of the tabular kind (``fleet_qlearning``), whose readings and
comparison this kind uses: set-up drives the agent from the seed
through its first ``run`` call and reads what that call leaves (the
fleet-mean response of each step, the table's norm, the whole tables of
a sample of cells), and the window calls ``run`` again and again. The
sampled tables are read from the chip that holds each cell, so the
sharded table is never gathered whole; after ``release`` no chip holds
any of it, and the reference follows the whole fleet on the first chip.
"""
from __future__ import annotations

import gc
import sys

import numpy as np

import fleets
import fleets_shared
import spec

tabular = spec.load_module("kinds", "fleet_qlearning")
compare = tabular.compare


def hyper(config: dict) -> dict:
    return dict(tabular.hyper(config), p_switch=config["p_switch"])


def reference_record(config: dict, fleet: dict, seed: int, steps: int,
                     dtype=None) -> dict:
    import jax.numpy as jnp
    ref = spec.load_module("reference", config["reference"])
    return ref.follow(fleet, hyper(config), seed, steps,
                      fleets.sample_cells(config, seed),
                      dtype=dtype or jnp.float32)


def control(config: dict, traffic: dict, seed: int, low) -> list:
    """The reference in precision ``low`` in the program's place, held
    to the same check as a run's first call."""
    fleet = fleets_shared.make(config, seed)
    steps = int(traffic["steps_per_call"])
    return compare(reference_record(config, fleet, seed, steps, dtype=low),
                   reference_record(config, fleet, seed, steps),
                   config["limits"])


def take_sharded_cells(q, cells) -> np.ndarray:
    """The whole tables of ``cells`` of a table split along its cells,
    each read on the device whose shard holds it."""
    ref = spec.load_module("reference", "fleet_qlearning")
    cells = np.asarray(cells)
    out = np.empty((len(cells),) + q.shape[1:], np.float32)
    for sh in q.addressable_shards:
        lo = sh.index[0].start or 0
        hi = lo + sh.data.shape[0]
        mine = np.flatnonzero((cells >= lo) & (cells < hi))
        if len(mine):
            out[mine] = ref.take_cells(sh.data, cells[mine] - lo)
    return out


class System:
    def __init__(self, config: dict, traffic: dict, seed: int, devices):
        import jax
        import jax.numpy as jnp
        from repro.fleet import (FleetConfig, FleetQConfig, FleetQLearning,
                                 SyntheticSource, shard)
        from repro.fleet.scenarios import FleetScenario
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.fleet.topology import Topology
        self.config, self.seed = config, seed
        self.steps_per_call = int(traffic["steps_per_call"])
        self.warm_calls = int(traffic["warm_calls"])
        self.cells = int(config["cells"])
        self.fleet = fleets_shared.make(config, seed)
        self.sample = fleets.sample_cells(config, seed)
        users = int(config["users"])
        self.mesh = shard.fleet_mesh(devices=devices)
        member = jnp.ones((self.cells, users), bool)
        topo = Topology(jnp.asarray(self.fleet["cell_edge"]),
                        jnp.asarray(self.fleet["edge_capacity"]),
                        jnp.float32(np.inf))
        # the step counter starts replicated over the mesh, as each call
        # gives it back, so no later call sees a new layout
        t0 = jax.device_put(jnp.int32(0), NamedSharding(self.mesh, P()))
        scen = FleetScenario(jnp.asarray(self.fleet["end_b"]),
                             jnp.asarray(self.fleet["edge_b"]), member,
                             member, t0, topo)
        p = float(config["p_switch"])
        fleet_cfg = FleetConfig(cells=self.cells, users=users, p_r2w=p,
                                p_w2r=p)
        qcfg = FleetQConfig(alpha=config["alpha"], gamma=config["gamma"],
                            eps_start=config["eps_start"],
                            eps_decay=config["eps_decay"],
                            eps_min=config["eps_min"], noise=config["noise"],
                            accuracy_threshold=config["accuracy_threshold"],
                            track_links=config["track_links"])
        self.agent = FleetQLearning(
            SyntheticSource(fleet_cfg, scen=scen), cfg=qcfg, seed=seed,
            impl=config["impl"], mesh=self.mesh)
        self.impl = self.agent._op_impl
        self._norm = jax.jit(lambda q: jnp.sqrt(jnp.sum(jnp.square(q))))
        self.window_steps = 0
        q = self.agent.q
        shards = {(s.device, s.data.shape) for s in q.addressable_shards}
        print(f"[bench] FleetQLearning impl {config['impl']!r} resolves to "
              f"{self.impl!r} under a {self.mesh.devices.size}-device fleet "
              f"mesh; Q {q.shape} {q.dtype}, {q.nbytes / 1e9:.3f} GB in "
              f"{len(shards)} shards of {sorted({s for _, s in shards})}; "
              f"{config['n_edges']} edges", file=sys.stderr, flush=True)

    def warm(self):
        """The first call from the seed (it compiles), read for the
        check, then the rest of the warm-up calls."""
        ms, _ = self.agent.run(self.steps_per_call)
        q = self.agent.q
        self.first = {"ms": np.asarray(ms, np.float64),
                      "q_norm": float(self._norm(q)),
                      "q_sample": take_sharded_cells(q, self.sample)}
        for _ in range(self.warm_calls - 1):
            self.agent.run(self.steps_per_call)

    def call(self) -> int:
        """One timed call; returns the cell-steps it did."""
        self.agent.run(self.steps_per_call)
        self.window_steps += self.steps_per_call
        return self.cells * self.steps_per_call

    def outcome(self):
        """(cell-steps of the window, cells whose Q-table is not finite)."""
        import jax.numpy as jnp
        self.bad_cells = int(
            (~jnp.isfinite(self.agent.q).all(axis=(1, 2))).sum())
        return self.cells * self.window_steps, self.bad_cells

    def work(self) -> dict:
        w = spec.load_module("work", "fleet_qlearning_shared")
        return w.work(self.cells, self.config["users"],
                      3 ** self.config["users"], self.config["n_edges"],
                      chips=self.mesh.devices.size)

    def release(self):
        del self.agent
        gc.collect()

    def check(self) -> list:
        ref = reference_record(self.config, self.fleet, self.seed,
                               self.steps_per_call)
        return compare(self.first, ref, self.config["limits"]) + [
            {"name": "nonfinite_cells", "value": self.bad_cells,
             "limit": 0}]
