"""Fleet tabular Q-learning (``repro.fleet.FleetQLearning``) under test.

Set-up builds one agent over the configuration's fleet and drives it
from the seed through its first ``run`` call: the window's own call, at
the window's size. What that call leaves is read for the check: the
fleet-mean response of each step, the norm of the Q-table (the table
starts at zero, so this is the norm of the change) and the whole tables
of a sample of cells drawn from the seed. The window then calls ``run``
again and again on that same agent.
"""
from __future__ import annotations

import gc
import sys

import numpy as np

import fleets
import spec

#: the first steps whose fleet-mean response is compared
LOSS_STEPS = 3


def hyper(config: dict) -> dict:
    return {"alpha": config["alpha"], "gamma": config["gamma"],
            "eps_start": config["eps_start"],
            "eps_decay": config["eps_decay"], "eps_min": config["eps_min"],
            "noise": config["noise"],
            "threshold": config["accuracy_threshold"],
            "track_links": config["track_links"],
            "states": states(config)}


def states(config: dict) -> int:
    """Rows of a cell's Q-table: job counts, times link bits if tracked."""
    users = config["users"]
    return (users + 1) ** 2 * (2 ** (users + 1) if config["track_links"]
                               else 1)


def readings(run: dict, ref: dict) -> dict:
    """Every number the check compares between a run's first call and
    the reference's:

    * ``loss_gap``: the widest relative gap of the fleet-mean response
      over the first ``LOSS_STEPS`` steps;
    * ``q_norm_gap``: the relative gap between the tables' norms;
    * ``cell_gap_p90``: over the sampled cells, the 90th percentile of
      each cell's widest entry gap over its reference table's largest
      magnitude: it sees where each write landed, which a norm cannot.
    """
    ms, ms_ref = np.asarray(run["ms"]), np.asarray(ref["ms"])
    rel = np.abs(ms - ms_ref) / np.abs(ms_ref)
    return {"loss_gap": float(rel[:LOSS_STEPS].max()),
            "q_norm_gap": float(abs(run["q_norm"] - ref["q_norm"])
                                / ref["q_norm"]),
            "cell_gap_p90": float(np.quantile(
                cell_gaps(run["q_sample"], ref["q_sample"]), 0.9))}


def cell_gaps(qs, qs_ref) -> np.ndarray:
    """Per sampled cell: the widest entry gap over the largest magnitude
    in the reference's table of that cell."""
    scale = np.abs(qs_ref).max(axis=(1, 2))
    return np.abs(qs - qs_ref).max(axis=(1, 2)) / np.maximum(scale, 1e-30)


def compare(run: dict, ref: dict, limits: dict) -> list:
    """The numbers compared, each beside its limit."""
    r = readings(run, ref)
    return [{"name": n, "value": r[n], "limit": lim}
            for n, lim in limits.items()]


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
    fleet = fleets.make(config, seed)
    steps = int(traffic["steps_per_call"])
    return compare(reference_record(config, fleet, seed, steps, dtype=low),
                   reference_record(config, fleet, seed, steps),
                   config["limits"])


class System:
    def __init__(self, config: dict, traffic: dict, seed: int, devices):
        import jax
        import jax.numpy as jnp
        from repro.fleet import (FleetConfig, FleetQConfig, FleetQLearning,
                                 SyntheticSource)
        from repro.fleet.scenarios import FleetScenario
        self.config, self.seed = config, seed
        self.steps_per_call = int(traffic["steps_per_call"])
        self.warm_calls = int(traffic["warm_calls"])
        self.cells = int(config["cells"])
        self.fleet = fleets.make(config, seed)
        self.sample = fleets.sample_cells(config, seed)
        users = int(config["users"])
        member = jnp.ones((self.cells, users), bool)
        scen = FleetScenario(jnp.asarray(self.fleet["end_b"]),
                             jnp.asarray(self.fleet["edge_b"]), member,
                             member, jnp.int32(0), None)
        qcfg = FleetQConfig(alpha=config["alpha"], gamma=config["gamma"],
                            eps_start=config["eps_start"],
                            eps_decay=config["eps_decay"],
                            eps_min=config["eps_min"], noise=config["noise"],
                            accuracy_threshold=config["accuracy_threshold"],
                            track_links=config["track_links"])
        self.agent = FleetQLearning(
            SyntheticSource(FleetConfig(cells=self.cells, users=users),
                            scen=scen),
            cfg=qcfg, seed=seed, impl=config["impl"])
        self.impl = self.agent._op_impl
        self._norm = jax.jit(lambda q: jnp.sqrt(jnp.sum(jnp.square(q))))
        self.window_steps = 0
        print(f"[bench] FleetQLearning impl {config['impl']!r} resolves to "
              f"{self.impl!r}; Q {self.agent.q.shape} "
              f"{self.agent.q.dtype}, {self.agent.q.nbytes / 1e9:.3f} GB",
              file=sys.stderr, flush=True)

    def warm(self):
        """The first call from the seed (it compiles), read for the
        check, then the rest of the warm-up calls."""
        ref = spec.load_module("reference", self.config["reference"])
        ms, _ = self.agent.run(self.steps_per_call)
        q = self.agent.q
        self.first = {"ms": np.asarray(ms, np.float64),
                      "q_norm": float(self._norm(q)),
                      "q_sample": ref.take_cells(q, self.sample)}
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
        w = spec.load_module("work", "fleet_qlearning")
        return w.work(self.cells, self.config["users"],
                      3 ** self.config["users"], self.impl)

    def release(self):
        del self.agent
        gc.collect()

    def check(self) -> list:
        ref = reference_record(self.config, self.fleet, self.seed,
                               self.steps_per_call)
        return compare(self.first, ref, self.config["limits"]) + [
            {"name": "nonfinite_cells", "value": self.bad_cells,
             "limit": 0}]
