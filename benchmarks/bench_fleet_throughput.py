"""Fleet simulator throughput (ISSUE-1 acceptance): env-steps/sec of the
jitted (cells, users) fleet env step vs looping the scalar
EndEdgeCloudEnv.step, plus the full RL loop and cells-to-convergence/sec
of population training.

Both env measurements are apples-to-apples: actions are drawn OUTSIDE
the timed region (a (steps,) array for the scalar env, a
(steps, cells, N) array scanned over for the fleet), and the timed work
is simulate + reward + state transition.

Emits:
  fleet_scalar_env_steps,<us/step>,steps_per_s=...
  fleet_vector_env_steps,<us/env-step>,steps_per_s=... cells=...
  fleet_speedup,<ratio>,target>=100x
  fleet_rl_steps,<us/env-step>,full RL loop (act+env+TD) steps_per_s=...
  fleet_training,<us/cell-step>,converged_cells_per_s=...

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
from repro.core import EXPERIMENTS, EndEdgeCloudEnv
from repro.fleet import (FleetConfig, FleetQConfig, FleetQLearning,
                         SyntheticSource, make_fleet_env_step,
                         mixed_table5_fleet)

CELLS = 1024 if FAST else 4096
USERS = 5


def bench_scalar(steps: int) -> float:
    """env.step()/sec of the Python-loop env, random actions precomputed."""
    env = EndEdgeCloudEnv(USERS, EXPERIMENTS["EXP-A"], seed=0)
    rng = np.random.default_rng(0)
    acts = [int(a) for a in
            rng.integers(0, env.spec.n_joint_actions, steps)]
    with Timer() as t:
        for a in acts:
            env.step(a)
    return steps / t.seconds


def bench_fleet_env(host_steps: int, cells: int = CELLS,
                    chunk: int = 50) -> float:
    """env-steps/sec of the jitted fleet env step (scan of ``chunk``
    steps per host call over precomputed per-user actions)."""
    cfg = FleetConfig(cells=cells, users=USERS)
    scen = mixed_table5_fleet(jax.random.PRNGKey(0), cells, USERS)
    env_step = make_fleet_env_step(SyntheticSource(cfg))

    def run_chunk(key, scen, actions):          # actions: (chunk, cells, N)
        def body(carry, a):
            key, scen = carry
            key, k = jax.random.split(key)
            scen2, _, ms, _, _ = env_step(k, scen, a)
            return (key, scen2), ms.mean()
        (key, scen), ms = jax.lax.scan(body, (key, scen), actions)
        return key, scen, ms

    run_chunk = jax.jit(run_chunk)
    rng = np.random.default_rng(1)
    actions = jnp.asarray(rng.integers(0, 10, (chunk, cells, USERS)),
                          jnp.int32)
    key = jax.random.PRNGKey(2)
    key, scen, _ = run_chunk(key, scen, actions)     # compile
    jax.block_until_ready(scen.end_b)
    n_chunks = max(1, host_steps // chunk)
    with Timer() as t:
        for _ in range(n_chunks):
            key, scen, ms = run_chunk(key, scen, actions)
        jax.block_until_ready(ms)
    return n_chunks * chunk * cells / t.seconds


def bench_fleet_rl(host_steps: int, cells: int = CELLS,
                   chunk: int = 50) -> float:
    """Full RL loop (greedy/explore + env + TD update) env-steps/sec on
    the fused act+update op. Measures the bare loop (``metrics=False``):
    the telemetry accumulator's overhead is gated separately
    (``fleet_dqn_obs_overhead_x``)."""
    scen = mixed_table5_fleet(jax.random.PRNGKey(0), cells, USERS)
    agent = FleetQLearning(scen, FleetConfig(cells=cells, users=USERS),
                           FleetQConfig(eps_decay=0.0), metrics=False)
    agent.run(chunk)                               # compile
    jax.block_until_ready(agent.q)
    n_chunks = max(1, host_steps // chunk)
    with Timer() as t:
        for _ in range(n_chunks):
            agent.run(chunk)
        jax.block_until_ready(agent.q)
    return n_chunks * chunk * cells / t.seconds


def main(tiny: bool = False):
    if tiny:
        cells, sc_steps, env_steps, rl_steps = 32, 200, 100, 40
        tr_cells, tr_steps, chunk = 16, 400, 20
    elif FAST:
        cells, sc_steps, env_steps, rl_steps = CELLS, 1000, 400, 200
        tr_cells, tr_steps, chunk = 64, 4000, 50
    else:
        cells, sc_steps, env_steps, rl_steps = CELLS, 5000, 2000, 1000
        tr_cells, tr_steps, chunk = 64, 20000, 50
    scalar_sps = bench_scalar(sc_steps)
    fleet_sps = bench_fleet_env(env_steps, cells, chunk)
    # best-of-N filters scheduler noise
    reps = 1 if tiny else 3
    rl_sps = max(bench_fleet_rl(rl_steps, cells, chunk)
                 for _ in range(reps))
    speedup = fleet_sps / scalar_sps
    emit("fleet_scalar_env_steps", 1e6 / scalar_sps,
         f"steps_per_s={scalar_sps:.0f}")
    emit("fleet_vector_env_steps", 1e6 / fleet_sps,
         f"steps_per_s={fleet_sps:.0f} cells={cells}")
    emit("fleet_speedup", speedup, "x vs scalar env (target >=100x)")
    emit("fleet_rl_steps", 1e6 / rl_sps,
         f"steps_per_s={rl_sps:.0f} (act+env+TD, {rl_sps/scalar_sps:.1f}x "
         f"scalar env alone)")

    # population training: converged cells / second (2-user cells)
    scen = mixed_table5_fleet(jax.random.PRNGKey(1), tr_cells, 2)
    agent = FleetQLearning(scen, FleetConfig(cells=tr_cells, users=2),
                           FleetQConfig(eps_decay=2e-3,
                                        accuracy_threshold=85.0))
    res = agent.train(max_steps=tr_steps, check_every=200)
    emit("fleet_training", 1e6 * res.wall_seconds / (res.steps * tr_cells),
         f"converged_cells_per_s={res.cells_per_second:.1f} "
         f"frac={res.frac_converged:.2f}")
    metrics = {
        "cells": cells, "users": USERS,
        "scalar_steps_per_s": scalar_sps,
        "fleet_env_steps_per_s": fleet_sps,
        "fleet_rl_steps_per_s": rl_sps,
        "rl_fused_tabular_steps_per_s": rl_sps,
        "speedup_x": speedup,
        "train_frac_converged": res.frac_converged,
        "train_converged_cells_per_s": res.cells_per_second,
    }
    save_json("fleet_throughput", metrics)
    return metrics


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="seconds-scale budgets (CI smoke)")
    main(tiny=ap.parse_args().tiny)
