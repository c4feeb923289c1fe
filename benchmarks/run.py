"""Benchmark runner: one module per paper table/figure. Prints
``name,us_per_call,derived`` CSV rows (see common.emit).

  PYTHONPATH=src python -m benchmarks.run [--only fig5,table11] [--json]
  REPRO_BENCH_MODE=full for paper-scale RL budgets.

``--json`` additionally writes ``results/BENCH_fleet.json``: the
fleet-scale headline numbers (env steps/sec, tabular + DQN RL-loop
steps/sec, converged cells/sec, DQN held-out reward ratio, topology
overhead/uplift, trace-replay speedup, sharded per-device throughput
and local-vs-alltoall aggregation cost, compiled-cost RL stage
fractions and the scaling-cliff diagnosis, SLO attainment measured vs
predicted + P99 tail + windowed-metrics overhead, async-bridge vs sync
dispatch throughput and the sim-to-real calibration loop) in one
machine-readable file
so the perf trajectory is tracked across PRs (see docs/BENCHMARKS.md).
Every JSON is stamped with a provenance manifest (git SHA, jax
version, config hash — ``repro.obs.report``); pretty-print or diff
runs with ``python tools/obsview.py``.
"""
import argparse
import sys
import time

from benchmarks import (bench_adaptation, bench_bridge,
                        bench_fig1_motivation,
                        bench_fig5_user_variability, bench_fig7_transfer,
                        bench_fleet_dqn, bench_fleet_sharded,
                        bench_fleet_throughput, bench_kernels,
                        bench_overhead, bench_profile, bench_slo,
                        bench_table8_decisions, bench_table9_constraints,
                        bench_table10_sota, bench_table11_convergence,
                        bench_topology, bench_trace_replay)
from benchmarks.common import save_json
from repro.compile_cache import enable_compile_cache

SUITES = {
    "fig1": bench_fig1_motivation,
    "fig5": bench_fig5_user_variability,
    "table8": bench_table8_decisions,
    "table9": bench_table9_constraints,
    "table10": bench_table10_sota,
    "table11": bench_table11_convergence,
    "fig7": bench_fig7_transfer,
    "overhead": bench_overhead,
    "kernels": bench_kernels,
    "adaptation": bench_adaptation,   # beyond-paper: mid-run network shift
    "fleet": bench_fleet_throughput,  # beyond-paper: vectorized fleet sim
    "fleet_dqn": bench_fleet_dqn,     # beyond-paper: shared-policy fleet DQN
    "topology": bench_topology,       # beyond-paper: shared edges + cloud q
    "trace_replay": bench_trace_replay,  # beyond-paper: trace + serving bridge
    "fleet_sharded": bench_fleet_sharded,  # beyond-paper: multi-device fleet
    "profile": bench_profile,  # compiled-cost stage fracs + cliff diagnosis
    "slo": bench_slo,  # windowed metrics overhead + SLO attainment/tails
    "bridge": bench_bridge,  # async bridge throughput + calibration loop
}

#: suites whose main() returns the headline dict folded into BENCH_fleet.json
FLEET_SUITES = ("fleet", "fleet_dqn", "topology", "trace_replay",
                "fleet_sharded", "profile", "slo", "bridge")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated suite names")
    ap.add_argument("--json", action="store_true",
                    help="write results/BENCH_fleet.json (fleet headline "
                         "metrics; implies running the fleet suites)")
    args = ap.parse_args()
    enable_compile_cache()
    names = list(SUITES) if not args.only else args.only.split(",")
    if args.json:
        names += [n for n in FLEET_SUITES if n not in names]
    print("name,us_per_call,derived")
    t0 = time.time()
    failures = []
    fleet_metrics = {}
    for name in names:
        print(f"# --- {name} ---", flush=True)
        try:
            out = SUITES[name].main()
            if name in FLEET_SUITES and isinstance(out, dict):
                fleet_metrics[name] = out
        except Exception as e:  # noqa
            import traceback
            traceback.print_exc()
            failures.append((name, e))
    if args.json:
        tp = fleet_metrics.get("fleet", {})
        dqn = fleet_metrics.get("fleet_dqn", {})
        topo = fleet_metrics.get("topology", {})
        trace = fleet_metrics.get("trace_replay", {})
        sh = fleet_metrics.get("fleet_sharded", {})
        prof = fleet_metrics.get("profile", {})
        slo = fleet_metrics.get("slo", {})
        br = fleet_metrics.get("bridge", {})
        save_json("BENCH_fleet", {
            "env_steps_per_s": tp.get("fleet_env_steps_per_s"),
            "rl_steps_per_s": tp.get("fleet_rl_steps_per_s"),
            "rl_fused_tabular_steps_per_s":
                tp.get("rl_fused_tabular_steps_per_s"),
            "dqn_rl_steps_per_s": dqn.get("dqn_rl_steps_per_s"),
            "rl_fused_dqn_steps_per_s": dqn.get("rl_fused_dqn_steps_per_s"),
            "rl_unfused_dqn_steps_per_s":
                dqn.get("rl_unfused_dqn_steps_per_s"),
            "rl_fused_dqn_speedup_x": dqn.get("rl_fused_dqn_speedup_x"),
            "converged_cells_per_s": tp.get("train_converged_cells_per_s"),
            "dqn_holdout_reward_ratio": dqn.get("holdout_reward_ratio"),
            "dqn_step_flatness": dqn.get("step_flatness"),
            "dqn_obs_overhead_x": dqn.get("obs_overhead_x"),
            "topology_env_overhead_x": topo.get("topology_env_overhead_x"),
            "topology_hot_edge_uplift": topo.get("hot_edge_reward_uplift"),
            "trace_env_steps_per_s": trace.get("trace_env_steps_per_s"),
            "trace_replay_speedup_x": trace.get("trace_replay_speedup_x"),
            "trace_serving_gap_x": trace.get("serving", {}).get("gap_x"),
            "trace_serving_p95_ms": trace.get("serving", {}).get("p95_ms"),
            "trace_serving_p99_ms": trace.get("serving", {}).get("p99_ms"),
            "slo_attainment_measured": slo.get("slo_attainment_measured"),
            "slo_attainment_predicted": slo.get("slo_attainment_predicted"),
            "slo_attainment_gap": slo.get("slo_attainment_gap"),
            "p99_ms": slo.get("p99_ms"),
            "windowed_overhead_x": slo.get("windowed_overhead_x"),
            "sync_throughput_rps": br.get("sync_throughput_rps"),
            "bridge_throughput_rps": br.get("bridge_throughput_rps"),
            "bridge_vs_sync_x": br.get("bridge_vs_sync_x"),
            "uncalibrated_gap_x": br.get("uncalibrated_gap_x"),
            "calibrated_gap_x": br.get("calibrated_gap_x"),
            "calibrated_dqn_holdout_reward_ratio":
                br.get("calibrated_dqn_holdout_reward_ratio"),
            "calibration": br.get("calibration"),
            "sharded_devices": sh.get("devices"),
            "sharded_env_steps_per_s": sh.get("sharded_env_steps_per_s"),
            "sharded_per_device_env_steps_per_s":
                sh.get("per_device_env_steps_per_s"),
            "sharded_per_device_flatness": sh.get("per_device_flatness"),
            "sharded_local_vs_alltoall_x": sh.get("local_vs_alltoall_x"),
            "rl_stage_fracs": prof.get("rl_stage_fracs"),
            "rl_dominant_stage": prof.get("dominant_stage_flops"),
            "env_flops_per_cell": prof.get("env_flops_per_cell"),
            "cliff_cells": prof.get("cliff_cells"),
            "cliff_classification": prof.get("cliff_classification"),
            "suites": fleet_metrics,
        }, wall_seconds=time.time() - t0,
            failures=[n for n, _ in failures])
        print("# wrote results/BENCH_fleet.json", flush=True)
    print(f"# done in {time.time()-t0:.0f}s; failures: "
          f"{[n for n, _ in failures] or 'none'}")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
