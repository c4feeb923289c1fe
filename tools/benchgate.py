"""Perf regression gate over ``results/BENCH_fleet.json``.

``benchmarks/run.py --json`` writes the fleet headline metrics; this
tool diffs a FRESH run against the tracked baseline with a per-key
direction+tolerance table and exits nonzero on regression — so a perf
regression fails the build instead of merging silently.

Usage:
  # full gate: fresh run vs tracked baseline
  REPRO_BENCH_OUT=/tmp/fresh PYTHONPATH=src python -m benchmarks.run --json
  python tools/benchgate.py results/BENCH_fleet.json /tmp/fresh/BENCH_fleet.json

  # structural mode (CI): the baseline itself is well-formed — manifest
  # present, every gated key populated — without rerunning benchmarks
  python tools/benchgate.py --structural results/BENCH_fleet.json

Comparisons are manifest-aware: a baseline recorded on another backend
or device count is not comparable (CPU CI numbers vs an accelerator
run would always "regress") — the gate refuses with exit 2 unless
``--force``. Exit codes: 0 pass, 1 regression, 2 not-comparable /
structurally broken / usage error.

Tolerances are sized for CI-class shared CPU runners where wall-clock
throughputs jitter tens of percent run-to-run; quality metrics
(``dqn_holdout_reward_ratio``) gate on an absolute floor instead.
``--tolerance-scale`` widens/narrows every relative band at once.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.obs.report import flatten, is_number, rel_diff  # noqa: E402

#: key -> (direction, tolerance). Directions:
#:   higher  — throughput-like, regression when new < old * (1 - tol)
#:   lower   — overhead/gap-like, regression when new > old * (1 + tol)
#:   floor   — quality, regression when new < tol (absolute; baseline
#:             value is informational only)
#:   ceiling — quality, regression when new > tol (absolute; the dual
#:             of floor — e.g. the calibrated model error must stay
#:             within 1.5x of the measured engines regardless of what
#:             the baseline recorded)
RULES = {
    "env_steps_per_s":            ("higher", 0.40),
    "rl_steps_per_s":             ("higher", 0.40),
    "dqn_rl_steps_per_s":         ("higher", 0.40),
    "converged_cells_per_s":      ("higher", 0.50),
    "trace_env_steps_per_s":      ("higher", 0.40),
    "sharded_env_steps_per_s":    ("higher", 0.40),
    "dqn_holdout_reward_ratio":   ("floor", 0.95),
    "dqn_obs_overhead_x":         ("lower", 0.10),
    "trace_serving_gap_x":        ("lower", 0.60),
    # ISSUE 8 — SLO attainment through the serving bridge. Attainment
    # fractions gate on absolute floors (a fraction of requests meeting
    # the QoS deadline, not a throughput); p99 and the windowed-metrics
    # overhead ratio are wall-clock-ish and get the wide CI bands.
    "slo_attainment_measured":    ("floor", 0.50),
    "slo_attainment_predicted":   ("floor", 0.50),
    "p99_ms":                     ("lower", 0.60),
    "windowed_overhead_x":        ("lower", 0.10),
    # ISSUE 9 — async serving bridge + sim-to-real calibration. The
    # bridge throughput gets the wide CI band of the other wall-clock
    # metrics; the calibration quality gates are absolute: the fitted
    # model must land within 1.5x of the measured engines (ceiling)
    # and the policy retrained on calibrated dynamics must still match
    # the oracle on a calibrated holdout (floor).
    "bridge_throughput_rps":      ("higher", 0.50),
    "calibrated_gap_x":           ("ceiling", 1.5),
    "calibrated_dqn_holdout_reward_ratio": ("floor", 0.95),
    # ISSUE 10 — fused RL hot path. Throughputs get the usual CI bands;
    # the DQN speedup ratio gates on an absolute floor (fused/unfused
    # head on the same box in the same run, so runner speed divides
    # out): the fused constrained DQN head must stay measurably ahead
    # of the legacy head (~1.18x measured, floor 1.02).
    "rl_fused_tabular_steps_per_s":  ("higher", 0.40),
    "rl_fused_dqn_steps_per_s":      ("higher", 0.40),
    "rl_unfused_dqn_steps_per_s":    ("higher", 0.40),
    "rl_fused_dqn_speedup_x":        ("floor", 1.02),
}

#: manifest fields that must match for numbers to be comparable
COMPARABLE_FIELDS = ("backend", "device_count")


def load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def check_comparable(base: dict, new: dict, force: bool):
    """Refuse cross-backend / cross-device-count diffs (exit 2) unless
    forced; returns a list of human-readable mismatch lines."""
    mb, mn = base.get("manifest"), new.get("manifest")
    problems = []
    if not mb or not mn:
        problems.append("manifest missing on "
                        + ("both" if not mb and not mn else
                           "baseline" if not mb else "new run"))
    else:
        for field in COMPARABLE_FIELDS:
            if mb.get(field) != mn.get(field):
                problems.append(f"{field}: baseline={mb.get(field)!r} "
                                f"new={mn.get(field)!r}")
        if mb.get("jax_version") != mn.get("jax_version"):
            print(f"note: jax_version differs "
                  f"({mb.get('jax_version')} -> {mn.get('jax_version')}) "
                  f"— comparing anyway")
    if problems and not force:
        print("NOT COMPARABLE (use --force to diff anyway):")
        for p in problems:
            print(f"  {p}")
        sys.exit(2)
    return problems


def gate(base: dict, new: dict, scale: float) -> int:
    """Apply RULES; print one line per gated key; return #regressions."""
    fb, fn = flatten(base), flatten(new)
    regressions = 0
    width = max(len(k) for k in RULES)
    for key, (direction, tol) in RULES.items():
        vb, vn = fb.get(key), fn.get(key)
        if not is_number(vn):
            print(f"  {key:<{width}}  SKIP (new run has no value: {vn!r})")
            continue
        if direction == "floor":
            ok = vn >= tol
            detail = f"{vn:.6g} vs floor {tol:.6g}"
        elif direction == "ceiling":
            ok = vn <= tol
            detail = f"{vn:.6g} vs ceiling {tol:.6g}"
        elif not is_number(vb):
            print(f"  {key:<{width}}  SKIP (baseline has no value: {vb!r})")
            continue
        else:
            rel = rel_diff(vb, vn)
            t = tol * scale
            ok = rel >= -t if direction == "higher" else rel <= t
            detail = (f"{vb:.6g} -> {vn:.6g} ({rel:+.1%}, "
                      f"{direction}-better, tol {t:.0%})")
        print(f"  {key:<{width}}  {'ok  ' if ok else 'REGR'}  {detail}")
        regressions += not ok
    return regressions


def structural(base: dict) -> int:
    """Baseline well-formedness: manifest fields + every gated key
    present and numeric. Returns #problems."""
    problems = []
    m = base.get("manifest")
    if not m:
        problems.append("no manifest attached")
    else:
        for field in COMPARABLE_FIELDS + ("git", "created_utc",
                                          "jax_version"):
            if m.get(field) is None:
                problems.append(f"manifest.{field} missing/null")
    fb = flatten(base)
    for key in RULES:
        if not is_number(fb.get(key)):
            problems.append(f"gated key {key!r} missing or non-numeric "
                            f"({fb.get(key)!r})")
    for p in problems:
        print(f"  STRUCTURAL: {p}")
    return len(problems)


def main() -> None:
    ap = argparse.ArgumentParser(
        description="gate a fresh bench JSON against the tracked "
                    "baseline (exit 1 on regression, 2 on mismatch)")
    ap.add_argument("paths", nargs="+",
                    help="baseline.json new.json — or one baseline "
                         "with --structural")
    ap.add_argument("--structural", action="store_true",
                    help="only check the baseline is well-formed "
                         "(manifest + all gated keys populated)")
    ap.add_argument("--force", action="store_true",
                    help="diff across backend/device-count mismatches")
    ap.add_argument("--tolerance-scale", type=float, default=1.0,
                    help="multiply every relative tolerance band "
                         "(floors/ceilings unaffected)")
    args = ap.parse_args()

    if args.structural:
        if len(args.paths) != 1:
            ap.error("--structural takes exactly one path")
        base = load(args.paths[0])
        print(f"structural check: {args.paths[0]}")
        n = structural(base)
        print(f"{n} structural problem(s)")
        sys.exit(0 if n == 0 else 2)

    if len(args.paths) != 2:
        ap.error("need baseline.json new.json (or --structural one.json)")
    base, new = load(args.paths[0]), load(args.paths[1])
    print(f"gate: {args.paths[1]} vs baseline {args.paths[0]}")
    check_comparable(base, new, args.force)
    n = gate(base, new, args.tolerance_scale)
    print(f"{n} regression(s)")
    sys.exit(0 if n == 0 else 1)


if __name__ == "__main__":
    main()
