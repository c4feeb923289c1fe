"""The control of a cell's correctness check: the plain reference put in
the program's place and computed one precision lower (bfloat16 for the
configuration's float32), compared with the float32 reference exactly as
a run's output is. It has to come out as not correct.

    python bench/control.py --workload <cell> --seeds <n> [<n> ...]

Prints one JSON line per seed with each number compared and its limit,
and exits 1 if the control passes any seed's check. The benchmark's own
runs never run it; ``bench/tests`` runs it at a test's size.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import spec  # noqa: E402


def control_checks(workload: str, seed: int, overrides=None) -> list:
    import jax.numpy as jnp
    bench = spec.load_benchmark()
    config = spec.load_config(spec.cell(bench, workload)["config"],
                              overrides)
    traffic = spec.load_traffic(spec.cell(bench, workload)["traffic"])
    kind = spec.load_module("kinds", config["kind"])
    return kind.control(config, traffic, spec.derive_seed(seed),
                        low=jnp.bfloat16)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    passed = 0
    for seed in args.seeds:
        checks = control_checks(args.workload, seed)
        ok = all(c["value"] <= c["limit"] for c in checks)
        passed += ok
        print(json.dumps({"seed": seed, "control_correct": ok,
                          "checks": checks}), flush=True)
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())
