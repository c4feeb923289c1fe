"""Record a chip trace of the tabular cell for the trace readers' tests.

    python bench/tests/record_trace.py --out <file.xplane.pb> \
        [--cells 4096] [--steps 20] [--seconds 0.5] [--seed 7]

Builds the ``tabular_train`` cell's system as ``bench/run.py`` does, at
``--cells`` cells and ``--steps`` steps a ``run`` call, warms it, and
captures ``--seconds`` of back-to-back calls (``bench/generators/scan``)
under the profiler; the capture is copied to ``--out`` and summarised
on standard output. Needs one accelerator chip.
"""
from __future__ import annotations

import argparse
import os
import shutil
import sys
import tempfile

TESTS = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(TESTS))

import device_trace  # noqa: E402
import run  # noqa: E402
import spec  # noqa: E402
import stage_trace  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--cells", type=int, default=4096)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seconds", type=float, default=0.5)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    import jax
    bench = spec.load_benchmark()
    cell = spec.cell(bench, "tabular_train")
    devices = run.require_chips(jax, cell["chips"])
    config = spec.load_config(cell["config"], {"cells": args.cells})
    traffic = dict(spec.load_traffic(cell["traffic"]),
                   steps_per_call=args.steps)
    kind = spec.load_module("kinds", config["kind"])
    generator = spec.load_module("generators", traffic["generator"])
    system = kind.System(config, traffic, spec.derive_seed(args.seed),
                         devices)
    system.warm()
    tmp = tempfile.mkdtemp(prefix="record-trace-")
    try:
        jax.profiler.start_trace(tmp)
        try:
            window = generator.drive(system, args.seconds, traffic)
        finally:
            jax.profiler.stop_trace()
        path = device_trace.find_xplane(tmp)
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        shutil.copyfile(path, args.out)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    trace = device_trace.reduce_dir(args.out, len(devices))
    stages = stage_trace.reduce_file(args.out, len(devices))
    stage_trace.log_cover(stages)
    print({"out": args.out, "bytes": os.path.getsize(args.out),
           "calls": window["calls"], "steps": window["steps"],
           "window_s": trace.window_s, "busy_s": trace.busy_s,
           "top_ops": trace.top_ops(12)})
    return 0


if __name__ == "__main__":
    sys.exit(main())
