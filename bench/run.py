"""Run one benchmark cell on the accelerator and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Everything a cell needs is found by name from ``BENCHMARK.json``:

* the cell's entry names its configuration and its traffic mix;
* ``bench/configs/<config>.json`` holds the configuration as it is run,
  and its ``kind`` names the module ``bench/kinds/<kind>.py`` that builds
  the system under test from it and the seed;
* ``bench/traffic/<traffic>.json`` holds the mix's parameters, and its
  ``generator`` names the module ``bench/generators/<generator>.py`` that
  drives the timed window;
* each per-layer metric has its reader ``bench/metrics/<metric>.py``.

The run needs as many accelerator chips as the cell asks for and never
falls back to the CPU. It builds the system, warms every shape the
window uses (set-up), measures for ``--seconds`` seconds with no
compilation inside the window, reads the device's peak memory, frees the
system and checks what the timed path produced against the plain
reference. With ``--trace 1`` the window runs under the profiler and the
metrics are the cell's per-layer metrics; with ``--trace 0`` they are
its end-to-end metrics. The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
with ``--trace 1`` also ``breakdown``, and last ``checks``, each number
compared beside its limit. The same numbers close standard error.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import spec  # noqa: E402  (bench/spec.py)


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell needs."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def require_chips(jax, chips: int):
    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise NoChip(f"this benchmark needs an accelerator; JAX found "
                     f"{len(devs)} cpu device(s) and no chip")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found {len(devs)} "
                     f"{devs[0].platform} device(s)")
    return devs[:chips]


def enable_compile_cache(jax) -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout: the path is part of the cache key, so it never moves."""
    path = os.path.join(ROOT, ".bench_cache", "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)  # no eviction
    return path


class CompileClock:
    """Compile seconds, compiles and persistent-cache hits, from JAX's
    own monitoring events."""

    def __init__(self, jax):
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def memory_peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True, overrides=None) -> dict:
    """One run of one cell; returns the result object. ``require_chip``
    and ``overrides`` (configuration keys replaced, for runs at a test's
    size) are for the harness's own tests, never the command line."""
    t_start = time.perf_counter()
    bench = spec.load_benchmark()
    cell = spec.cell(bench, workload)
    import jax
    t_import = time.perf_counter()
    if require_chip:
        devices = require_chips(jax, cell["chips"])
    else:
        devices = jax.devices()[:cell["chips"]]
    t_devices = time.perf_counter()
    cache = enable_compile_cache(jax)
    clock = CompileClock(jax)
    config = spec.load_config(cell["config"], overrides)
    traffic = spec.load_traffic(cell["traffic"])
    kind = spec.load_module("kinds", config["kind"])
    generator = spec.load_module("generators", traffic["generator"])
    log(f"cell {workload}: config {config['name']} (kind {config['kind']}),"
        f" traffic {traffic['name']} (generator {traffic['generator']}), "
        f"{len(devices)} x {devices[0].device_kind}; seed {seed}; compile "
        f"cache {cache}")

    system = kind.System(config, traffic, spec.derive_seed(seed), devices)
    t_built = time.perf_counter()
    system.warm()
    t_warm = time.perf_counter()
    setup_s = t_warm - t_start
    log(f"set-up {setup_s:.3f} s: compile {clock.seconds:.3f} s in "
        f"{clock.compiles} compiles, {clock.cache_hits} persistent-cache "
        f"hits; the rest {setup_s - clock.seconds:.3f} s. By phase: import "
        f"{t_import - t_start:.3f} s, devices {t_devices - t_import:.3f} s, "
        f"build {t_built - t_devices:.3f} s, warm {t_warm - t_built:.3f} s")

    compiles0 = clock.compiles
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        if trace:
            jax.profiler.start_trace(trace_dir)
        try:
            window = generator.drive(system, seconds, traffic)
        finally:
            if trace:
                jax.profiler.stop_trace()
        in_window = clock.compiles - compiles0
        log(f"window {window['window_s']:.3f} s, {window['calls']} calls; "
            f"{in_window} compiles inside the window")
        peak = memory_peak_bytes(devices)
        attempted, failed = system.outcome()
        metrics = {}
        extra = {}
        if trace:
            import device_trace
            summary = device_trace.reduce_dir(trace_dir, len(devices))
            ctx = spec.ReaderContext(trace=summary, work=system.work(),
                                     window=window, system=system,
                                     device_kind=devices[0].device_kind)
            for m in spec.per_layer_metrics(bench, workload):
                reader = spec.load_module("metrics", m["name"])
                value = reader.read(ctx)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            extra["busy_s"] = summary.busy_s
            extra["window_s"] = summary.window_s
            breakdown = {"device_ops": summary.top_ops(10),
                         "idle_gaps": summary.top_gaps(10)}
        else:
            e2e = dict(window["metrics"], setup_s=setup_s)
            for m in spec.end_to_end_metrics(bench, workload):
                if m["name"] not in e2e:
                    raise KeyError(f"cell {workload} reports no "
                                   f"{m['name']}")
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    system.release()
    t_check = time.perf_counter()
    checks = system.check() + [{"name": "compiles_in_window",
                                "value": in_window, "limit": 0}]
    log(f"check against the reference took "
        f"{time.perf_counter() - t_check:.3f} s")
    correct = all(c["value"] <= c["limit"] for c in checks)
    result = {"correct": correct, "attempted": int(attempted),
              "failed": int(failed), "metrics": metrics,
              "device": dict({"platform": devices[0].platform,
                              "kind": devices[0].device_kind,
                              "count": len(devices),
                              "memory_peak_bytes": peak}, **extra)}
    if trace:
        result["breakdown"] = breakdown
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in checks}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except NoChip as e:
        print(f"[bench] {e}", file=sys.stderr, flush=True)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
