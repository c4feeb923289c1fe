"""Training traffic: back-to-back calls of the system's training call.

Parameters (``bench/traffic/<mix>.json``): ``steps_per_call``, the
training steps of one call, and ``warm_calls``, the calls made in
set-up. Each call blocks until its result is on the host. The window
runs whole calls until ``seconds`` have passed, and ``train_rate`` is
every cell-step of the window over the window's whole time.
"""
from __future__ import annotations

import time

import jax


def drive(system, seconds: float, traffic: dict) -> dict:
    calls = work = 0
    with jax.profiler.TraceAnnotation("bench.window"):
        t0 = time.perf_counter()
        while True:
            with jax.profiler.TraceAnnotation("bench.call"):
                work += system.call()
            calls += 1
            elapsed = time.perf_counter() - t0
            if elapsed >= seconds:
                break
    return {"window_s": elapsed, "calls": calls,
            "steps": calls * int(traffic["steps_per_call"]),
            "metrics": {"train_rate": work / elapsed}}
