"""``BENCHMARK.json`` and the files it names, found by name.

A cell's configuration is ``bench/configs/<config>.json``, its traffic
``bench/traffic/<traffic>.json``; code is found the same way, as
``bench/<family>/<name>.py`` (``kinds``, ``generators``, ``metrics``,
``reference``, ``work``). A later cell adds files; nothing here names
one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
from typing import Any, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
SRC = os.path.join(ROOT, "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)


def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cell(bench: dict, workload: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == workload:
            return w
    raise KeyError(f"no cell {workload!r} in BENCHMARK.json (cells: "
                   f"{[w['name'] for w in bench['workloads']]})")


def _load_json(family: str, name: str) -> dict:
    with open(os.path.join(HERE, family, f"{name}.json")) as f:
        data = json.load(f)
    if data.get("name") != name:
        raise ValueError(f"bench/{family}/{name}.json names itself "
                         f"{data.get('name')!r}")
    return data


def load_config(name: str, overrides: Optional[dict] = None) -> dict:
    config = _load_json("configs", name)
    if overrides:
        config = dict(config, **overrides)
    return config


def load_traffic(name: str) -> dict:
    return _load_json("traffic", name)


def load_module(family: str, name: str):
    """``bench/<family>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(HERE, family, f"{name}.py")
    key = f"bench_{family}__{name}"
    if key in sys.modules:
        return sys.modules[key]
    if not os.path.exists(path):
        raise FileNotFoundError(f"no {family} module {name!r} at {path}")
    s = importlib.util.spec_from_file_location(key, path)
    mod = importlib.util.module_from_spec(s)
    sys.modules[key] = mod
    s.loader.exec_module(mod)
    return mod


def derive_seed(seed: int) -> int:
    """Any whole number (the driver's exceed 32 bits) to the 31-bit seed
    that the inputs, the weights and the program's keys are drawn from."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0]
               & 0x7FFFFFFF)


def _applies(metric: dict, workload: str, e2e_names) -> bool:
    if "workloads" in metric:
        return workload in metric["workloads"]
    return metric.get("moves") in e2e_names if "moves" in metric else True


def end_to_end_metrics(bench: dict, workload: str) -> list:
    return [m for m in bench["end_to_end"] if _applies(m, workload, ())]


def per_layer_metrics(bench: dict, workload: str) -> list:
    e2e = {m["name"] for m in end_to_end_metrics(bench, workload)}
    return [m for m in bench["per_layer"] if _applies(m, workload, e2e)]


@dataclasses.dataclass
class ReaderContext:
    """What a per-layer metric's reader may read: the reduced device
    trace, the required work of the system's ops, the window's own
    record and the system under test (its spans and counters)."""
    trace: Any
    work: dict
    window: dict
    system: Any
    device_kind: str
