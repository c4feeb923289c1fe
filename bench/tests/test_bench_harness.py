"""The benchmark harness on the CPU: it finds every file a cell names, it
refuses to run without a chip, and a whole run at a test's size drives
the timed path and checks it against the reference."""
import json
import os
import re
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import run as bench_run  # noqa: E402
import spec  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark()


def test_every_cell_finds_its_files_by_name(bench):
    for w in bench["workloads"]:
        config = spec.load_config(w["config"])
        traffic = spec.load_traffic(w["traffic"])
        assert hasattr(spec.load_module("kinds", config["kind"]), "System")
        assert hasattr(spec.load_module("generators", traffic["generator"]),
                       "drive")
        assert os.path.exists(os.path.join(
            BENCH, "reference", f"{config['reference']}.py"))
        metrics = spec.per_layer_metrics(bench, w["name"])
        assert metrics, w["name"]
        for m in metrics:
            assert callable(spec.load_module("metrics", m["name"]).read)


def test_configs_name_their_files(bench):
    for c in bench["configs"]:
        assert c["file"] == f"bench/configs/{c['name']}.json"
        config = spec.load_config(c["name"])
        assert config["reduced"] == c["reduced"]
        assert config["test_size"]["cells"] < config["cells"]
        assert set(c["reduced"]) <= set(config["assumed"])


def test_names_units_and_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bench[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        assert set(m.get("workloads", [])) <= cells


def test_seeds_of_any_size_derive_31_bit_seeds():
    seeds = [0, 1, 2 ** 31 + 7, 2 ** 40 + 3, 12345678901234]
    derived = [spec.derive_seed(s) for s in seeds]
    assert all(0 <= d < 2 ** 31 for d in derived)
    assert len(set(derived)) == len(seeds)
    assert spec.derive_seed(2 ** 40 + 3) == spec.derive_seed(2 ** 40 + 3)


def test_run_exits_nonzero_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "tabular_train", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "needs an accelerator" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


@pytest.fixture
def no_cache(monkeypatch):
    """Runs here leave JAX's persistent cache off."""
    monkeypatch.setattr(bench_run, "enable_compile_cache",
                        lambda jax: "off")


def test_a_whole_run_at_a_test_size(no_cache):
    r = bench_run.run_cell("tabular_train", 2 ** 33 + 5, 0.5, False,
                           require_chip=False, overrides={"cells": 64})
    assert r["correct"] is True
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) == {"train_rate", "setup_s"}
    assert r["metrics"]["train_rate"]["unit"] == "cell-steps/s"
    assert r["attempted"] > 0 and r["failed"] == 0
    steps = spec.load_traffic("scan")["steps_per_call"]
    assert r["attempted"] % (64 * steps) == 0
    for c in r["checks"].values():
        assert c["value"] <= c["limit"]
    json.dumps(r)
