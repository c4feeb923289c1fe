"""The ``sharded_train_4chip`` cell on the CPU at its test size: its
fleet generator, its required work, its readers and, on four host
devices, the run through the fleet mesh against its reference.

The runs on four devices are made once, in a child process on a forced
4-device host platform (JAX fixes its device count when it starts):
this file run as a script. There a sound run has to be correct with its
Q-table split one block a device, and each fault of ``faults_shared.py``
has to read ``correct: false``. The bfloat16 control of this cell, and
a sound run on one device, are cases of ``test_bench_correct.py``, which
takes every cell of ``BENCHMARK.json``.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, TESTS)

import device_trace  # noqa: E402
import faults_shared  # noqa: E402
import fleets_shared  # noqa: E402
import spec  # noqa: E402

CELL, CONFIG, DEVICES = "sharded_train_4chip", "sharded-markov", 4
SEED = 2 ** 33 + 7


def _runs() -> dict:
    """The child's side: the runs on four devices, as one JSON object."""
    import jax
    import run as bench_run
    bench_run.enable_compile_cache = lambda jax: "off"
    small = spec.load_config(CONFIG)["test_size"]
    config = spec.load_config(CONFIG, small)
    kind = spec.load_module("kinds", config["kind"])
    system = kind.System(config, spec.load_traffic("scan"), 5,
                         jax.devices()[:DEVICES])
    q = system.agent.q
    out = {"shards": sorted({s.data.shape[0] for s in q.addressable_shards}),
           "shard_devices": len({s.device for s in q.addressable_shards})}
    del system, q
    out["sound"] = bench_run.run_cell(CELL, SEED, 0.3, False,
                                      require_chip=False, overrides=small)
    for how in faults_shared.FAULTS:
        with pytest.MonkeyPatch.context() as mp:
            faults_shared.plant(mp, how)
            out[how] = bench_run.run_cell(CELL, SEED, 0.3, False,
                                          require_chip=False,
                                          overrides=small)
    return out


@pytest.fixture(scope="module")
def four_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count="
                        f"{DEVICES}")
    env["PYTHONPATH"] = (os.path.join(ROOT, "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    p = subprocess.run([sys.executable, __file__], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.splitlines()[-1])


def test_sound_run_on_four_devices_is_correct(four_devices):
    r = four_devices["sound"]
    assert r["device"]["count"] == DEVICES
    assert r["correct"] is True, r["checks"]
    assert r["checks"]["compiles_in_window"]["value"] == 0
    cells = spec.load_config(CONFIG)["test_size"]["cells"]
    assert four_devices["shards"] == [cells // DEVICES]
    assert four_devices["shard_devices"] == DEVICES


def test_run_reports_train_rate_and_setup_s(four_devices):
    r = four_devices["sound"]
    assert set(r["metrics"]) == {"train_rate", "setup_s"}
    assert r["metrics"]["train_rate"]["unit"] == "cell-steps/s"
    assert r["metrics"]["train_rate"]["value"] > 0
    assert r["attempted"] > 0 and r["failed"] == 0


@pytest.mark.parametrize("how", faults_shared.FAULTS)
def test_planted_fault_is_not_correct(four_devices, how):
    r = four_devices[how]
    assert r["correct"] is False, r["checks"]
    # every fault moves where the writes land
    assert r["checks"]["cell_gap_p90"]["value"] > 0.1
    if how == "quarter_left_out":
        gap = r["checks"]["q_norm_gap"]["value"]
        assert gap == pytest.approx(1 - np.sqrt(0.75), abs=0.03)


def test_generator_keeps_each_edge_within_a_chip():
    config = spec.load_config(CONFIG)
    fleet = fleets_shared.make(config, 12345)
    cpb, epb = fleets_shared.blocks(config)
    cells = np.arange(config["cells"])
    assert ((fleet["cell_edge"] // epb) == (cells // cpb)).all()
    per_edge = np.bincount(fleet["cell_edge"], minlength=config["n_edges"])
    assert per_edge.mean() == config["cells"] / config["n_edges"] == 16
    assert (per_edge > 0).mean() > 0.99
    assert fleet["edge_capacity"].shape == (config["n_edges"],)
    again = fleets_shared.make(config, 12345)
    other = fleets_shared.make(config, 12346)
    for k in ("end_b", "edge_b", "cell_edge"):
        assert (fleet[k] == again[k]).all()
        assert (fleet[k] != other[k]).any()
    # the links start in Table 5's patterns: half of the slots Weak
    weak = np.concatenate([fleet["end_b"].ravel(), fleet["edge_b"]])
    assert weak.mean() == pytest.approx(0.5, abs=0.01)


def test_required_work_adds_links_and_edge_sums():
    shared = spec.load_module("work", "fleet_qlearning_shared")
    isolated = spec.load_module("work", "fleet_qlearning")
    cells, users, actions, edges = 262144, 5, 243, 16384
    w = shared.work(cells, users, actions, edges, chips=4)
    base = isolated.step(cells, users, actions)
    assert w["chips"] == 4 and set(w) == {"chips", "step"}
    assert w["step"]["flops"] == base["flops"] + cells * (2 * 6 + 2)
    assert w["step"]["bytes"] == base["bytes"] + 4 * (cells * (6 + 4)
                                                      + 2 * edges)
    half = shared.step(cells // 2, users, actions, edges // 2)
    assert 2 * half["bytes"] == w["step"]["bytes"]


def _trace(ops):
    return device_trace.TraceSummary(
        window_s=1.0, busy_s=1.0, ops={n: (s, 1.0) for n, s in ops.items()},
        op_stats={n: {} for n in ops}, gaps=[], devices=4)


def test_collective_reader_sums_collectives_by_opcode():
    reader = spec.load_module("metrics", "collective_ms.sharded")
    ops = {"all-reduce.60 (all-reduce)": 0.02,
           "all-gather-start.3 (all-gather-start)": 0.01,
           "all-gather-done.3 (all-gather-done)": 0.005,
           "collective-permute.1 (collective-permute)": 0.005,
           "fusion.7 (fusion)": 0.5, "all-reduce-like.2 (fusion)": 0.25}
    ctx = spec.ReaderContext(trace=_trace(ops), work={},
                             window={"steps": 40}, system=None,
                             device_kind="TPU v5 lite")
    assert reader.read(ctx) == pytest.approx(1e3 * 0.04 / 40)
    ctx.trace = _trace({"fusion.7 (fusion)": 0.5})
    assert reader.read(ctx) is None


def _plane(name, lines):
    return name, [(ln, evs) for ln, evs in lines]


def test_contention_reader_counts_the_scope_on_every_chip():
    """Two chips, one window of 1 s: the scope's leaf ops are clipped to
    the window and their time is a mean over the chips."""
    reader = spec.load_module("metrics", "contention_ms.sharded")
    E = device_trace.Event
    scoped = "jit(run)/while/body/closed_call/fleet.respond/fleet.contention/"
    planes = [_plane("/host:CPU", [("main", [E("bench.window", 1.0, 2.0)])])]
    raw = [("/host:CPU", [], {})]
    for d, (a, b) in enumerate([(1.1, 1.2), (0.9, 1.3)]):
        planes.append(_plane(f"/device:TPU:{d}", [("XLA Ops", [
            E("while.1 (while)", 0.5, 2.0), E("add.1 (add)", a, b),
            E("fusion.2 (fusion)", 1.4, 1.9)])]))
        raw.append((f"/device:TPU:{d}", [[1, 2, 3]],
                    {1: "jit(run)/while", 2: scoped + "reduce_sum",
                     3: "jit(run)/while/body/closed_call/fleet.update/x"}))
    got = reader.per_step_ms(planes, raw, 2, steps=10)
    assert got == pytest.approx(1e3 * (0.1 + 0.3) / 2 / 10)
    raw = [(n, ids, {k: v.replace("fleet.contention/", "")
                     for k, v in ops.items()}) for n, ids, ops in raw]
    assert reader.per_step_ms(planes, raw, 2, steps=10) is None


if __name__ == "__main__":
    print(json.dumps(_runs()))
