"""Compile-only checks of the RL kernels for a described TPU v5e.

The TPU compiler is installed with JAX, so the fleet agents' Pallas
kernels are lowered and compiled for a ``v5e:2x2`` topology that is
described, not attached: Mosaic refuses here what it would refuse on
the chip (unaligned blocks, scalar stores to VMEM, scoped-VMEM
overflow). Shapes are those of ``chip_smoke.py``. Nothing runs, so
nothing here says anything about results or speed.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and the test workers all
import this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops

TAB_CELLS, STATES, ACTIONS = 65536, 36, 243   # the smoke's 2.29 GB Q-table
DQN_CELLS, USERS, HIDDEN, TOPK, N_ACT = 16384, 5, 128, 5, 10


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _shape(one_chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def test_tabular_kernel_compiles_for_v5e(one_chip):
    fn = jax.jit(lambda q, s, a, r, s2: ops.fused_tabular_update(
        q, s, a, r, s2, alpha=0.9, gamma=0.1, impl="pallas",
        interpret=False), donate_argnums=0)
    idx = _shape(one_chip, (TAB_CELLS,), jnp.int32)
    compiled = fn.lower(_shape(one_chip, (TAB_CELLS, STATES, ACTIONS)), idx,
                        idx, _shape(one_chip, (TAB_CELLS,)), idx).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("threshold", [0.0, 85.0])
def test_dqn_head_compiles_for_v5e(one_chip, threshold):
    """The fused head at the agent's defaults (5 users, hidden 128,
    ``topk=5``), with and without the QoS goal, at the block size the
    VMEM budget picks."""
    params = [{"w": _shape(one_chip, (i, o)), "b": _shape(one_chip, (o,))}
              for i, o in ((11, HIDDEN), (HIDDEN, HIDDEN), (HIDDEN, N_ACT))]
    per_user = _shape(one_chip, (DQN_CELLS, USERS))
    fn = jax.jit(lambda act, mem, end, agg, p, allowed, acc: ops.dqn_head(
        act, mem, end, agg, p, allowed, acc, threshold=threshold,
        topk=TOPK, impl="pallas", interpret=False))
    compiled = fn.lower(per_user, per_user, per_user,
                        _shape(one_chip, (DQN_CELLS, 8)), params,
                        _shape(one_chip, (USERS, N_ACT)),
                        _shape(one_chip, (N_ACT,))).compile()
    assert "tpu_custom_call" in compiled.as_text()
