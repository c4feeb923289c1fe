"""Faults planted in the timed path, under the tabular kernel's own entry
``repro.kernels.ops.fused_tabular_update``; each has to make the check
read ``correct: false``.

* ``unchanged``: the update returns the table it was given.
* ``half_fleet``: the second half of the fleet keeps its old entries.
* ``wrong_column``: each cell's update lands in action ``a + 1``.
* ``stale_greedy``: the next greedy action handed back is the argmax of
  the updated row ``s``, not of row ``s2``.
"""
from __future__ import annotations

FAULTS = ("unchanged", "half_fleet", "wrong_column", "stale_greedy")


def broken_update(real, how: str):
    """``real`` (``fused_tabular_update``) with fault ``how`` planted."""
    import jax.numpy as jnp
    from repro.kernels.ref import first_argmax_ref

    def broken(q, s, a, r, s2, **kw):
        rows = jnp.arange(q.shape[0])
        if how == "unchanged":
            _, greedy2, td = real(q, s, a, r, s2, **kw)
            return q, greedy2, td
        if how == "half_fleet":
            old = q[rows, s, a]
            q_new, greedy2, td = real(q, s, a, r, s2, **kw)
            half = q.shape[0] // 2
            return (q_new.at[rows[half:], s[half:], a[half:]].set(old[half:]),
                    greedy2, td)
        if how == "wrong_column":
            return real(q, s, (a + 1) % q.shape[2], r, s2, **kw)
        if how == "stale_greedy":
            q_new, _, td = real(q, s, a, r, s2, **kw)
            return q_new, first_argmax_ref(q_new[rows, s]), td
        raise ValueError(f"unknown fault {how!r}")

    return broken


def plant(monkeypatch, how: str):
    """Plant fault ``how`` for the agents built from now on."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "fused_tabular_update",
                        broken_update(ops.fused_tabular_update, how))
