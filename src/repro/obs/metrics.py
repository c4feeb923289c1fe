"""In-scan metrics: a jit-safe pytree accumulator with exact merges.

``MetricsAccumulator`` lives inside the ``lax.scan`` carry of the fleet
training loops (``FleetQLearning.run``, ``FleetDQN.run``), so telemetry
is recorded at full device speed with **zero host syncs** — nothing is
fetched until :meth:`MetricsAccumulator.summary` is called on the host.

Design constraints, in order:

1. *Bit-identical under sharding.* The fleet parity discipline
   (``fleet.shard``, CHANGES.md) only holds for per-cell elementwise
   work plus integer cross-device sums. Each metric therefore carries a
   ``lanes`` axis (lanes = cells for per-cell signals): updates are
   elementwise along lanes, histograms are integer compare-and-sums
   (``bins * n`` compares for ``n`` samples, see :func:`_bin_counts`),
   and the only cross-lane float reduction — producing the scalar
   mean/std/min/max — happens host-side in float64 numpy at
   ``summary()`` time. A histogram's count is a sum of integers, so
   however the partitioner splits the samples over devices and
   all-reduces the partial counts, it is the same number. A sharded
   accumulator (lane leaves sharded along the fleet axis via
   :meth:`place`) is bit-identical to the single-device one.
2. *Plain merge.* ``merge`` is plain ``+`` on count/total/sumsq/hist
   and ``min``/``max`` on extrema — associative, and exact on the
   integer leaves and extrema, which is what lets the partitioner (or a
   host loop over shards) reduce accumulators freely; float sums carry
   the usual reassociation ULPs across *different* chunkings.
3. *Fixed shapes.* Every leaf has a static shape, so the accumulator
   scans and donates like the Q-table / replay buffer it travels with.

Values outside ``[lo, hi)`` clip into the edge bins of the histogram
(they still count exactly toward count/total/sumsq/min/max), and the
per-stream ``underflow``/``overflow`` integer counters record exactly
how many samples did so — so a mis-estimated range degrades the
histogram *visibly* (``quantiles()`` warns on clipped tails), never
the moments.

Time resolution (ISSUE 8): a ``MetricDef`` with ``n_windows > 0``
additionally carries a ``(n_windows, lanes)`` ring of per-window
count/total/min/max leaves. The window slot is ``step // window_len``
(mod ``n_windows``) — an integer index into the replicated window
axis, scatter-updated elementwise along the lane axis, i.e. the same
op class as the base update — so windowed telemetry inherits the full
sharding bit-identity, and ``summary()`` reports a learning-curve
time series instead of one number per run.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Dict, Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import timeline


def _bin_counts(idx: jnp.ndarray, bins: int) -> jnp.ndarray:
    """Count ``idx`` (ints in ``[0, bins)``) into ``bins`` i32 bins.

    A compare-and-sum with the bins on the leading axis and the samples
    on the trailing one: XLA fuses compare, convert and reduce into one
    reduction and never materialises the ``(bins, n)`` mask. It costs
    ``bins * n`` compares, which at every ``MetricDef`` in the repo (32
    or 64 bins) is far cheaper on a TPU than a scatter-add, whose
    updates to shared bins run one after another. The counts equal the
    scatter's exactly.
    """
    return (idx[None, :] == jnp.arange(bins, dtype=idx.dtype)[:, None]
            ).sum(1, dtype=jnp.int32)


@dataclasses.dataclass(frozen=True)
class MetricDef:
    """Static description of one metric stream.

    lo/hi  : histogram range (values outside clip into the edge bins
             and bump the per-stream underflow/overflow counters)
    bins   : number of fixed-width histogram bins
    lanes  : independent accumulation lanes. Use ``lanes=cells`` for
             per-cell signals so updates stay elementwise along the
             fleet axis (the sharding-exactness mechanism); ``lanes=1``
             for scalars like epsilon.
    n_windows : > 0 adds a ``(n_windows, lanes)`` ring of per-window
             count/total/min/max leaves; update ``step`` lands in slot
             ``(step // window_len) % n_windows``. 0 (default) keeps
             the stream windowless (no extra leaves).
    window_len : updates per window slot (the time resolution of the
             ring; size it as ``total_steps // n_windows`` to cover a
             run without wrapping).
    """
    lo: float = 0.0
    hi: float = 1.0
    bins: int = 32
    lanes: int = 1
    n_windows: int = 0
    window_len: int = 1

    def __post_init__(self):
        if not self.hi > self.lo:
            raise ValueError(f"MetricDef needs hi > lo, got [{self.lo}, {self.hi})")
        if self.bins < 1 or self.lanes < 1:
            raise ValueError("MetricDef needs bins >= 1 and lanes >= 1")
        if self.n_windows < 0 or self.window_len < 1:
            raise ValueError(
                "MetricDef needs n_windows >= 0 and window_len >= 1")


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class MetricsAccumulator:
    """A dict of named metric streams as a registered pytree.

    Per metric the leaves are::

        count     : (lanes,) i32   samples per lane
        total     : (lanes,) f32   sum per lane
        sumsq     : (lanes,) f32   sum of squares per lane
        mn/mx     : (lanes,) f32   running extrema (+inf/-inf when empty)
        hist      : (bins,)  i32   fixed-bin histogram over all lanes
        underflow : ()       i32   samples below lo (clipped into bin 0)
        overflow  : ()       i32   samples at/above hi (clipped into
                                   bin bins-1)

    and, when the def declares ``n_windows > 0``, the per-window ring::

        wcount    : (n_windows, lanes) i32
        wtotal    : (n_windows, lanes) f32
        wmn/wmx   : (n_windows, lanes) f32

    ``data`` maps name -> leaf dict; ``defs`` (static aux data) maps
    name -> :class:`MetricDef`; ``step`` is the accumulator's own i32
    update counter — it selects the window slot, so windowed streams
    need no external clock threaded through the scan.
    """
    data: Dict[str, Dict[str, jnp.ndarray]]
    defs: Dict[str, MetricDef]
    step: jnp.ndarray = None

    # -- pytree protocol -------------------------------------------------
    def tree_flatten(self):
        names = tuple(sorted(self.data))
        children = (self.step,) + tuple(self.data[n] for n in names)
        return children, (names, tuple((n, self.defs[n]) for n in names))

    @classmethod
    def tree_unflatten(cls, aux, children):
        names, defs = aux
        return cls(dict(zip(names, children[1:])), dict(defs), children[0])

    # -- construction ----------------------------------------------------
    @classmethod
    def create(cls, defs: Mapping[str, MetricDef]) -> "MetricsAccumulator":
        data = {}
        for name, df in defs.items():
            data[name] = {
                "count": jnp.zeros((df.lanes,), jnp.int32),
                "total": jnp.zeros((df.lanes,), jnp.float32),
                "sumsq": jnp.zeros((df.lanes,), jnp.float32),
                "mn": jnp.full((df.lanes,), jnp.inf, jnp.float32),
                "mx": jnp.full((df.lanes,), -jnp.inf, jnp.float32),
                "hist": jnp.zeros((df.bins,), jnp.int32),
                "underflow": jnp.zeros((), jnp.int32),
                "overflow": jnp.zeros((), jnp.int32),
            }
            if df.n_windows:
                data[name].update(
                    wcount=jnp.zeros((df.n_windows, df.lanes), jnp.int32),
                    wtotal=jnp.zeros((df.n_windows, df.lanes), jnp.float32),
                    wmn=jnp.full((df.n_windows, df.lanes), jnp.inf,
                                 jnp.float32),
                    wmx=jnp.full((df.n_windows, df.lanes), -jnp.inf,
                                 jnp.float32))
        return cls(data, dict(defs), jnp.zeros((), jnp.int32))

    # -- accumulation (pure; jit/scan/donation friendly) -----------------
    def update(self, values: Mapping[str, jnp.ndarray]) -> "MetricsAccumulator":
        """Fold one observation per metric into a new accumulator.

        Each value is reshaped to ``(lanes, k)``; the ``k`` samples per
        lane fold elementwise into that lane. With ``k == 1`` (the fleet
        training case) the per-lane update is a single elementwise
        add/min/max — exactly the op class the sharding parity relies
        on. Windowed streams additionally scatter the same elementwise
        row update into slot ``(step // window_len) % n_windows`` of
        their ring — an integer index on the *replicated* window axis,
        so the partitioned program stays bit-identical too. Metrics not
        named in ``values`` pass through unchanged, so the pytree
        structure is stable under jit.
        """
        data = dict(self.data)
        for name, val in values.items():
            if name not in data:
                raise KeyError(f"unknown metric {name!r}; have {sorted(data)}")
            df = self.defs[name]
            x = jnp.asarray(val, jnp.float32)
            if x.size % df.lanes:
                raise ValueError(
                    f"metric {name!r}: value of size {x.size} does not "
                    f"split into {df.lanes} lanes")
            x = x.reshape(df.lanes, -1)
            k = x.shape[1]
            d = data[name]
            scale = df.bins / (df.hi - df.lo)
            idx = jnp.clip(((x - df.lo) * scale).astype(jnp.int32),
                           0, df.bins - 1)
            data[name] = {
                "count": d["count"] + jnp.int32(k),
                "total": d["total"] + x.sum(-1),
                "sumsq": d["sumsq"] + (x * x).sum(-1),
                "mn": jnp.minimum(d["mn"], x.min(-1)),
                "mx": jnp.maximum(d["mx"], x.max(-1)),
                "hist": d["hist"] + _bin_counts(idx.ravel(), df.bins),
                # integer cross-lane sums — the second op class the
                # sharding discipline admits (bit-exact psum)
                "underflow": d["underflow"]
                + (x < df.lo).sum().astype(jnp.int32),
                "overflow": d["overflow"]
                + (x >= df.hi).sum().astype(jnp.int32),
            }
            if df.n_windows:
                slot = (self.step // df.window_len) % df.n_windows
                data[name].update(
                    wcount=d["wcount"].at[slot].add(jnp.int32(k)),
                    wtotal=d["wtotal"].at[slot].add(x.sum(-1)),
                    wmn=d["wmn"].at[slot].min(x.min(-1)),
                    wmx=d["wmx"].at[slot].max(x.max(-1)))
        return MetricsAccumulator(data, self.defs, self.step + 1)

    def merge(self, other: "MetricsAccumulator") -> "MetricsAccumulator":
        """Associative combine: sum / sum / min / max / sum.

        Merging chunked accumulators equals single-stream accumulation
        exactly on the integer leaves (count, hist) and the extrema;
        the float total/sumsq agree up to summation-reassociation ULPs
        — the same caveat CHANGES.md documents for eager-vs-jit. The
        *sharded-vs-single-device* guarantee is stronger (bit-identical)
        because there the program and its reduction order are identical,
        only the layout differs.
        """
        if self.defs != other.defs:
            raise ValueError("cannot merge accumulators with different specs")
        data = {}
        for name, d in self.data.items():
            o = other.data[name]
            data[name] = {
                "count": d["count"] + o["count"],
                "total": d["total"] + o["total"],
                "sumsq": d["sumsq"] + o["sumsq"],
                "mn": jnp.minimum(d["mn"], o["mn"]),
                "mx": jnp.maximum(d["mx"], o["mx"]),
                "hist": d["hist"] + o["hist"],
                "underflow": d["underflow"] + o["underflow"],
                "overflow": d["overflow"] + o["overflow"],
            }
            if self.defs[name].n_windows:
                # window slots merge positionally: meaningful when both
                # halves cover the same time axis (e.g. shard merges);
                # sequential chunks should share ONE accumulator instead
                data[name].update(
                    wcount=d["wcount"] + o["wcount"],
                    wtotal=d["wtotal"] + o["wtotal"],
                    wmn=jnp.minimum(d["wmn"], o["wmn"]),
                    wmx=jnp.maximum(d["wmx"], o["wmx"]))
        return MetricsAccumulator(data, self.defs,
                                  jnp.maximum(self.step, other.step))

    # -- placement -------------------------------------------------------
    def place(self, shard_fn: Callable, replicate_fn: Callable
              ) -> "MetricsAccumulator":
        """Place leaves for sharded training.

        Lane leaves of multi-lane metrics (lanes = cells) go through
        ``shard_fn(x, axis)`` (shard along the fleet axis — axis 0 of
        the base leaves, axis 1 of the ``(n_windows, lanes)`` ring);
        histograms, under/overflow counters, the step counter, and
        single-lane leaves go through ``replicate_fn``. With this
        placement the jitted update partitions into per-device
        elementwise work plus integer compare-and-sums — bit-identical
        to the single-device program.
        """
        replicated = ("hist", "underflow", "overflow")
        data = {}
        for name, d in self.data.items():
            sharded = self.defs[name].lanes > 1
            leaf = {}
            for k, v in d.items():
                if k in replicated or not sharded:
                    leaf[k] = replicate_fn(v)
                elif k in ("wcount", "wtotal", "wmn", "wmx"):
                    leaf[k] = shard_fn(v, 1)      # lanes are axis 1
                else:
                    leaf[k] = shard_fn(v, 0)
            data[name] = leaf
        return MetricsAccumulator(data, dict(self.defs),
                                  replicate_fn(self.step))

    # -- host-side reporting ---------------------------------------------
    def summary(self) -> Dict[str, dict]:
        """Fetch + reduce on the host (the only device->host transfer).

        Cross-lane reduction happens here in float64 numpy, keeping the
        device program free of float cross-device reductions.
        """
        out = {}
        for name, d in self.data.items():
            df = self.defs[name]
            count = np.asarray(d["count"], np.int64)
            total = np.asarray(d["total"], np.float64)
            sumsq = np.asarray(d["sumsq"], np.float64)
            n = int(count.sum())
            entry = {
                "count": n,
                "lanes": df.lanes,
                "hist": [int(v) for v in np.asarray(d["hist"])],
                "edges": [float(v) for v in
                          np.linspace(df.lo, df.hi, df.bins + 1)],
                "underflow": int(d["underflow"]),
                "overflow": int(d["overflow"]),
            }
            if df.n_windows:
                wc = np.asarray(d["wcount"], np.int64)     # (W, lanes)
                wt = np.asarray(d["wtotal"], np.float64)
                wmn = np.asarray(d["wmn"], np.float64)
                wmx = np.asarray(d["wmx"], np.float64)
                cnt = wc.sum(-1)                            # (W,)
                with np.errstate(invalid="ignore", divide="ignore"):
                    mean = wt.sum(-1) / cnt
                filled = cnt > 0
                steps = int(self.step)
                entry["windows"] = {
                    "n_windows": df.n_windows,
                    "window_len": df.window_len,
                    "count": [int(v) for v in cnt],
                    "mean": [float(m) if ok else None
                             for m, ok in zip(mean, filled)],
                    "min": [float(v.min()) if ok else None for v, ok in
                            zip(np.where(wc > 0, wmn, np.inf), filled)],
                    "max": [float(v.max()) if ok else None for v, ok in
                            zip(np.where(wc > 0, wmx, -np.inf), filled)],
                    "last_slot": ((steps - 1) // df.window_len)
                    % df.n_windows if steps else None,
                    "wrapped": steps > df.n_windows * df.window_len,
                }
            if n:
                mean = float(total.sum() / n)
                var = max(float(sumsq.sum() / n) - mean * mean, 0.0)
                valid = count > 0
                entry.update(
                    mean=mean,
                    std=math.sqrt(var),
                    min=float(np.asarray(d["mn"])[valid].min()),
                    max=float(np.asarray(d["mx"])[valid].max()),
                )
            else:
                entry.update(mean=None, std=None, min=None, max=None)
            out[name] = entry
        return out

    def quantiles(self, name: str,
                  qs: Sequence[float] = timeline.QUANTILES,
                  warn: bool = True) -> Dict[str, object]:
        """Histogram-derived quantiles of one stream (host-side).

        Delegates to :func:`repro.obs.timeline.hist_quantiles`: each
        quantile is the midpoint of the bin holding that order
        statistic, within one ``bin_width`` of the exact value — and
        the stream's explicit underflow/overflow counts flag clipped
        tails (``clipped=True`` + a ``UserWarning`` unless
        ``warn=False``), where the bound no longer holds.
        """
        d = self.data[name]
        df = self.defs[name]
        return timeline.hist_quantiles(
            np.asarray(d["hist"]), np.linspace(df.lo, df.hi, df.bins + 1),
            qs, underflow=int(d["underflow"]), overflow=int(d["overflow"]),
            warn=warn)

    def lane_means(self, name: str) -> np.ndarray:
        """Per-lane means (NaN for empty lanes) — e.g. per-cell reward."""
        d = self.data[name]
        count = np.asarray(d["count"], np.float64)
        total = np.asarray(d["total"], np.float64)
        with np.errstate(invalid="ignore", divide="ignore"):
            return total / np.where(count > 0, count, np.nan)
