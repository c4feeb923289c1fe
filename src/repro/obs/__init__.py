"""repro.obs — observability for the fleet reproduction.

Four independent seams, all optional and all zero-cost when unused:

* :mod:`repro.obs.metrics` — ``MetricsAccumulator``, a jit-safe pytree
  of count/sum/sumsq/min/max + fixed-bin histograms that rides inside
  the ``lax.scan`` carry of fleet training loops with zero host syncs.
* :mod:`repro.obs.spans` — ``SpanRecorder``, a host-side span recorder
  emitting Chrome-trace/Perfetto JSON, wrapping
  ``jax.profiler.TraceAnnotation`` so device work nests under spans.
* :mod:`repro.obs.report` — ``run_manifest``/``attach_manifest``, the
  provenance stamp (git SHA, jax version, mesh shape, config hash)
  attached to bench JSONs and training results, plus the shared
  ``flatten``/``rel_diff`` helpers behind ``tools/obsview.py`` and the
  ``tools/benchgate.py`` perf-regression gate.
* :mod:`repro.obs.prof` — ``CostProfile``/``stage_costs``/
  ``scaling_sweep``, compiled-cost profiling of jitted fleet programs
  (flops / bytes / roofline terms from ``cost_analysis``), the RL-loop
  stage breakdown, and the scaling-cliff classifier.
* :mod:`repro.obs.timeline` — pure-numpy time-resolved reductions:
  exact vs histogram-derived latency quantiles (P50/P90/P95/P99 with a
  one-bin-width agreement bound), SLO attainment counting, and the
  windowed learning-curve series behind ``tools/obsview.py
  --timeline``.

The package imports only jax/numpy/stdlib; every other layer may import
it (see docs/ARCHITECTURE.md layering rules).
"""
from repro.obs.metrics import MetricDef, MetricsAccumulator
from repro.obs.prof import (CostProfile, DevicePeaks, device_peaks,
                            profile_fn, scaling_sweep, stage_costs)
from repro.obs.report import (attach_manifest, config_hash, flatten,
                              rel_diff, run_manifest)
from repro.obs.spans import SpanRecorder, span, validate_chrome_trace
from repro.obs.timeline import (QUANTILES, attainment, exact_quantiles,
                                hist_quantiles, quantile_key, window_series)

__all__ = [
    "CostProfile",
    "DevicePeaks",
    "MetricDef",
    "MetricsAccumulator",
    "QUANTILES",
    "SpanRecorder",
    "attach_manifest",
    "attainment",
    "config_hash",
    "device_peaks",
    "exact_quantiles",
    "flatten",
    "hist_quantiles",
    "profile_fn",
    "quantile_key",
    "rel_diff",
    "run_manifest",
    "scaling_sweep",
    "span",
    "stage_costs",
    "validate_chrome_trace",
    "window_series",
]
