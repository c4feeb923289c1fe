"""``telemetry_ms.train``: device time a training step spends under the
program's ``fleet.telemetry`` scope, the in-scan metrics and the
per-step means that ``run`` returns, in ms a step (``stage_trace``)."""
import stage_trace


def read(ctx):
    s = stage_trace.summary(ctx)
    return s.per_step_ms("telemetry") if s else None
