"""``update_ms.train``: device time a training step spends under the
program's ``fleet.update`` scope, the TD update (the ``tabular_rl``
kernel on the chip), in ms a step (``stage_trace``)."""
import stage_trace


def read(ctx):
    s = stage_trace.summary(ctx)
    return s.per_step_ms("update") if s else None
