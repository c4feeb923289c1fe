"""``env_ms.train``: device time a training step spends under the
program's ``fleet.act``, ``fleet.respond`` and ``fleet.scenario``
scopes (the eps-greedy draw, the response model, the scenario step),
in ms a step (``stage_trace``)."""
import stage_trace


def read(ctx):
    s = stage_trace.summary(ctx)
    return s.per_step_ms("act", "respond", "scenario") if s else None
