"""``call_ms.train``: device time of the ops outside the scan body,
paid once a ``run`` call (the ``fleet.prologue`` greedy gather, the
layout copies around the scan, the key split), in ms a call
(``stage_trace``)."""
import stage_trace


def read(ctx):
    s = stage_trace.summary(ctx)
    return s.per_call_ms() if s else None
