"""``mfu.train``: the training step's required work at the chips' peak,
as a share of the traced time per step (%). The least time of one step
is the larger of its required FLOPs over peak FLOP/s and its required
bytes over peak bytes/s (``bench/work``), for all chips together; the
traced time per step is the traced window over the steps it ran."""
import peaks


def read(ctx):
    step = ctx.work.get("step")
    steps = ctx.window.get("steps", 0)
    if step is None or not steps or ctx.trace.window_s <= 0:
        return None
    least = peaks.least_seconds(step, ctx.device_kind,
                                ctx.work["chips"])
    return 100.0 * least * steps / ctx.trace.window_s
