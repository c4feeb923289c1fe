"""``tabular_rl_roofline``: the ``tabular_rl`` kernel's required work at
the chip's peak, as a share of its device time in the trace (%).

Required work per call is ``bench/work``'s count for every cell; the
kernel's events are the device ops named like the kernel. Without the
compiled kernel in the trace (the ``ref`` path, or a mesh) it reads
nothing."""
import peaks

#: how the compiled kernel shows in the device trace: the custom call of
#: ``repro.kernels.ops.fused_tabular_update``
KERNEL = r"^fused_tabular_update(\.\d+)? \(custom-call\)$"


def read(ctx):
    work = ctx.work.get("tabular_rl")
    if work is None:
        return None
    seconds, calls = ctx.trace.match(KERNEL)
    if seconds <= 0 or calls <= 0:
        return None
    least = peaks.least_seconds(work, ctx.device_kind)
    return 100.0 * least * calls / seconds
