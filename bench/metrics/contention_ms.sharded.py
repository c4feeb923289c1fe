"""``contention_ms.sharded``: device time a training step spends under
the program's ``fleet.contention`` scope, the shared-edge sums and the
reductions around them, in ms a step, averaged over the mesh's chips.

The scope sits inside ``fleet.respond``, and ``stage_trace`` gives each
op its outermost stage, so this reader matches the scope in each op's
``tf_op`` itself, with ``stage_trace``'s reading of the raw planes: the
leaf ops of each chip's ``XLA Ops`` line, clipped to the window, over
the steps of the ``fleet.run`` spans in it. A trace without the scope
reads nothing."""
import sys

import device_trace
import stage_trace

SCOPE = "fleet.contention"


def per_step_ms(planes, raw, devices: int, steps: int):
    """ms a step under ``SCOPE``, a mean over ``devices``; None where
    no op is under it."""
    host = [e for pname, lines in planes if pname.startswith("/host:")
            for _, evs in lines for e in evs
            if e.name == device_trace.WINDOW]
    if not host or not steps:
        return None
    w0, w1 = min(e.start for e in host), max(e.end for e in host)
    dev = stage_trace._device_ops(planes, raw, devices)
    seconds, found = 0.0, False
    for d_ops in dev:
        tf_op = {id(e): op for e, op in d_ops}
        for e in device_trace.leaves([e for e, _ in d_ops]):
            if SCOPE in tf_op[id(e)] and e.end > w0 and e.start < w1:
                found = True
                seconds += (min(e.end, w1) - max(e.start, w0)) / len(dev)
    return 1e3 * seconds / steps if found else None


def read(ctx):
    s = stage_trace.summary(ctx)
    if s is None:
        return None
    try:
        path, planes = stage_trace._capture_of(ctx)
        return per_step_ms(planes, stage_trace.raw_planes(path),
                           ctx.trace.devices, s.steps)
    except (OSError, ValueError, IndexError, TypeError) as e:
        print(f"[bench] contention: unreadable: {e!r}", file=sys.stderr,
              flush=True)
        return None
