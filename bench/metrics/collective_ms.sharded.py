"""``collective_ms.sharded``: device time a training step spends in the
collectives between the mesh's chips, in ms a step, averaged over the
chips: the ops whose HLO opcode is ``all-reduce``, ``all-gather``,
``reduce-scatter``, ``all-to-all`` or ``collective-permute`` (or the
``-start``/``-done`` halves of one), over the steps of the window. A
trace without any such op reads nothing."""
import re

#: the opcode ``device_trace`` keeps in each op's name, ``name (opcode)``
COLLECTIVE = re.compile(r"\((all-reduce|all-gather|reduce-scatter|"
                        r"all-to-all|collective-permute)(-start|-done)?\)$")


def read(ctx):
    steps = ctx.window.get("steps", 0)
    ops = [sec for name, (sec, _) in ctx.trace.ops.items()
           if COLLECTIVE.search(name)]
    if not steps or not ops:
        return None
    return 1e3 * sum(ops) / steps
