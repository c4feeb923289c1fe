"""``idle.train``: the share of the traced training window in which no
op ran on the device (%), averaged over the chips used."""


def read(ctx):
    t = ctx.trace
    if t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
