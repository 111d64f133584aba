"""Share of the traced window in which a chip ran no operation, in %,
mean over chips: 100 * (1 - union of device-op intervals / window)."""

from benchlib import trace as tr


def read(ctx):
    w = ctx["window"]
    if not w or not ctx["trace"]["devices"] or w[1] <= w[0]:
        return None
    return 100.0 * (1.0 - tr.busy_ns(ctx["trace"], w) / (w[1] - w[0]))
