"""Pairs the sweep must consider per second of sweep-kernel device time,
per chip: ordered pairs of distinct live agents in the same or adjacent
cells, counted from per-cell occupancy of the traced steps' inputs (not
from padded slots), over the kernel's device time summed over chips.  It
reads the same work whatever implements the sweep."""

from benchlib import trace as tr


def read(ctx):
    if not ctx["kernels"] or not ctx["window"] or not ctx["pairs_per_step"]:
        return None
    ns = tr.time_in(ctx["trace"], ctx["window"], ctx["kernels"],
                    next(iter(ctx["modules"])) if len(ctx["modules"]) == 1
                    else None)
    if not ns:
        return None
    return ctx["pairs_per_step"] * ctx["steps"] / (ns * ctx["chips"] / 1e9)
