"""Device time of the step's sort ops per simulated step, in ms, mean over
chips.  Read only where every sort in the step is the binning's
(``repro/core/grid.py``), as checked in the step's jaxpr."""

from benchlib import trace as tr


def read(ctx):
    if not ctx["sorts"] or not ctx["window"] or not ctx["steps"]:
        return None
    ns = tr.time_in(ctx["trace"], ctx["window"], ctx["sorts"],
                    next(iter(ctx["modules"])) if len(ctx["modules"]) == 1
                    else None)
    return None if ns is None else ns / 1e6 / ctx["steps"]
