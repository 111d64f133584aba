"""Device time of the step's collective ops (aura exchange, migration,
the guards' all-reduces) during which that chip runs no other op, per
simulated step, in ms, mean over chips."""

from benchlib import trace as tr


def read(ctx):
    if not ctx["collectives"] or not ctx["window"] or not ctx["steps"]:
        return None
    ns = tr.exposed_ns(ctx["trace"], ctx["window"], ctx["collectives"],
                       next(iter(ctx["modules"]))
                       if len(ctx["modules"]) == 1 else None)
    return None if ns is None else ns / 1e6 / ctx["steps"]
