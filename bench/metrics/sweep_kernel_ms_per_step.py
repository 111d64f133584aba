"""Device time of the Pallas sweep kernel (the step's ``tpu_custom_call``
custom calls) per simulated step, in ms, mean over chips."""

from benchlib import trace as tr


def read(ctx):
    if not ctx["kernels"] or not ctx["window"] or not ctx["steps"]:
        return None
    ns = tr.time_in(ctx["trace"], ctx["window"], ctx["kernels"],
                    next(iter(ctx["modules"])) if len(ctx["modules"]) == 1
                    else None)
    return None if ns is None else ns / 1e6 / ctx["steps"]
