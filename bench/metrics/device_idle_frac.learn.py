"""Idle share of the devices in the traced window of a learner cell:
1 - (union of device operation intervals) / window, mean over the chips."""


def read(ctx):
    trace = getattr(ctx, "trace", None)
    if trace is None or trace.window_s <= 0 or not trace.busy_by_device:
        return None
    return trace.idle_frac
