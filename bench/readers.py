"""Shared arithmetic of the per-layer readers in bench/metrics/."""


def roofline_share(ctx, kernel: str, pattern):
    """100 * least time of the kernel's required work / its device time.

    The kernel's device time is the sum of the trace's operations that
    `pattern` matches; the least time is the larger of operations over
    the bf16 peak and bytes over HBM bandwidth, times the calls made in
    the traced window.  None where the trace shows no such operation.
    """
    trace = getattr(ctx, "trace", None)
    calls = getattr(ctx, "kernel_calls", {}).get(kernel)
    if trace is None or not calls:
        return None
    seconds = trace.seconds_matching(pattern)
    if seconds <= 0:
        return None
    least = ctx.kernel_work[kernel].least_seconds(ctx.peaks) * calls
    return 100.0 * least / seconds
