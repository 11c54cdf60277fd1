"""Share of the chips' busy time spent in collectives in the traced window
of a sharded learner cell: device time of the all-reduce and all-gather
operations, summed over the chips, over the chips' summed busy time.

A collective's HLO instruction is named after the JAX primitive that made
it (`psum.11`, `all_gather.3`) or after its opcode (`all-gather.8`, and
`all-reduce-start.2`/`all-reduce-done.2` where XLA splits it)."""
import re

PATTERN = re.compile(
    r"^(psum|all_gather|all-(reduce|gather)(-start|-done)?)(\.\d+)?$")


def read(ctx):
    trace = getattr(ctx, "trace", None)
    if trace is None or not trace.busy_by_device:
        return None
    seconds = trace.seconds_matching(PATTERN)
    if seconds <= 0:
        return None
    return seconds / sum(trace.busy_by_device.values())
