"""Roofline share of the `amtl_event_batch` Pallas kernel, in %: the
least time of the B columns a batch of events touches, over the kernel's
device time in the trace."""
import re

from bench.readers import roofline_share

PATTERN = re.compile(r"^amtl_event_batch(\.\d+)?$")


def read(ctx):
    return roofline_share(ctx, "amtl_event_batch", PATTERN)
