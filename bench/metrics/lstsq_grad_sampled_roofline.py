"""Roofline share of the `lstsq_grad_sampled` Pallas kernel, in %: the
least time of the b selected rows of a writer, over the kernel's device
time in the trace."""
import re

from bench.readers import roofline_share

PATTERN = re.compile(r"^lstsq_grad_sampled(\.\d+)?$")


def read(ctx):
    return roofline_share(ctx, "lstsq_grad_sampled", PATTERN)
