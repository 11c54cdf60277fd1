"""Share of the chips' bf16 peak that the required operations of the
events completed in the traced window make up (bench/work.py), in %."""


def read(ctx):
    work = getattr(ctx, "work", None)
    if work is None or not getattr(ctx, "window_s", 0) or work.flops <= 0:
        return None
    peak = ctx.chips * ctx.peaks["bf16_flops_per_s"]
    return 100.0 * work.flops / ctx.window_s / peak
