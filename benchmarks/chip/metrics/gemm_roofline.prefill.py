"""The GeMM kernel's share of its roofline in the prefill-chunk programs:
the summed least time of the GeMMs the chunks need over the summed kernel
time."""

from benchlib import readings


def read(ctx):
    return readings.gemm_roofline(ctx, kinds=("prefill",))
