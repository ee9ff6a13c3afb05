"""The GeMM kernel's share of its roofline over all its calls: the summed
least time of the GeMMs the steps need over the summed kernel time."""

from benchlib import readings


def read(ctx):
    return readings.gemm_roofline(ctx, kinds=("prefill", "decode"))
