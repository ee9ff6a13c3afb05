"""The decode step's Mamba-2 mixers against their roofline: the least time
of reading and writing each active row's state once and the mixers' weights
once (arch decode_mamba_least_s), over the "mamba" scope's device time per
decode step."""

from benchlib import kind_roofline


def read(ctx):
    return kind_roofline.read(ctx, "mamba", "decode_mamba_least_s")
