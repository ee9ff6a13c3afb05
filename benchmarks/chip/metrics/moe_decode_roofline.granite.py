"""The decode step's expert layers against their roofline: the least time
of reading the touched experts, the shared expert and the router once
(arch decode_moe_least_s), over the "moe" scope's device time per decode
step."""

from benchlib import kind_roofline


def read(ctx):
    return kind_roofline.read(ctx, "moe", "decode_moe_least_s")
