"""Useful prefill FLOPs over the prefill-chunk programs' device time at
peak: every prompt token's projections, causal attention over its context,
and one head row per chunk."""

from benchlib import readings


def read(ctx):
    return readings.prefill_mfu(ctx)
