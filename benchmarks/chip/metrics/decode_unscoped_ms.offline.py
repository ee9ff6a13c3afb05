"""Device time per decode step in the leaf operations under none of the
step program's layer-kind scopes (embed, attn, ffn, unembed, ...): the
copies and slices XLA adds around the layers, each instant counted once,
over the traced window."""

from benchlib import engine_spans


def read(ctx):
    r = engine_spans.reading(ctx)
    return None if r is None else engine_spans.decode_kind_ms(r, "")
