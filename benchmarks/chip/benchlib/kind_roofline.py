"""A layer kind's share of its roofline in the decode step: the least time
of the kind's layers over each traced decode tick's active rows (the
architecture module's `<name>(m, rows, peaks)`), over the device time of
the leaf operations under the kind's scope per decode step
(engine_spans.decode_kind_ms)."""

from __future__ import annotations

from typing import Optional

from benchlib import engine_spans


def read(ctx, kind: str, least_name: str) -> Optional[float]:
    least_s = getattr(ctx.arch, least_name, None)
    r = engine_spans.reading(ctx)
    if least_s is None or r is None:
        return None
    ms = engine_spans.decode_kind_ms(r, kind)
    rows = [t.meta["rows"] for t in r.ticks
            if t.kind == "decode" and t.meta.get("rows")]
    if not ms or not rows:
        return None
    least = sum(least_s(ctx.dims, n, ctx.peaks) for n in rows) / len(rows)
    return 100.0 * least / (ms * 1e-3)
