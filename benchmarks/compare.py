"""Diff two machine-readable benchmark reports (BENCH_smoke.json).

Usage:
  python benchmarks/compare.py BASE.json HEAD.json [--fail-on-change]

Compares every row shared by the two reports and prints one line per row
that moved beyond its tolerance, plus rows that appeared or disappeared.

With ``--fail-on-change`` (how CI runs it) the comparison is a *gate*:
exit 1 when any **gating** difference exists.  What gates:

  * a numeric row moved beyond its per-row tolerance (the table below —
    wall-clock rows get wide tolerances because shared-runner noise is
    routinely 2-3x; deterministic counters/ratios stay tight);
  * a row present in the baseline vanished (a silently-dropped benchmark
    is itself a regression).  Rows *added* by the head report never gate —
    that is just a PR growing coverage;
  * the head report recorded section errors (a section that crashed must
    not pass by producing no rows).

What never gates, but is still printed:

  * rows marked **informational** — ``value == "informational"`` (how
    cluster_bench reports an unmeetable-bar row) or a ``derived`` field
    containing the word "informational";
  * percentage-delta and NLL-delta rows (pure noise amplifiers: a µs-level
    wobble swings them across zero).

Row direction is not assumed: the report prints the signed relative change
and lets the reader decide (a "regression" in a *_ms row is an increase;
in a *_tok_s row a decrease).
"""

from __future__ import annotations

import argparse
import fnmatch
import json
import sys
from typing import Dict, Optional, Tuple

# Per-row tolerance overrides, first fnmatch wins; None = informational
# (report-only, never gates).  Everything else gates at the --tolerance
# default.
PER_ROW_TOLERANCE: Tuple[Tuple[str, Optional[float]], ...] = (
    ("*overhead_pct", None),       # (on-off)/off of two µs-scale timings
    ("*nll_delta", None),          # tiny float deltas wobble across zero
    ("*reduction*", None),         # percentage-of-timing rows
    ("*_ns", 3.0),                 # wall-clock rows: shared CI runners
    ("*_us", 3.0),                 # routinely jitter 2-3x between runs;
    ("*_us_*", 3.0),               # gate only on catastrophic blowups
    ("*_ms", 3.0),
    ("*tok_s*", 2.0),
    ("*speedup*", 1.0),
    ("sched/preemptions", 0.5),    # tick-driven, but batch-finish timing
                                   # can shift a victim count by one

    ("*trace_events", 0.5),        # tick counts wobble with scheduling
)


def tolerance_for(name: str, default: float) -> Optional[float]:
    for pat, tol in PER_ROW_TOLERANCE:
        if fnmatch.fnmatch(name, pat):
            return tol
    return default


def is_informational(row: Optional[dict]) -> bool:
    if not isinstance(row, dict):
        return False
    if row.get("value") == "informational":
        return True
    return "informational" in str(row.get("derived", ""))


def load_rows(path: str) -> Tuple[Dict[str, dict], dict]:
    with open(path) as f:
        report = json.load(f)
    rows = {}
    for section, body in report.get("sections", {}).items():
        for row in body.get("rows", []):
            rows[row["name"]] = row
    return rows, report


def as_number(v):
    if isinstance(v, bool):
        return None
    if isinstance(v, (int, float)):
        return float(v)
    try:
        return float(v)
    except (TypeError, ValueError):
        return None


def compare(base_rows: Dict[str, dict], head_rows: Dict[str, dict],
            tolerance: float):
    """Yields (kind, name, detail, gates) for every difference worth
    printing; `gates` is True when the difference should fail a gating
    run."""
    for name in sorted(set(base_rows) | set(head_rows)):
        base, head = base_rows.get(name), head_rows.get(name)
        info = is_informational(base) or is_informational(head)
        if head is None:
            yield "removed", name, f"was {base['value']}", not info
            continue
        if base is None:
            # new coverage, not a regression
            yield "added", name, f"now {head['value']}", False
            continue
        tol = tolerance_for(name, tolerance)
        exempt = info or tol is None
        b, h = as_number(base["value"]), as_number(head["value"])
        if b is None or h is None:
            if base["value"] != head["value"]:
                yield ("changed", name,
                       f"{base['value']} -> {head['value']}", not exempt)
            continue
        if b == 0.0:
            if h != 0.0:
                yield "changed", name, f"{b} -> {h}", not exempt
            continue
        rel = (h - b) / abs(b)
        # informational rows still print past the default tolerance so big
        # moves stay visible in the log — they just never gate
        print_tol = tol if tol is not None else tolerance
        if abs(rel) > print_tol:
            yield "changed", name, f"{b} -> {h} ({rel:+.0%})", not exempt


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("base", help="baseline BENCH_smoke.json")
    ap.add_argument("head", help="candidate BENCH_smoke.json")
    ap.add_argument("--tolerance", type=float, default=0.25,
                    help="default relative tolerance for rows without a "
                         "per-row override (default 0.25)")
    ap.add_argument("--fail-on-change", action="store_true",
                    help="gate: exit 1 on any gating difference (beyond-"
                         "tolerance move, removed row, head section error)")
    args = ap.parse_args(argv)

    base_rows, base_report = load_rows(args.base)
    head_rows, head_report = load_rows(args.head)
    diffs = list(compare(base_rows, head_rows, args.tolerance))
    n_num = sum(1 for n in base_rows
                if as_number(base_rows[n]["value"]) is not None)
    print(f"compared {len(set(base_rows) & set(head_rows))} shared rows "
          f"({n_num} numeric in base), default tolerance "
          f"{args.tolerance:.0%}")
    for section, body in head_report.get("sections", {}).items():
        base_s = base_report.get("sections", {}).get(section, {})
        if base_s.get("seconds") and body.get("seconds"):
            print(f"  # {section}: {base_s['seconds']}s -> {body['seconds']}s")
    gating = [d for d in diffs if d[3]]
    for kind, name, detail, gates in diffs:
        mark = "" if gates else " [non-gating]"
        print(f"  {kind:8s} {name}: {detail}{mark}")
    errors = head_report.get("errors")
    if errors:
        print(f"head report has section errors: {errors}")
    if not diffs and not errors:
        print("no rows moved beyond tolerance")
        return 0
    if args.fail_on_change and (gating or errors):
        print(f"FAIL: {len(gating)} gating difference(s)"
              + (f", {len(errors)} section error(s)" if errors else ""))
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
