"""Benchmark aggregator: one section per paper table/figure.

Prints ``name,value,derived`` CSV rows (the harness contract) — for
reproduction benchmarks `value` is the reproduced metric and `derived`
carries the paper's reference value.  Sections: fig5, table2, fig7, table3,
kernel (incl. autotuner deltas), decode_attn (paged decode attention vs the
gather baseline, incl. int8 KV), serving (incl. float-vs-w8a8), spec
(speculative decoding), sched (interactive p95 under batch load, FIFO vs
KV-swap preemption), cluster, obs (tracing overhead; also writes
BENCH_trace.json), plus roofline rows when dry-run results exist.  Expected runtime: ~2 min total on CPU; per-script details in each
module's docstring and EXPERIMENTS.md.

``--fast`` (= `make bench-smoke`, wired into CI) sets REPRO_BENCH_FAST=1
before any section imports: every section still runs its real code paths,
and the wall-clock-heavy ones (serving, spec, table3's host GeMM timing)
consume the flag to shrink their problems — the analytic sections (fig5,
table2, fig7, kernel) are already seconds-fast and run unchanged.
Benchmark rot thus fails CI instead of lurking until the next full
`make bench`.  Fast-mode numbers are smoke signals, not results.

Every section logs ``# begin <name>`` / ``# <name>: <seconds>s`` to stderr
as it runs, so a CI timeout is attributable to a section instead of to
"the benchmark step".  ``--json PATH`` additionally writes the rows as a
machine-readable report (per-section rows + wall-clock + errors); with
``--fast`` it defaults to BENCH_smoke.json, which CI uploads as an artifact
and benchmarks/compare.py diffs across runs.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="smoke run: same code paths, shrunken problems "
                         "(exports REPRO_BENCH_FAST=1)")
    ap.add_argument("--only", default=None,
                    help="run a single section (fig5|table2|fig7|table3|"
                         "kernel|decode_attn|serving|spec|sched|cluster|obs)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write a machine-readable report (default "
                         "BENCH_smoke.json with --fast; see "
                         "benchmarks/compare.py)")
    args = ap.parse_args(argv)
    if args.fast:
        os.environ["REPRO_BENCH_FAST"] = "1"
    # Default the report path only for a FULL fast run: `--only X --fast`
    # writing BENCH_smoke.json would silently replace a complete smoke
    # report with a one-section one (and compare.py would then report every
    # other section's rows as removed).
    json_path = args.json or (
        "BENCH_smoke.json" if args.fast and not args.only else None)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (
        cluster_bench,
        decode_bench,
        fig5_ablation,
        fig7_gemmini,
        kernel_bench,
        obs_bench,
        sched_bench,
        serving_bench,
        spec_bench,
        table2_dnn,
        table3_efficiency,
    )

    modules = [
        ("fig5", fig5_ablation),
        ("table2", table2_dnn),
        ("fig7", fig7_gemmini),
        ("table3", table3_efficiency),
        ("kernel", kernel_bench),
        ("decode_attn", decode_bench),
        ("serving", serving_bench),
        ("spec", spec_bench),
        ("sched", sched_bench),
        ("cluster", cluster_bench),
        ("obs", obs_bench),
    ]
    if args.only:
        modules = [(n, m) for n, m in modules if n == args.only]
        if not modules:
            raise SystemExit(f"unknown section {args.only!r}")
    print("name,value,derived")
    report = {"fast": bool(args.fast), "sections": {}, "errors": []}
    ok = True
    for name, mod in modules:
        print(f"# begin {name}", file=sys.stderr, flush=True)
        t0 = time.time()
        section_rows = []
        try:
            for row in mod.rows():
                print(f"{row['name']},{row['value']},{row['derived']}")
                section_rows.append({"name": row["name"], "value": row["value"],
                                     "derived": row["derived"]})
        except Exception as e:  # pragma: no cover
            ok = False
            print(f"{name}/ERROR,{e!r},", file=sys.stderr)
            report["errors"].append({"section": name, "error": repr(e)})
        dt = time.time() - t0
        print(f"# {name}: {dt:.1f}s", file=sys.stderr, flush=True)
        report["sections"][name] = {"seconds": round(dt, 2),
                                    "rows": section_rows}

    if not args.only:
        # roofline rows from any dry-run results present on disk
        try:
            from benchmarks import roofline_table
            for row in roofline_table.rows():
                print(f"{row['name']},{row['value']},{row['derived']}")
            opt = os.path.join(os.path.dirname(roofline_table.RESULTS), "dryrun_opt")
            for row in roofline_table.rows(opt):
                print(f"{row['name'].replace('roofline/', 'roofline-opt/')},"
                      f"{row['value']},{row['derived']}")
        except Exception:
            pass
    if json_path:
        with open(json_path, "w") as f:
            json.dump(report, f, indent=1, sort_keys=True)
        print(f"# wrote {json_path}", file=sys.stderr)
    if not ok:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
