"""Collective/memory attribution: which model ops generate the traffic.

Groups collective bytes (x loop trip counts) by the jax op_name metadata so
the hillclimb can target the dominant source.

  PYTHONPATH=src python -m repro.launch.diagnose --arch qwen3-14b --shape train_4k
"""

import argparse
import os
import re
from collections import defaultdict

from repro.launch import hlo_cost


def _force_host_devices(n: int = 512) -> None:
    """Expose `n` fake host devices so dryrun can build many-device meshes
    on CPU.  Must run before jax initializes its backend — main() calls
    this ahead of the dryrun import.  Kept out of module scope on purpose:
    importing this module (e.g. from tests or other launchers) must not
    mutate the process environment."""
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={n} "
        + os.environ.get("XLA_FLAGS", "")
    )


def attribute(text: str, top: int = 15):
    comps = hlo_cost.parse_module(text)
    entry = next(c for c in comps.values() if c.is_entry)

    # multipliers (same walk as hlo_cost.analyze)
    refs = {}
    for comp in comps.values():
        out = []
        for op in comp.ops:
            if op.opcode == "while":
                mw = re.search(r"body=%?([\w.\-]+)", op.rest)
                mt = re.search(r'known_trip_count":\{"n":"(\d+)"', op.rest)
                t = int(mt.group(1)) if mt else 1
                if mw:
                    out.append((mw.group(1), t))
            for attr in ("calls", "to_apply", "true_computation", "false_computation"):
                ma = re.search(attr + r"=%?([\w.\-]+)", op.rest)
                if ma:
                    out.append((ma.group(1), 1))
        refs[comp.name] = out
    mult = defaultdict(float)
    stack = [(entry.name, 1.0)]
    while stack:
        name, m = stack.pop()
        mult[name] += m
        for callee, k in refs.get(name, []):
            stack.append((callee, m * k))

    by_name = defaultdict(float)
    count = defaultdict(int)
    for comp in comps.values():
        m = mult.get(comp.name, 0)
        if not m:
            continue
        for op in comp.ops:
            base = None
            for c in hlo_cost.COLLECTIVES:
                if op.opcode == c or op.opcode.startswith(c + "-start"):
                    base = c
                    break
            if not base:
                continue
            _, nbytes = hlo_cost._type_elems_bytes(op.type_str)
            mo = re.search(r'op_name="([^"]*)"', op.rest)
            tag = mo.group(1) if mo else "?"
            # strip indices for grouping
            tag = re.sub(r"\[\d+\]", "", tag)
            by_name[f"{base} :: {tag}"] += nbytes * m
            count[f"{base} :: {tag}"] += int(m)
    rows = sorted(by_name.items(), key=lambda kv: -kv[1])
    return rows[:top], count


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--mesh", default="single")
    ap.add_argument("--top", type=int, default=15)
    ap.add_argument("--hlo", default=None, help="reuse a dumped HLO file")
    args = ap.parse_args()

    if args.hlo and os.path.exists(args.hlo):
        text = open(args.hlo).read()
    else:
        _force_host_devices()
        import repro.launch.dryrun as dr

        dump = args.hlo or f"/tmp/hlo_{args.arch}_{args.shape}_{args.mesh}.txt"
        res = dr.run_cell(args.arch, args.shape, args.mesh, verbose=True,
                          dump_hlo=dump)
        if "not_served" in res:
            return
        text = open(dump).read()
    rows, count = attribute(text, args.top)
    print("\ntop collective sources (bytes/device x trips):")
    for k, v in rows:
        print(f"  {v/1e9:9.2f} GB  x{count[k]:<6d} {k[:120]}")


if __name__ == "__main__":
    main()
