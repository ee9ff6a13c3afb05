import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes and record memory / cost / collective analysis.

This is the proof that the distribution config is coherent: a sharding
mismatch, compile-time OOM, or unsupported collective fails here.

Usage:
  python -m repro.launch.dryrun --arch qwen3-14b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both --out benchmarks/results
"""

import argparse
import json
import time
import traceback

import jax

from repro import configs
from repro.launch import mesh as mesh_lib
from repro.launch import roofline as rl
from repro.launch import steps as steps_lib
from repro.parallel import sharding as shard_lib
from repro.parallel.logical import use_rules
from jax.sharding import NamedSharding, PartitionSpec as P


def run_cell(arch: str, shape_name: str, mesh_kind: str, verbose: bool = True,
             dump_hlo: str | None = None):
    cfg = configs.get(arch)
    shape = configs.SHAPES[shape_name]
    if shape["kind"] == "decode":
        # Decode cells lower the paged step that serving runs, over the
        # state an Engine of global_batch slots at seq_len would hold.
        try:
            d_struct = steps_lib.paged_state_struct(
                cfg, shape["global_batch"], shape["seq_len"])
        except NotImplementedError as e:
            if verbose:
                print(f"== {arch} x {shape_name} x {mesh_kind}: not served: "
                      f"{e} ==")
            return {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                    "not_served": str(e)}
    multi = mesh_kind == "multi"
    mesh = mesh_lib.make_production_mesh(multi_pod=multi)
    chips = mesh.size
    plan = shard_lib.make_plan(
        mesh, cfg.param_count(), n_kv_heads=cfg.n_kv_heads,
        serving=(shape["kind"] != "train"),
        force_attn_seq=False if shape["kind"] == "decode" else None,
    )
    rules = plan.activation_rules()

    p_struct = steps_lib.params_struct(cfg)
    p_shard = shard_lib.param_sharding(p_struct, mesh, plan)
    specs = steps_lib.input_specs(cfg, shape)

    t0 = time.time()
    with use_rules(mesh, rules):
        if shape["kind"] == "train":
            opt_cfg = steps_lib.optimizer_config(cfg)
            o_struct = steps_lib.opt_state_struct(cfg, p_struct, opt_cfg)
            o_shard = {
                "m": shard_lib.param_sharding(o_struct["m"], mesh, plan),
                "v": shard_lib.param_sharding(o_struct["v"], mesh, plan),
                "count": NamedSharding(mesh, P()),
            }
            b_shard = shard_lib.batch_sharding(specs["batch"], mesh, plan)
            step = steps_lib.make_train_step(cfg, opt_cfg)
            with mesh:
                lowered = jax.jit(
                    step, in_shardings=(p_shard, o_shard, b_shard),
                    donate_argnums=(0, 1),
                ).lower(p_struct, o_struct, specs["batch"])
        elif shape["kind"] == "prefill":
            b_shard = shard_lib.batch_sharding(specs["batch"], mesh, plan)
            step = steps_lib.make_prefill_step(cfg)
            with mesh:
                lowered = jax.jit(step, in_shardings=(p_shard, b_shard)).lower(
                    p_struct, specs["batch"]
                )
        else:  # decode
            d_shard = shard_lib.cache_sharding(d_struct, mesh, plan)
            tok, act = specs["tokens"], specs["active"]
            t_shard = shard_lib.batch_sharding((tok, act), mesh, plan)
            step = steps_lib.make_paged_serve_step(cfg)
            with mesh:
                lowered = jax.jit(
                    step, in_shardings=(p_shard, d_shard, *t_shard),
                    donate_argnums=(1,),
                ).lower(p_struct, d_struct, tok, act)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    from repro.launch import hlo_cost
    hlo_text = compiled.as_text()
    if dump_hlo:
        with open(dump_hlo, "w") as f:
            f.write(hlo_text)
    lac = hlo_cost.analyze(hlo_text)
    roof = rl.analyze(
        arch=arch, shape_name=shape_name, shape=shape,
        mesh_name=mesh_kind, chips=chips, cfg=cfg, compiled=compiled, lac=lac,
    )
    mem = compiled.memory_analysis()
    result = roof.row()
    result.update(
        lower_s=t_lower, compile_s=t_compile,
        memory_analysis=str(mem),
        collectives={k: int(v) for k, v in lac.collective_bytes_by_op.items()},
        collective_counts=lac.collective_count,
    )
    if verbose:
        print(f"== {arch} x {shape_name} x {mesh_kind} ({chips} chips) ==")
        print(f"  lower {t_lower:.1f}s compile {t_compile:.1f}s")
        print(f"  memory_analysis: {mem}")
        ca = compiled.cost_analysis()
        ca = ca[0] if isinstance(ca, list) else ca
        print(f"  cost_analysis: flops={ca.get('flops', 0):.3e} "
              f"bytes={ca.get('bytes accessed', 0):.3e}")
        print(f"  collectives: {result['collectives']}")
        print(f"  roofline: compute {roof.compute_s*1e3:.2f}ms "
              f"memory {roof.memory_s*1e3:.2f}ms "
              f"collective {roof.collective_s*1e3:.2f}ms -> {roof.bound}-bound, "
              f"MFU {roof.mfu*100:.1f}%, useful/HLO {roof.useful_flops_ratio:.2f}")
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None, help="directory for per-cell JSON")
    args = ap.parse_args()

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    if args.all:
        cells = [
            (a, s) for a in configs.list_archs()
            if a not in ("bert-base", "vit-b-16")
            for s in configs.shapes_for(a)
        ]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    failures = []
    for arch, shape in cells:
        for mk in meshes:
            if args.out:
                fn = os.path.join(args.out, f"{arch}__{shape}__{mk}.json")
                if os.path.exists(fn):
                    print(f"skip (done): {arch} x {shape} x {mk}")
                    continue
            try:
                res = run_cell(arch, shape, mk)
                if args.out and "not_served" not in res:
                    os.makedirs(args.out, exist_ok=True)
                    fn = os.path.join(args.out, f"{arch}__{shape}__{mk}.json")
                    with open(fn, "w") as f:
                        json.dump(res, f, indent=1, default=str)
            except Exception as e:
                traceback.print_exc()
                failures.append((arch, shape, mk, repr(e)))
    if failures:
        print("FAILURES:")
        for f in failures:
            print(" ", f)
        raise SystemExit(1)
    print(f"dry-run OK: {len(cells)} cells x {meshes}")


if __name__ == "__main__":
    main()
