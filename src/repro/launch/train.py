"""Training launcher: end-to-end driver usable both for the CPU example
(~100M-param model, a few hundred steps) and as the template for a real
multi-pod job (same step function the dry-run lowers).

  PYTHONPATH=src python -m repro.launch.train --arch gemma3-1b --preset 100m \
      --steps 300 --batch 8 --seq 128
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax

from repro import configs
from repro.data import SyntheticLMData
from repro.launch import steps as steps_lib
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.models import model as M
from repro.optim import adamw_init
from repro.parallel import sharding as shard_lib
from repro.parallel.logical import use_rules
from repro.runtime import Supervisor, TrainLoopConfig


def preset_config(arch: str, preset: str):
    cfg = configs.get(arch)
    if preset == "full":
        return cfg
    if preset == "smoke":
        return configs.get_smoke(arch)
    if preset == "100m":
        # ~100M-param member of the same family (CPU-trainable).
        kw = dict(
            n_layers=8, d_model=512, n_heads=8,
            n_kv_heads=min(cfg.n_kv_heads, 4), head_dim=64,
            d_ff=2048 if cfg.d_ff else 0, vocab=min(cfg.vocab, 32768),
            group_size=1, dtype="float32",
        )
        if cfg.family == "hybrid":
            kw["attn_every"] = 4
            kw["attn_offset"] = None
            kw["group_size"] = 4
        if cfg.mamba and cfg.mamba.mamba2:
            kw["mamba"] = dataclasses.replace(
                cfg.mamba, n_heads=2 * 512 // cfg.mamba.head_dim)
        if cfg.embedding_multiplier == cfg.d_model ** 0.5:
            kw["embedding_multiplier"] = 512 ** 0.5     # Gemma's sqrt(d)
        if cfg.family == "ssm":
            kw["slstm_every"] = 4
            kw["group_size"] = 4
            kw["d_ff"] = 0
        if cfg.moe:
            kw["moe"] = dataclasses.replace(cfg.moe, num_experts=8, d_ff_expert=1024,
                                            top_k=min(cfg.moe.top_k, 8),
                                            first_expert=0, experts_held=None)
        if cfg.local_ratio:
            kw["group_size"] = cfg.local_ratio + 1
            kw["n_layers"] = 2 * (cfg.local_ratio + 1)
        if cfg.family == "encdec":
            kw["encoder_layers"] = 4
            kw["encoder_seq"] = 64
        if cfg.family == "vlm":
            kw["prefix_len"] = 16
        return dataclasses.replace(cfg, **kw)
    raise ValueError(preset)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-1b", choices=configs.list_archs())
    ap.add_argument("--preset", default="100m", choices=["smoke", "100m", "full"])
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--fail-at", type=int, default=None,
                    help="inject a failure at this step (fault-tolerance demo)")
    ap.add_argument("--quant", default=None, choices=[None, "int8"])
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = preset_config(args.arch, args.preset)
    mesh = make_local_mesh(args.model_parallel)
    plan = shard_lib.make_plan(mesh, cfg.param_count(), force_fsdp=False)
    print(f"arch={cfg.name} params={cfg.param_count()/1e6:.1f}M "
          f"mesh={dict(zip(mesh.axis_names, mesh.devices.shape))}")

    key = jax.random.PRNGKey(0)
    with use_rules(mesh, plan.activation_rules()):
        params = M.init_model(key, cfg)
        opt_cfg = steps_lib.optimizer_config(cfg)
        opt_state = adamw_init(params, opt_cfg)
        train_step = steps_lib.make_train_step(
            cfg, opt_cfg, base_lr=args.lr, total_steps=args.steps
        )
        p_shard = shard_lib.param_sharding(params, mesh, plan)
        params = jax.device_put(params, p_shard)

        extras = {}
        if cfg.family == "encdec":
            extras["frames"] = (cfg.encoder_seq, cfg.d_model)
        if cfg.family == "vlm":
            extras["patches"] = (cfg.prefix_len, M.VISION_DIM)
        data = SyntheticLMData(cfg.vocab, args.batch, args.seq, extras=extras)

        jstep = jax.jit(train_step, donate_argnums=(0, 1))
        sup = Supervisor(
            jstep,
            data_at=data.batch_at,
            loop_cfg=TrainLoopConfig(
                total_steps=args.steps, ckpt_every=args.ckpt_every,
                ckpt_dir=args.ckpt_dir,
            ),
            simulate_failure_at=args.fail_at,
        )
        if args.resume:
            restored = sup.restore(params, opt_state)
            if restored:
                params, opt_state, start = restored
                print(f"resumed from step {start}")
        t0 = time.time()
        out = sup.run(params, opt_state)
        dt = time.time() - t0

    losses = [m["loss"] for m in out["metrics"]]
    print(json.dumps({
        "steps": out["step"], "restarts": out["restarts"],
        "straggler_steps": out["straggler_steps"],
        "first_loss": losses[0] if losses else None,
        "last_loss": losses[-1] if losses else None,
        "wall_s": round(dt, 1),
    }, indent=1))
    return out


if __name__ == "__main__":
    main()
