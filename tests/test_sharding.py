"""Sharding rules + a subprocess mini dry-run (8 fake devices)."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.parallel.logical import resolve_spec
from jax.sharding import PartitionSpec as P


def test_resolve_spec_basic():
    rules = {"batch": ("pod", "data"), "heads": "model", "embed": None}
    assert resolve_spec(["batch", None, "heads"], rules) == P(("pod", "data"), None, "model")
    assert resolve_spec(["embed"], rules) == P(None)


def test_resolve_spec_no_duplicate_axes():
    rules = {"batch": "data", "seq": "data"}
    # second use of an already-consumed mesh axis falls back to replication
    assert resolve_spec(["batch", "seq"], rules) == P("data", None)


def test_resolve_spec_tuple_dedup():
    rules = {"batch": ("data", "model"), "heads": "model"}
    spec = resolve_spec(["batch", "heads"], rules)
    assert spec == P(("data", "model"), None)


MINI_DRYRUN = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, jax
    from repro import configs
    from repro.launch import steps as steps_lib, roofline as rl, hlo_cost
    from repro.launch.mesh import make_mesh
    from repro.parallel import sharding as shard_lib
    from repro.parallel.logical import use_rules
    from jax.sharding import NamedSharding, PartitionSpec as P

    arch = os.environ["ARCH"]
    cfg = configs.get_smoke(arch)
    mesh = make_mesh((4, 2), ("data", "model"))
    plan = shard_lib.make_plan(mesh, cfg.param_count(),
                               force_mode=os.environ.get("MODE", "dp"))
    p_struct = steps_lib.params_struct(cfg)
    p_shard = shard_lib.param_sharding(p_struct, mesh, plan)
    opt_cfg = steps_lib.optimizer_config(cfg)
    o_struct = steps_lib.opt_state_struct(cfg, p_struct, opt_cfg)
    o_shard = {"m": shard_lib.param_sharding(o_struct["m"], mesh, plan),
               "v": shard_lib.param_sharding(o_struct["v"], mesh, plan),
               "count": NamedSharding(mesh, P())}
    shape = dict(kind="train", seq_len=32, global_batch=8)
    specs = steps_lib.input_specs(cfg, shape)
    b_shard = shard_lib.batch_sharding(specs["batch"], mesh, plan)
    step = steps_lib.make_train_step(cfg, opt_cfg)
    with use_rules(mesh, plan.activation_rules()), mesh:
        lowered = jax.jit(step, in_shardings=(p_shard, o_shard, b_shard)).lower(
            p_struct, o_struct, specs["batch"])
        compiled = lowered.compile()
    lac = hlo_cost.analyze(compiled.as_text())
    print(json.dumps({"flops": lac.flops, "collective_bytes": lac.collective_bytes,
                      "ok": True}))
""")


@pytest.mark.parametrize("arch,mode", [
    ("qwen3-14b", "tp"), ("gemma3-1b", "dp"), ("dbrx-132b", "tp"),
    ("jamba-1.5-large-398b", "tp"),
])
def test_mini_dryrun_compiles(arch, mode, tmp_path):
    """The full dry-run machinery on an 8-device mesh with smoke configs:
    sharding rules + jit lowering + compile + loop-aware cost analysis."""
    env = dict(os.environ, ARCH=arch, MODE=mode,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run(
        [sys.executable, "-c", MINI_DRYRUN], env=env,
        capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["ok"] and res["flops"] > 0
