"""The system under test: the repository's serving Engine, built the way
``repro.launch.serve`` builds it, with the benchmark's own seeded weights.

This is the only module of the benchmark that imports the program.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from benchlib import weights as W
from benchlib.cells import Config, CellError


def arch_config(conf: Config):
    """The program's ArchConfig for `conf`: the repository's published
    configuration with the depth (and the published norm epsilon and rope
    base) of the file; every width is checked against the file."""
    from repro import configs

    m = conf.dims
    cfg = dataclasses.replace(configs.get(conf.repo_config), n_layers=m.n_layers, norm_eps=m.norm_eps,
                              rope_theta=m.rope_theta, dtype=m.dtype)
    want = dict(d_model=m.d_model, n_heads=m.n_heads, n_kv_heads=m.n_kv_heads,
                resolved_head_dim=m.head_dim, d_ff=m.d_ff, vocab=m.vocab,
                qk_norm=m.qk_norm, tie_embeddings=False, qkv_bias=False,
                family="dense", mlp_variant="swiglu", norm="rms",
                post_block_norm=False, local_window=None, moe=None)
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        diff = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
        raise CellError(f"{conf.name}: repository config {conf.repo_config!r} "
                        f"differs from the file (program, file): {diff}")
    cfg.n_groups        # raises unless group_size divides the depth
    return cfg


def _program_tree(key, m, cfg):
    """The program's parameter pytree (models/model.py layout: layers
    stacked per scanned group, sub-layer i of group g is layer g*G + i)."""
    dt = jnp.dtype(m.dtype)
    G, n_groups = cfg.group_size, cfg.n_groups
    shapes = W.layer_shapes(m)

    def stacked(i, name):
        return jnp.stack([W.layer_tensor(key, g * G + i, name, shapes[name], dt)
                          for g in range(n_groups)])

    blocks = {}
    for i in range(G):
        mixer = {n: stacked(i, n) for n in ("wq", "wk", "wv", "wo")}
        if m.qk_norm:
            mixer["q_norm"] = stacked(i, "q_norm")
            mixer["k_norm"] = stacked(i, "k_norm")
        blocks[f"sub{i}"] = {
            "norm1": stacked(i, "attn_norm"),
            "mixer": mixer,
            "norm2": stacked(i, "mlp_norm"),
            "ffn": {n: stacked(i, n) for n in ("w_gate", "w_up", "w_down")},
        }
    gs = W.global_shapes(m)
    return {
        "embed": W.global_tensor(key, "embed", gs["embed"], dt),
        "final_norm": W.global_tensor(key, "final_norm", gs["final_norm"], dt),
        "head": W.global_tensor(key, "head", gs["head"], dt),
        "blocks": blocks,
    }


def make_params(seed: int, conf: Config, cfg):
    """Every weight, on the device, in the served dtype, from one jitted
    call; its tree is checked against the program's own init."""
    from repro.models import model as M

    build = jax.jit(functools.partial(_program_tree, m=conf.dims, cfg=cfg))
    want = jax.eval_shape(lambda: M.init_model(jax.random.PRNGKey(0), cfg))
    got = jax.eval_shape(build, W.base_key(0))
    if jax.tree_util.tree_structure(got) != jax.tree_util.tree_structure(want):
        raise CellError(f"{conf.name}: weight tree differs from the program's")
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        if (a.shape, a.dtype) != (b.shape, b.dtype):
            raise CellError(f"{conf.name}: weight {a.shape}/{a.dtype} where "
                            f"the program has {b.shape}/{b.dtype}")
    return jax.block_until_ready(build(W.base_key(seed)))


def make_engine(conf: Config, cfg, params):
    """An Engine as serve.main builds one (analytic autotune, greedy), not
    yet warmed."""
    from repro.serving.engine import Engine

    e = conf.engine
    return Engine(cfg, params=params, slots=int(e["slots"]),
                  max_seq=int(e["max_seq"]), block_size=int(e["block_size"]),
                  max_chunk=int(e["max_chunk"]),
                  num_blocks=int(e["num_blocks"]) if "num_blocks" in e else None,
                  autotune=True)


def request_spec(prompt, max_new: int):
    from repro.serving.request import RequestSpec

    return RequestSpec(prompt=prompt, max_new=max_new)


def compile_cache(path: str) -> None:
    """Point the program's compile cache (and JAX's) at `path`."""
    import os

    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
