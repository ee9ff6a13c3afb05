"""The system under test: the repository's serving Engine, built the way
``repro.launch.serve`` builds it, with the benchmark's own seeded weights.

This is the only module of the benchmark that imports the program.  An
architecture module (arch/*.py) reaches the program only through
`program_config`.
"""

from __future__ import annotations

import dataclasses
import functools

import jax

from benchlib import weights as W
from benchlib.cells import Config, CellError


def program_config(conf: Config, fields: dict, want: dict):
    """The program's ArchConfig for `conf`: the repository's configuration
    `conf.repo_config` with `fields` (the depth and the published values
    of the file) put in, then checked field by field against `want`, the
    file's widths and features as the architecture module names them."""
    from repro import configs

    cfg = dataclasses.replace(configs.get(conf.repo_config), **fields)
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        diff = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
        raise CellError(f"{conf.name}: repository config {conf.repo_config!r} "
                        f"differs from the file (program, file): {diff}")
    cfg.n_groups        # raises unless group_size divides the depth
    return cfg


def make_params(seed: int, conf: Config, cfg):
    """Every weight, on the device, in the served dtype, from one jitted
    call of the architecture's `program_tree`; its tree is checked against
    the program's own init."""
    from repro.models import model as M

    build = jax.jit(functools.partial(conf.arch.program_tree, m=conf.dims,
                                      cfg=cfg))
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
