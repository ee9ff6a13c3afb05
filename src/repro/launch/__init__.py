"""Launchers and step builders: the stable ``repro.launch`` API surface.

Everything is lazy (mirroring repro.serving's ``__getattr__`` table):
``from repro.launch import serve`` or ``repro.launch.Engine`` resolves on
first touch without importing every launcher — train pulls in the
optimizer stack, dryrun fakes 512 devices, and none of that should load
just to reach the serving CLI.
"""

import importlib

_SUBMODULES = (
    "compile_cache", "diagnose", "dryrun", "hlo_cost", "mesh", "roofline",
    "serve", "steps", "train",
)

_LAZY = {
    # steps: the one-definition step builders (dry-run and real launchers)
    "make_train_step": ("repro.launch.steps", "make_train_step"),
    "make_prefill_step": ("repro.launch.steps", "make_prefill_step"),
    "make_serve_step": ("repro.launch.steps", "make_serve_step"),
    "make_paged_serve_step": ("repro.launch.steps", "make_paged_serve_step"),
    "make_prefill_chunk_step": ("repro.launch.steps", "make_prefill_chunk_step"),
    "input_specs": ("repro.launch.steps", "input_specs"),
    "optimizer_config": ("repro.launch.steps", "optimizer_config"),
    # serve: engine facade + comparison harness
    "Engine": ("repro.launch.serve", "Engine"),
    "autotune_for_serving": ("repro.launch.serve", "autotune_for_serving"),
    "serving_gemm_shapes": ("repro.launch.serve", "serving_gemm_shapes"),
    "compare_prefill": ("repro.launch.serve", "compare_prefill"),
    "serve_cluster": ("repro.launch.serve", "serve_cluster"),
    # meshes
    "make_local_mesh": ("repro.launch.mesh", "make_local_mesh"),
    "make_production_mesh": ("repro.launch.mesh", "make_production_mesh"),
}

__all__ = sorted(set(_SUBMODULES) | set(_LAZY))


def __getattr__(name: str):
    if name in _LAZY:
        module, attr = _LAZY[name]
        return getattr(importlib.import_module(module), attr)
    if name in _SUBMODULES:
        return importlib.import_module(f"repro.launch.{name}")
    raise AttributeError(f"module 'repro.launch' has no attribute {name!r}")


def __dir__():
    return sorted(set(globals()) | set(__all__))
