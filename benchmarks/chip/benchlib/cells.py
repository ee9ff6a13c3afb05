"""Find a cell's pieces by name: BENCHMARK.json, the configuration file
and its architecture module, the traffic file and its generator kind, and
the metric readers.

Everything a later PR may add is a file, found by the name that
BENCHMARK.json gives it or that such a file names:

  configs/<config>.json          a model configuration (published keys);
                                 its "model_type" is served by ...
  arch/<module>.py               ... the one module that lists the type in
                                 its MODEL_TYPES (every module is scanned)
  traffic/<traffic>.json         a traffic mix; its "kind" names ...
  traffic_kinds/<kind>.py        ... the generator that reads it
  metrics/<metric>.py            one reader per metric

An architecture module holds everything the benchmark knows of one family
of layer equations, so that a configuration of a new architecture is added
by files alone.  It provides:

  MODEL_TYPES    {model_type: facts}: the published model types it serves,
                 each with facts of its own (a dict) that its functions
                 read.  No two modules may claim one type.
  WIDTH_KEYS     published keys that size its layers beyond the generic
                 WIDTH_KEYS below (for example a state or expert width),
                 which no configuration may cut or change; may be ().
  check(raw)     raises CellError for a configuration whose equations the
                 module does not implement (a tied head, a window, a bias).
  dims(raw)      a frozen, hashable object of the configuration's numbers
                 with at least `name`, `n_layers`, `vocab` and `dtype`; the
                 module's functions take it as `m`, and the metric readers
                 as `ctx.dims` beside the module as `ctx.arch`.
  program_config(conf)
                 the program's ArchConfig for the configuration, each width
                 checked against the file (system.program_config).
  LAYER_TENSORS, GLOBAL_TENSORS
                 the names of its tensors; a name's position is its fold-in
                 index in weights.py, so a tuple only ever grows at its end.
  init(name)     optional: for a tensor that weights.py's default draws do
                 not suit (a recurrence's decay, a router), a function
                 (key, shape) -> float32 array, else None; the module passes
                 it to weights.layer_tensor / global_tensor.
  program_tree(key, m, cfg)
                 every weight in the program's parameter layout, drawn
                 through weights.py; system.make_params traces it in one
                 jitted call and checks it against the program's own init.
  served_gaps(m, seed, requests, control=None)
                 the plain reference.  It imports nothing of the program and
                 takes none of its arrays: it draws the weights again from
                 the seed, upcast to float32, runs every product at
                 Precision.HIGHEST, and runs a recurrence over each
                 request's own tokens.  With `control` ("int8") it also
                 computes the control: the same forward with every product
                 and the cached state in that precision.  It returns what
                 reference.compare returns.
  prefill_chunk_flops(m, start, chunk), decode_flops(m, contexts),
  step_gemms(m, rows, head_rows), decode_attn_least_s(m, contexts, peaks)
                 the work the mathematics needs, from shapes alone (see
                 work.py), not what a kernel does: padding, idle slots and
                 tiles count for nothing, so no change to the program can
                 move them.  The metric readers divide them by device time.

arch/dense_gqa.py is the first such module (Mistral and Qwen3).
"""

from __future__ import annotations

import dataclasses
import glob
import importlib.util
import json
import os
import sys
from typing import Optional

CHIP_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(CHIP_DIR))

# Published keys that size a layer.  No configuration may change one: a
# narrower model measures a different kernel shape.
WIDTH_KEYS = ("hidden_size", "intermediate_size", "num_attention_heads",
              "num_key_value_heads", "head_dim", "vocab_size")


class CellError(ValueError):
    """A BENCHMARK.json entry or one of its files is missing or unsound."""


@dataclasses.dataclass(frozen=True)
class Config:
    name: str
    raw: dict
    arch: object                   # the arch/*.py module of its model_type
    dims: object                   # arch.dims(raw)
    engine: dict
    repo_config: str


def _read_json(path: str, what: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise CellError(f"{what}: no file {os.path.relpath(path, ROOT)}")


def benchmark(root: str = ROOT) -> dict:
    return _read_json(os.path.join(root, "BENCHMARK.json"), "BENCHMARK.json")


def workload(name: str, bench: dict) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise CellError(f"no workload {name!r} in BENCHMARK.json; known: "
                    f"{[w['name'] for w in bench['workloads']]}")


def load_config(name: str, chip_dir: str = CHIP_DIR) -> Config:
    """Read configs/<name>.json and check it: only depth-like keys may be
    cut from the published values, and its model_type must be served by an
    architecture module, whose own checks it passes."""
    raw = _read_json(os.path.join(chip_dir, "configs", f"{name}.json"),
                     f"configuration {name!r}")
    raw = dict(raw, name=name)
    for key in ("source", "reduced", "published", "model_type", "engine",
                "repo_config"):
        if key not in raw:
            raise CellError(f"configuration {name!r} lacks {key!r}")
    try:
        arch = arch_module(raw["model_type"], chip_dir)
    except CellError as e:
        raise CellError(f"configuration {name!r}: {e}") from None
    widths = WIDTH_KEYS + tuple(arch.WIDTH_KEYS)
    reduced = raw["reduced"]
    bad = sorted(set(reduced) & set(widths))
    if bad:
        raise CellError(f"configuration {name!r} cuts widths {bad}; only "
                        f"depth may be cut")
    for key, published in raw["published"].items():
        if key in widths:
            raise CellError(f"configuration {name!r}: width {key!r} differs "
                            f"from the published value")
        if key not in reduced:
            raise CellError(f"configuration {name!r}: {key!r} differs from "
                            f"the source and is not listed in 'reduced'")
        if raw.get(key) == published:
            raise CellError(f"configuration {name!r}: {key!r} listed as "
                            f"reduced but equals the published value")
    missing = [k for k in reduced if k not in raw["published"]]
    if missing:
        raise CellError(f"configuration {name!r}: reduced keys {missing} "
                        f"have no published value")
    arch.check(raw)
    return Config(name=name, raw=raw, arch=arch, dims=arch.dims(raw),
                  engine=dict(raw["engine"]), repo_config=raw["repo_config"])


def arch_module(model_type: str, chip_dir: str = CHIP_DIR):
    """The one module of arch/*.py whose MODEL_TYPES lists `model_type`."""
    claims = {}
    for path in sorted(glob.glob(os.path.join(chip_dir, "arch", "*.py"))):
        mod = _load_module(path, "architecture")
        for t in mod.MODEL_TYPES:
            claims.setdefault(t, []).append((path, mod))
    found = claims.get(model_type, [])
    if len(found) > 1:
        raise CellError(f"model_type {model_type!r} is claimed by more than "
                        f"one module: "
                        f"{[os.path.relpath(p, chip_dir) for p, _ in found]}")
    if not found:
        raise CellError(f"model_type {model_type!r} has no plain reference "
                        f"(no module in arch/ serves it); known: "
                        f"{sorted(claims)}")
    return found[0][1]


def load_traffic(name: str, chip_dir: str = CHIP_DIR) -> dict:
    spec = _read_json(os.path.join(chip_dir, "traffic", f"{name}.json"),
                      f"traffic {name!r}")
    if "kind" not in spec:
        raise CellError(f"traffic {name!r} lacks 'kind'")
    return spec


# Loaded modules by (path, modification time).  A module is executed once
# per process: an architecture module's jitted reference keeps its compiled
# programs, and its dims class stays one class.
_MODULES: dict = {}


def _load_module(path: str, what: str):
    if not os.path.isfile(path):
        raise CellError(f"{what}: no file {os.path.relpath(path, ROOT)}")
    key = (os.path.abspath(path), os.stat(path).st_mtime_ns)
    if key in _MODULES:
        return _MODULES[key]
    sub = os.path.basename(os.path.dirname(path))
    base = os.path.basename(path)[:-3].replace(".", "_")
    mod_name = f"bench_{sub}_{base}"
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    # Registered before it runs: dataclasses look their module up by name.
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    _MODULES[key] = mod
    return mod


def traffic_kind(kind: str, chip_dir: str = CHIP_DIR):
    """The generator module traffic_kinds/<kind>.py (its `generate`)."""
    return _load_module(os.path.join(chip_dir, "traffic_kinds", f"{kind}.py"),
                        f"traffic kind {kind!r}")


def metric_reader(name: str, chip_dir: str = CHIP_DIR):
    """The reader metrics/<name>.py (its `read(ctx)`)."""
    return _load_module(os.path.join(chip_dir, "metrics", f"{name}.py"),
                        f"metric {name!r}")


def cell_metrics(bench: dict, cell: str, section: str) -> list:
    """The metric entries of `section` ("end_to_end" or "per_layer") that
    this cell reports: those that list it, or that list no cells."""
    out = []
    for m in bench[section]:
        cells: Optional[list] = m.get("workloads")
        if cells is None or cell in cells:
            out.append(m)
    return out
