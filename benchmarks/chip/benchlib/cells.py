"""Find a cell's pieces by name: BENCHMARK.json, the configuration file,
the traffic file and its generator kind, and the metric readers.

Everything a later PR may add is a file found by the name that
BENCHMARK.json gives it:

  configs/<config>.json          a model configuration (published keys)
  traffic/<traffic>.json         a traffic mix; its "kind" names ...
  traffic_kinds/<kind>.py        ... the generator that reads it
  metrics/<metric>.py            one reader per metric
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Optional

CHIP_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(CHIP_DIR))

# Published keys that size a layer.  No configuration may change one: a
# narrower model measures a different kernel shape.
WIDTH_KEYS = ("hidden_size", "intermediate_size", "num_attention_heads",
              "num_key_value_heads", "head_dim", "vocab_size")
# Model types whose layer equations the plain reference implements.
MODEL_TYPES = {"mistral": False, "qwen3": True}     # -> qk-norm


class CellError(ValueError):
    """A BENCHMARK.json entry or one of its files is missing or unsound."""


@dataclasses.dataclass(frozen=True)
class ModelDims:
    """The numbers of one configuration, under the benchmark's own names."""

    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    norm_eps: float
    rope_theta: float
    qk_norm: bool
    dtype: str

    @property
    def layer_params(self) -> int:
        """Weights of one layer's projections (norm vectors excluded)."""
        d, D = self.d_model, self.head_dim
        attn = d * D * (self.n_heads + 2 * self.n_kv_heads) + self.n_heads * D * d
        return attn + 3 * d * self.d_ff

    @property
    def trunk_params(self) -> int:
        return self.n_layers * self.layer_params

    @property
    def head_params(self) -> int:
        return self.d_model * self.vocab


@dataclasses.dataclass(frozen=True)
class Config:
    name: str
    raw: dict
    dims: ModelDims
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
    cut from the published values, and the model must be one whose layer
    equations the reference implements."""
    raw = _read_json(os.path.join(chip_dir, "configs", f"{name}.json"),
                     f"configuration {name!r}")
    for key in ("source", "reduced", "published", "model_type", "engine",
                "repo_config"):
        if key not in raw:
            raise CellError(f"configuration {name!r} lacks {key!r}")
    reduced = raw["reduced"]
    bad = sorted(set(reduced) & set(WIDTH_KEYS))
    if bad:
        raise CellError(f"configuration {name!r} cuts widths {bad}; only "
                        f"depth may be cut")
    for key, published in raw["published"].items():
        if key in WIDTH_KEYS:
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
    if raw["model_type"] not in MODEL_TYPES:
        raise CellError(f"configuration {name!r}: model_type "
                        f"{raw['model_type']!r} has no plain reference; "
                        f"known: {sorted(MODEL_TYPES)}")
    if raw.get("tie_word_embeddings", False):
        raise CellError(f"configuration {name!r}: tied embeddings are not "
                        f"in the reference")
    if raw.get("sliding_window") or raw.get("attention_bias"):
        raise CellError(f"configuration {name!r}: windows and attention "
                        f"biases are not in the reference")
    if raw.get("hidden_act", "silu") != "silu":
        raise CellError(f"configuration {name!r}: activation "
                        f"{raw['hidden_act']!r} is not in the reference")
    dims = ModelDims(
        name=name,
        n_layers=int(raw["num_hidden_layers"]),
        d_model=int(raw["hidden_size"]),
        n_heads=int(raw["num_attention_heads"]),
        n_kv_heads=int(raw["num_key_value_heads"]),
        head_dim=int(raw["head_dim"]),
        d_ff=int(raw["intermediate_size"]),
        vocab=int(raw["vocab_size"]),
        norm_eps=float(raw["rms_norm_eps"]),
        rope_theta=float(raw["rope_theta"]),
        qk_norm=MODEL_TYPES[raw["model_type"]],
        dtype=raw.get("torch_dtype", "bfloat16"),
    )
    return Config(name=name, raw=raw, dims=dims, engine=dict(raw["engine"]),
                  repo_config=raw["repo_config"])


def load_traffic(name: str, chip_dir: str = CHIP_DIR) -> dict:
    spec = _read_json(os.path.join(chip_dir, "traffic", f"{name}.json"),
                      f"traffic {name!r}")
    if "kind" not in spec:
        raise CellError(f"traffic {name!r} lacks 'kind'")
    return spec


def _load_module(path: str, what: str):
    if not os.path.isfile(path):
        raise CellError(f"{what}: no file {os.path.relpath(path, ROOT)}")
    mod_name = "bench_" + os.path.basename(path)[:-3].replace(".", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
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
