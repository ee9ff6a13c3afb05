"""Fixtures for the chip benchmark's CPU tests: a copy of the benchmark in
a temporary checkout, with a smoke-sized cell the CPU can run."""

import json
import os
import shutil
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
CHIP = os.path.join(REPO, "benchmarks", "chip")
sys.path.insert(0, CHIP)
sys.path.insert(1, os.path.join(REPO, "src"))

SMOKE_CONFIG = {
    "name": "smoke-nemo",
    "source": "https://huggingface.co/mistralai/Mistral-Nemo-Base-2407/blob/main/config.json",
    "repo_config": "mistral-nemo-12b-smoke",
    "reduced": ["num_hidden_layers"],
    "published": {"num_hidden_layers": 40},
    "model_type": "mistral",
    "hidden_act": "silu",
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2,
    "vocab_size": 256, "rms_norm_eps": 1e-05, "rope_theta": 1000000.0,
    "tie_word_embeddings": False, "torch_dtype": "bfloat16",
    "engine": {"slots": 4, "max_seq": 256, "block_size": 16, "max_chunk": 32},
    "check": {"max_served_gap": 0.05, "tokens": 160},
}

# The offline queue at smoke size: more requests than the 4 slots finish.
SMOKE_OFFLINE = {
    "kind": "offline", "requests": 24, "warm_in_s": 0.5,
    "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.6,
               "min": 8, "max": 64},
    "output": {"dist": "lognormal", "median": 40, "sigma": 0.4,
               "min": 16, "max": 96},
}

SMOKE_TRAFFIC = {
    "kind": "poisson", "rate_per_s": 20.0, "warm_in_s": 0.5,
    "prompt": {"dist": "lognormal", "median": 40, "sigma": 0.8,
               "min": 8, "max": 120},
    "output": {"dist": "lognormal", "median": 12, "sigma": 0.6,
               "min": 4, "max": 40},
}


def make_checkout(dest, config=SMOKE_CONFIG, traffic=SMOKE_TRAFFIC,
                  cell="smoke-chat", like="nemo-chat-poisson"):
    """A checkout at `dest` holding BENCHMARK.json and benchmarks/chip with
    one smoke cell added by files only, reporting the metrics of `like`."""
    chip = os.path.join(dest, "benchmarks", "chip")
    shutil.copytree(CHIP, chip, ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(chip, "configs", config["name"] + ".json"),
              "w") as f:
        json.dump(config, f)
    with open(os.path.join(chip, "traffic", cell + ".json"), "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"].append({"name": cell, "config": config["name"],
                               "traffic": cell, "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m and like in m["workloads"]:
            m["workloads"].append(cell)
    with open(os.path.join(dest, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return str(dest), chip


@pytest.fixture
def checkout(tmp_path):
    return make_checkout(tmp_path)


@pytest.fixture(autouse=True)
def smoke_arch(monkeypatch):
    """The smoke configuration's repository config: the program's smoke
    preset of Mistral-Nemo, under a name of its own."""
    from repro import configs

    get = configs.get
    monkeypatch.setattr(configs, "get", lambda name: (
        configs.get_smoke("mistral-nemo-12b")
        if name == SMOKE_CONFIG["repo_config"] else get(name)))
