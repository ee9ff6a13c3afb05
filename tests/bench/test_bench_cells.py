"""The configuration loader holds every width to the published value, and
a configuration, a traffic mix or a metric reader is found by its name in
files a later change adds."""

import copy
import json
import os

import pytest

import conftest
from benchlib import cells, harness

NAMES = ["mistral-nemo-12b-d8", "qwen3-14b-d8"]


@pytest.mark.parametrize("name", NAMES)
def test_depth_alone_is_cut(name):
    conf = cells.load_config(name)
    assert conf.raw["reduced"] == ["num_hidden_layers"]
    assert conf.raw["published"] == {"num_hidden_layers": 40}
    assert conf.dims.n_layers == 8
    assert conf.raw["source"].startswith("https://huggingface.co/")


@pytest.mark.parametrize("name", NAMES)
def test_program_config_matches_the_file(name):
    from repro import configs

    conf = cells.load_config(name)
    cfg = conf.arch.program_config(conf)
    full = configs.get(conf.repo_config)
    for k in ("d_model", "n_heads", "n_kv_heads", "d_ff", "vocab",
              "resolved_head_dim", "qk_norm"):
        assert getattr(cfg, k) == getattr(full, k)
    assert cfg.n_layers == 8 and cfg.norm_eps == conf.dims.norm_eps


def _write(chip, raw):
    with open(os.path.join(chip, "configs", raw["name"] + ".json"), "w") as f:
        json.dump(raw, f)


def _variant(chip, **changes):
    raw = copy.deepcopy(conftest.SMOKE_CONFIG)
    raw.update(changes)
    raw["name"] = "variant"
    _write(chip, raw)
    return lambda: cells.load_config("variant", chip)


def test_width_cut_is_refused(checkout):
    _, chip = checkout
    load = _variant(chip, hidden_size=32, reduced=["num_hidden_layers",
                                                   "hidden_size"],
                    published={"num_hidden_layers": 40, "hidden_size": 64})
    with pytest.raises(cells.CellError, match="widths"):
        load()


def test_width_in_published_is_refused(checkout):
    _, chip = checkout
    load = _variant(chip, published={"num_hidden_layers": 40,
                                     "vocab_size": 512})
    with pytest.raises(cells.CellError, match="width"):
        load()


def test_unlisted_change_is_refused(checkout):
    _, chip = checkout
    load = _variant(chip, published={"num_hidden_layers": 40,
                                     "rope_theta": 10000.0})
    with pytest.raises(cells.CellError, match="not listed"):
        load()


def test_model_without_reference_is_refused(checkout):
    _, chip = checkout
    with pytest.raises(cells.CellError, match="reference"):
        _variant(chip, model_type="deepseek_v3")()
    with pytest.raises(cells.CellError, match="tied"):
        _variant(chip, tie_word_embeddings=True)()


def test_configuration_added_as_a_file(checkout):
    root, chip = checkout
    conf = cells.load_config("smoke-nemo", chip)
    assert conf.dims.d_model == 64 and conf.engine["slots"] == 4
    bench = cells.benchmark(root)
    assert cells.workload("smoke-chat", bench)["config"] == "smoke-nemo"


def test_metric_reader_added_as_a_file(checkout):
    root, chip = checkout
    with open(os.path.join(chip, "metrics", "ticks_per_s.py"), "w") as f:
        f.write("def read(ctx):\n"
                "    w = ctx.window\n"
                "    return len(w.ticks) / (w.t_end - w.t_start)\n")
    with open(os.path.join(chip, "metrics", "never_read.py"), "w") as f:
        f.write("def read(ctx):\n    return None\n")
    entries = [{"name": "ticks_per_s", "unit": "1/s"},
               {"name": "never_read", "unit": "%"}]

    class W:
        ticks = [object()] * 30
        t_start, t_end = 1.0, 4.0

    class Ctx:
        window = W

    got = harness.read_metrics(entries, Ctx, chip)
    assert got == {"ticks_per_s": {"value": 10.0, "unit": "1/s"}}


def test_cell_metrics_follow_workloads():
    bench = cells.benchmark()
    e2e = {m["name"] for m in cells.cell_metrics(bench, "nemo-reason-offline",
                                                 "end_to_end")}
    assert e2e == {"out_tok_s", "setup_s"}
    layer = {m["name"] for m in cells.cell_metrics(bench, "nemo-chat-poisson",
                                                   "per_layer")}
    assert "decode_mfu.poisson" in layer and "decode_mfu.offline" not in layer


def test_every_metric_has_a_reader():
    bench = cells.benchmark()
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(cells.metric_reader(m["name"]).read)


def test_missing_files_are_named():
    with pytest.raises(cells.CellError, match="no file"):
        cells.load_config("no-such-config")
    with pytest.raises(cells.CellError, match="no file"):
        cells.traffic_kind("no-such-kind")
