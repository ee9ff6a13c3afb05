"""Architectures are files (arch/*.py): a configuration's model_type finds
its module by a scan, a new type is served by one new file, and the dense
module draws the same weights, reads the same reference gaps and counts the
same work as the code it came from (pinned below)."""

import filecmp
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import conftest
from benchlib import cells, harness, system, weights

DENSE_PATH = os.path.join(conftest.CHIP, "arch", "dense_gqa.py")


def _add_arch(chip, filename, *overrides):
    """Copy the dense module into the checkout's arch/ as `filename`, with
    `overrides` (lines of Python) appended to it."""
    with open(DENSE_PATH) as f:
        src = f.read()
    with open(os.path.join(chip, "arch", filename), "w") as f:
        f.write(src + "\n" + "\n".join(overrides) + "\n")


def _write_config(chip, raw):
    with open(os.path.join(chip, "configs", raw["name"] + ".json"), "w") as f:
        json.dump(raw, f)


def _tree_digest(params) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def test_program_tree_is_pinned(checkout):
    """The smoke configuration's weights at seed 3, bit for bit as drawn
    before the dense code moved into arch/."""
    _, chip = checkout
    conf = cells.load_config("smoke-nemo", chip)
    params = system.make_params(3, conf, conf.arch.program_config(conf))
    assert _tree_digest(params) == \
        "8bedca98c7484fac55b60ccc9b03f2c8ec7f8e9a68510fba91f4d744e77090e5"


# (sum, max) of each request's gaps, served and int8 control, as read
# before the move (float64 sums of the reference's float32 gaps).
GAPS = {
    False: (2**31 + 77, 3, {
        "served": [(11.950811624526978, 3.286287784576416),
                   (10.971565246582031, 3.946873188018799),
                   (174.01519191265106, 5.126219272613525)],
        "int8": [(0.0, 0.0), (0.0, 0.0),
                 (0.0764927864074707, 0.04805779457092285)]}),
    True: (5, 1, {
        "served": [(20.009388327598572, 4.7379631996154785)],
        "int8": [(0.11132693290710449, 0.11132693290710449)]}),
}


@pytest.mark.parametrize("qk_norm", [False, True], ids=["mistral", "qwen3"])
def test_served_gaps_are_pinned(qk_norm):
    dense = cells.arch_module("qwen3" if qk_norm else "mistral")
    m = dense.Dims(name="tiny", n_layers=2, d_model=64, n_heads=4,
                   n_kv_heads=2, head_dim=16, d_ff=128, vocab=256,
                   norm_eps=1e-5, rope_theta=1e6, qk_norm=qk_norm,
                   dtype="bfloat16")
    seed, n, want = GAPS[qk_norm]
    rng = np.random.default_rng(11)
    requests = [(rng.integers(0, 256, p, dtype=np.int32),
                 rng.integers(0, 256, s, dtype=np.int32))
                for p, s in [(40, 6), (9, 3), (70, 60)]][:n]
    got = dense.served_gaps(m, seed, requests, control="int8")
    assert {k: [(float(g.sum()), float(g.max())) for g in v]
            for k, v in got.items()} == want


def _changed_files(chip):
    """Files of the checkout's benchmark that are not the repository's own,
    byte for byte."""
    out = set()
    for dirpath, dirnames, filenames in os.walk(chip):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for f in filenames:
            rel = os.path.relpath(os.path.join(dirpath, f), chip)
            repo = os.path.join(conftest.CHIP, rel)
            if not (os.path.isfile(repo) and filecmp.cmp(
                    repo, os.path.join(chip, rel), shallow=False)):
                out.add(rel)
    return out


def test_architecture_added_as_a_file(tmp_path):
    """A model_type no module served before is served by one new file under
    arch/, beside its configuration and traffic files, with no edit to any
    file the benchmark has; a smoke run of it reads correct."""
    config = dict(conftest.SMOKE_CONFIG, model_type="nemo_copy")
    root, chip = conftest.make_checkout(tmp_path, config)
    _add_arch(chip, "nemo_copy.py",
              'MODEL_TYPES = {"nemo_copy": {"qk_norm": False}}')
    assert _changed_files(chip) == {
        os.path.join("arch", "nemo_copy.py"),
        os.path.join("configs", "smoke-nemo.json"),
        os.path.join("traffic", "smoke-chat.json")}
    conf = cells.load_config("smoke-nemo", chip)
    assert conf.arch.__file__ == os.path.join(chip, "arch", "nemo_copy.py")
    args = harness.parse(["--workload", "smoke-chat", "--seed", "2147483701",
                          "--seconds", "1.5", "--trace", "0"])
    res = harness.run(args, root=root, chip_dir=chip, require_chip=False)
    assert res["correct"] is True, res["check"]


def test_two_modules_claiming_one_type_is_refused(checkout):
    _, chip = checkout
    _add_arch(chip, "dense_again.py")
    with pytest.raises(cells.CellError, match="more than one module"):
        cells.load_config("smoke-nemo", chip)


def test_arch_width_key_cut_is_refused(checkout):
    """A width the module adds to the generic ones may be neither cut nor
    changed."""
    _, chip = checkout
    _add_arch(chip, "wide.py", 'MODEL_TYPES = {"wide": {"qk_norm": False}}',
              'WIDTH_KEYS = ("expert_width",)')
    raw = dict(conftest.SMOKE_CONFIG, name="wide", model_type="wide",
               expert_width=32,
               reduced=["num_hidden_layers", "expert_width"],
               published={"num_hidden_layers": 40, "expert_width": 64})
    _write_config(chip, raw)
    with pytest.raises(cells.CellError, match="cuts widths"):
        cells.load_config("wide", chip)
    _write_config(chip, dict(raw, reduced=["num_hidden_layers"]))
    with pytest.raises(cells.CellError, match="width 'expert_width'"):
        cells.load_config("wide", chip)
    _write_config(chip, dict(raw, reduced=["num_hidden_layers"],
                             published={"num_hidden_layers": 40}))
    assert cells.load_config("wide", chip).raw["expert_width"] == 32


def test_init_draws_only_the_tensors_it_names():
    """A module's init replaces the default draw of the tensors it names
    and leaves every other tensor's numbers as they were."""
    names = ("attn_norm", "wq", "decay")
    key = weights.base_key(7)

    def init(name):
        if name == "decay":
            return lambda k, shape: -jnp.exp(jax.random.uniform(k, shape))
        return None

    for name, shape in (("attn_norm", (8,)), ("wq", (8, 4))):
        a = weights.layer_tensor(key, names, 2, name, shape, init=init)
        b = weights.layer_tensor(key, names, 2, name, shape)
        assert np.array_equal(np.asarray(a), np.asarray(b))
    d = np.asarray(weights.layer_tensor(key, names, 2, "decay", (16,),
                                        jnp.float32, init=init))
    assert np.all(d < -1.0) and np.all(d > -np.e)
