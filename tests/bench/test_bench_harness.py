"""A whole run of a smoke-sized cell on the CPU, the harness's look for a
chip skipped: sound, it reads correct; with the timed path broken
underneath, or with the reference in int8 as the control, it does not."""

import os
import subprocess
import sys

import jax.numpy as jnp
import pytest

import conftest
from benchlib import harness

SEED = 2**31 + 77


@pytest.fixture(scope="module")
def smoke(tmp_path_factory):
    return conftest.make_checkout(tmp_path_factory.mktemp("checkout"))


@pytest.fixture(scope="module")
def smoke_offline(tmp_path_factory):
    return conftest.make_checkout(
        tmp_path_factory.mktemp("offline"), traffic=conftest.SMOKE_OFFLINE,
        cell="smoke-offline", like="nemo-reason-offline")


def run(smoke, *extra, hook=None, cell="smoke-chat"):
    root, chip = smoke
    args = harness.parse(["--workload", cell, "--seed", str(SEED),
                          "--seconds", "1.5", "--trace", "0", *extra])
    return harness.run(args, root=root, chip_dir=chip, require_chip=False,
                       engine_hook=hook)


def test_sound_run_is_correct(smoke):
    res = run(smoke)
    assert res["correct"] is True, res["check"]
    assert list(res)[-1] == "check"
    assert set(res["metrics"]) == {"ttft_p50_ms", "itl_p95_ms", "setup_s"}
    assert "out_tok_s" not in res["metrics"]
    assert res["attempted"] > 10 and res["failed"] == 0
    assert res["device"]["count"] == 1
    chk = res["check"]
    assert chk["max_served_gap"]["value"] <= chk["max_served_gap"]["limit"]
    assert chk["served_tokens"]["value"] >= 160


def test_offline_window_opens_after_its_warm_in(smoke_offline):
    res = run(smoke_offline, cell="smoke-offline")
    assert res["correct"] is True, res["check"]
    assert set(res["metrics"]) == {"out_tok_s", "setup_s"}
    assert res["metrics"]["out_tok_s"]["value"] > 0
    assert res["attempted"] == conftest.SMOKE_OFFLINE["requests"]


def _wrap_decode(fault):
    def hook(engine):
        inner = engine._decode_fn

        def decode(params, state, tokens, active):
            return fault(inner, params, state, tokens, active)

        engine._decode_fn = decode
    return hook


def _token_altered(inner, params, state, tokens, active):
    logits, new = inner(params, state, tokens, active)
    return logits.at[:, :, 7].add(1e3), new


def _state_unchanged(inner, params, state, tokens, active):
    lengths = jnp.array(state.lengths)     # the step donates `state`
    logits, new = inner(params, state, tokens, active)
    return logits, new._replace(lengths=lengths)


def _half_batch(inner, params, state, tokens, active):
    half = jnp.arange(active.shape[0]) < active.shape[0] // 2
    return inner(params, state, tokens, jnp.asarray(active) & half)


@pytest.mark.parametrize("fault", [_token_altered, _state_unchanged,
                                   _half_batch],
                         ids=["token_altered", "state_unchanged",
                              "half_batch_left_out"])
def test_broken_timed_path_is_not_correct(smoke, fault):
    res = run(smoke, hook=_wrap_decode(fault))
    assert res["correct"] is False, res["check"]


@pytest.mark.parametrize("control", [("--control", "int8")],
                         ids=["reference_in_int8"])
def test_int8_control_is_not_correct(smoke, control):
    """The control reads a wider gap than the limit: the reference
    computed in int8 at the served positions, as run on the chip."""
    res = run(smoke, *control)
    assert res["correct"] is False, res["check"]


def _run_script(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmarks", "chip", "run.py"),
         "--workload", "nemo-chat-poisson", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_no_chip_exits_nonzero_without_a_result():
    p = _run_script(conftest.REPO)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "no TPU" in p.stderr


def test_bare_checkout_exits_nonzero(smoke, tmp_path):
    root, _ = smoke
    import shutil
    shutil.copytree(os.path.join(root, "benchmarks"),
                    os.path.join(tmp_path, "benchmarks"))
    shutil.copy(os.path.join(root, "BENCHMARK.json"), tmp_path)
    p = _run_script(str(tmp_path))
    assert p.returncode != 0 and p.stdout == ""


@pytest.fixture(scope="module")
def smoke_mean(tmp_path_factory):
    """The smoke cell with a configuration that compares the mean gap in
    place of the widest, as a configuration may name either."""
    config = dict(conftest.SMOKE_CONFIG,
                  check={"mean_served_gap": 2e-4, "tokens": 160})
    return conftest.make_checkout(tmp_path_factory.mktemp("mean"), config)


@pytest.mark.parametrize("control, correct", [((), True),
                                              (("--control", "int8"), False)],
                         ids=["sound", "reference_in_int8"])
def test_mean_gap_is_compared_where_configured(smoke_mean, control, correct):
    res = run(smoke_mean, *control)
    assert res["correct"] is correct, res["check"]
    assert "mean_served_gap" in res["check"]
    assert "max_served_gap" not in res["check"]
