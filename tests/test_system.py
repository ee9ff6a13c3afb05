"""End-to-end system tests: training convergence, serving, int8 mode,
HLO cost analyzer, and data/training determinism."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest


@pytest.fixture(autouse=True)
def _own_compile_cache(tmp_path, monkeypatch):
    """The launchers' mains turn on JAX's persistent compile cache: keep it
    in this test's tmp dir, and restore the process setting after."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "xla"))
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    compilation_cache.reset_cache()


def test_compile_cache_dir_honours_env_else_fixed(tmp_path, monkeypatch):
    """$JAX_COMPILATION_CACHE_DIR wins; without it the cache goes to the
    same fixed .jax_cache/ at the repo root on every call."""
    from repro.launch.compile_cache import enable_compile_cache

    assert enable_compile_cache() == str(tmp_path / "xla")
    assert jax.config.jax_compilation_cache_dir == str(tmp_path / "xla")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    first, second = enable_compile_cache(), enable_compile_cache()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert first == second == os.path.join(root, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first


def test_training_loss_decreases(tmp_path):
    """A tiny LM trained through the full launcher improves its loss."""
    from repro.launch import train as T

    out = T.main([
        "--arch", "gemma3-1b", "--preset", "smoke",
        "--steps", "40", "--batch", "4", "--seq", "32",
        "--ckpt-every", "1000", "--ckpt-dir", str(tmp_path / "ck"),
        "--lr", "3e-3",
    ])
    losses = [m["loss"] for m in out["metrics"]]
    assert losses[-1] < losses[0] - 0.2, losses


def test_serving_generates(tmp_path):
    from repro.launch import serve

    gen = serve.main(["--arch", "qwen3-14b", "--requests", "2",
                      "--prompt-len", "6", "--gen-len", "4"])
    assert gen.shape == (2, 4)
    assert gen.dtype.kind == "i"


def test_serve_cluster_exits_nonzero_on_handle_error(monkeypatch):
    """A cluster run whose requests resolve with errors must not exit 0."""
    from repro.launch import serve
    from repro.serving.engine import Engine

    def broken_submit(self, *args, **kwargs):
        raise RuntimeError("engine refused the request")

    monkeypatch.setattr(Engine, "submit", broken_submit)
    with pytest.raises(SystemExit) as exit_info:
        serve.main(["--arch", "gemma3-1b", "--replicas", "2",
                    "--requests", "2", "--slots", "1",
                    "--prompt-len", "4", "--gen-len", "2"])
    assert exit_info.value.code not in (0, None)
    assert "2 of 2 requests failed" in str(exit_info.value.code)


def test_int8_linear_close_to_f32():
    from repro.kernels import ops

    x = jax.random.normal(jax.random.PRNGKey(0), (16, 64))
    w = jax.random.normal(jax.random.PRNGKey(1), (64, 32)) * 0.1
    y8 = ops.linear(x, w, quant="int8", backend="xla")
    rel = float(jnp.linalg.norm(y8 - x @ w) / jnp.linalg.norm(x @ w))
    assert rel < 0.03


def test_hlo_cost_matches_xla_on_loop_free():
    from repro.launch import hlo_cost

    def f(a, b):
        return jnp.tanh(a @ b).sum()

    comp = jax.jit(f).lower(
        jax.ShapeDtypeStruct((64, 128), jnp.float32),
        jax.ShapeDtypeStruct((128, 32), jnp.float32),
    ).compile()
    ca = comp.cost_analysis()
    ca = ca[0] if isinstance(ca, list) else ca
    mine = hlo_cost.analyze(comp.as_text())
    assert mine.flops == pytest.approx(float(ca["flops"]), rel=0.05)


def test_hlo_cost_scales_scan_trip_count():
    from repro.launch import hlo_cost

    def g(x, w):
        def body(h, _):
            return jnp.tanh(h @ w), None
        return jax.lax.scan(body, x, None, length=7)[0]

    comp = jax.jit(g).lower(
        jax.ShapeDtypeStruct((32, 32), jnp.float32),
        jax.ShapeDtypeStruct((32, 32), jnp.float32),
    ).compile()
    mine = hlo_cost.analyze(comp.as_text())
    assert mine.flops == pytest.approx(7 * 2 * 32 ** 3, rel=0.01)
    assert 7 in mine.trip_counts.values()


def test_hlo_cost_counts_collectives():
    from repro.launch import hlo_cost

    # single-device: no collectives expected
    comp = jax.jit(lambda a: a * 2).lower(
        jax.ShapeDtypeStruct((8,), jnp.float32)).compile()
    mine = hlo_cost.analyze(comp.as_text())
    assert mine.collective_bytes == 0


def test_train_determinism(tmp_path):
    from repro.launch import train as T

    outs = []
    for i in range(2):
        out = T.main([
            "--arch", "bert-base", "--preset", "smoke",
            "--steps", "10", "--batch", "2", "--seq", "16",
            "--ckpt-every", "1000", "--ckpt-dir", str(tmp_path / f"d{i}"),
        ])
        outs.append([m["loss"] for m in out["metrics"]])
    np.testing.assert_allclose(outs[0], outs[1], rtol=1e-6)
