"""Autotuner + kernel registry: enumeration legality, ranking determinism,
cache round-trips, and tuned_gemm correctness/performance."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.dataflow import GemmShape
from repro.core.generator import (
    CASE_STUDY,
    MXU_LANES,
    MXU_SUBLANES,
    TpuGemmSpec,
    VMEM_BUDGET_BYTES,
)
from repro.core.workloads import bert_base, resnet18, vit_b_16
from repro.kernels import ops, ref
from repro.kernels.flash_decode import FlashDecodeSpec
from repro.kernels.registry import make_kernel, register_kernel, registered_kernels
from repro import tuning


def _tuner(tmp_path, **kw):
    cache = tuning.TuneCache(path=str(tmp_path / "tunecache.json"))
    return tuning.Autotuner(cache=cache, **kw)


# Three real workload shapes (core/workloads.py): the largest-MAC GeMM of
# ViT-B-16 (FFN up), BERT-base (FFN up at seq 512) and ResNet18 (a mid conv).
WORKLOAD_SHAPES = [
    GemmShape(197, 768, 3072),
    GemmShape(512, 768, 3072),
    GemmShape(784, 1152, 128),
]


def test_workload_shapes_come_from_extraction():
    """The shapes above really occur in the im2col extraction lists."""
    extracted = {g for fn in (vit_b_16, bert_base, resnet18) for g, _ in fn()}
    for g in WORKLOAD_SHAPES:
        assert g in extracted, g


# -- candidate enumeration ---------------------------------------------------


@pytest.mark.parametrize("mkn", [(197, 768, 3072), (64, 64, 64), (4096, 4096, 4096)])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int8"])
def test_candidates_legal(mkn, dtype):
    g = GemmShape(*mkn)
    cands = tuning.enumerate_tiles(g, dtype)
    assert cands, "candidate set must be non-empty"
    bits = tuning.dtype_bits(dtype)
    for s in cands:
        assert s.tm % MXU_SUBLANES == 0
        assert s.tk % MXU_LANES == 0 and s.tn % MXU_LANES == 0
        assert s.vmem_bytes(bits) <= VMEM_BUDGET_BYTES
        assert s.int8 == (dtype == "int8")
    # no duplicates
    keys = [(s.tm, s.tk, s.tn) for s in cands]
    assert len(keys) == len(set(keys))


def test_candidates_include_default_and_respect_cap():
    g = GemmShape(197, 768, 3072)
    default = CASE_STUDY.tpu_kernel_spec(g)
    for cap in (None, 4):
        cands = tuning.enumerate_tiles(g, "int8", max_candidates=cap)
        assert (default.tm, default.tk, default.tn) in {
            (s.tm, s.tk, s.tn) for s in cands
        }
        if cap is not None:
            assert len(cands) <= cap


def test_candidates_never_exceed_padded_problem():
    g = GemmShape(8, 128, 128)
    for s in tuning.enumerate_tiles(g, "float32"):
        assert s.tm <= 8 and s.tk <= 128 and s.tn <= 128


# -- analytic model + ranking ------------------------------------------------


def test_predict_is_positive_and_padding_aware():
    g = GemmShape(197, 768, 768)
    small = TpuGemmSpec(tm=200, tk=128, tn=128)
    oversized = TpuGemmSpec(tm=512, tk=128, tn=128)  # pads M 197 -> 512
    p_small = tuning.predict(small, g, "bfloat16")
    p_big = tuning.predict(oversized, g, "bfloat16")
    assert p_small.clocks > 0 and 0 < p_small.utilization <= 1
    assert p_big.clocks > p_small.clocks  # padded passes cost real clocks


def test_analytic_ranking_deterministic(tmp_path):
    g = GemmShape(512, 768, 3072)
    results = [
        _tuner(tmp_path / str(i), persist=False).tune(g, "bfloat16")
        for i in range(3)
    ]
    assert len({r.spec for r in results}) == 1
    assert len({r.score for r in results}) == 1


@pytest.mark.parametrize("shape", WORKLOAD_SHAPES)
def test_tuned_beats_or_matches_default(shape, tmp_path):
    """Acceptance: model-predicted throughput of the tuned tile >= default's."""
    tuner = _tuner(tmp_path, persist=False)
    for dtype in ("int8", "bfloat16"):
        res = tuner.tune(shape, dtype)
        default = CASE_STUDY.tpu_kernel_spec(shape)
        tuned_clk = tuning.predict_clocks(res.spec, shape, dtype)
        default_clk = tuning.predict_clocks(default, shape, dtype)
        assert tuned_clk <= default_clk, (res.spec, default)


# -- cache -------------------------------------------------------------------


def test_cache_json_roundtrip(tmp_path):
    path = str(tmp_path / "tc.json")
    cache = tuning.TuneCache(path=path)
    spec = TpuGemmSpec(tm=256, tk=128, tn=512, depth=3, int8=False)
    key = tuning.cache_key(GemmShape(512, 768, 3072), "bfloat16", "pallas")
    cache.put(key, tuning.CacheEntry(spec=spec, score=123.5, source="analytic"))

    raw = json.load(open(path))  # human-readable on disk (EXPERIMENTS.md dumps)
    assert raw[key]["tm"] == 256 and raw[key]["source"] == "analytic"

    fresh = tuning.TuneCache(path=path)
    hit = fresh.get(key)
    assert hit is not None and hit.spec == spec and hit.score == 123.5


def test_cache_hit_path(tmp_path):
    """Second tune of the same problem resolves from cache, not a re-search."""
    tuner = _tuner(tmp_path)
    g = WORKLOAD_SHAPES[0]
    first = tuner.tune(g, "int8")
    assert not first.from_cache
    again = tuner.tune(g, "int8")
    assert again.from_cache and again.spec == first.spec
    assert tuner.cache.hits >= 1

    # ...including across processes (a fresh cache object on the same file)
    tuner2 = tuning.Autotuner(cache=tuning.TuneCache(path=tuner.cache.path))
    cold = tuner2.tune(g, "int8")
    assert cold.from_cache and cold.spec == first.spec


def test_cache_lru_eviction_keeps_disk(tmp_path):
    cache = tuning.TuneCache(path=str(tmp_path / "tc.json"), lru_size=2)
    spec = TpuGemmSpec(tm=128, tk=128, tn=128)
    keys = [f"k{i}" for i in range(4)]
    for k in keys:
        cache.put(k, tuning.CacheEntry(spec=spec, score=1.0, source="analytic"))
    assert len(cache._lru) == 2          # LRU bounded
    assert len(cache) == 4               # disk registry keeps everything
    assert cache.get(keys[0]) is not None  # evicted entries refill from disk


def test_wallclock_mode_does_not_reuse_analytic_winners(tmp_path):
    """Mode is part of the cache key: --tune-mode wallclock after an
    analytic run must re-search, not resolve the analytic entry."""
    path = str(tmp_path / "tc.json")
    g = GemmShape(64, 128, 128)
    analytic = tuning.Autotuner(cache=tuning.TuneCache(path=path))
    assert not analytic.tune(g, "float32").from_cache
    wallclock = tuning.Autotuner(
        cache=tuning.TuneCache(path=path), mode="wallclock",
        max_candidates=2, wallclock_iters=1,
    )
    res = wallclock.tune(g, "float32", backend="interpret")
    assert not res.from_cache
    # ...and each mode hits its own entry on the second query
    assert analytic.tune(g, "float32").from_cache
    assert wallclock.tune(g, "float32", backend="interpret").from_cache


def test_wallclock_does_not_trust_analytic_fallback(tmp_path):
    """An analytic *fallback* stored under the wallclock key (host couldn't
    measure) must not satisfy a later wallclock tune on a capable host."""
    path = str(tmp_path / "tc.json")
    g = GemmShape(64, 128, 128)
    kw = dict(mode="wallclock", max_candidates=2, wallclock_iters=1)
    # "pallas" is unmeasurable on a CPU host -> analytic fallback persisted
    fallback = tuning.Autotuner(cache=tuning.TuneCache(path=path), **kw)
    first = fallback.tune(g, "float32", backend="pallas")
    assert first.source == "analytic"
    # "interpret" shares the pallas tuning key but IS measurable -> re-search
    capable = tuning.Autotuner(cache=tuning.TuneCache(path=path), **kw)
    second = capable.tune(g, "float32", backend="interpret")
    assert not second.from_cache and second.source == "wallclock"
    # measured winner now satisfies the next query
    assert capable.tune(g, "float32", backend="interpret").from_cache


def test_search_space_params_separate_cache_keys(tmp_path):
    """Explicit depth sweeps / candidate caps don't alias the default key."""
    tuner = _tuner(tmp_path)
    g = GemmShape(64, 128, 128)
    tuner.tune(g, "float32", backend="pipelined")               # default sweep
    res = tuner.tune(g, "float32", backend="pipelined", depth=8)
    assert not res.from_cache and res.spec.depth == 8
    capped = tuning.Autotuner(cache=tuner.cache, max_candidates=2)
    assert not capped.tune(g, "float32").from_cache


def test_env_truthy_disables_on_zero():
    from repro.tuning.autotuner import env_truthy

    assert not env_truthy("0") and not env_truthy("false") and not env_truthy("")
    assert not env_truthy(None) and not env_truthy("off")
    assert env_truthy("1") and env_truthy("true") and env_truthy("yes")


def test_memory_only_cache_never_touches_disk(tmp_path):
    path = tmp_path / "never-created.json"
    cache = tuning.TuneCache(path=str(path), persistent=False)
    spec = TpuGemmSpec(tm=128, tk=128, tn=128)
    cache.put("k", tuning.CacheEntry(spec=spec, score=1.0, source="analytic"))
    cache.save()
    assert not path.exists()
    assert cache.get("k") is not None  # still served from memory


def test_corrupt_cache_file_is_ignored(tmp_path):
    path = tmp_path / "tc.json"
    path.write_text("{not json")
    cache = tuning.TuneCache(path=str(path))
    assert len(cache) == 0 and cache.get("anything") is None


# -- tuned_gemm end to end ---------------------------------------------------


@pytest.mark.parametrize("mkn", [(64, 128, 128), (100, 200, 150), (129, 256, 130)])
@pytest.mark.parametrize("dtype", ["float32", "int8"])
def test_tuned_gemm_matches_oracle(mkn, dtype, tmp_path):
    tuner = _tuner(tmp_path)
    key = jax.random.PRNGKey(0)
    k1, k2 = jax.random.split(key)
    m, k, n = mkn
    if dtype == "int8":
        a = jax.random.randint(k1, (m, k), -127, 128, jnp.int8)
        b = jax.random.randint(k2, (k, n), -127, 128, jnp.int8)
    else:
        a = jax.random.normal(k1, (m, k), jnp.float32)
        b = jax.random.normal(k2, (k, n), jnp.float32)
    out = tuning.tuned_gemm(a, b, backend="interpret", tuner=tuner)
    expect = ref.gemm_ref(a, b)
    if dtype == "int8":
        np.testing.assert_array_equal(out, expect)
    else:
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=1e-5, atol=1e-4)


def test_wallclock_mode_interpret(tmp_path):
    """Empirical ranking path: times real kernels (interpret on CPU)."""
    tuner = _tuner(tmp_path, mode="wallclock", max_candidates=2,
                   wallclock_iters=1, persist=False)
    res = tuner.tune(GemmShape(64, 128, 128), "float32", backend="interpret")
    assert res.source == "wallclock"
    a = jnp.ones((64, 128), jnp.float32)
    b = jnp.ones((128, 128), jnp.float32)
    out = ops.gemm(a, b, spec=res.spec, backend="interpret")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref.gemm_ref(a, b)),
                               rtol=1e-6)


# mistral-nemo-12b's decode attention as the chip benchmark serves it: 32
# slots, 8 kv heads of 4 query heads, D=128, 16-token blocks, 256 columns.
NEMO_DECODE = tuning.DecodeShape(slots=32, kv_heads=8, groups=4,
                                 head_dim=128, sq=1, block_size=16,
                                 max_blocks=256)


@pytest.mark.parametrize("shape,steps", [
    (NEMO_DECODE, 32),                                     # 8 blocks a step
    (NEMO_DECODE._replace(block_size=128, max_blocks=32), 32),  # 1 a step
    (NEMO_DECODE._replace(max_blocks=4), 1),               # one 64-key step
])
def test_decode_specs_split_the_kernel_steps(shape, steps):
    """Split candidates are powers of two up to the kernel's step count
    (128 keys a step) and 16, crossed with every fallback chunk width;
    on one TensorCore every split only adds a program and a partial, so
    the cost rises with the split factor at any chunk width."""
    specs = tuning.enumerate_decode_specs(shape)
    splits = sorted({s.num_splits for s in specs})
    assert splits == [2 ** i for i in range(min(steps, 16).bit_length())]
    for cols in {s.cols_per_iter for s in specs}:
        costs = [tuning.predict_decode_cost(
            FlashDecodeSpec(num_splits=n, cols_per_iter=cols), shape)
            for n in splits]
        assert costs == sorted(costs) and len(set(costs)) == len(costs)


def test_decode_cost_prices_the_full_table_in_128_key_steps():
    """The kernel term is slots x steps of 128 keys plus a per-step cost,
    with each split's program and combine on top: doubling the table adds
    exactly the steps' cost, whatever the split factor."""
    spec = FlashDecodeSpec(num_splits=4, cols_per_iter=8)
    half = NEMO_DECODE._replace(max_blocks=128)
    step = 128 * 8 * 128 * 2 + 1000.0          # keys x rows x D x 2 + fixed
    # the fallback term of 8-column chunks: iterations x 4000
    fallback = (256 - 128) // 8 * 4000.0
    grown = (tuning.predict_decode_cost(spec, NEMO_DECODE)
             - tuning.predict_decode_cost(spec, half))
    assert grown == 32 * 16 * step + fallback


def test_decode_tuner_binds_one_split_at_nemo_shape(tmp_path):
    """The analytic winner at Nemo's decode shape: one split (the v5e has
    one TensorCore, so splits buy nothing) and 8-column fallback chunks."""
    r = tuning.tune_decode(NEMO_DECODE, "bfloat16",
                           tuner=_tuner(tmp_path, persist=False))
    assert (r.spec.num_splits, r.spec.cols_per_iter) == (1, 8)


@pytest.mark.parametrize("kind", ["gemm", "decode"])
def test_wallclock_raises_when_every_candidate_fails(kind, tmp_path,
                                                     monkeypatch):
    """A wallclock search in which no candidate runs raises and names the
    candidates: it never hands back an untested analytic winner."""
    from repro.tuning import decode as tdecode

    def refuse(*args, **kwargs):
        raise ValueError("refused by the compiler")

    tuner = _tuner(tmp_path, mode="wallclock", max_candidates=2,
                   wallclock_iters=1, persist=False)
    if kind == "gemm":
        monkeypatch.setattr(tuning.Autotuner, "_time_spec", refuse)
        call = lambda: tuner.tune(GemmShape(64, 128, 128), "float32",
                                  backend="interpret")
        named = "tile=("
    else:
        monkeypatch.setattr(tdecode, "_time_candidate", refuse)
        shape = tuning.DecodeShape(slots=2, kv_heads=1, groups=2, head_dim=16,
                                   sq=1, block_size=4, max_blocks=4)
        call = lambda: tuning.tune_decode(shape, "float32", mode="wallclock",
                                          tuner=tuner)
        named = "splits=1 cols=1"
    with pytest.raises(RuntimeError, match="candidates failed") as err:
        call()
    assert named in str(err.value) and "refused by the compiler" in str(err.value)
    assert len(tuner.cache) == 0          # nothing cached for the failed search


def test_ops_dispatch_through_enabled_tuner(tmp_path):
    """tuning.enable() routes spec-less ops.gemm calls through the tuner."""
    tuner = _tuner(tmp_path)
    old = tuning.get_tuner()
    tuning.set_tuner(tuner)
    tuning.enable()
    try:
        a = jnp.ones((64, 128), jnp.float32)
        b = jnp.ones((128, 128), jnp.float32)
        out = ops.gemm(a, b, backend="interpret")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref.gemm_ref(a, b)),
                                   rtol=1e-6)
        assert len(tuner.cache) >= 1  # the dispatch populated this cache

        # An explicitly passed non-default config is designer intent: it
        # bypasses the tuner and uses its own tpu_kernel_spec mapping.
        import dataclasses

        before = len(tuner.cache)
        custom = dataclasses.replace(CASE_STUDY, D_stream=4)
        a2 = jnp.ones((8, 128), jnp.float32)
        out2 = ops.gemm(a2, b, config=custom, backend="interpret")
        np.testing.assert_allclose(np.asarray(out2), np.asarray(ref.gemm_ref(a2, b)),
                                   rtol=1e-6)
        assert len(tuner.cache) == before
    finally:
        tuning.disable()
        tuning.set_tuner(old)


# -- kernel registry ---------------------------------------------------------


def test_registry_builtins():
    assert {"pallas", "pipelined", "dequant"} <= set(registered_kernels())


def test_registry_rejects_duplicates_and_unknown():
    with pytest.raises(ValueError):
        register_kernel("pallas", lambda spec, interpret=False: None)
    with pytest.raises(KeyError):
        make_kernel("no-such-kernel", TpuGemmSpec(tm=128, tk=128, tn=128))


def test_registry_memoizes_specializations():
    spec = TpuGemmSpec(tm=128, tk=128, tn=128)
    assert make_kernel("pallas", spec, interpret=True) is make_kernel(
        "pallas", spec, interpret=True
    )


def test_registered_kernel_is_dispatchable(tmp_path):
    """A newly registered variant is reachable by name, like the built-ins."""
    calls = []

    def factory(spec, *, interpret=False):
        def fn(a, b):
            calls.append(spec)
            return ref.gemm_ref(a, b)

        return fn

    register_kernel("test-variant", factory)
    try:
        fn = make_kernel("test-variant", TpuGemmSpec(tm=128, tk=128, tn=128))
        a = jnp.ones((128, 128), jnp.float32)
        fn(a, a)
        assert calls
    finally:
        from repro.kernels import registry as _registry

        _registry._REGISTRY.pop("test-variant", None)
        _registry._make_cached.cache_clear()
