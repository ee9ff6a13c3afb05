"""Paged flash-decode tests: kernel-vs-oracle equivalence (interpret mode),
split-K identity, the bounded fallback, int8 KV residency fidelity, engine
token identity across decode backends (dense/hybrid/recurrent, speculative
verify included), and decode-spec tuning persistence."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.kernels import flash_decode as fd
from repro.kernels.registry import make_kernel, registered_kernels
from repro.models.attention import decode_attention
from repro.serving import kv_cache as kvc
from repro.serving.engine import Engine

FAMILY_ARCHS = ["gemma3-1b", "jamba-1.5-large-398b", "xlstm-1.3b"]


@pytest.fixture(autouse=True)
def _reset_decode_globals():
    """The backend/spec hooks are process-wide trace-time state; never let
    one test's binding leak into the next."""
    yield
    fd.set_decode_backend(None)
    fd.set_decode_spec(None)


# ---------------------------------------------------------------------------
# kernel-level equivalence (interpret mode on CPU)
# ---------------------------------------------------------------------------

B, BS, MAX_BLOCKS, HKV, GROUPS, D = 3, 4, 6, 2, 2, 16
LENGTHS = np.array([5, 12, MAX_BLOCKS * BS], np.int32)   # ragged, one at cap


def _make_pool(seed=0, kv_precision="float"):
    """A lived-in pool: ragged per-slot lengths, every live position written
    through ``write_kv`` (so int8 pools quantize exactly as serving does)."""
    rng = np.random.default_rng(seed)
    num_blocks = 1 + B * MAX_BLOCKS
    cache = kvc.init_paged_kv(num_blocks, BS, HKV, D, jnp.float32,
                              kv_precision=kv_precision)
    alloc = kvc.BlockAllocator(num_blocks, BS)
    tables = kvc.BlockTables(B, MAX_BLOCKS)
    for s in range(B):
        tables.ensure(s, int(LENGTHS[s]), alloc)
    bt = tables.array()
    L = int(LENGTHS.max())
    k_new = jnp.asarray(rng.normal(size=(B, L, HKV, D)), jnp.float32)
    v_new = jnp.asarray(rng.normal(size=(B, L, HKV, D)), jnp.float32)
    cache = kvc.write_kv(cache, bt, k_new, v_new, 0)
    return cache, bt


def _query(sq, seed=1):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(B, sq, HKV * GROUPS, D)), jnp.float32)
    idx = jnp.asarray(LENGTHS - sq, jnp.int32)   # first query position
    return q, idx


def _oracle(q, cache, bt, idx, window=None):
    k, v = kvc.gather_kv(cache, bt)
    return decode_attention(q, k, v, index=idx, window=window)


# Rows of the live-step walk: keys visible to each row's last query, the
# empty row (null table) and a released row (null table, stale length).
WALK_KEYS = [0, 1, 127, 128, 129, None]      # None: the full table
WALK_TOKENS, WALK_STALE = 768, 300


def _walk_pool(bs, sq, kv_precision):
    """Seven rows over a 768-token table: ragged lengths around the
    kernel's 128-token step, a full row, an empty row and a released row
    whose length is stale.  Returns the pool, table, query, first query
    positions and the rows that hold keys."""
    max_blocks = WALK_TOKENS // bs
    keys = [WALK_TOKENS if n is None else n and max(n, sq) for n in WALK_KEYS]
    B = len(keys) + 1
    rng = np.random.default_rng(7)
    num_blocks = 1 + B * max_blocks
    cache = kvc.init_paged_kv(num_blocks, bs, HKV, D, jnp.float32,
                              kv_precision=kv_precision)
    alloc = kvc.BlockAllocator(num_blocks, bs)
    tables = kvc.BlockTables(B, max_blocks)
    for r, n in enumerate(keys):
        tables.ensure(r, n, alloc)
    tables.ensure(B - 1, WALK_STALE, alloc)
    k_new = jnp.asarray(rng.normal(size=(B, WALK_TOKENS, HKV, D)),
                        jnp.float32)
    v_new = jnp.asarray(rng.normal(size=(B, WALK_TOKENS, HKV, D)),
                        jnp.float32)
    cache = kvc.write_kv(cache, tables.array(), k_new, v_new, 0)
    tables.release(B - 1, alloc)
    q = jnp.asarray(rng.normal(size=(B, sq, HKV * GROUPS, D)), jnp.float32)
    idx = np.array([max(n - sq, 0) for n in keys] + [WALK_STALE], np.int32)
    live = [r for r, n in enumerate(keys) if n]
    return cache, tables.array(), q, jnp.asarray(idx), live


@pytest.mark.parametrize("bs,sq,window,kv_precision,splits", [
    (BS, 1, None, "float", 1),      # plain decode, 32 blocks a step
    (BS, 1, None, "float", 4),      # split-K (6 steps over 4 splits)
    (BS, 3, None, "float", 2),      # Sq > 1 (speculative verify width)
    (BS, 1, 6, "float", 1),         # sliding window
    (BS, 3, 6, "float", 4),         # everything at once
    (16, 1, None, "float", 1),      # 8 blocks per step
    (16, 1, None, "float", 4),      # split over the 6 steps
    (16, 64, None, "float", 1),     # a 64-query prefill chunk
    (16, 1, 512, "float", 2),       # window 512
    (16, 1, None, "int8", 1),       # int8 pool with scales
    (128, 1, None, "float", 1),     # one 128-token block per step
    (128, 64, 512, "int8", 3),      # everything at once
])
def test_flash_kernel_matches_oracle(bs, sq, window, kv_precision, splits):
    """The Pallas kernel (interpret mode) reproduces gather_kv +
    decode_attention on every row with keys: GQA packing, windows, Sq > 1,
    split-K, int8 scales, and lengths on each side of a 128-key step and
    at the full table.  The empty row and the released row (null table,
    stale length) read alike, as rows with no key."""
    cache, bt, q, idx, live = _walk_pool(bs, sq, kv_precision)
    got = np.asarray(fd.flash_decode_attention(
        q, cache, bt, idx, window=window,
        spec=fd.FlashDecodeSpec(num_splits=splits), interpret=True))
    want = np.asarray(_oracle(q, cache, bt, idx, window=window))
    np.testing.assert_allclose(got[live], want[live], rtol=1e-5, atol=1e-5)
    empty, released = got[0], got[-1]
    np.testing.assert_array_equal(released, empty)
    np.testing.assert_array_equal(empty, np.zeros_like(empty))


@pytest.mark.parametrize("bs,max_blocks,steps", [
    (16, 48, 8), (16, 4, 4), (16, 12, 4), (128, 6, 1), (48, 16, 1),
    (4, 6, 2),
])
def test_live_steps_counts_the_walk(bs, max_blocks, steps):
    """The step count the kernel walks and the engine's kv_blocks counter
    reads: blocks per step cover 128 keys and divide the table; a step is
    live while it starts at or before the last query and its first entry
    is not the null block; numpy and jax agree."""
    assert fd.blocks_per_step(bs, max_blocks) == steps
    tokens = steps * bs
    n_steps = max_blocks // steps
    rows = np.arange(1, 1 + 4 * max_blocks, dtype=np.int32).reshape(
        4, max_blocks)
    rows[1, :] = kvc.NULL_BLOCK                  # released
    rows[2, steps:] = kvc.NULL_BLOCK             # table ends after step 0
    idx = np.array([0, 5 * tokens, 3 * tokens, tokens * n_steps - 1],
                   np.int32)
    want = np.array([1, 0, 1, n_steps])
    np.testing.assert_array_equal(fd.live_steps(idx, 1, rows, bs), want)
    np.testing.assert_array_equal(
        np.asarray(fd.live_steps(jnp.asarray(idx), 1, jnp.asarray(rows), bs)),
        want)
    # a query block reaching into the next step makes it live
    assert fd.live_steps(np.array([tokens - 1], np.int32), 2,
                         rows[3:], bs)[0] == min(2, n_steps)


@pytest.mark.parametrize("cols", [1, 3, 8])
def test_blocked_fallback_matches_oracle(cols):
    """The bounded while_loop fallback matches the oracle at every chunk
    width, including a chunk larger than the table (clamped)."""
    cache, bt = _make_pool()
    for sq, window in [(1, None), (3, None), (1, 6)]:
        q, idx = _query(sq)
        want = _oracle(q, cache, bt, idx, window=window)
        got = fd.ref_paged_decode(q, cache, bt, idx, window=window,
                                  cols_per_iter=cols)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


def test_split_k_identity():
    """Split-K is a pure reassociation: any split factor produces the same
    output as the unsplit walk (combine stage included)."""
    cache, bt = _make_pool()
    q, idx = _query(1)
    base = fd.flash_decode_attention(
        q, cache, bt, idx, spec=fd.FlashDecodeSpec(num_splits=1),
        interpret=True)
    for splits in (2, 3, 6, 17):   # 17 > max_blocks: clamps to 6
        split = fd.flash_decode_attention(
            q, cache, bt, idx, spec=fd.FlashDecodeSpec(num_splits=splits),
            interpret=True)
        np.testing.assert_allclose(np.asarray(split), np.asarray(base),
                                   rtol=1e-6, atol=1e-6)


def test_int8_pool_kernel_and_fallback():
    """int8 residency: the in-kernel dequant reproduces the gather path's
    dequantized view tightly, stays within the w8a8 fidelity bar of the
    float pool, and actually shrinks the pool bytes."""
    cache_f, bt = _make_pool(kv_precision="float")
    cache_q, _ = _make_pool(kv_precision="int8")
    assert cache_q.quantized and not cache_f.quantized
    assert kvc.pool_bytes(cache_q) < kvc.pool_bytes(cache_f)
    for sq in (1, 3):
        q, idx = _query(sq)
        # vs the int8 gather oracle (same dequantized values): tight
        want_q = _oracle(q, cache_q, bt, idx)
        for got in (
            fd.flash_decode_attention(q, cache_q, bt, idx, interpret=True),
            fd.ref_paged_decode(q, cache_q, bt, idx, cols_per_iter=2),
        ):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want_q),
                                       rtol=1e-5, atol=1e-5)
        # vs the float pool: the quantization error bar (test_quant's bar)
        want_f = np.asarray(_oracle(q, cache_f, bt, idx))
        got = np.asarray(
            fd.flash_decode_attention(q, cache_q, bt, idx, interpret=True))
        rel = np.linalg.norm(got - want_f) / max(np.linalg.norm(want_f), 1e-9)
        assert rel < 0.15, rel


def test_registry_and_dispatcher():
    """"flash_decode" resolves through the kernel registry, and the
    dispatcher's backends all agree (interpret vs blocked vs gather)."""
    assert "flash_decode" in registered_kernels()
    cache, bt = _make_pool()
    q, idx = _query(1)
    fn = make_kernel("flash_decode", fd.FlashDecodeSpec(num_splits=2),
                     interpret=True)
    want = np.asarray(_oracle(q, cache, bt, idx))
    np.testing.assert_allclose(np.asarray(fn(q, cache, bt, idx)), want,
                               rtol=1e-5, atol=1e-5)
    for backend in ("gather", "blocked", "interpret"):
        got = fd.paged_decode_attention(q, cache, bt, idx, backend=backend)
        np.testing.assert_allclose(np.asarray(got), want,
                                   rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError):
        fd.set_decode_backend("nope")


# ---------------------------------------------------------------------------
# engine-level: token identity across decode backends
# ---------------------------------------------------------------------------

def _serve(cfg, backend, *, kv_precision="float", speculative=False):
    """Warm + serve a small deterministic workload with the decode backend
    bound at trace time (exactly how the engine binds it in production)."""
    eng = Engine(cfg, slots=2, max_seq=64, block_size=8, max_chunk=16,
                 kv_precision=kv_precision, speculative=speculative, seed=0)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
               for n in (7, 19, 12)]
    with fd.decode_backend(backend):
        eng.warmup()
        for p in prompts:
            eng.submit(p, max_new=6)
        results = eng.run()
    return {rid: out.tolist() for rid, out in results.items()}, eng


@pytest.mark.parametrize("arch", FAMILY_ARCHS)
def test_engine_backend_token_identity(arch):
    """The bounded fallback serves token-identical streams to the legacy
    gather path across the dense, hybrid, and recurrent families — refills,
    chunked prefill, and ragged lengths included."""
    cfg = configs.get_smoke(arch)
    gather, _ = _serve(cfg, "gather")
    blocked, _ = _serve(cfg, "blocked")
    assert gather == blocked


def test_engine_speculative_token_identity():
    """Batched verification (Sq > 1 through the paged kernel path) stays
    token-identical to the gather baseline."""
    cfg = configs.get_smoke("gemma3-1b")
    gather, eg = _serve(cfg, "gather", speculative=2)
    blocked, eb = _serve(cfg, "blocked", speculative=2)
    assert gather == blocked
    # Same schedule => same speculative behavior, not just same tokens.
    assert eg.metrics.spec_accepted_tokens == eb.metrics.spec_accepted_tokens


def test_engine_int8_kv_serves_and_accounts():
    """An int8-KV engine serves every request to completion, and the metrics
    report the (smaller) pool honestly."""
    cfg = configs.get_smoke("gemma3-1b")
    toks_f, ef = _serve(cfg, "blocked", kv_precision="float")
    toks_q, eq = _serve(cfg, "blocked", kv_precision="int8")
    assert set(toks_q) == set(toks_f)
    assert all(len(v) == 6 for v in toks_q.values())
    assert eq.metrics.kv_precision == "int8"
    assert 0 < eq.metrics.kv_pool_bytes < ef.metrics.kv_pool_bytes
    assert eq.metrics.kv_slot_capacity == ef.metrics.kv_slot_capacity == 2
    s = eq.metrics.summary()
    assert "kv_pool=" in s and "int8" in s and "slots@max_seq=2" in s


# ---------------------------------------------------------------------------
# tuning: decode winners persist next to GeMM tiles
# ---------------------------------------------------------------------------

def test_decode_tuning_cache_roundtrip(tmp_path):
    """tune_decode caches its winner under a "kind"-discriminated entry that
    survives a disk round trip, and a second query is a cache hit."""
    from repro import tuning

    path = str(tmp_path / "tunecache.json")
    shape = tuning.DecodeShape(slots=2, kv_heads=2, groups=2, head_dim=16,
                               sq=1, block_size=4, max_blocks=8)
    t1 = tuning.Autotuner(cache=tuning.TuneCache(path))
    r1 = tuning.tune_decode(shape, "float32", tuner=t1)
    assert not r1.from_cache and r1.candidates > 1
    assert tuning.tune_decode(shape, "float32", tuner=t1).from_cache
    # fresh process: the winner comes back from disk with the same spec
    t2 = tuning.Autotuner(cache=tuning.TuneCache(path))
    r2 = tuning.tune_decode(shape, "float32", tuner=t2)
    assert r2.from_cache and r2.spec == r1.spec
    raw = t2.cache.dump()
    key = tuning.decode_cache_key(shape, "float32")
    assert raw[key]["kind"] == "flash_decode"
    # GeMM entries (no "kind") still decode alongside
    entry = tuning.CacheEntry.from_json(
        {"tm": 8, "tk": 128, "tn": 128, "score": 1.0, "source": "analytic"})
    assert entry.spec.tm == 8


def test_engine_warmup_binds_tuned_spec(tmp_path, monkeypatch):
    """Engine(autotune=True) tunes the decode shape during warmup and binds
    the winner through set_decode_spec before tracing (attention archs
    only — a pure-recurrent stack binds nothing)."""
    from repro import tuning

    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "tc.json"))
    assert fd.get_decode_spec() is None
    cfg = configs.get_smoke("gemma3-1b")
    eng = Engine(cfg, slots=2, max_seq=32, block_size=8, max_chunk=8,
                 autotune=True, seed=0)
    eng.warmup()
    spec = fd.get_decode_spec()
    assert isinstance(spec, fd.FlashDecodeSpec)
    key = tuning.decode_cache_key(
        tuning.serving_decode_shape(cfg, slots=2, block_size=8,
                                    max_blocks=eng.max_blocks_per_slot),
        cfg.dtype)
    assert tuning.get_tuner().cache.get(key).spec == spec
    tuning.disable()
    tuning.set_tuner(None)
