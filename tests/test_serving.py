"""Serving engine tests: paged decode and chunked prefill against the
dense forward, scheduler slot refill under unequal generation lengths,
block-table reuse, and prefill work proportional to real prompt tokens."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import model as M
from repro.serving import kv_cache as kvc
from repro.serving.engine import Engine
from repro.serving.prefill import chunk_buckets, plan_chunks
from repro.serving.request import RequestSpec
from repro.serving.scheduler import Scheduler

# One arch per serving family (all float32 smoke configs -> tight tolerances).
FAMILY_ARCHS = ["gemma3-1b", "jamba-1.5-large-398b", "xlstm-1.3b"]
TOL = dict(rtol=3e-4, atol=3e-4)


def _paged_state_with_tables(cfg, slots, block_size, max_blocks, need_tokens):
    num_blocks = 1 + slots * max_blocks
    state = M.init_paged_decode_state(
        cfg, slots, num_blocks=num_blocks, block_size=block_size,
        max_blocks_per_slot=max_blocks)
    alloc = kvc.BlockAllocator(num_blocks, block_size)
    tables = kvc.BlockTables(slots, max_blocks)
    for s in range(slots):
        tables.ensure(s, need_tokens, alloc)
    return state._replace(block_tables=tables.array())


@pytest.mark.parametrize("arch", FAMILY_ARCHS)
def test_paged_and_chunked_match_dense_decode(arch):
    """Chunked prefill through the paged cache gives the dense forward's
    logits at the prompt's last position, and each paged decode step its
    logits at the next position over the prompt plus the generated tokens
    — for the dense, hybrid, and recurrent families."""
    cfg = configs.get_smoke(arch)
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    slots, prompt_len, gen = 2, 6, 3
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab, size=(slots, prompt_len)).astype(np.int32)

    # paged: chunked prefill per slot (6 tokens = chunks 4 + 2), then decode
    pstate = _paged_state_with_tables(cfg, slots, 4, 8, prompt_len + gen + 1)
    got = []
    for s in range(slots):
        pos, lp = 0, None
        for c in plan_chunks(prompt_len, max_chunk=4):
            lp, pstate = M.prefill_chunk(
                params, cfg, pstate,
                jnp.asarray(prompts[s:s + 1, pos:pos + c]), jnp.int32(s))
            pos += c
        got.append(np.asarray(lp)[0, -1])
    got = [np.stack(got)]
    fed = []
    for _ in range(gen):
        tok = np.argmax(got[-1], -1)[:, None].astype(np.int32)
        fed.append(tok)
        lp, pstate = M.paged_decode_step(params, cfg, pstate, jnp.asarray(tok))
        got.append(np.asarray(lp)[:, -1])
    assert int(pstate.lengths[0]) == prompt_len + gen

    # dense reference: one forward over the prompt and the fed tokens
    seq = np.concatenate([prompts, *fed], axis=1)
    ref = np.asarray(M.forward(params, cfg, {"tokens": jnp.asarray(seq)}))
    for i, g in enumerate(got):
        np.testing.assert_allclose(g, ref[:, prompt_len - 1 + i], **TOL)


def test_paged_decode_per_slot_lengths():
    """Slots at *different* positions decode correctly: a slot refilled later
    matches the same prompt served alone (state isolation across slots)."""
    cfg = configs.get_smoke("gemma3-1b")
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(1)
    p0 = rng.integers(0, cfg.vocab, size=6).astype(np.int32)
    p1 = rng.integers(0, cfg.vocab, size=3).astype(np.int32)

    # Serve p1 alone (slot 0 of a 1-slot state) as the reference.
    ref, ref_state = None, _paged_state_with_tables(cfg, 1, 4, 8, 16)
    for c, pos in ((2, 0), (1, 2)):
        ref, ref_state = M.prefill_chunk(
            params, cfg, ref_state, jnp.asarray(p1[None, pos:pos + c]),
            jnp.int32(0))

    # Two-slot state: slot 0 holds 6 tokens of p0, then slot 1 prefills p1.
    st = _paged_state_with_tables(cfg, 2, 4, 8, 16)
    for c, pos in ((4, 0), (2, 4)):
        _, st = M.prefill_chunk(
            params, cfg, st, jnp.asarray(p0[None, pos:pos + c]), jnp.int32(0))
    out = None
    for c, pos in ((2, 0), (1, 2)):
        out, st = M.prefill_chunk(
            params, cfg, st, jnp.asarray(p1[None, pos:pos + c]), jnp.int32(1))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)
    assert st.lengths.tolist() == [6, 3]


def test_engine_slot_refill_unequal_lengths():
    """Continuous batching: more requests than slots, unequal max_new; every
    request completes with its own token budget, prefill work is proportional
    to real prompt tokens, and all blocks return to the pool."""
    cfg = configs.get_smoke("gemma3-1b")
    eng = Engine(cfg, slots=2, max_seq=32, block_size=4, max_chunk=4, seed=0)
    eng.warmup()
    rng = np.random.default_rng(2)
    lens, gens = [5, 3, 7, 4], [2, 5, 1, 3]
    prompts = [rng.integers(0, cfg.vocab, size=n).astype(np.int32) for n in lens]
    reqs = [eng.submit(p, max_new=g) for p, g in zip(prompts, gens)]
    results = eng.run()

    assert sorted(results) == [r.rid for r in reqs]
    for r, g in zip(reqs, gens):
        assert len(results[r.rid]) == g, (r.rid, results[r.rid])
    # prefill proportional to real tokens (regression for the old padded
    # token-by-token loop, which burned slots * max(len) dead steps)
    assert eng.metrics.prefill_tokens == sum(lens)
    assert eng.metrics.decode_tokens == sum(gens) - len(gens)  # first tokens
    # come from the final prefill chunk, not from a decode step
    assert eng.alloc.in_use == 0 and eng.alloc.available == eng.num_blocks - 1
    assert eng.metrics.cold_compiles == 0  # warmup covered every step shape


def test_engine_matches_isolated_run():
    """A request served through a busy 2-slot engine generates the same
    tokens as the same request served alone."""
    cfg = configs.get_smoke("jamba-1.5-large-398b")
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
               for n in (6, 4, 5)]

    busy = Engine(cfg, params=params, slots=2, max_seq=32, block_size=4,
                  max_chunk=4)
    busy.warmup()
    reqs = [busy.submit(p, max_new=3) for p in prompts]
    got = busy.run()

    for p, r in zip(prompts, reqs):
        solo = Engine(cfg, params=params, slots=1, max_seq=32, block_size=4,
                      max_chunk=4)
        solo.warmup()
        sr = solo.submit(p, max_new=3)
        want = solo.run()[sr.rid]
        np.testing.assert_array_equal(got[r.rid], want)


def test_prompt_logits_match_forward_and_leave_engine_idle():
    """Engine.prompt_logits runs the compiled chunk steps on a scratch
    state: the next-token logits equal the dense forward's, nothing
    compiles after warmup, and the live engine still serves afterwards."""
    cfg = configs.get_smoke("gemma3-1b")
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    eng = Engine(cfg, params=params, slots=2, max_seq=32, block_size=4,
                 max_chunk=8)
    eng.warmup()
    prompt = np.random.default_rng(5).integers(0, cfg.vocab, size=21)
    want = M.forward(params, cfg, {"tokens": jnp.asarray(prompt[None])},
                     last_only=True)[0, -1]
    np.testing.assert_allclose(eng.prompt_logits(prompt), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    assert eng.metrics.cold_compiles == 0
    assert not np.asarray(eng.state.lengths).any()
    r = eng.submit(RequestSpec(prompt=prompt, max_new=2))
    assert len(eng.run()[r.rid]) == 2
    with pytest.raises(ValueError):
        eng.prompt_logits(np.zeros((33,), np.int32))


def test_block_table_reuse_after_completion():
    """Freed blocks are handed to the next request: a pool far smaller than
    total demand still serves everything, and the same physical block ids
    get reused across requests."""
    cfg = configs.get_smoke("gemma3-1b")
    # usable pool: 4 blocks of 4 tokens; each request needs 2 blocks
    eng = Engine(cfg, slots=2, max_seq=16, block_size=4, num_blocks=5,
                 max_chunk=4)
    eng.warmup()
    rng = np.random.default_rng(4)
    n_req = 4
    for _ in range(n_req):
        eng.submit(rng.integers(0, cfg.vocab, size=5).astype(np.int32),
                   max_new=2)
    seen_blocks = set()
    while eng.scheduler.has_work:
        assert eng.tick()
        for slot_blocks in eng.tables.blocks:
            seen_blocks.update(slot_blocks)
    results = eng.results
    assert len(results) == n_req and all(len(t) == 2 for t in results.values())
    # 4 requests x 2 blocks = 8 block-uses served by <= 4 physical blocks
    assert len(seen_blocks) <= 4
    assert kvc.NULL_BLOCK not in seen_blocks
    assert eng.metrics.peak_blocks_in_use <= 4
    assert eng.alloc.in_use == 0


def test_engine_admission_queue_backpressure():
    """max_queue bounds the admission queue; overflow submissions are
    rejected, not crashed."""
    cfg = configs.get_smoke("gemma3-1b")
    eng = Engine(cfg, slots=1, max_seq=16, block_size=4, max_chunk=4,
                 max_queue=2)
    prompts = np.arange(4, dtype=np.int32)
    assert eng.submit(prompts, max_new=1) is not None
    assert eng.submit(prompts, max_new=1) is not None
    assert eng.submit(prompts, max_new=1) is None
    assert eng.scheduler.rejected == 1
    with pytest.raises(ValueError):
        eng.submit(np.zeros((0,), np.int32), max_new=1)  # nothing to prefill
    with pytest.raises(ValueError):
        eng.submit(np.zeros((20,), np.int32), max_new=1)  # exceeds max_seq


# -- host-side units (no jit, fast) ------------------------------------------


def test_plan_chunks_exact_and_bucketed():
    buckets = set(chunk_buckets(8))
    assert buckets == {8, 4, 2, 1}
    for L in range(0, 40):
        plan = plan_chunks(L, max_chunk=8)
        assert sum(plan) == L
        assert all(c in buckets for c in plan)
        # largest-first greedy: at most log2(C) trailing sub-max chunks
        assert plan == sorted(plan, reverse=True)


def test_scheduler_interleaves_prefill_and_decode():
    sched = Scheduler(slots=2, max_chunk=4)
    sched.submit(np.arange(8, dtype=np.int32), max_new=4)
    sched.submit(np.arange(6, dtype=np.int32), max_new=4)
    sched.admit(lambda req: True)
    kinds = []
    for _ in range(4):
        act = sched.next_action()
        kinds.append(act[0])
        if act[0] == "prefill":
            _, req, chunk = act
            sched.on_prefill(req, chunk, 0)
        else:
            for r in act[1]:
                sched.on_token(r, 1, 0)
    # nothing decodes until the first prompt completes; then phases mix
    assert kinds == ["prefill", "prefill", "decode", "prefill"]

    # with one request decoding and one prefilling, actions alternate
    sched2 = Scheduler(slots=2, max_chunk=4)
    a = sched2.submit(np.arange(4, dtype=np.int32), max_new=8)
    sched2.submit(np.arange(8, dtype=np.int32), max_new=8)
    sched2.admit(lambda req: True)
    act = sched2.next_action()           # a's only chunk
    sched2.on_prefill(a, act[2], 0)
    seq = []
    for _ in range(4):
        act = sched2.next_action()
        seq.append(act[0])
        if act[0] == "prefill":
            sched2.on_prefill(act[1], act[2], 0)
        else:
            for r in act[1]:
                sched2.on_token(r, 1, 0)
    assert seq == ["decode", "prefill", "decode", "prefill"]


def test_scheduler_fifo_admission_blocks_behind_head():
    sched = Scheduler(slots=3, max_chunk=4)
    big = sched.submit(np.arange(8, dtype=np.int32), max_new=4)
    small = sched.submit(np.arange(2, dtype=np.int32), max_new=1)
    admitted = sched.admit(lambda req: req is small)  # big can't fit
    assert admitted == []                 # FIFO: small must wait behind big
    assert sched.queue[0] is big and len(sched.queue) == 2


def _scatter_kv(cache, block_tables, k_new, v_new, start):
    """The scatter ``write_kv`` replaced: (block, offset) index arrays on
    either side of the head axis, positions past a table's capacity on the
    null block.  The reference its in-place write must match bit for bit."""
    B, S, H, D = k_new.shape
    bs = cache.block_size
    pos = jnp.broadcast_to(jnp.asarray(start, jnp.int32), (B,))[:, None] \
        + jnp.arange(S, dtype=jnp.int32)[None, :]
    cap = block_tables.shape[1] * bs
    blk = jnp.take_along_axis(block_tables, jnp.minimum(pos, cap - 1) // bs,
                              axis=1)
    blk = jnp.where(pos < cap, blk, kvc.NULL_BLOCK).reshape(-1)
    off = (pos % bs).reshape(-1)
    k_scale, v_scale = cache.k_scale, cache.v_scale
    if cache.quantized:
        k_new, ks = kvc.quantize_kv_tokens(k_new)
        v_new, vs = kvc.quantize_kv_tokens(v_new)
        k_scale = k_scale.at[blk, :, off].set(ks.reshape(-1, H))
        v_scale = v_scale.at[blk, :, off].set(vs.reshape(-1, H))
    return kvc.PagedKVCache(
        k=cache.k.at[blk, :, off].set(
            k_new.astype(cache.k.dtype).reshape(-1, H, D)),
        v=cache.v.at[blk, :, off].set(
            v_new.astype(cache.v.dtype).reshape(-1, H, D)),
        k_scale=k_scale, v_scale=v_scale)


@pytest.mark.parametrize("kv_precision", ["float", "int8"])
@pytest.mark.parametrize("rows,tokens,start", [
    (32, 1, None), (1, 256, 0), (1, 256, 37)],
    ids=["decode32", "chunk256_at_block", "chunk256_mid_block"])
def test_write_kv_in_place_matches_scatter(rows, tokens, start, kv_precision):
    """``write_kv`` into two layers' pools merged along the block axis gives
    each layer exactly the old scatter's pool, bit for bit, and leaves the
    other layer's rows as they were.  Decode: 32 rows at their own
    positions, three idle rows on all-null tables and one row past its
    table's capacity, all landing in that layer's null block; chunks of 256
    tokens starting at a block boundary and mid-block."""
    G, nb, bs, H, D = 2, 65, 16, 2, 8
    width = 2 if rows > 1 else 20
    rng = np.random.default_rng(rows + (start or 0))
    pool = kvc.init_paged_kv(G * nb, bs, H, D, jnp.bfloat16,
                             kv_precision=kv_precision)
    pool = kvc.PagedKVCache(*[
        None if a is None else jnp.asarray(
            rng.integers(-100, 100, size=a.shape) if a.dtype == jnp.int8
            else rng.uniform(0.5, 2.0, size=a.shape), a.dtype)
        for a in pool])
    owned = rng.permutation(np.arange(1, nb))[:rows * width]
    tables = owned.reshape(rows, width).astype(np.int32)
    if start is None:
        starts = rng.integers(0, width * bs, size=rows).astype(np.int32)
        tables[[5, 17, 30]] = kvc.NULL_BLOCK          # idle rows
        starts[9] = width * bs + 5                    # past capacity
    else:
        starts = np.full((rows,), start, np.int32)
    k_new = jnp.asarray(rng.normal(size=(rows, tokens, H, D)), jnp.float32)
    v_new = jnp.asarray(rng.normal(size=(rows, tokens, H, D)), jnp.float32)

    write = jax.jit(lambda c, bt, k, v, s, base: kvc.write_kv(
        c, bt, k, v, s, block_base=base))
    scatter = jax.jit(_scatter_kv)
    for g in range(G):
        got = write(pool, tables, k_new, v_new, starts, jnp.int32(g * nb))
        layer = jax.tree_util.tree_map(lambda a: a[g * nb:(g + 1) * nb], pool)
        want = scatter(layer, tables, k_new, v_new, starts)
        for a, w, before in zip(got, want, pool):
            if a is None:
                continue
            a, before = np.asarray(a), np.asarray(before)
            np.testing.assert_array_equal(a[g * nb:(g + 1) * nb],
                                          np.asarray(w))
            rest = np.ones(G * nb, bool)
            rest[g * nb:(g + 1) * nb] = False
            np.testing.assert_array_equal(a[rest], before[rest])


def test_block_allocator_reservations():
    alloc = kvc.BlockAllocator(num_blocks=8, block_size=4)
    assert alloc.available == 7
    assert alloc.reserve(5)
    assert alloc.available == 2 and not alloc.can_reserve(3)
    ids = alloc.alloc(5)
    assert len(set(ids)) == 5 and kvc.NULL_BLOCK not in ids
    assert alloc.in_use == 5 and alloc.available == 2
    alloc.free(ids)
    assert alloc.in_use == 0 and alloc.available == 7
    with pytest.raises(ValueError):
        alloc.free([kvc.NULL_BLOCK])
