"""Granite 4.0-H in the program: the Mamba-2 mixer (SSD prefill against
its own recurrence, chunked prefill resuming a carried state), the expert
layer that holds a share of the experts with a shared one, drop-free
routing, the multipliers and NoPE, and the serving path end to end.  Smoke
sizes on the CPU, seeded random weights."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import attention as attn_lib
from repro.models import model as M
from repro.models import moe as moe_lib
from repro.models import ssm

NAME = "granite-4.0-h-small"


def smoke(**kw):
    return dataclasses.replace(configs.get_smoke(NAME), **kw)


def mamba_params(cfg, seed=0):
    return ssm.init_mamba(jax.random.PRNGKey(seed), cfg)


def test_published_config():
    cfg = configs.get(NAME)
    assert cfg.layer_kinds() == ("mamba",) * 5 + ("attn",) + ("mamba",) * 4
    assert [i for i in range(40)
            if cfg.layer_kinds()[i % 10] == "attn"] == [5, 15, 25, 35]
    mc = cfg.mamba
    assert (mc.n_heads * mc.head_dim, mc.conv_dim(cfg.d_model)) == \
        (2 * 4096, 8192 + 256)
    # 32B parameters, 9B of them active (the published 32B-A9B).
    assert round(cfg.param_count() / 1e9) == 32
    assert round(cfg.active_param_count() / 1e9) == 9


def test_ssd_prefill_equals_the_per_token_recurrence():
    """The chunked (SSD) form over 4 chunks equals the O(1) decode update
    run token by token: the same mathematics, f32 rounding apart."""
    cfg = smoke()
    p = mamba_params(cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 64))
    whole, _ = ssm.mamba2_block(x, p, cfg)
    st = ssm.init_mamba2_state(cfg, 2)
    outs = []
    for t in range(32):
        y, st = ssm.mamba2_block(x[:, t:t + 1], p, cfg, state=st)
        outs.append(y)
    np.testing.assert_allclose(np.concatenate(outs, 1), whole, rtol=1e-5,
                               atol=1e-6)


@pytest.mark.parametrize("chunks", [1, 2, 4])
def test_prefill_in_chunks_gives_one_state(chunks):
    """A 32-token prefill in 1, 2 or 4 pieces, each resuming the carried
    state, leaves the same state and the same outputs."""
    cfg = smoke()
    p = mamba_params(cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 32, 64))
    ref_y, ref_st = ssm.mamba2_block(x, p, cfg,
                                     state=ssm.init_mamba2_state(cfg, 1))
    st, ys, n = ssm.init_mamba2_state(cfg, 1), [], 32 // chunks
    for i in range(chunks):
        y, st = ssm.mamba2_block(x[:, i * n:(i + 1) * n], p, cfg, state=st)
        ys.append(y)
    np.testing.assert_allclose(np.concatenate(ys, 1), ref_y, rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(st.h, ref_st.h, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(st.conv, ref_st.conv)


@pytest.mark.parametrize("chunk", [8, 16, 32])
def test_chunked_prefill_logits_match_the_full_forward(chunk):
    """Engine-style prefill of one slot in chunks of 8, 16 or 32 tokens
    through the paged state gives the last position's logits of the full
    forward (f32: 1e-5 of the logits' range, the two orders of summation)."""
    cfg = smoke()
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(3), (1, 32), 0, cfg.vocab)
    full = M.forward(params, cfg, {"tokens": toks})
    st = M.init_paged_decode_state(cfg, 2, num_blocks=9, block_size=16,
                                   max_blocks_per_slot=4)
    st = st._replace(block_tables=jnp.array([[1, 2, 3, 4], [5, 6, 7, 8]]))
    for i in range(0, 32, chunk):
        lg, st = M.prefill_chunk(params, cfg, st, toks[:, i:i + chunk],
                                 jnp.int32(1))
        np.testing.assert_allclose(lg[0, -1], full[0, i + chunk - 1],
                                   rtol=1e-4, atol=1e-5)


def test_engine_prefill_then_decode_matches_the_full_forward():
    """Requests served through the Engine (chunked prefill, then paged
    decode beside other slots) give, at every served position, the logits
    the full forward gives over the same tokens.  Tolerance 2e-5 absolute
    against logits of about 1e-2 (f32 smoke weights; the logits are divided
    by 16): the paths sum in different orders, nothing else."""
    from repro.serving.engine import Engine
    from repro.serving.request import RequestSpec

    cfg = smoke()
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    eng = Engine(cfg, params=params, slots=3, max_seq=96, block_size=16,
                 max_chunk=16)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 256, n).astype(np.int32) for n in (37, 5, 20)]
    seen = {}
    orig = eng._decode_fn

    def spy(params_, state, tokens, active):
        logits, new = orig(params_, state, tokens, active)
        for s, req in enumerate(eng.scheduler.slots):
            if req is not None and bool(active[s]):
                seen.setdefault(req.rid, []).append(np.asarray(logits[s, 0]))
        return logits, new

    eng._decode_fn = spy
    reqs = [eng.submit(RequestSpec(prompt=p, max_new=8)) for p in prompts]
    out = eng.run()
    for r, p in zip(reqs, prompts):
        toks = np.concatenate([p, out[r.rid][:-1]])
        full = np.asarray(M.forward(params, cfg, {"tokens": toks[None]}))[0]
        # First token from the prefill's last position, then one per decode.
        assert list(out[r.rid]) == list(full[len(p) - 1:].argmax(-1))
        got = np.stack(seen[r.rid])
        np.testing.assert_allclose(got, full[len(p):len(p) + len(got)],
                                   rtol=0, atol=2e-5)
        assert np.abs(full).max() < 1.0


def _moe_cfg(**kw):
    cfg = smoke()
    return dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, **kw))


def test_expert_shares_sum_to_the_uncut_layer():
    """Four chips of two experts each: their parts, with the shared expert
    (which every chip computes) counted once, add up to the uncut layer."""
    cfg = _moe_cfg()
    E = cfg.moe.num_experts
    p = moe_lib.init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 64))
    whole = moe_lib.moe_block(x, p, cfg)
    shared = M.layers.mlp(x, p["shared"], "swiglu")
    parts = []
    for first in range(0, E, 2):
        c = _moe_cfg(first_expert=first, experts_held=2)
        share = {k: (v[first:first + 2] if k in ("w_gate", "w_up", "w_down")
                     else v) for k, v in p.items()}
        parts.append(moe_lib.moe_block(x, share, c))
    np.testing.assert_allclose(sum(parts) - (len(parts) - 1) * shared, whole,
                               rtol=1e-5, atol=1e-6)


def test_routing_skewed_onto_one_expert_drops_no_token():
    """Every token routed first to expert 0 (a router biased onto it): with
    drop-free routing each token's pair is computed, as a dense sum over
    its top-k gives; a capacity factor of 1.25 would have dropped most."""
    cfg = _moe_cfg()
    p = moe_lib.init_moe(jax.random.PRNGKey(0), cfg)
    p["router"] = p["router"].at[:, 0].add(50.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (1, 64, 64)))
    got = moe_lib.moe_block(x, p, cfg)

    x2 = x.reshape(64, 64)
    top, idx = jax.lax.top_k(x2 @ p["router"], cfg.moe.top_k)
    assert bool(jnp.all(idx[:, 0] == 0))
    gates = jax.nn.softmax(top, -1)
    want = M.layers.mlp(x2, p["shared"], "swiglu")
    for e in range(cfg.moe.num_experts):
        g = jnp.sum(jnp.where(idx == e, gates, 0.0), -1)[:, None]
        h = jax.nn.silu(x2 @ p["w_gate"][e]) * (x2 @ p["w_up"][e])
        want = want + g * (h @ p["w_down"][e])
    np.testing.assert_allclose(got[0], want, rtol=1e-4, atol=1e-5)
    dropping = moe_lib.moe_block(x, p, _moe_cfg(drop_free=False))
    assert float(jnp.abs(dropping[0] - want).max()) > 1e-2


def test_collect_states_on_mamba2_raises():
    cfg = smoke()
    p = mamba_params(cfg)
    with pytest.raises(NotImplementedError, match="Mamba-2"):
        ssm.mamba2_block(jnp.zeros((1, 2, 64)), p, cfg,
                         state=ssm.init_mamba2_state(cfg, 1),
                         collect_states=True)


def test_multipliers_and_nope():
    """The embedding is scaled by 12, the logits divided by 16, every
    residual add weighted by 0.22, and attention has no rotary embedding:
    permuting the keys before a query leaves its output unchanged."""
    cfg = smoke()
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    toks = jnp.array([[3, 9, 27]])
    x = M._embed_tokens(params, cfg, toks)
    np.testing.assert_allclose(x, params["embed"][toks] * 12.0, rtol=1e-6)
    h = jax.random.normal(jax.random.PRNGKey(5), (1, 3, 64))
    logits = M._unembed(h, params, cfg)
    np.testing.assert_allclose(logits * 16.0, h @ params["embed"].T,
                               rtol=1e-5, atol=1e-6)
    p = attn_lib.init_attention(jax.random.PRNGKey(1), cfg)
    seq = jax.random.normal(jax.random.PRNGKey(2), (1, 6, 64))
    perm = seq[:, jnp.array([2, 0, 4, 1, 3, 5])]
    a, _ = attn_lib.attention(seq, p, cfg, positions=jnp.arange(6)[None])
    b, _ = attn_lib.attention(perm, p, cfg, positions=jnp.arange(6)[None])
    np.testing.assert_allclose(a[:, -1], b[:, -1], rtol=1e-5, atol=1e-6)
    roped, _ = attn_lib.attention(perm, p, dataclasses.replace(cfg, nope=False),
                                  positions=jnp.arange(6)[None])
    assert float(jnp.abs(roped[:, -1] - a[:, -1]).max()) > 1e-4


def test_dense_attention_scale_is_still_head_dim():
    """A dense config leaves attention_multiplier unset, and every attention
    path then scales by D ** -0.5 exactly: the same numbers as passing that
    scale, bit for bit; granite's 1/128 gives others."""
    from repro.kernels import flash_decode as fd
    from repro.serving import kv_cache as kvc

    nemo = configs.get_smoke("mistral-nemo-12b")
    assert configs.get("mistral-nemo-12b").attention_multiplier is None
    D = nemo.resolved_head_dim
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (2, 1, 4, D))
    pool = kvc.init_paged_kv(9, 16, 2, D, jnp.float32)
    pool = pool._replace(k=jax.random.normal(ks[1], pool.k.shape),
                         v=jax.random.normal(ks[2], pool.v.shape))
    tables = jnp.array([[1, 2, 3, 4], [5, 6, 7, 8]])
    idx = jnp.array([40, 17])
    for backend in ("gather", "blocked", "interpret"):
        a = fd.paged_decode_attention(q, pool, tables, idx, backend=backend)
        b = fd.paged_decode_attention(q, pool, tables, idx, backend=backend,
                                      scale=D ** -0.5)
        c = fd.paged_decode_attention(q, pool, tables, idx, backend=backend,
                                      scale=0.0078125)
        np.testing.assert_array_equal(a, b)
        assert float(jnp.abs(a - c).max()) > 1e-3
    p = attn_lib.init_attention(jax.random.PRNGKey(1), nemo)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 8, nemo.d_model))
    pos = jnp.arange(8)[None]
    a, _ = attn_lib.attention(x, p, nemo, positions=pos)
    b, _ = attn_lib.attention(
        x, p, dataclasses.replace(nemo, attention_multiplier=D ** -0.5),
        positions=pos)
    np.testing.assert_array_equal(a, b)
