"""Per-arch smoke tests (deliverable f) + decode/teacher-forcing consistency."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import model as M

ARCHS = configs.list_archs()


def make_batch(cfg, B=2, S=16, seed=0):
    k = jax.random.PRNGKey(seed)
    k1, k2, k3 = jax.random.split(k, 3)
    batch = {
        "tokens": jax.random.randint(k1, (B, S), 0, cfg.vocab),
        "labels": jax.random.randint(k2, (B, S), 0, cfg.vocab),
    }
    if cfg.family == "encdec":
        batch["frames"] = jax.random.normal(k3, (B, cfg.encoder_seq, cfg.d_model))
    if cfg.family == "vlm":
        batch["patches"] = jax.random.normal(k3, (B, cfg.prefix_len, M.VISION_DIM))
    return batch


@pytest.mark.parametrize("name", ARCHS)
def test_smoke_forward_and_grad(name):
    """Reduced same-family config: one forward + train grad on CPU; the
    last-position forward is the full forward's last position (past the
    VLM prefix and beside the encoder alike)."""
    cfg = configs.get_smoke(name)
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    batch = make_batch(cfg)
    logits = M.forward(params, cfg, batch)
    assert logits.shape == (2, 16, cfg.vocab)
    assert not bool(jnp.any(jnp.isnan(logits))), name
    last = M.forward(params, cfg, batch, last_only=True)
    np.testing.assert_allclose(last, logits[:, -1:], rtol=1e-5, atol=1e-6)
    loss, grads = jax.value_and_grad(M.loss_fn)(params, cfg, batch)
    assert np.isfinite(float(loss))
    gn = sum(float(jnp.sum(jnp.abs(g))) for g in jax.tree_util.tree_leaves(grads))
    assert np.isfinite(gn) and gn > 0


def paged_state(cfg, slots, max_len, block_size=4):
    """A paged decode state whose slots own disjoint blocks for max_len
    tokens each (block 0 stays the null block)."""
    width = -(-max_len // block_size)
    state = M.init_paged_decode_state(
        cfg, slots, num_blocks=1 + slots * width, block_size=block_size,
        max_blocks_per_slot=width)
    tables = 1 + jnp.arange(slots * width, dtype=jnp.int32)
    return state._replace(block_tables=tables.reshape(slots, width))


@pytest.mark.parametrize("name", ARCHS)
def test_smoke_decode_shapes(name):
    """One paged decode step for every family paged serving serves; the
    encoder-decoder and prefix-LM families are refused."""
    cfg = configs.get_smoke(name)
    B = 2
    if cfg.family in ("encdec", "vlm"):
        with pytest.raises(NotImplementedError, match=cfg.family):
            paged_state(cfg, B, 24)
        return
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    state = paged_state(cfg, B, 24)
    tok = jnp.zeros((B, 1), jnp.int32)
    logits, state = M.paged_decode_step(params, cfg, state, tok)
    assert logits.shape == (B, 1, cfg.vocab)
    assert not bool(jnp.any(jnp.isnan(logits)))
    assert state.lengths.tolist() == [1] * B


@pytest.mark.parametrize("name", ["qwen3-14b", "gemma3-1b", "xlstm-1.3b",
                                  "jamba-1.5-large-398b", "dbrx-132b"])
def test_decode_matches_forward(name):
    """Teacher-forced paged decode must reproduce the training forward
    logits."""
    cfg = configs.get_smoke(name)
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    B, S = 2, 8
    batch = make_batch(cfg, B, S)
    ref_logits = np.asarray(M.forward(params, cfg, batch), np.float32)

    state = paged_state(cfg, B, S)
    outs = []
    for t in range(S):
        lg, state = M.paged_decode_step(params, cfg, state,
                                        batch["tokens"][:, t:t + 1])
        outs.append(np.asarray(lg[:, 0], np.float32))
    dec_logits = np.stack(outs, axis=1)
    np.testing.assert_allclose(dec_logits, ref_logits, rtol=2e-2, atol=2e-3)


def test_local_window_masks_long_range():
    """gemma3 local layers: token attends only within the window."""
    from repro.models.attention import blockwise_attention

    B, S, H, D = 1, 32, 2, 8
    k = jax.random.PRNGKey(0)
    q = jax.random.normal(k, (B, S, H, D))
    kk = jax.random.normal(jax.random.PRNGKey(1), (B, S, H, D))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, H, D))
    out_w = blockwise_attention(q, kk, v, causal=True, window=4, block_kv=8)
    # perturb keys/values far outside the window of the last query
    kk2 = kk.at[:, :8].set(jax.random.normal(jax.random.PRNGKey(3), (B, 8, H, D)))
    v2 = v.at[:, :8].set(0.0)
    out_w2 = blockwise_attention(q, kk2, v2, causal=True, window=4, block_kv=8)
    np.testing.assert_allclose(out_w[:, -1], out_w2[:, -1], rtol=1e-5, atol=1e-6)


def test_blockwise_matches_dense_attention():
    """Online-softmax blockwise attention == dense softmax attention."""
    from repro.models.attention import blockwise_attention

    B, S, Hq, Hkv, D = 2, 24, 4, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, Hq, D))
    k = jax.random.normal(ks[1], (B, S, Hkv, D))
    v = jax.random.normal(ks[2], (B, S, Hkv, D))
    out = blockwise_attention(q, k, v, causal=True, block_kv=8)

    # dense reference
    kr = jnp.repeat(k, Hq // Hkv, axis=2)
    vr = jnp.repeat(v, Hq // Hkv, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kr) * D ** -0.5
    mask = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    expect = jnp.einsum("bhqk,bkhd->bqhd", p, vr)
    np.testing.assert_allclose(out, expect, rtol=1e-4, atol=1e-5)


def test_prefix_lm_bidirectional_prefix():
    """VLM prefix tokens attend bidirectionally; suffix stays causal."""
    from repro.models.attention import blockwise_attention

    B, S, H, D = 1, 16, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, H, D))
    v = jax.random.normal(ks[2], (B, S, H, D))
    out = blockwise_attention(q, k, v, causal=True, prefix_len=6, block_kv=4)
    # query 0 (inside prefix) must see key 5 (also prefix, in its "future"):
    v2 = v.at[:, 5].set(v[:, 5] + 10.0)
    out2 = blockwise_attention(q, k, v2, causal=True, prefix_len=6, block_kv=4)
    assert float(jnp.max(jnp.abs(out2[:, 0] - out[:, 0]))) > 1e-4
    # but a suffix key in the future of a suffix query stays hidden:
    v3 = v.at[:, 15].set(v[:, 15] + 10.0)
    out3 = blockwise_attention(q, k, v3, causal=True, prefix_len=6, block_kv=4)
    np.testing.assert_allclose(out3[:, 10], out[:, 10], rtol=1e-6)


@pytest.mark.parametrize("name", ARCHS)
def test_full_config_constants(name):
    """Full production configs hold the assignment's exact constants."""
    cfg = configs.get(name)
    expected = {
        "whisper-medium": (24, 1024, 16, 16, 4096, 51865),
        "qwen3-14b": (40, 5120, 40, 8, 17408, 151936),
        "mistral-nemo-12b": (40, 5120, 32, 8, 14336, 131072),
        "qwen2.5-14b": (48, 5120, 40, 8, 13824, 152064),
        "gemma3-1b": (26, 1152, 4, 1, 6912, 262144),
        "dbrx-132b": (40, 6144, 48, 8, 10752, 100352),
        "arctic-480b": (35, 7168, 56, 8, 4864, 32000),
        "paligemma-3b": (18, 2048, 8, 1, 16384, 257216),
        "jamba-1.5-large-398b": (72, 8192, 64, 8, 24576, 65536),
        "xlstm-1.3b": (48, 2048, 4, 4, 0, 50304),
    }
    if name in expected:
        got = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
               cfg.d_ff, cfg.vocab)
        assert got == expected[name], (name, got)


def test_moe_param_counts_match_published():
    assert configs.get("dbrx-132b").param_count() / 1e9 == pytest.approx(132, rel=0.05)
    assert configs.get("arctic-480b").param_count() / 1e9 == pytest.approx(480, rel=0.05)
    j = configs.get("jamba-1.5-large-398b")
    assert j.param_count() / 1e9 == pytest.approx(398, rel=0.05)
    assert j.active_param_count() / 1e9 == pytest.approx(94, rel=0.1)


# The layer-kind scopes each arch's step programs carry (blocks.MIXER_SCOPE,
# "ffn"/"moe", and model.py's "embed" and "unembed").
SCOPES = {
    "mistral-nemo-12b": {"embed", "attn", "ffn", "unembed"},
    "jamba-1.5-large-398b": {"embed", "attn", "mamba", "ffn", "moe",
                             "unembed"},
    "xlstm-1.3b": {"embed", "mlstm", "slstm", "unembed"},
}


@pytest.mark.parametrize("step", ["paged_serve_step", "prefill_chunk_step"])
@pytest.mark.parametrize("name", sorted(SCOPES))
def test_step_programs_carry_layer_kind_scopes(name, step):
    """The lowered HLO of the serving steps names each operation's layer
    kind in its op_name metadata, so a profile of the step splits by kind."""
    import re

    from repro.launch import steps

    cfg = configs.get_smoke(name)
    params = jax.eval_shape(lambda: M.init_model(jax.random.PRNGKey(0), cfg))
    state = jax.eval_shape(lambda: M.init_paged_decode_state(
        cfg, 2, num_blocks=9, block_size=4, max_blocks_per_slot=4))
    if step == "paged_serve_step":
        fn = steps.make_paged_serve_step(cfg)
        args = (jax.ShapeDtypeStruct((2, 1), jnp.int32),
                jax.ShapeDtypeStruct((2,), jnp.bool_))
    else:
        fn = steps.make_prefill_chunk_step(cfg)
        args = (jax.ShapeDtypeStruct((1, 4), jnp.int32),
                jax.ShapeDtypeStruct((), jnp.int32))
    assert fn.__name__ == step
    text = jax.jit(fn).lower(params, state, *args).as_text(
        dialect="hlo", debug_info=True)
    names = re.findall(r'op_name="([^"]*)"', text)
    assert any(n.startswith(f"jit({step})/") for n in names)
    scopes = {seg for n in names for seg in n.split("/")}
    want = SCOPES[name]
    assert want <= scopes
    others = {"embed", "attn", "mamba", "mlstm", "slstm", "ffn", "moe",
              "unembed"} - want
    assert not scopes & others
