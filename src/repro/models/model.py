"""Model assembly: decoder LMs, hybrid/SSM LMs, encoder-decoder (whisper),
and prefix-LM VLM (paligemma), with scan-over-groups execution, the paged
serving steps and the training loss.

Public API:
  init_model(key, cfg)                         -> params
  forward(params, cfg, batch)                  -> logits        (train/prefill)
  loss_fn(params, cfg, batch)                  -> scalar loss
  init_paged_decode_state(cfg, slots, ...)     -> PagedDecodeState
  paged_decode_step(params, cfg, state, tokens, active)
                                               -> (logits, PagedDecodeState)
  prefill_chunk(params, cfg, state, tokens, slot)
                                               -> (logits, PagedDecodeState)

`batch` dict keys: "tokens" (B, S) int32 always; "frames" (B, S_enc, d) for
encdec (audio frontend stub); "patches" (B, P, d_vision) for vlm.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import blocks, layers
from repro.models.config import ArchConfig
from repro.parallel.logical import shard

VISION_DIM = 1152  # SigLIP-so400m width (paligemma stub frontend)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_model(key, cfg: ArchConfig):
    ks = jax.random.split(key, 6)
    dt = cfg.jax_dtype
    params: Dict[str, Any] = {
        "embed": layers.init_embedding(ks[0], cfg.vocab, cfg.d_model, dt),
        "final_norm": blocks._init_norm(cfg),
    }
    gkeys = jax.random.split(ks[1], cfg.n_groups)
    cross = cfg.family == "encdec"
    params["blocks"] = jax.vmap(
        lambda k: blocks.init_group(k, cfg, cross_attention=cross)
    )(gkeys)
    if not cfg.tie_embeddings:
        params["head"] = layers._init_dense(ks[2], cfg.d_model, cfg.vocab, dt)
    if cfg.family == "encdec":
        ekeys = jax.random.split(ks[3], cfg.encoder_layers)
        enc_cfg = cfg  # same width; encoder blocks are non-causal, no cross
        params["encoder_blocks"] = jax.vmap(
            lambda k: blocks.init_block(k, enc_cfg, "attn")
        )(ekeys)
        params["encoder_norm"] = blocks._init_norm(cfg)
    if cfg.family == "vlm":
        params["projector"] = layers._init_dense(ks[4], VISION_DIM, cfg.d_model, dt)
    return params


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------

def _run_groups(x, gparams, cfg, *, positions, causal=True, prefix_len=0,
                encoder_out=None):
    def body(h, gp):
        h, _ = blocks.apply_group(
            h, gp, cfg, positions=positions, causal=causal,
            prefix_len=prefix_len, encoder_out=encoder_out,
        )
        return h, None

    if cfg.remat:
        # Activation checkpointing at group granularity: backward recomputes
        # inside a group, activation memory stays O(n_groups * group I/O).
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, gparams)
    return x


def _run_encoder(frames, params, cfg):
    """Whisper encoder over stubbed conv-frontend frame embeddings."""
    x = frames.astype(cfg.jax_dtype)
    positions = jnp.arange(x.shape[1])

    def body(h, bp):
        h, _ = blocks.apply_block(h, bp, cfg, "attn", positions=positions, causal=False)
        return h, None

    x, _ = jax.lax.scan(body, x, params["encoder_blocks"])
    return blocks._norm(x, params["encoder_norm"], cfg)


def trunk(params, cfg: ArchConfig, batch: Dict[str, jax.Array]) -> jax.Array:
    """Final hidden states (B, S, d) before the unembedding."""
    tokens = batch["tokens"]
    S = tokens.shape[1]
    x = layers.embed(tokens, params["embed"], cfg.embedding_multiplier)
    prefix_len = 0
    encoder_out = None
    positions = jnp.arange(S)
    if cfg.family == "vlm":
        prefix = layers.dense(batch["patches"].astype(cfg.jax_dtype), params["projector"])
        x = jnp.concatenate([prefix, x], axis=1)
        prefix_len = prefix.shape[1]
        positions = jnp.arange(x.shape[1])
    elif cfg.family == "encdec":
        encoder_out = _run_encoder(batch["frames"], params, cfg)
    x = shard(x, "batch", "seq", "embed")
    x = _run_groups(
        x, params["blocks"], cfg, positions=positions,
        prefix_len=prefix_len, encoder_out=encoder_out,
    )
    x = blocks._norm(x, params["final_norm"], cfg)
    if cfg.family == "vlm":
        x = x[:, prefix_len:]
    return x


def forward(
    params, cfg: ArchConfig, batch: Dict[str, jax.Array], *,
    last_only: bool = False,
) -> jax.Array:
    """Logits for the whole sequence, or only the final position when
    `last_only` (serving prefill: the (B, S, vocab) tensor at 32k x 262k
    vocab is ~TBs and is never needed — only the next-token logits are)."""
    x = trunk(params, cfg, batch)
    if last_only:
        x = x[:, -1:]
    with jax.named_scope("unembed"):
        return _unembed(x, params, cfg)


def _head(x, params, cfg):
    """The final norm and the unembedding, under one named scope."""
    with jax.named_scope("unembed"):
        return _unembed(blocks._norm(x, params["final_norm"], cfg), params,
                        cfg)


def _unembed(x, params, cfg):
    # "head_q" is the int8-resident copy of the tied embedding table that
    # quant.quantize_params adds for serving: without it, a tied-head model
    # in w8a8 mode would re-quantize the (vocab x d) table every decode step.
    if "head_q" in params:
        logits = layers.dense(x, params["head_q"])
        logits = shard(logits, "batch", "seq", "vocab")
    elif cfg.tie_embeddings:
        logits = layers.unembed(x, params["embed"])
    else:
        logits = layers.dense(x, params["head"])
        logits = shard(logits, "batch", "seq", "vocab")
    if cfg.logits_scaling != 1.0:
        logits = logits / jnp.asarray(cfg.logits_scaling, logits.dtype)
    return logits


def loss_fn(
    params, cfg: ArchConfig, batch: Dict[str, jax.Array], *, chunk: int = 512,
) -> jax.Array:
    """Next-token cross-entropy, computed over sequence chunks.

    The (B, S, vocab) f32 logits of a 262k-vocab model at 4k tokens are
    ~4.3 GB per sequence; chunking the unembedding + softmax (with remat on
    the chunk body) keeps loss memory O(B * chunk * vocab) regardless of S.
    """
    x = trunk(params, cfg, batch)                       # (B, S, d)
    labels = batch["labels"]
    mask = batch.get("mask", jnp.ones_like(labels, jnp.float32))
    B, S, _ = x.shape
    chunk = min(chunk, S)
    while S % chunk:
        chunk //= 2
    n_chunks = S // chunk

    def chunk_loss(_, xs):
        xc, lc, mc = xs                                 # (B, chunk, .) each
        logits = _unembed(xc, params, cfg).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(logp, lc[..., None], axis=-1)[..., 0]
        return None, (jnp.sum(ll * mc), jnp.sum(mc))

    resh = lambda t: jnp.moveaxis(
        t.reshape(t.shape[0], n_chunks, chunk, *t.shape[2:]), 1, 0)
    _, (lls, ms) = jax.lax.scan(
        jax.checkpoint(chunk_loss), None, (resh(x), resh(labels), resh(mask))
    )
    return -jnp.sum(lls) / jnp.maximum(jnp.sum(ms), 1.0)


# ---------------------------------------------------------------------------
# paged serving: per-slot lengths, block-table KV addressing, chunked prefill
# ---------------------------------------------------------------------------

class PagedDecodeState(NamedTuple):
    """Serving decode state: shared KV block pools + per-slot request state.

    Every slot tracks its own length, so slots can be refilled mid-flight
    (continuous batching) without re-initializing anyone else's state.
    """

    caches: Any                   # per-group tuple-of-kind states (stacked);
                                  # attention kinds hold PagedKVCache pools
    block_tables: jax.Array       # (slots, max_blocks) int32 into the pool
    lengths: jax.Array            # (slots,) int32 tokens held per slot


def init_paged_decode_state(
    cfg: ArchConfig, slots: int, *, num_blocks: int, block_size: int,
    max_blocks_per_slot: int, kv_precision: str = "float",
) -> PagedDecodeState:
    if cfg.family in ("encdec", "vlm"):
        raise NotImplementedError(
            f"paged serving not wired for family {cfg.family!r}")
    kinds = cfg.layer_kinds()

    def make_group(_):
        return tuple(
            blocks.init_paged_cache_for_kind(
                cfg, kind, slots, num_blocks, block_size,
                kv_precision=kv_precision)
            for kind in kinds
        )

    caches = jax.vmap(make_group)(jnp.arange(cfg.n_groups))
    return PagedDecodeState(
        caches=caches,
        block_tables=jnp.zeros((slots, max_blocks_per_slot), jnp.int32),
        lengths=jnp.zeros((slots,), jnp.int32),
    )


def _trunk_step(params, cfg, x, positions, caches, cache_index, block_tables,
                collect_states=False):
    """Scan the block groups in decode mode; returns (hidden, new_caches).

    Paged KV pools ride in the scan's carry, recurrent states in its
    xs/ys.  Each stacked ``(G, nb, H, bs, D)`` pool is seen as one
    ``(G * nb, H, bs, D)`` pool (merging the leading dims is a bitcast), so
    group g's block b is row ``g * nb + b`` and every layer writes its new
    tokens in place.  Scanned as xs/ys, each pool would be sliced out and
    written back whole on every step, and the donated state could not
    alias it."""
    from repro.serving.kv_cache import PagedKVCache

    paged = [isinstance(c, PagedKVCache) for c in caches]
    G = cfg.n_groups
    pools = tuple(
        jax.tree_util.tree_map(lambda a: a.reshape((-1,) + a.shape[2:]), c)
        for c, p in zip(caches, paged) if p)
    nb = pools[0].num_blocks // G if pools else 0

    def split(cs):
        """Per-kind caches -> (pools, the rest with None in pools' places)."""
        return (tuple(c for c, p in zip(cs, paged) if p),
                tuple(None if p else c for c, p in zip(cs, paged)))

    def join(pools, rest):
        it = iter(pools)
        return tuple(next(it) if p else c for c, p in zip(rest, paged))

    def body(carry, xs):
        h, pools = carry
        g, gp, gcache = xs
        h, new_caches = blocks.apply_group(
            h, gp, cfg, positions=positions, causal=True,
            caches=join(pools, gcache), cache_index=cache_index,
            block_tables=block_tables, block_base=g * nb,
            collect_states=collect_states,
        )
        pools, rest = split(new_caches)
        return (h, pools), rest

    (x, pools), rest = jax.lax.scan(
        body, (x, pools),
        (jnp.arange(G, dtype=jnp.int32), params["blocks"], split(caches)[1]))
    pools = tuple(
        jax.tree_util.tree_map(lambda a: a.reshape((G, -1) + a.shape[1:]), c)
        for c in pools)
    return x, join(pools, rest)


def _embed_tokens(params, cfg, tokens):
    with jax.named_scope("embed"):
        return layers.embed(tokens, params["embed"], cfg.embedding_multiplier)


def paged_decode_step(
    params, cfg: ArchConfig, state: PagedDecodeState, tokens: jax.Array,
    active: Optional[jax.Array] = None,
) -> Tuple[jax.Array, PagedDecodeState]:
    """One token for every *active* slot at its own position: tokens (B, 1)
    -> logits (B, 1, vocab).

    `active` (B,) bool masks slots that are idle or mid-prefill while this
    decode batch runs: their lengths and recurrent states are held (the
    whole batch computes, but inactive updates are discarded), so
    interleaved prefill chunks resume exactly where they left off.  Inactive
    KV writes land at/above the slot's true length — positions the mask
    hides until a real token overwrites them — or in the null block."""
    x = _embed_tokens(params, cfg, tokens)
    positions = state.lengths[:, None]
    x, new_caches = _trunk_step(
        params, cfg, x, positions, state.caches, state.lengths,
        state.block_tables,
    )
    if active is not None:
        new_caches = _select_slots(active, new_caches, state.caches)
        new_lengths = state.lengths + active.astype(jnp.int32)
    else:
        new_lengths = state.lengths + 1
    logits = _head(x, params, cfg)
    return logits, PagedDecodeState(
        caches=new_caches, block_tables=state.block_tables,
        lengths=new_lengths,
    )


def _select_slots(active, new_caches, old_caches):
    """Keep updates only for active slots.  Paged KV pools pass through —
    an inactive slot's write sits at/above its length, invisible until a
    real write replaces it — while per-slot recurrent states revert."""
    from repro.serving.kv_cache import PagedKVCache

    out = []
    for n, o in zip(new_caches, old_caches):
        if isinstance(n, PagedKVCache):
            out.append(n)
            continue

        def sel(a, b):
            mask = active.reshape((1, -1) + (1,) * (a.ndim - 2))
            return jnp.where(mask, a, b)

        out.append(jax.tree_util.tree_map(sel, n, o))
    return tuple(out)


def paged_verify_step(
    params, cfg: ArchConfig, state: PagedDecodeState, tokens: jax.Array,
    active: jax.Array, limits: jax.Array, eos: jax.Array,
) -> Tuple[jax.Array, jax.Array, PagedDecodeState]:
    """Score S drafted positions per slot in ONE paged forward pass and
    greedily accept the longest matching prefix — speculative decoding's
    batched verification.

    Where ``paged_decode_step`` issues an M=slots GEMV per token, this step
    runs every hot matmul at M = slots * S — the software analogue of the
    paper's output buffering / input pre-fetching: K sequential ticks of
    starved GEMV become one well-fed GEMM (see README §Speculative).

    Inputs per slot row:
      tokens (B, S) int32 — [last committed token, d_1 .. d_{S-1}]: the not-
        yet-consumed tail token followed by the drafter's S-1 guesses.  Rows
        with fewer real drafts pad arbitrarily and bound acceptance via
        ``limits``.
      active (B,) bool   — slots decoding this tick (others fully held).
      limits (B,) int32  — max tokens this slot may emit this tick (>= 1 for
        active slots; caps acceptance at request max_new and draft length).
      eos    (B,) int32  — per-slot EOS id, -1 for none; emission stops at
        the first EOS so host and device lengths never diverge.

    Returns (greedy (B, S) int32, n_new (B,) int32, new_state):
      greedy[i, :n_new[i]] are slot i's committed tokens this tick —
      identical to what n_new[i] successive ``paged_decode_step`` calls
      would emit under greedy decoding (token-identity is tested per
      family).  KV for all S positions is written through the block tables;
      positions at/after the new length hold rejected-draft garbage that the
      causal length mask hides until a later write replaces it (exactly the
      inactive-slot convention of ``paged_decode_step``).  Recurrent (SSM /
      xLSTM) layers cannot be masked after the fact, so their per-position
      states are collected during the pass and the state at the accepted
      position is selected — checkpoint-and-restore at token granularity,
      not KV rewind.
    """
    B, S = tokens.shape
    x = _embed_tokens(params, cfg, tokens)
    positions = state.lengths[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    x, per_pos = _trunk_step(
        params, cfg, x, positions, state.caches, state.lengths,
        state.block_tables, collect_states=True,
    )
    logits = _head(x, params, cfg)                          # (B, S, vocab)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # (B, S)

    # Greedy acceptance: drafted token i is kept iff it equals the model's
    # argmax at the previous position (given all earlier drafts, which the
    # causal mask already conditioned on); the run stops at the first miss.
    match = (tokens[:, 1:] == greedy[:, :-1]).astype(jnp.int32)   # (B, S-1)
    acc = jnp.sum(jnp.cumprod(match, axis=1), axis=1)             # drafts kept
    acc = jnp.minimum(acc, jnp.maximum(limits, 1) - 1)
    # One bonus token always falls out of the last accepted position; clamp
    # emission at the first EOS so the host never records past it.
    emit = jnp.arange(S, dtype=jnp.int32)[None, :] <= acc[:, None]
    eos_hit = (greedy == eos[:, None]) & emit
    first_eos = jnp.argmax(eos_hit, axis=1).astype(jnp.int32)
    n_new = jnp.where(jnp.any(eos_hit, axis=1), first_eos + 1, acc + 1)
    n_new = jnp.where(active, n_new, 0).astype(jnp.int32)

    sel = jnp.maximum(n_new - 1, 0)       # state after the n_new-th token
    caches = _commit_verified(active, sel, per_pos, state.caches)
    return greedy, n_new, PagedDecodeState(
        caches=caches, block_tables=state.block_tables,
        lengths=state.lengths + n_new,
    )


def _commit_verified(active, idx, per_pos_caches, old_caches):
    """Select each slot's recurrent state at its accepted position (leaves
    (G, B, S, ...) -> (G, B, ...)); inactive slots revert to their old
    state.  Paged KV pools pass through — rejected-position writes sit
    beyond the committed length, invisible until overwritten."""
    from repro.serving.kv_cache import PagedKVCache

    out = []
    for n, o in zip(per_pos_caches, old_caches):
        if isinstance(n, PagedKVCache):
            out.append(n)
            continue

        def commit(a, b):
            i = idx.reshape((1, -1, 1) + (1,) * (a.ndim - 3))
            picked = jnp.take_along_axis(a, i, axis=2)[:, :, 0]
            mask = active.reshape((1, -1) + (1,) * (picked.ndim - 2))
            return jnp.where(mask, picked, b)

        out.append(jax.tree_util.tree_map(commit, n, o))
    return tuple(out)


# ---------------------------------------------------------------------------
# Sampling: temperature / top-k / top-p with per-request on-device PRNG keys
# ---------------------------------------------------------------------------


def _adjusted_logits(logits, temperature, top_k, top_p):
    """Apply temperature / top-k / top-p to logits (..., V); the knob arrays
    broadcast over logits.shape[:-1].  Returns unnormalized log-probs with
    truncated entries at -inf — feed straight into ``jax.random.categorical``
    (softmax of the result is the sampling distribution p-tilde).

    Rows with ``temperature <= 0`` are *greedy*: they collapse to a one-hot
    0/-inf row at ``argmax(logits)``, so a categorical draw over them emits
    exactly the token the greedy decode paths would (argmax over float32 is
    exact for every pool dtype — bf16 upcasts losslessly)."""
    V = logits.shape[-1]
    logits = logits.astype(jnp.float32)
    temperature = jnp.asarray(temperature, jnp.float32)
    top_k = jnp.asarray(top_k, jnp.int32)
    top_p = jnp.asarray(top_p, jnp.float32)
    greedy = temperature <= 0.0
    scaled = logits / jnp.where(greedy, 1.0, temperature)[..., None]
    desc = jnp.sort(scaled, axis=-1)[..., ::-1]
    # top-k: keep entries >= the kth-largest (k=0 disables). Ties at the
    # threshold all survive — harmless broadening, never exclusion.
    k = jnp.where(top_k > 0, jnp.minimum(top_k, V), V)
    kth = jnp.take_along_axis(desc, (k - 1)[..., None], axis=-1)
    keep = scaled >= kth
    # top-p (nucleus): keep the smallest prefix of the sorted distribution
    # whose mass reaches top_p.  Exclusive cumsum: a token stays while the
    # mass *before* it is < top_p, so the boundary token is always included
    # and top_p=1.0 keeps everything.
    probs = jax.nn.softmax(desc, axis=-1)
    before = jnp.cumsum(probs, axis=-1) - probs
    in_nucleus = before < top_p[..., None]
    cutoff = jnp.min(jnp.where(in_nucleus, desc, jnp.inf), axis=-1,
                     keepdims=True)
    keep = keep & (scaled >= cutoff)
    adj = jnp.where(keep, scaled, -jnp.inf)
    onehot = (jnp.arange(V, dtype=jnp.int32)[None, :].reshape(
        (1,) * (logits.ndim - 1) + (V,))
        == jnp.argmax(logits, axis=-1, keepdims=True))
    return jnp.where(greedy[..., None], jnp.where(onehot, 0.0, -jnp.inf), adj)


def _fold_keys(seeds, idx):
    """Per-element PRNG keys: fold the 0-based generated-token index into
    PRNGKey(seed).  The stream is a pure function of (seed, index) — never
    of batch composition, tick boundaries, or chunking — so a seeded
    request replays bitwise-identically whatever else the engine is
    serving.  seeds/idx share a shape; returns that shape + key tail."""
    shape = idx.shape
    flat = jax.vmap(
        lambda s, i: jax.random.fold_in(jax.random.PRNGKey(s), i)
    )(jnp.asarray(seeds, jnp.int32).reshape(-1),
      jnp.asarray(idx, jnp.int32).reshape(-1))
    return flat.reshape(shape + flat.shape[1:])


def sample_tokens(logits, seeds, gen_idx, temperature, top_k, top_p):
    """Draw one token per row from adjusted logits (..., V) using the
    per-(seed, gen_idx) key stream; greedy rows return argmax exactly."""
    adj = _adjusted_logits(logits, temperature, top_k, top_p)
    keys = _fold_keys(seeds, gen_idx)
    toks = jax.vmap(jax.random.categorical)(
        keys.reshape((-1,) + keys.shape[len(gen_idx.shape):]),
        adj.reshape(-1, adj.shape[-1]))
    return toks.reshape(adj.shape[:-1]).astype(jnp.int32)


def paged_decode_sample_step(
    params, cfg: ArchConfig, state: PagedDecodeState, tokens: jax.Array,
    active: Optional[jax.Array], temperature: jax.Array, top_k: jax.Array,
    top_p: jax.Array, seeds: jax.Array, gen_idx: jax.Array,
) -> Tuple[jax.Array, PagedDecodeState]:
    """``paged_decode_step`` + on-device sampling: returns (tokens (B,),
    new_state).  The trunk pass is byte-identical to the greedy step; only
    the head differs (sample vs host-side argmax), and greedy rows inside a
    mixed batch still emit argmax (see ``_adjusted_logits``)."""
    logits, new_state = paged_decode_step(params, cfg, state, tokens, active)
    sampled = sample_tokens(logits[:, -1], seeds, gen_idx,
                            temperature, top_k, top_p)
    return sampled, new_state


def paged_verify_sample_step(
    params, cfg: ArchConfig, state: PagedDecodeState, tokens: jax.Array,
    active: jax.Array, limits: jax.Array, eos: jax.Array,
    temperature: jax.Array, top_k: jax.Array, top_p: jax.Array,
    seeds: jax.Array, gen_idx: jax.Array,
) -> Tuple[jax.Array, jax.Array, PagedDecodeState]:
    """Speculative verification under stochastic sampling: the rejection-
    sampling analogue of ``paged_verify_step`` (same inputs + the sampling
    knob arrays; same (out (B, S), n_new (B,), state) contract).

    The drafter is deterministic (a point mass at its guess d_j), so full
    leftover-distribution rejection sampling reduces to: accept d_j with
    probability p-tilde(d_j) — a uniform draw from the position's key —
    and on the first real rejection resample from p-tilde with the rejected
    token masked out (the leftover distribution after removing the point
    mass's accepted share).  The bonus token after a fully-accepted (or
    limit-capped) run samples p-tilde unmasked, exactly like a decode tick.
    Every emitted position is therefore distributed exactly p-tilde —
    speculation changes wall-clock, not the output law.  Greedy rows
    (temperature <= 0) degenerate to the argmax accept rule of
    ``paged_verify_step``: p-tilde(d) is 0 or 1, and the masked resample
    can only land on the argmax.

    Position j consumes the uniform at key (seed, gen_idx + j), and the
    resample folds one extra step off that key — a run with the same seeds
    and drafts replays bitwise-identically, though the realized stream
    differs from the non-speculative stream for the same seed (same law,
    different draws).
    """
    B, S = tokens.shape
    x = _embed_tokens(params, cfg, tokens)
    positions = state.lengths[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    x, per_pos = _trunk_step(
        params, cfg, x, positions, state.caches, state.lengths,
        state.block_tables, collect_states=True,
    )
    logits = _head(x, params, cfg)                          # (B, S, vocab)
    V = logits.shape[-1]

    bcast = lambda a: jnp.broadcast_to(jnp.asarray(a)[:, None], (B, S))
    adj = _adjusted_logits(logits, bcast(temperature), bcast(top_k),
                           bcast(top_p))
    probs = jax.nn.softmax(adj, axis=-1)                    # p-tilde
    idx = bcast(gen_idx) + jnp.arange(S, dtype=jnp.int32)[None, :]
    keys = _fold_keys(bcast(seeds), idx)                    # (B, S, key)
    u = jax.vmap(jax.random.uniform)(
        keys.reshape((-1,) + keys.shape[2:])).reshape(B, S)

    # Accept drafted token d_j (input tokens[:, j+1], scored at position j)
    # with probability p-tilde(d_j); the kept run is the capped prefix of
    # consecutive accepts, mirroring the greedy cumprod.
    drafts = tokens[:, 1:]                                  # (B, S-1)
    p_draft = jnp.take_along_axis(
        probs[:, :-1], drafts[..., None], axis=-1)[..., 0]
    accept = (u[:, :S - 1] < p_draft).astype(jnp.int32)
    acc_raw = jnp.sum(jnp.cumprod(accept, axis=1), axis=1)
    acc = jnp.minimum(acc_raw, jnp.maximum(limits, 1) - 1)

    # Position acc emits a fresh sample: with the rejected draft masked out
    # when a real rejection stopped the run (leftover distribution), or
    # unmasked when the run ended by draft/limit exhaustion (bonus token).
    rejected = (acc == acc_raw) & (acc < S - 1)
    rows = jnp.arange(B)
    key2 = jax.vmap(lambda kk: jax.random.fold_in(kk, 1))(keys[rows, acc])
    bad = tokens[rows, jnp.minimum(acc + 1, S - 1)]
    masked = jnp.where(
        rejected[:, None] & (jnp.arange(V)[None, :] == bad[:, None]),
        -jnp.inf, adj[rows, acc])
    final = jax.vmap(jax.random.categorical)(key2, masked).astype(jnp.int32)

    draft_shift = jnp.pad(drafts, ((0, 0), (0, 1)))         # (B, S)
    out = jnp.where(jnp.arange(S, dtype=jnp.int32)[None, :] < acc[:, None],
                    draft_shift, final[:, None]).astype(jnp.int32)

    emit = jnp.arange(S, dtype=jnp.int32)[None, :] <= acc[:, None]
    eos_hit = (out == eos[:, None]) & emit
    first_eos = jnp.argmax(eos_hit, axis=1).astype(jnp.int32)
    n_new = jnp.where(jnp.any(eos_hit, axis=1), first_eos + 1, acc + 1)
    n_new = jnp.where(active, n_new, 0).astype(jnp.int32)

    sel = jnp.maximum(n_new - 1, 0)
    caches = _commit_verified(active, sel, per_pos, state.caches)
    return out, n_new, PagedDecodeState(
        caches=caches, block_tables=state.block_tables,
        lengths=state.lengths + n_new,
    )


def _slice_slot_caches(caches, slot, width: int = 1):
    """Per-kind slot slice: SSM states are per-slot (axis 1 under the group
    axis); paged KV pools are shared and pass through whole."""
    from repro.serving.kv_cache import PagedKVCache

    out = []
    for c in caches:
        if isinstance(c, PagedKVCache):
            out.append(c)
        else:
            out.append(jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_slice_in_dim(a, slot, width, axis=1), c))
    return tuple(out)


def _merge_slot_caches(full, part, slot):
    """Write a slot-sliced cache update back; pools come back whole."""
    from repro.serving.kv_cache import PagedKVCache

    out = []
    for f, pt in zip(full, part):
        if isinstance(f, PagedKVCache):
            out.append(pt)
        else:
            out.append(jax.tree_util.tree_map(
                lambda a, b: jax.lax.dynamic_update_slice_in_dim(
                    a, b.astype(a.dtype), slot, axis=1), f, pt))
    return tuple(out)


def prefill_chunk(
    params, cfg: ArchConfig, state: PagedDecodeState, tokens: jax.Array,
    slot: jax.Array,
) -> Tuple[jax.Array, PagedDecodeState]:
    """Advance one slot by a chunk of C prompt tokens: tokens (1, C) ->
    (last-position logits (1, 1, vocab), updated state).

    The chunk attends causally over the slot's block-table view (which the
    same step just wrote), and SSM states advance by C tokens via their
    chunked scans — C-fold fewer step dispatches than token-by-token, the
    input-prefetch/output-buffering analogue.  The LM head runs on the last
    position only (the (1, C, vocab) tensor is never needed)."""
    C = tokens.shape[1]
    start = jax.lax.dynamic_slice_in_dim(state.lengths, slot, 1)       # (1,)
    tables = jax.lax.dynamic_slice_in_dim(state.block_tables, slot, 1, axis=0)
    caches = _slice_slot_caches(state.caches, slot)
    x = _embed_tokens(params, cfg, tokens)
    positions = start[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
    x, part_caches = _trunk_step(
        params, cfg, x, positions, caches, start, tables)
    logits = _head(x[:, -1:], params, cfg)
    new_lengths = jax.lax.dynamic_update_slice(
        state.lengths, start + jnp.int32(C), (slot,))
    return logits, PagedDecodeState(
        caches=_merge_slot_caches(state.caches, part_caches, slot),
        block_tables=state.block_tables,
        lengths=new_lengths,
    )


def reset_slots(
    cfg: ArchConfig, state: PagedDecodeState, mask: jax.Array,
) -> PagedDecodeState:
    """Zero the recurrent state and length of every masked slot for fresh
    requests — slot refill without re-initializing the whole batch, and one
    step per admission wave however many slots it fills.  KV pages need no
    reset: freed blocks are re-written before the length mask exposes them."""
    from repro.serving.kv_cache import PagedKVCache

    kinds = cfg.layer_kinds()
    fresh = []
    for kind, cur in zip(kinds, state.caches):
        if isinstance(cur, PagedKVCache):
            fresh.append(cur)
            continue
        one = blocks.init_cache_for_kind(cfg, kind, 1)      # batch-1 template

        def sel(full, init):
            m = mask.reshape((1, -1) + (1,) * (full.ndim - 2))
            return jnp.where(m, init[None].astype(full.dtype), full)

        fresh.append(jax.tree_util.tree_map(sel, cur, one))
    lengths = jnp.where(mask, 0, state.lengths)
    return PagedDecodeState(
        caches=tuple(fresh), block_tables=state.block_tables, lengths=lengths)
