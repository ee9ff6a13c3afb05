"""State-space / recurrent blocks: Mamba (Jamba's 7/8 layers) and xLSTM
(mLSTM matrix-memory + sLSTM scalar-memory blocks).

Training/prefill uses a chunked scan: a `lax.scan` over sequence chunks with
an associative scan inside each chunk, so activation memory is
O(B * chunk * d_inner * d_state) instead of O(B * S * ...).  Decode is a
single O(1) state update — this is why the ``long_500k`` shape runs for the
SSM/hybrid architectures and is skipped for full attention.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers
from repro.parallel.logical import shard


# ---------------------------------------------------------------------------
# Mamba (selective SSM, v1 parameterization)
# ---------------------------------------------------------------------------

class MambaState(NamedTuple):
    h: jax.Array          # (B, d_inner, d_state) SSM state
    conv: jax.Array       # (B, d_conv - 1, d_inner) causal-conv tail


def init_mamba(key, cfg):
    if cfg.mamba.mamba2:
        return init_mamba2(key, cfg)
    d = cfg.d_model
    mc = cfg.mamba
    di = mc.expand * d
    dtr = mc.resolved_dt_rank(d)
    ks = jax.random.split(key, 6)
    dt = cfg.jax_dtype
    return {
        "w_in": layers._init_dense(ks[0], d, 2 * di, dt),
        "conv_w": (jax.random.normal(ks[1], (mc.d_conv, di)) * 0.1).astype(dt),
        "conv_b": jnp.zeros((di,), dt),
        "w_x": layers._init_dense(ks[2], di, dtr + 2 * mc.d_state, dt),
        "w_dt": layers._init_dense(ks[3], dtr, di, dt),
        "b_dt": jnp.zeros((di,), jnp.float32),
        # S4D-real init: A_log = log(1..d_state), broadcast over channels.
        "A_log": jnp.log(jnp.tile(jnp.arange(1, mc.d_state + 1, dtype=jnp.float32), (di, 1))),
        "D": jnp.ones((di,), jnp.float32),
        "w_out": layers._init_dense(ks[4], di, d, dt),
    }


def _mamba_inner(x_in, p, cfg):
    """Shared projections: returns (dA, dBx, C, x_conv) per token."""
    mc = cfg.mamba
    dtr = mc.resolved_dt_rank(cfg.d_model)
    # quant="none": the dt/B/C projections feed exp() in the selective-scan
    # discretization — int8 noise there compounds through the recurrence, so
    # they opt out of the w8a8 precision mode (quant/modes.py).
    xdb = layers.dense(x_in, p["w_x"], quant="none").astype(jnp.float32)
    dt, B_ssm, C_ssm = jnp.split(xdb, [dtr, dtr + mc.d_state], axis=-1)
    dt = jax.nn.softplus(
        layers.dense(dt.astype(x_in.dtype), p["w_dt"], quant="none").astype(jnp.float32)
        + p["b_dt"]
    )  # (..., di)
    A = -jnp.exp(p["A_log"])  # (di, ds)
    dA = jnp.exp(dt[..., None] * A)                     # (..., di, ds)
    dBx = dt[..., None] * B_ssm[..., None, :] * x_in.astype(jnp.float32)[..., None]
    return dA, dBx, C_ssm


def mamba_block(
    x: jax.Array,
    p,
    cfg,
    *,
    state: Optional[MambaState] = None,
    chunk: int = 16,
    collect_states: bool = False,
) -> Tuple[jax.Array, Optional[MambaState]]:
    """x: (B, S, d) -> (B, S, d).

    state is None -> training/prefill-from-scratch (no state returned).
    state given, S == 1 -> decode: one O(1) update.
    state given, S > 1  -> chunked prefill: advance the carried state by S
    tokens with the chunked selective scan (conv context and h both resume
    from the state), returning the updated state.

    ``collect_states`` (requires a carried state) returns a MambaState with
    an extra position axis — h/conv *after every token* (B, S, ...) — so
    speculative verification can restore the state at any accepted position.

    A Mamba-2 config (``cfg.mamba.n_heads > 0``) runs ``mamba2_block``.
    """
    if cfg.mamba.mamba2:
        return mamba2_block(x, p, cfg, state=state,
                            collect_states=collect_states)
    B, S, d = x.shape
    mc = cfg.mamba
    di = mc.expand * d
    xz = layers.dense(x, p["w_in"])
    x_in, z = jnp.split(xz, 2, axis=-1)         # (B, S, di) each
    x_in = shard(x_in, "batch", "seq", "mlp")

    if state is not None and S == 1 and not collect_states:
        # --- decode: O(1) update --------------------------------------------
        conv_ctx = jnp.concatenate([state.conv, x_in.astype(state.conv.dtype)], axis=1)
        w = p["conv_w"].astype(jnp.float32)     # (dc, di)
        xc = jnp.einsum("btd,td->bd", conv_ctx.astype(jnp.float32), w) + p["conv_b"].astype(jnp.float32)
        xc = jax.nn.silu(xc)[:, None, :].astype(x.dtype)            # (B, 1, di)
        dA, dBx, C_ssm = _mamba_inner(xc, p, cfg)
        h = state.h * dA[:, 0] + dBx[:, 0]                           # (B, di, ds)
        y = jnp.einsum("bds,bs->bd", h, C_ssm[:, 0])[:, None, :]
        y = y + p["D"] * xc.astype(jnp.float32)
        new_state = MambaState(h=h, conv=conv_ctx[:, 1:])
        out = layers.dense(
            (y * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype), p["w_out"]
        )
        return shard(out, "batch", "seq", "embed"), new_state

    # --- training / prefill: chunked selective scan --------------------------
    # The causal-conv context and the SSM state h resume from `state` when
    # given (chunked prefill), and start at zero otherwise.
    dc = mc.d_conv
    tail = (state.conv if state is not None
            else jnp.zeros((B, dc - 1, di), x_in.dtype))
    xp = jnp.concatenate([tail.astype(x_in.dtype), x_in], axis=1)
    w = p["conv_w"].astype(jnp.float32)
    xc = sum(
        xp[:, i : i + S].astype(jnp.float32) * w[i] for i in range(dc)
    ) + p["conv_b"].astype(jnp.float32)
    xc = jax.nn.silu(xc).astype(x.dtype)        # (B, S, di)

    chunk = min(chunk, S)
    while S % chunk:
        chunk //= 2
    n_chunks = S // chunk

    def chunk_body(h, xc_c):
        # Discretization (dt/B/C projections, exp) fused INTO the chunk body:
        # the (B, chunk, di, d_state) tensors exist one chunk at a time
        # instead of O(S) — at 32k tokens x d_inner 16k the full-sequence
        # version is ~34 TB/device (EXPERIMENTS.md §Perf, jamba iteration 1).
        dA_c, dBx_c, C_c = _mamba_inner(xc_c, p, cfg)

        def combine(a, b):
            return (a[0] * b[0], b[0] * a[1] + b[1])

        # Prefix products/sums within the chunk (inclusive).
        pA, pBx = jax.lax.associative_scan(combine, (dA_c, dBx_c), axis=1)
        h_c = pA * h[:, None] + pBx             # (B, chunk, di, ds)
        y_c = jnp.einsum("bcds,bcs->bcd", h_c, C_c)
        if collect_states:
            return h_c[:, -1], (y_c, h_c)
        return h_c[:, -1], y_c

    resh = lambda t: jnp.moveaxis(t.reshape(B, n_chunks, chunk, *t.shape[2:]), 1, 0)
    h0 = (state.h if state is not None
          else jnp.zeros((B, di, mc.d_state), jnp.float32))
    # checkpoint: backward recomputes one chunk at a time; only the per-chunk
    # carry states (B, di, ds) are saved across the sequence.
    h_final, ys = jax.lax.scan(jax.checkpoint(chunk_body), h0, resh(xc))
    per_pos = None
    if collect_states:
        assert state is not None, "collect_states needs a carried state"
        ys, h_all = ys                          # h_all: (n_chunks, B, chunk, di, ds)
        h_pos = jnp.moveaxis(h_all, 0, 1).reshape(B, S, di, mc.d_state)
        # Conv tail after token j is the last (d_conv - 1) inputs up to j —
        # a slice of xp, which already prepends the carried tail.
        conv_pos = jnp.stack([xp[:, j + 1: j + dc] for j in range(S)], axis=1)
        per_pos = MambaState(h=h_pos, conv=conv_pos.astype(tail.dtype))
    y = jnp.moveaxis(ys, 0, 1).reshape(B, S, di)
    y = y + p["D"] * xc.astype(jnp.float32)
    out = layers.dense((y * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype), p["w_out"])
    new_state = (MambaState(h=h_final, conv=xp[:, S:].astype(tail.dtype))
                 if state is not None else None)
    return shard(out, "batch", "seq", "embed"), (per_pos if collect_states else new_state)


def init_mamba_state(cfg, batch: int):
    if cfg.mamba.mamba2:
        return init_mamba2_state(cfg, batch)
    mc = cfg.mamba
    di = mc.expand * cfg.d_model
    return MambaState(
        h=jnp.zeros((batch, di, mc.d_state), jnp.float32),
        conv=jnp.zeros((batch, mc.d_conv - 1, di), cfg.jax_dtype),
    )


# ---------------------------------------------------------------------------
# Mamba-2 (SSD: multi-head, one scalar decay per head, shared B/C)
# ---------------------------------------------------------------------------

class Mamba2State(NamedTuple):
    h: jax.Array          # (B, n_heads, head_dim, d_state) SSM state, f32
    conv: jax.Array       # (B, d_conv - 1, conv_dim) causal-conv tail of xBC


def init_mamba2(key, cfg):
    """Mamba-2 mixer weights (x @ w): in_proj (d, d_inner + conv_dim +
    n_heads) giving z, xBC and dt; a depthwise conv over xBC with bias; per
    head dt_bias, A_log and D; the gated norm's weight; out_proj."""
    d = cfg.d_model
    mc = cfg.mamba
    di, H = mc.expand * d, mc.n_heads
    if H * mc.head_dim != di:
        raise ValueError(f"Mamba-2 heads {H} x {mc.head_dim} != d_inner {di}")
    cd = mc.conv_dim(d)
    ks = jax.random.split(key, 5)
    dt = cfg.jax_dtype
    # Mamba-2's init: dt ~ log-uniform on [0.001, 0.1] stored as its inverse
    # softplus, A ~ U(1, 16) stored as log.
    dt0 = jnp.exp(jax.random.uniform(ks[2], (H,), jnp.float32,
                                     jnp.log(1e-3), jnp.log(1e-1)))
    return {
        "w_in": layers._init_dense(ks[0], d, di + cd + H, dt),
        "conv_w": (jax.random.normal(ks[1], (mc.d_conv, cd)) * 0.1).astype(dt),
        "conv_b": jnp.zeros((cd,), dt),
        "dt_bias": dt0 + jnp.log(-jnp.expm1(-dt0)),
        "A_log": jnp.log(jax.random.uniform(ks[3], (H,), jnp.float32, 1.0, 16.0)),
        "D": jnp.ones((H,), jnp.float32),
        "norm": layers.init_rmsnorm(di, dt),
        "w_out": layers._init_dense(ks[4], di, d, dt),
    }


def _ssd_chunk(h, x, Bm, Cm, dt, A):
    """One SSD chunk of L tokens, resuming from state h (B, H, P, N).
    x (B, L, H, P), Bm/Cm (B, L, G, N), dt (B, L, H), A (H,), all f32.

    With a_t = dt_t A and cumulative decay c_t = a_1 + .. + a_t in the chunk:
      y_t = sum_{s<=t} exp(c_t - c_s) dt_s (C_t . B_s) x_s + exp(c_t) h C_t
      h'  = exp(c_L) h + sum_s exp(c_L - c_s) dt_s x_s (x) B_s
    which is the recurrence h <- exp(dt A) h + dt x (x) B, y = h C, over the
    chunk.  Returns (h', y (B, L, H, P))."""
    Bsz, L, H, P = x.shape
    G = Bm.shape[2]
    J = H // G
    c = jnp.cumsum(dt * A, axis=1)                               # (B, L, H)
    seg = c[:, :, None, :] - c[:, None, :, :]                    # (B, t, s, H)
    causal = jnp.tril(jnp.ones((L, L), bool))[None, :, :, None]
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    cb = jnp.einsum("btgn,bsgn->bgts", Cm, Bm)                   # (B, G, t, s)
    w = (decay * dt[:, None, :, :]).reshape(Bsz, L, L, G, J)
    w = w * jnp.moveaxis(cb, 1, 3)[..., None]                    # (B, t, s, G, J)
    xg = x.reshape(Bsz, L, G, J, P)
    y = jnp.einsum("btsgj,bsgjp->btgjp", w, xg)
    hg = h.reshape(Bsz, G, J, P, -1)
    y = y + jnp.einsum("bgjpn,btgn->btgjp", hg, Cm) * jnp.exp(
        c).reshape(Bsz, L, G, J)[..., None]
    tail = (jnp.exp(c[:, -1:] - c) * dt).reshape(Bsz, L, G, J)   # (B, s, G, J)
    h_new = (jnp.exp(c[:, -1])[:, :, None, None] * h
             + jnp.einsum("bsgj,bsgjp,bsgn->bgjpn", tail, xg, Bm
                          ).reshape(h.shape))
    return h_new, y.reshape(Bsz, L, H, P)


def mamba2_block(
    x: jax.Array,
    p,
    cfg,
    *,
    state: Optional[Mamba2State] = None,
    collect_states: bool = False,
) -> Tuple[jax.Array, Optional[Mamba2State]]:
    """Mamba-2 mixer, x: (B, S, d) -> (B, S, d), with mamba_block's contract:
    no state -> prefill from scratch (no state returned); S == 1 with a
    state -> the O(1) decode update; S > 1 with a state -> advance it through
    SSD chunks of min(chunk_size, S) tokens (conv tail and h both resume).

      z, xBC, dt = x in_proj;  xBC = silu(conv(xBC) + conv_b) = (x, B, C)
      dt = softplus(dt + dt_bias);  A = -exp(A_log)          (per head)
      h <- exp(dt A) h + dt x (x) B;  y = h C + D x          (per head)
      out = (rms(y * silu(z)) * norm) out_proj     (rms per group of heads)
    """
    if collect_states:
        raise NotImplementedError(
            "Mamba-2 layers keep no per-position states: speculative "
            "verification through a Mamba-2 layer is not implemented")
    Bsz, S, d = x.shape
    mc = cfg.mamba
    di, H, P, N, G = (mc.expand * d, mc.n_heads, mc.head_dim, mc.d_state,
                      mc.n_groups)
    cd = mc.conv_dim(d)
    zxbcdt = layers.dense(x, p["w_in"])
    z, xbc, dt = jnp.split(zxbcdt, [di, di + cd], axis=-1)
    dc = mc.d_conv
    tail = (state.conv if state is not None
            else jnp.zeros((Bsz, dc - 1, cd), xbc.dtype))
    xp = jnp.concatenate([tail.astype(xbc.dtype), xbc], axis=1)  # (B, S+dc-1, cd)
    w = p["conv_w"].astype(jnp.float32)
    xc = sum(xp[:, i: i + S].astype(jnp.float32) * w[i] for i in range(dc))
    xc = jax.nn.silu(xc + p["conv_b"].astype(jnp.float32))
    xs, Bm, Cm = jnp.split(xc, [di, di + G * N], axis=-1)
    xs = shard(xs.reshape(Bsz, S, H, P), "batch", "seq", "heads", None)
    Bm = Bm.reshape(Bsz, S, G, N)
    Cm = Cm.reshape(Bsz, S, G, N)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])  # (B, S, H)
    A = -jnp.exp(p["A_log"])
    h0 = (state.h if state is not None
          else jnp.zeros((Bsz, H, P, N), jnp.float32))

    if state is not None and S == 1:
        # --- decode: O(1) update ------------------------------------------
        J = H // G
        Bh = jnp.repeat(Bm[:, 0], J, axis=1)                        # (B, H, N)
        Ch = jnp.repeat(Cm[:, 0], J, axis=1)
        h = (jnp.exp(dt[:, 0] * A)[:, :, None, None] * h0
             + (dt[:, 0, :, None] * xs[:, 0])[..., None] * Bh[:, :, None, :])
        y = jnp.einsum("bhpn,bhn->bhp", h, Ch)[:, None]             # (B, 1, H, P)
    else:
        # --- prefill: SSD over chunks -------------------------------------
        L = min(mc.chunk_size, S)
        while S % L:
            L //= 2
        nc = S // L
        resh = lambda t: jnp.moveaxis(                               # noqa: E731
            t.reshape(Bsz, nc, L, *t.shape[2:]), 1, 0)

        def body(hc, xs_c):
            return _ssd_chunk(hc, *xs_c, A)

        h, ys = jax.lax.scan(jax.checkpoint(body), h0,
                             (resh(xs), resh(Bm), resh(Cm), resh(dt)))
        y = jnp.moveaxis(ys, 0, 1).reshape(Bsz, S, H, P)
    y = y + p["D"][:, None] * xs
    g = y.reshape(Bsz, S, G, di // G) * jax.nn.silu(
        z.astype(jnp.float32)).reshape(Bsz, S, G, di // G)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + cfg.norm_eps)
    g = g.reshape(Bsz, S, di) * p["norm"].astype(jnp.float32)
    out = layers.dense(g.astype(x.dtype), p["w_out"])
    new_state = (Mamba2State(h=h, conv=xp[:, S:].astype(tail.dtype))
                 if state is not None else None)
    return shard(out, "batch", "seq", "embed"), new_state


def init_mamba2_state(cfg, batch: int) -> Mamba2State:
    mc = cfg.mamba
    return Mamba2State(
        h=jnp.zeros((batch, mc.n_heads, mc.head_dim, mc.d_state), jnp.float32),
        conv=jnp.zeros((batch, mc.d_conv - 1, mc.conv_dim(cfg.d_model)),
                       cfg.jax_dtype),
    )


# ---------------------------------------------------------------------------
# xLSTM: mLSTM (matrix memory) and sLSTM (scalar memory) blocks
# ---------------------------------------------------------------------------

def _chunked_scan(step_fn, init_state, seq_tensors, S: int, chunk: int = 64,
                  collect_states: bool = False):
    """Two-level recurrent scan: outer over chunks (carries saved), inner
    over tokens inside a jax.checkpoint'd chunk body.

    Backward memory is O(S/chunk * |state|) saved carries plus one chunk of
    recomputed residuals — without this, AD of a 4k-step scan over the
    mLSTM's (B, H, hd, hd) matrix memory saves ~17 GB/layer.

    seq_tensors: pytree of (B, S, ...) arrays; returns (final_state, ys)
    with ys stacked back to (B, S, ...).

    ``collect_states`` makes ys ``(ys, states)`` where ``states`` carries the
    recurrent state *after every token* (leaves (B, S, ...)).  Speculative
    verification needs this: on a partial draft acceptance the engine restores
    the state at the accepted position — checkpoint-and-restore of the
    recurrence, at token granularity (models/model.py::paged_verify_step).
    """
    chunk = min(chunk, S)
    while S % chunk:
        chunk //= 2
    n_chunks = S // chunk

    def to_chunks(t):  # (B, S, ...) -> (n_chunks, chunk, B, ...)
        B = t.shape[0]
        t = jnp.moveaxis(t, 1, 0).reshape(n_chunks, chunk, B, *t.shape[2:])
        return t

    xs = jax.tree_util.tree_map(to_chunks, seq_tensors)

    if collect_states:
        base_step = step_fn

        def step_fn(s, t):
            ns, y = base_step(s, t)
            return ns, (y, ns)

    def chunk_body(state, chunk_xs):
        state, ys = jax.lax.scan(step_fn, state, chunk_xs)
        return state, ys

    final, ys = jax.lax.scan(jax.checkpoint(chunk_body), init_state, xs)

    def merge(t):  # (n_chunks, chunk, B, ...) -> (B, S, ...)
        t = t.reshape(n_chunks * chunk, *t.shape[2:])
        return jnp.moveaxis(t, 0, 1)

    return final, jax.tree_util.tree_map(merge, ys)

class MLSTMState(NamedTuple):
    C: jax.Array   # (B, H, hd, hd) matrix memory
    n: jax.Array   # (B, H, hd) normalizer
    m: jax.Array   # (B, H) log-space stabilizer


class SLSTMState(NamedTuple):
    c: jax.Array   # (B, H, hd)
    n: jax.Array   # (B, H, hd)
    h: jax.Array   # (B, H, hd)
    m: jax.Array   # (B, H)


def init_mlstm(key, cfg):
    d = cfg.d_model
    di = 2 * d                       # up-projection factor 2 (xLSTM block)
    H = cfg.n_heads
    ks = jax.random.split(key, 7)
    dt = cfg.jax_dtype
    return {
        "w_up": layers._init_dense(ks[0], d, 2 * di, dt),
        "w_q": layers._init_dense(ks[1], di, di, dt),
        "w_k": layers._init_dense(ks[2], di, di, dt),
        "w_v": layers._init_dense(ks[3], di, di, dt),
        "w_i": layers._init_dense(ks[4], di, H, dt),
        "w_f": layers._init_dense(ks[5], di, H, dt),
        "b_i": jnp.zeros((H,), jnp.float32),
        "b_f": jnp.full((H,), 3.0, jnp.float32),   # forget-gate bias init
        "w_down": layers._init_dense(ks[6], di, d, dt),
    }


def mlstm_block(x, p, cfg, *, state: Optional[MLSTMState] = None,
                collect_states: bool = False):
    """mLSTM block: up-proj, matrix-memory recurrence, gated down-proj.

    ``collect_states`` (requires a carried state) returns an MLSTMState with
    an extra position axis (leaves (B, S, ...)): the state after every token,
    for speculative-verification restore at the accepted position."""
    B, S, d = x.shape
    di = 2 * d
    H = cfg.n_heads
    hd = di // H
    up = layers.dense(x, p["w_up"])
    xm, z = jnp.split(up, 2, axis=-1)            # (B, S, di)
    xm = shard(xm, "batch", "seq", "mlp")

    def heads(w):
        return layers.dense(xm, w).reshape(B, S, H, hd).astype(jnp.float32)

    q, k, v = heads(p["w_q"]), heads(p["w_k"]) * hd ** -0.5, heads(p["w_v"])
    # quant="none": gate pre-activations feed log-space exponentials in the
    # recurrence — they stay float under the w8a8 precision mode.
    i_pre = (layers.dense(xm, p["w_i"], quant="none").astype(jnp.float32) + p["b_i"])
    f_pre = (layers.dense(xm, p["w_f"], quant="none").astype(jnp.float32) + p["b_f"])

    if state is None:
        st = MLSTMState(
            C=jnp.zeros((B, H, hd, hd), jnp.float32),
            n=jnp.zeros((B, H, hd), jnp.float32),
            m=jnp.full((B, H), -1e30, jnp.float32),
        )
    else:
        st = state

    def step(s: MLSTMState, t):
        qt, kt, vt, it, ft = t                   # (B,H,hd) x3, (B,H) x2
        log_f = -jax.nn.softplus(-ft)            # log sigmoid(f)
        m_new = jnp.maximum(log_f + s.m, it)
        f_sc = jnp.exp(log_f + s.m - m_new)[..., None]
        i_sc = jnp.exp(it - m_new)[..., None]
        C = f_sc[..., None] * s.C + (i_sc * vt)[..., None] * kt[..., None, :]
        n = f_sc * s.n + i_sc * kt
        denom = jnp.maximum(
            jnp.abs(jnp.einsum("bhd,bhd->bh", n, qt))[..., None], 1.0
        )
        h = jnp.einsum("bhij,bhj->bhi", C, qt) / denom
        return MLSTMState(C, n, m_new), h

    if state is None and S > 1:
        # Chunkwise-parallel form: per-token (hd x hd) matrix-memory updates
        # become (chunk x chunk) flash-like block matmuls — the xLSTM kernel
        # formulation.  Equivalent to the sequential scan (tests), ~50x less
        # HBM traffic at hd=512 (EXPERIMENTS.md §Perf, xlstm).
        hs, _ = _mlstm_chunkwise(q, k, v, i_pre, f_pre, st)
        h = hs.reshape(B, S, di).astype(x.dtype)
        new_state = None
    else:
        assert state is not None or not collect_states, \
            "collect_states needs a carried state"
        new_state, hs = _chunked_scan(step, st, (q, k, v, i_pre, f_pre), S,
                                      collect_states=collect_states)
        if collect_states:
            hs, new_state = hs
        h = hs.reshape(B, S, di).astype(x.dtype)
    out = layers.dense(h * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype), p["w_down"])
    return shard(out, "batch", "seq", "embed"), (new_state if state is not None else None)


def _mlstm_chunkwise(q, k, v, i_pre, f_pre, st: MLSTMState, chunk: int = 64):
    """Chunkwise-parallel stabilized mLSTM.

    Within a chunk (log-space gates): F_t = cumsum(log f), a_s = i_s - F_s,
    M_t = max(m_prev, cummax a_s), decay D[t,s] = exp(a_s - M_t) for s<=t.
      h_t = [exp(m_prev - M_t) (C_prev q_t) + sum_s D[t,s](q_t k_s) v_s]
            / max(|exp(m_prev - M_t)(n_prev q_t) + sum_s D[t,s](q_t k_s)|, 1)
    State closes each chunk with the same quantities at t = chunk.
    q/k/v: (B, S, H, hd) f32; i_pre/f_pre: (B, S, H).
    """
    B, S, H, hd = q.shape
    chunk = min(chunk, S)
    while S % chunk:
        chunk //= 2
    n_chunks = S // chunk

    def to_c(t):  # (B,S,...) -> (n_chunks, B, chunk, ...)
        return jnp.moveaxis(
            t.reshape(B, n_chunks, chunk, *t.shape[2:]), 1, 0)

    def chunk_body(state, xs):
        qc, kc, vc, ic, fc = xs            # (B, chunk, H, ...) per chunk
        log_f = -jax.nn.softplus(-fc)      # (B, chunk, H)
        F = jnp.cumsum(log_f, axis=1)      # inclusive
        a = ic - F                         # (B, chunk, H)
        M = jnp.maximum(
            state.m[:, None], jax.lax.cummax(a, axis=1))  # (B, chunk, H)
        # intra-chunk: D[t,s] = exp(F_t - F_s + i_s - m_t) = exp(a_s - M_t)
        D = jnp.exp(a[:, None, :, :] - M[:, :, None, :])  # (B, t, s, H)
        tri = jnp.tril(jnp.ones((chunk, chunk), bool))
        D = jnp.where(tri[None, :, :, None], D, 0.0)
        qk = jnp.einsum("bthd,bshd->btsh", qc, kc)        # (B, t, s, H)
        w = D * qk
        num_intra = jnp.einsum("btsh,bshd->bthd", w, vc)
        den_intra = jnp.sum(w, axis=2)                    # (B, t, H)
        # inter-chunk: carry C_prev / n_prev with stabilizer m_prev
        scale = jnp.exp(state.m[:, None] - M)             # (B, t, H)
        num_inter = scale[..., None] * jnp.einsum(
            "bhij,bthj->bthi", state.C, qc)
        den_inter = scale * jnp.einsum("bhd,bthd->bth", state.n, qc)
        num = num_intra + num_inter
        den = den_intra + den_inter
        h = num / jnp.maximum(jnp.abs(den), 1.0)[..., None]  # (B, t, H, hd)
        # close the chunk: state at t = chunk
        M_c = M[:, -1]                                    # (B, H)
        w_end = jnp.exp(a - M_c[:, None])                 # (B, s, H)
        C_new = scale[:, -1][..., None, None] * state.C + jnp.einsum(
            "bsh,bshd,bshe->bhde", w_end, vc, kc)
        n_new = scale[:, -1][..., None] * state.n + jnp.einsum(
            "bsh,bshd->bhd", w_end, kc)
        m_new = F[:, -1] + M_c        # m_t = F_t + M_t at t = chunk
        return MLSTMState(C_new, n_new, m_new), h

    final, hs = jax.lax.scan(
        jax.checkpoint(chunk_body), st,
        (to_c(q), to_c(k), to_c(v), to_c(i_pre), to_c(f_pre)),
    )
    # (n_chunks, B, chunk, H, hd) -> (B, S, H*hd)
    hs = jnp.moveaxis(hs, 0, 1).reshape(B, S, H, hd)
    return hs.reshape(B, S, H * hd), final


def init_slstm(key, cfg):
    d = cfg.d_model
    H = cfg.n_heads
    hd = d // H
    ks = jax.random.split(key, 9)
    dt = cfg.jax_dtype
    p = {f"w_{g}": layers._init_dense(ks[i], d, d, dt) for i, g in enumerate("izfo")}
    p.update({f"r_{g}": (jax.random.normal(ks[4 + i], (H, hd, hd)) * hd ** -0.5).astype(dt)
              for i, g in enumerate("izfo")})
    p["b_f"] = jnp.full((H, hd), 3.0, jnp.float32)
    k_up, k_dn = jax.random.split(ks[8])
    ff = int(8 / 3 * d) // 8 * 8
    p["w_ff_up"] = layers._init_dense(k_up, d, 2 * ff, dt)
    p["w_ff_down"] = layers._init_dense(k_dn, ff, d, dt)
    return p


def slstm_block(x, p, cfg, *, state: Optional[SLSTMState] = None,
                collect_states: bool = False):
    """sLSTM block: scalar-memory LSTM with head-wise recurrence + GLU FFN.

    ``collect_states`` (requires a carried state) returns an SLSTMState with
    an extra position axis (leaves (B, S, ...)): the state after every token,
    for speculative-verification restore at the accepted position."""
    B, S, d = x.shape
    H = cfg.n_heads
    hd = d // H
    pre = {
        # quant="none": LSTM gate projections (exponential/gated recurrence
        # inputs) stay float under the w8a8 precision mode.
        g: layers.dense(x, p[f"w_{g}"], quant="none").reshape(B, S, H, hd)
        .astype(jnp.float32)
        for g in "izfo"
    }
    if state is None:
        st = SLSTMState(
            c=jnp.zeros((B, H, hd), jnp.float32),
            n=jnp.zeros((B, H, hd), jnp.float32),
            h=jnp.zeros((B, H, hd), jnp.float32),
            m=jnp.full((B, H), -1e30, jnp.float32),
        )
    else:
        st = state

    rec = {g: p[f"r_{g}"].astype(jnp.float32) for g in "izfo"}

    def step(s: SLSTMState, t):
        def r(g):
            return jnp.einsum("bhj,hij->bhi", s.h, rec[g])

        i_pre = t["i"] + r("i")
        f_pre = t["f"] + r("f") + p["b_f"]
        z_t = jnp.tanh(t["z"] + r("z"))
        o_t = jax.nn.sigmoid(t["o"] + r("o"))
        log_f = -jax.nn.softplus(-f_pre)               # (B, H, hd)
        m_new = jnp.maximum(
            jnp.max(log_f, -1) + s.m, jnp.max(i_pre, -1)
        )                                              # (B, H)
        f_sc = jnp.exp(log_f + (s.m - m_new)[..., None])
        i_sc = jnp.exp(i_pre - m_new[..., None])
        c = f_sc * s.c + i_sc * z_t
        n = f_sc * s.n + i_sc
        h = o_t * c / jnp.maximum(n, 1.0)
        return SLSTMState(c, n, h, m_new), h

    assert state is not None or not collect_states, \
        "collect_states needs a carried state"
    new_state, hs = _chunked_scan(step, st, pre, S,
                                  collect_states=collect_states)
    if collect_states:
        hs, new_state = hs
    h = hs.reshape(B, S, d).astype(x.dtype)
    # GLU feed-forward (proj factor 4/3, xLSTM-style), fused into the block.
    up = layers.dense(h, p["w_ff_up"])
    a, b = jnp.split(up, 2, axis=-1)
    out = layers.dense(jax.nn.gelu(a.astype(jnp.float32)).astype(x.dtype) * b, p["w_ff_down"])
    return shard(out, "batch", "seq", "embed"), (new_state if state is not None else None)


def init_mlstm_state(cfg, batch: int) -> MLSTMState:
    di = 2 * cfg.d_model
    H = cfg.n_heads
    hd = di // H
    return MLSTMState(
        C=jnp.zeros((batch, H, hd, hd), jnp.float32),
        n=jnp.zeros((batch, H, hd), jnp.float32),
        m=jnp.full((batch, H), -1e30, jnp.float32),
    )


def init_slstm_state(cfg, batch: int) -> SLSTMState:
    H = cfg.n_heads
    hd = cfg.d_model // H
    z = lambda: jnp.zeros((batch, H, hd), jnp.float32)
    return SLSTMState(c=z(), n=z(), h=z(), m=jnp.full((batch, H), -1e30, jnp.float32))
