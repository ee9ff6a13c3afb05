"""Plain float32 reference of a dense GQA decoder (Mistral / Qwen3 layer
equations as published), for the comparison that decides `correct`.

It imports nothing of the program and takes none of its arrays: the weights
are made again from the seed (benchlib/weights.py), upcast from the served
bf16 values (exact), and every product runs at Precision.HIGHEST.  It runs
layer by layer over one request at a time, with attention in blocks of
queries, so that it fits beside nothing else on one chip.

Per layer, for x (T, d) at positions 0..T-1:

  h = rms(x) * attn_norm;  q, k, v = h wq, h wk, h wv   (heads of D)
  [qk-norm: q = rms(q) * q_norm, k = rms(k) * k_norm, per head]
  q, k = rope(q), rope(k)            (rotate-half, base rope_theta)
  x += softmax(q k^T / sqrt(D), causal) v  wo      (GQA: Hq/Hkv per kv head)
  h = rms(x) * mlp_norm;  x += (silu(h w_gate) * h w_up) w_down

then logits = (rms(x) * final_norm) head.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import weights as W

HI = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512
BUCKETS = (1024, 2048, 4096, 8192, 16384)
# Served rows are padded to a power of two, at least this many, so that the
# head's programs take one of a few shapes and a run finds them compiled:
# a request still decoding at the close has a length of its own.
MIN_ROWS = 128


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos[:, None].astype(jnp.float32) * freqs          # (T, D/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def _q8(x, axis):
    """Symmetric int8 codes of x and their scales along `axis`."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(x / s), -127, 127).astype(jnp.int8), s


def _mm_int8(a, b):
    """The control's product: a per row and b per column as int8, summed
    in int32 (w8a8, the precision below bf16)."""
    qa, sa = _q8(a, -1)
    qb, sb = _q8(b, 0)
    acc = jax.lax.dot(qa, qb, preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * sa * sb


def _kv_int8(x):
    """K or V (T, Hkv, D) as the int8 KV pool holds it: codes with one
    scale per position and kv head."""
    q, s = _q8(x, -1)
    return q.astype(jnp.float32) * s


MATMULS = {"float32": _mm, "int8": _mm_int8}
# The control also keeps K and V in its precision, as the program's own
# int8 serving path (w8a8 weights with an int8 KV pool) does.
KV = {"float32": lambda x: x, "int8": _kv_int8}


@functools.partial(jax.jit, static_argnames=("m", "precision"))
def _layer(x, w: Dict[str, jax.Array], *, m, precision="float32"):
    mm = MATMULS[precision]
    T = x.shape[0]
    D, Hq, Hkv = m.head_dim, m.n_heads, m.n_kv_heads
    pos = jnp.arange(T)
    h = _rms(x, w["attn_norm"], m.norm_eps)
    q = mm(h, w["wq"]).reshape(T, Hq, D)
    k = mm(h, w["wk"]).reshape(T, Hkv, D)
    v = mm(h, w["wv"]).reshape(T, Hkv, D)
    if m.qk_norm:
        q = _rms(q, w["q_norm"], m.norm_eps)
        k = _rms(k, w["k_norm"], m.norm_eps)
    q = _rope(q, pos, m.rope_theta)
    k = KV[precision](_rope(k, pos, m.rope_theta))
    v = KV[precision](v)
    rep = Hq // Hkv
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    qb = q.reshape(T // QUERY_BLOCK, QUERY_BLOCK, Hq, D)

    def block(args):
        i, qi = args
        s = jnp.einsum("qhd,khd->hqk", qi, k, precision=HI) * D ** -0.5
        rows = i * QUERY_BLOCK + jnp.arange(QUERY_BLOCK)
        s = jnp.where(rows[None, :, None] >= pos[None, None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    o = jax.lax.map(block, (jnp.arange(T // QUERY_BLOCK), qb))
    x = x + mm(o.reshape(T, Hq * D), w["wo"])
    h = _rms(x, w["mlp_norm"], m.norm_eps)
    g = jax.nn.silu(mm(h, w["w_gate"]))
    return x + mm(g * mm(h, w["w_up"]), w["w_down"])


@functools.partial(jax.jit, static_argnames=("m",))
def _layer_weights(key, layer, *, m):
    return {n: W.layer_tensor(key, layer, n, s).astype(jnp.float32)
            for n, s in W.layer_shapes(m).items()}


@functools.partial(jax.jit, static_argnames=("m", "name"))
def _global(key, *, m, name):
    return W.global_tensor(key, name, W.global_shapes(m)[name])


@functools.partial(jax.jit, static_argnames=("rows", "precision"))
def _logits(x, start, final_norm, head, eps, *, rows, precision="float32"):
    """Logits of `rows` positions of x from `start` (past x's end: zeros)."""
    x = jnp.pad(x, ((0, rows), (0, 0)))
    hidden = jax.lax.dynamic_slice_in_dim(x, start, rows)
    return MATMULS[precision](_rms(hidden, final_norm, eps), head)


@jax.jit
def _argmax(logits):
    return jnp.argmax(logits, axis=-1)


@jax.jit
def _gaps(logits, tokens):
    """Each row's gap of `tokens` below the row's best logit."""
    got = jnp.take_along_axis(logits, tokens[:, None], axis=-1)[:, 0]
    return jnp.max(logits, axis=-1) - got


def row_bucket(n: int) -> int:
    return max(MIN_ROWS, 1 << max(0, n - 1).bit_length())


def bucket(n: int) -> int:
    for b in BUCKETS:
        if n <= b:
            return b
    return -(-n // QUERY_BLOCK) * QUERY_BLOCK


def served_gaps(m, seed: int, requests: List[tuple],
                control: Optional[str] = None) -> Dict[str, List[np.ndarray]]:
    """For each (prompt, served tokens): the reference's gap of every
    served token below its best logit at that position (0 where the
    served token is the reference's own choice), under "served".  Inputs
    are the prompt and all but the last served token, padded at the end
    to a bucket (causal: padding never reaches an earlier position).

    With `control` ("int8"), also the control, under its name: the same
    forward over the same inputs with every projection, the head and the
    K and V it attends over in that precision, and at each position the
    reference's gap of the token the control puts first."""
    precisions = ["float32"] + ([control] if control else [])
    key = W.base_key(seed)
    embed = _global(key, m=m, name="embed")
    seqs = []
    for prompt, served in requests:
        toks = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        T = bucket(len(toks))
        padded = np.zeros((T,), np.int32)
        padded[:len(toks)] = toks
        seqs.append(embed[jnp.asarray(padded)].astype(jnp.float32))
    del embed
    runs = {p: list(seqs) for p in precisions}
    del seqs
    for layer in range(m.n_layers):
        w = _layer_weights(key, layer, m=m)
        for p in precisions:
            runs[p] = [_layer(x, w, m=m, precision=p) for x in runs[p]]
        del w
    final_norm = _global(key, m=m, name="final_norm").astype(jnp.float32)
    head = _global(key, m=m, name="head").astype(jnp.float32)
    out = {"served": [], **({control: []} if control else {})}
    for i, (prompt, served) in enumerate(requests):
        n, rows = len(served), row_bucket(len(served))
        start = jnp.int32(len(prompt) - 1)
        tokens = np.zeros((rows,), np.int32)
        tokens[:n] = served
        ref = _logits(runs["float32"][i], start, final_norm, head,
                      m.norm_eps, rows=rows)
        out["served"].append(np.asarray(
            _gaps(ref, jnp.asarray(tokens)), np.float64)[:n])
        if control:
            ctl = _logits(runs[control][i], start, final_norm, head,
                          m.norm_eps, rows=rows, precision=control)
            out[control].append(np.asarray(
                _gaps(ref, _argmax(ctl)), np.float64)[:n])
    return out
