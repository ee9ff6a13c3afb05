"""What the plain float32 references of arch/*.py share, for the comparison
that decides `correct`.

Nothing here imports the program.  Every product runs at
Precision.HIGHEST; the control's runs in int8 (w8a8, the precision below the
served bf16).  Inputs are each request's prompt and all but the last served
token, padded at the end to a bucket (causal: padding never reaches an
earlier position), so a run finds the reference's programs compiled
whatever lengths it served.
"""

from __future__ import annotations

import functools
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512
BUCKETS = (1024, 2048, 4096, 8192, 16384)
# Served rows are padded to a power of two, at least this many, so that the
# head's programs take one of a few shapes and a run finds them compiled:
# a request still decoding at the close has a length of its own.
MIN_ROWS = 128


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, pos, theta):
    """Rotate-half rotary embedding of x (T, H, D) at positions pos (T,)."""
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


# A product by precision: the reference's and the control's.
MATMULS = {"float32": _mm, "int8": _mm_int8}
# The control also keeps K and V in its precision, as the program's own
# int8 serving path (w8a8 weights with an int8 KV pool) does.
KV = {"float32": lambda x: x, "int8": _kv_int8}


def causal_attention(q, k, v):
    """softmax(q k^T / sqrt(D), causal) v for q (T, Hq, D) at positions
    0..T-1 and k, v (T, Hkv, D), Hq/Hkv query heads to a kv head, in blocks
    of QUERY_BLOCK queries; returns (T / QUERY_BLOCK, QUERY_BLOCK, Hq, D)."""
    T, Hq, D = q.shape
    pos = jnp.arange(T)
    rep = Hq // k.shape[1]
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

    return jax.lax.map(block, (jnp.arange(T // QUERY_BLOCK), qb))


def row_bucket(n: int) -> int:
    return max(MIN_ROWS, 1 << max(0, n - 1).bit_length())


def bucket(n: int) -> int:
    for b in BUCKETS:
        if n <= b:
            return b
    return -(-n // QUERY_BLOCK) * QUERY_BLOCK


def inputs(embed, requests: List[tuple]) -> List[jax.Array]:
    """Each request's inputs, embedded by `embed` (V, d) and upcast to
    float32: its prompt and all but its last served token, padded at the
    end to a bucket."""
    seqs = []
    for prompt, served in requests:
        toks = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        padded = np.zeros((bucket(len(toks)),), np.int32)
        padded[:len(toks)] = toks
        seqs.append(embed[jnp.asarray(padded)].astype(jnp.float32))
    return seqs


@functools.partial(jax.jit, static_argnames=("rows", "precision"))
def logits(x, start, final_norm, head, eps, *, rows, precision="float32"):
    """Logits of `rows` positions of x from `start` (past x's end: zeros)."""
    x = jnp.pad(x, ((0, rows), (0, 0)))
    hidden = jax.lax.dynamic_slice_in_dim(x, start, rows)
    return MATMULS[precision](rms(hidden, final_norm, eps), head)


@jax.jit
def _argmax(logits):
    return jnp.argmax(logits, axis=-1)


@jax.jit
def _gaps(logits, tokens):
    """Each row's gap of `tokens` below the row's best logit."""
    got = jnp.take_along_axis(logits, tokens[:, None], axis=-1)[:, 0]
    return jnp.max(logits, axis=-1) - got


def compare(requests: List[tuple], runs: Dict[str, list],
            control: Optional[str],
            head: Callable) -> Dict[str, List[np.ndarray]]:
    """For each (prompt, served tokens): the reference's gap of every
    served token below its best logit at that position (0 where the served
    token is the reference's own choice), under "served"; with `control`,
    under its name, the reference's gap of the token the control puts first
    at each of those positions.  `runs[p][i]` is request i's last hidden
    state in precision p, and `head(x, start, rows, p)` the logits of
    `rows` positions of x from `start` in precision p."""
    out = {"served": [], **({control: []} if control else {})}
    for i, (prompt, served) in enumerate(requests):
        n, rows = len(served), row_bucket(len(served))
        start = jnp.int32(len(prompt) - 1)
        tokens = np.zeros((rows,), np.int32)
        tokens[:n] = served
        ref = head(runs["float32"][i], start, rows, "float32")
        out["served"].append(np.asarray(
            _gaps(ref, jnp.asarray(tokens)), np.float64)[:n])
        if control:
            ctl = head(runs[control][i], start, rows, control)
            out[control].append(np.asarray(
                _gaps(ref, _argmax(ctl)), np.float64)[:n])
    return out
