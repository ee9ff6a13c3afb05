"""Dense GQA decoder: Mistral and Qwen3 layer equations as published.

The architecture module of `mistral` and `qwen3` configurations (the
interface every arch/*.py provides is in benchlib/cells.py).

Tensors per layer, with the shapes of the published checkpoints (x @ w):

  attn_norm (d,)   wq (d, Hq*D)   wk, wv (d, Hkv*D)   wo (Hq*D, d)
  q_norm, k_norm (D,)            [qk-norm models only]
  mlp_norm (d,)    w_gate, w_up (d, F)   w_down (F, d)

plus embed (V, d), final_norm (d,) and an untied head (d, V), drawn by
benchlib/weights.py.

The plain float32 reference (`served_gaps`), per layer, for x (T, d) at
positions 0..T-1:

  h = rms(x) * attn_norm;  q, k, v = h wq, h wk, h wv   (heads of D)
  [qk-norm: q = rms(q) * q_norm, k = rms(k) * k_norm, per head]
  q, k = rope(q), rope(k)            (rotate-half, base rope_theta)
  x += softmax(q k^T / sqrt(D), causal) v  wo      (GQA: Hq/Hkv per kv head)
  h = rms(x) * mlp_norm;  x += (silu(h w_gate) * h w_up) w_down

then logits = (rms(x) * final_norm) head.  It runs layer by layer over one
request at a time, with attention in blocks of queries, so that it fits
beside nothing else on one chip.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp

from benchlib import reference as R
from benchlib import weights as W
from benchlib import work
from benchlib.cells import CellError

# Model types whose layer equations this module implements.
MODEL_TYPES = {"mistral": {"qk_norm": False}, "qwen3": {"qk_norm": True}}
# Every width of these layers is a generic one (cells.WIDTH_KEYS).
WIDTH_KEYS = ()

LAYER_TENSORS = ("attn_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm",
                 "mlp_norm", "w_gate", "w_up", "w_down")
GLOBAL_TENSORS = ("embed", "final_norm", "head")


@dataclasses.dataclass(frozen=True)
class Dims:
    """The numbers of one configuration, under the benchmark's own names."""

    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    norm_eps: float
    rope_theta: float
    qk_norm: bool
    dtype: str

    @property
    def layer_params(self) -> int:
        """Weights of one layer's projections (norm vectors excluded)."""
        d, D = self.d_model, self.head_dim
        attn = d * D * (self.n_heads + 2 * self.n_kv_heads) + self.n_heads * D * d
        return attn + 3 * d * self.d_ff

    @property
    def trunk_params(self) -> int:
        return self.n_layers * self.layer_params

    @property
    def head_params(self) -> int:
        return self.d_model * self.vocab


def check(raw: dict) -> None:
    """Refuse what the reference does not implement."""
    name = raw["name"]
    if raw.get("tie_word_embeddings", False):
        raise CellError(f"configuration {name!r}: tied embeddings are not "
                        f"in the reference")
    if raw.get("sliding_window") or raw.get("attention_bias"):
        raise CellError(f"configuration {name!r}: windows and attention "
                        f"biases are not in the reference")
    if raw.get("hidden_act", "silu") != "silu":
        raise CellError(f"configuration {name!r}: activation "
                        f"{raw['hidden_act']!r} is not in the reference")


def dims(raw: dict) -> Dims:
    return Dims(
        name=raw["name"],
        n_layers=int(raw["num_hidden_layers"]),
        d_model=int(raw["hidden_size"]),
        n_heads=int(raw["num_attention_heads"]),
        n_kv_heads=int(raw["num_key_value_heads"]),
        head_dim=int(raw["head_dim"]),
        d_ff=int(raw["intermediate_size"]),
        vocab=int(raw["vocab_size"]),
        norm_eps=float(raw["rms_norm_eps"]),
        rope_theta=float(raw["rope_theta"]),
        qk_norm=MODEL_TYPES[raw["model_type"]]["qk_norm"],
        dtype=raw.get("torch_dtype", "bfloat16"),
    )


def program_config(conf):
    """The program's ArchConfig: the repository's published configuration
    with the depth (and the published norm epsilon and rope base) of the
    file; every width is checked against the file."""
    from benchlib import system

    m = conf.dims
    return system.program_config(
        conf, dict(n_layers=m.n_layers, norm_eps=m.norm_eps,
                   rope_theta=m.rope_theta, dtype=m.dtype),
        dict(d_model=m.d_model, n_heads=m.n_heads, n_kv_heads=m.n_kv_heads,
             resolved_head_dim=m.head_dim, d_ff=m.d_ff, vocab=m.vocab,
             qk_norm=m.qk_norm, tie_embeddings=False, qkv_bias=False,
             family="dense", mlp_variant="swiglu", norm="rms",
             post_block_norm=False, local_window=None, moe=None))


# --- weights ---------------------------------------------------------------

def layer_shapes(m: Dims) -> dict:
    """name -> shape of one layer's tensors."""
    d, D, F = m.d_model, m.head_dim, m.d_ff
    shapes = {
        "attn_norm": (d,), "wq": (d, m.n_heads * D),
        "wk": (d, m.n_kv_heads * D), "wv": (d, m.n_kv_heads * D),
        "wo": (m.n_heads * D, d), "mlp_norm": (d,),
        "w_gate": (d, F), "w_up": (d, F), "w_down": (F, d),
    }
    if m.qk_norm:
        shapes["q_norm"] = (D,)
        shapes["k_norm"] = (D,)
    return shapes


def global_shapes(m: Dims) -> dict:
    return {"embed": (m.vocab, m.d_model), "final_norm": (m.d_model,),
            "head": (m.d_model, m.vocab)}


def _layer_tensor(key, layer, name, shape, dtype=jnp.bfloat16):
    return W.layer_tensor(key, LAYER_TENSORS, layer, name, shape, dtype)


def _global_tensor(key, name, shape, dtype=jnp.bfloat16):
    return W.global_tensor(key, GLOBAL_TENSORS, name, shape, dtype)


def program_tree(key, m: Dims, cfg):
    """The program's parameter pytree (models/model.py layout: layers
    stacked per scanned group, sub-layer i of group g is layer g*G + i)."""
    dt = jnp.dtype(m.dtype)
    G, n_groups = cfg.group_size, cfg.n_groups
    shapes = layer_shapes(m)

    def stacked(i, name):
        return jnp.stack([_layer_tensor(key, g * G + i, name, shapes[name], dt)
                          for g in range(n_groups)])

    blocks = {}
    for i in range(G):
        mixer = {n: stacked(i, n) for n in ("wq", "wk", "wv", "wo")}
        if m.qk_norm:
            mixer["q_norm"] = stacked(i, "q_norm")
            mixer["k_norm"] = stacked(i, "k_norm")
        blocks[f"sub{i}"] = {
            "norm1": stacked(i, "attn_norm"),
            "mixer": mixer,
            "norm2": stacked(i, "mlp_norm"),
            "ffn": {n: stacked(i, n) for n in ("w_gate", "w_up", "w_down")},
        }
    gs = global_shapes(m)
    return {
        "embed": _global_tensor(key, "embed", gs["embed"], dt),
        "final_norm": _global_tensor(key, "final_norm", gs["final_norm"], dt),
        "head": _global_tensor(key, "head", gs["head"], dt),
        "blocks": blocks,
    }


# --- the plain reference ---------------------------------------------------

@functools.partial(jax.jit, static_argnames=("m", "precision"))
def _layer(x, w: Dict[str, jax.Array], *, m, precision="float32"):
    mm = R.MATMULS[precision]
    T = x.shape[0]
    D, Hq, Hkv = m.head_dim, m.n_heads, m.n_kv_heads
    pos = jnp.arange(T)
    h = R.rms(x, w["attn_norm"], m.norm_eps)
    q = mm(h, w["wq"]).reshape(T, Hq, D)
    k = mm(h, w["wk"]).reshape(T, Hkv, D)
    v = mm(h, w["wv"]).reshape(T, Hkv, D)
    if m.qk_norm:
        q = R.rms(q, w["q_norm"], m.norm_eps)
        k = R.rms(k, w["k_norm"], m.norm_eps)
    q = R.rope(q, pos, m.rope_theta)
    k = R.KV[precision](R.rope(k, pos, m.rope_theta))
    v = R.KV[precision](v)
    o = R.causal_attention(q, k, v)
    x = x + mm(o.reshape(T, Hq * D), w["wo"])
    h = R.rms(x, w["mlp_norm"], m.norm_eps)
    g = jax.nn.silu(mm(h, w["w_gate"]))
    return x + mm(g * mm(h, w["w_up"]), w["w_down"])


@functools.partial(jax.jit, static_argnames=("m",))
def _layer_weights(key, layer, *, m):
    return {n: _layer_tensor(key, layer, n, s).astype(jnp.float32)
            for n, s in layer_shapes(m).items()}


@functools.partial(jax.jit, static_argnames=("m", "name"))
def _global(key, *, m, name):
    return _global_tensor(key, name, global_shapes(m)[name])


def served_gaps(m: Dims, seed: int, requests: List[tuple],
                control: Optional[str] = None) -> Dict[str, list]:
    """For each (prompt, served tokens): the reference's gap of every
    served token below its best logit at that position, under "served",
    and with `control` ("int8") the control's, under its name
    (reference.compare).  The control is the same forward over the same
    inputs with every projection, the head and the K and V it attends over
    in that precision."""
    precisions = ["float32"] + ([control] if control else [])
    key = W.base_key(seed)
    seqs = R.inputs(_global(key, m=m, name="embed"), requests)
    runs = {p: list(seqs) for p in precisions}
    del seqs
    for layer in range(m.n_layers):
        w = _layer_weights(key, layer, m=m)
        for p in precisions:
            runs[p] = [_layer(x, w, m=m, precision=p) for x in runs[p]]
        del w
    final_norm = _global(key, m=m, name="final_norm").astype(jnp.float32)
    head = _global(key, m=m, name="head").astype(jnp.float32)

    def logits(x, start, rows, precision):
        return R.logits(x, start, final_norm, head, m.norm_eps, rows=rows,
                        precision=precision)

    return R.compare(requests, runs, control, logits)


# --- work the mathematics needs --------------------------------------------

def attn_flops(m: Dims, ctx: int) -> float:
    """Score and value products of one query over `ctx` keys, all layers."""
    return 4.0 * m.n_heads * m.head_dim * ctx * m.n_layers


def prefill_chunk_flops(m: Dims, start: int, chunk: int) -> float:
    """Useful FLOPs of one prefill chunk of `chunk` tokens at positions
    start .. start+chunk-1: the projections of every token, causal
    attention over each token's context, and one head row."""
    ctx_sum = chunk * start + chunk * (chunk + 1) // 2
    return (2.0 * m.trunk_params * chunk + attn_flops(m, 1) * ctx_sum
            + 2.0 * m.head_params)


def decode_flops(m: Dims, contexts: Iterable[int]) -> float:
    """Useful FLOPs of one decode step: each active row runs the trunk and
    the head, and attends over its context."""
    ctx = list(contexts)
    return (2.0 * (m.trunk_params + m.head_params) * len(ctx)
            + sum(attn_flops(m, c) for c in ctx))


def layer_gemms(m: Dims, rows: int) -> List[Tuple[int, int, int]]:
    """(M, K, N) of one layer's projections over `rows` tokens."""
    d, D, F = m.d_model, m.head_dim, m.d_ff
    return [(rows, d, m.n_heads * D), (rows, d, m.n_kv_heads * D),
            (rows, d, m.n_kv_heads * D), (rows, m.n_heads * D, d),
            (rows, d, F), (rows, d, F), (rows, F, d)]


def step_gemms(m: Dims, rows: int,
               head_rows: int) -> List[Tuple[int, int, int]]:
    """Every GeMM of one step: the layers' projections over `rows` tokens
    and the head over `head_rows`."""
    return (layer_gemms(m, rows) * m.n_layers
            + [(head_rows, m.d_model, m.vocab)])


def decode_attn_least_s(m: Dims, contexts: Iterable[int],
                        peaks: work.Peaks) -> float:
    """The least time of one step's decode attention, all layers: the K and
    V of each active row's live context read once, its query read and its
    output written, against the score and value products."""
    ctx = list(contexts)
    kv = sum(2 * c * m.n_kv_heads * m.head_dim for c in ctx)
    qo = 2 * len(ctx) * m.n_heads * m.head_dim
    nbytes = work.BYTES * (kv + qo) * m.n_layers
    ops = sum(attn_flops(m, c) for c in ctx)
    return max(ops / peaks.flops, nbytes / peaks.hbm_bw)
