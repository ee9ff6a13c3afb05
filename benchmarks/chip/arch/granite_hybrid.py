"""Granite 4.0-H (granitemoehybrid): Mamba-2 and NoPE GQA layers, each with
an expert layer of routed experts and a shared expert, as published.

The architecture module of `granitemoehybrid` configurations (the interface
every arch/*.py provides is in benchlib/cells.py).

Tensors per layer, with the shapes of the published checkpoint (x @ w);
H Mamba heads of P, state N, G groups, d_inner = H P, conv_dim = d_inner +
2 G N, E routed experts of which this chip holds the first `held`:

  input_norm (d,)                               every layer
  Mamba-2:   in_proj (d, d_inner + conv_dim + H)   conv_w (d_conv, conv_dim)
             conv_b (conv_dim,)   dt_bias, A_log, D (H,)   mamba_norm (d_inner,)
             out_proj (d_inner, d)
  attention: wq (d, Hq*D)   wk, wv (d, Hkv*D)   wo (Hq*D, d)
  post_norm (d,)   router (d, E)
  w_gate, w_up (held, d, F)   w_down (held, F, d)   (expert e is drawn on
             its own, so a chip's share is the same numbers at any cut)
  shared_gate, shared_up (d, Fs)   shared_down (Fs, d)

plus embed (V, d) and final_norm (d,), drawn by benchlib/weights.py; the
head is the embedding (tied).

The plain float32 reference (`served_gaps`), per layer, for x (T, d):

  Mamba-2 layer:
    z, xBC, dt = rms(x) in_proj;  xBC = silu(causal_conv(xBC) + conv_b)
    x_, B, C = split(xBC);  dt = softplus(dt + dt_bias);  A = -exp(A_log)
    token by token:  h <- exp(dt A) h + dt x_ (x) B;  y = h C + D x_
    x += 0.22 (rms(y silu(z)) mamba_norm) out_proj      (1 group: all d_inner)
  attention layer (no position embedding):
    x += 0.22 softmax(q k^T * attention_multiplier, causal) v wo
  every layer:
    h = rms(x) post_norm;  top-10 of the router's E logits, softmax over them
    x += 0.22 (sum over held experts e of w_e swiglu_e(h) + swiglu_s(h))

with x = embed[tok] * 12 first and logits = rms(x) final_norm embed^T / 16
last.  Experts not held here add nothing, in the program and here alike.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp

from benchlib import reference as R
from benchlib import weights as W
from benchlib import work
from benchlib.cells import CellError

MODEL_TYPES = {"granitemoehybrid": {}}
# Published widths of these layers beyond the generic ones (cells.WIDTH_KEYS).
WIDTH_KEYS = ("mamba_n_heads", "mamba_d_head", "mamba_d_state",
              "mamba_n_groups", "mamba_d_conv", "mamba_expand",
              "shared_intermediate_size", "num_experts_per_tok")

LAYER_TENSORS = ("input_norm", "in_proj", "conv_w", "conv_b", "dt_bias",
                 "A_log", "D", "mamba_norm", "out_proj", "wq", "wk", "wv",
                 "wo", "post_norm", "router", "w_gate", "w_up", "w_down",
                 "shared_gate", "shared_up", "shared_down")
GLOBAL_TENSORS = ("embed", "final_norm")
# Tensors the program holds in float32 (the rest in the served dtype).
F32_TENSORS = ("dt_bias", "A_log", "D", "router")
KINDS = {"mamba": "mamba", "attention": "attn"}


@dataclasses.dataclass(frozen=True)
class Dims:
    """The numbers of one configuration, under the benchmark's own names."""

    name: str
    n_layers: int
    kinds: Tuple[str, ...]         # "mamba" | "attn" per layer
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    vocab: int
    norm_eps: float
    m_heads: int                   # Mamba-2 heads, each of m_head_dim
    m_head_dim: int
    d_state: int
    n_groups: int
    d_conv: int
    expand: int
    chunk_size: int
    n_experts: int                 # the router's outputs (published)
    held: int                      # experts this chip holds: 0 .. held-1
    top_k: int
    d_ff_expert: int
    shared_d_ff: int
    embedding_multiplier: float
    attention_multiplier: float
    residual_multiplier: float
    logits_scaling: float
    dtype: str

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.d_state

    @property
    def n_mamba(self) -> int:
        return self.kinds.count("mamba")

    @property
    def n_attn(self) -> int:
        return self.kinds.count("attn")

    @property
    def mamba_params(self) -> int:
        """One Mamba-2 layer's projections (in_proj, out_proj)."""
        return self.d_model * (self.d_inner + self.conv_dim + self.m_heads) \
            + self.d_inner * self.d_model

    @property
    def attn_params(self) -> int:
        d, D = self.d_model, self.head_dim
        return d * D * (self.n_heads + 2 * self.n_kv_heads) \
            + self.n_heads * D * d

    @property
    def expert_params(self) -> int:
        return 3 * self.d_model * self.d_ff_expert

    @property
    def shared_params(self) -> int:
        return 3 * self.d_model * self.shared_d_ff

    @property
    def router_params(self) -> int:
        return self.d_model * self.n_experts

    @property
    def state_elems(self) -> int:
        """Elements of one slot's h in one Mamba-2 layer."""
        return self.m_heads * self.m_head_dim * self.d_state

    @property
    def conv_tail_elems(self) -> int:
        """Elements of one slot's conv tail in one Mamba-2 layer."""
        return (self.d_conv - 1) * self.conv_dim


def check(raw: dict) -> None:
    """Refuse what the reference does not implement."""
    name = raw["name"]
    if not raw.get("tie_word_embeddings", False):
        raise CellError(f"configuration {name!r}: the reference has a tied "
                        f"head only")
    if raw.get("position_embedding_type") != "nope":
        raise CellError(f"configuration {name!r}: the reference's attention "
                        f"has no position embedding")
    if raw.get("attention_bias") or raw.get("mamba_proj_bias"):
        raise CellError(f"configuration {name!r}: biases on the attention "
                        f"or Mamba projections are not in the reference")
    if not raw.get("mamba_conv_bias", False):
        raise CellError(f"configuration {name!r}: the reference's conv has "
                        f"a bias")
    if raw.get("hidden_act", "silu") != "silu" or \
            raw.get("normalization_function", "rmsnorm") != "rmsnorm":
        raise CellError(f"configuration {name!r}: only SiLU and RMSNorm are "
                        f"in the reference")
    types = raw.get("layer_types", [])
    if len(types) != int(raw["num_hidden_layers"]) or \
            set(types) - set(KINDS):
        raise CellError(f"configuration {name!r}: layer_types must name "
                        f"each of the layers, as 'mamba' or 'attention'")


def dims(raw: dict) -> Dims:
    d, hq = int(raw["hidden_size"]), int(raw["num_attention_heads"])
    return Dims(
        name=raw["name"],
        n_layers=int(raw["num_hidden_layers"]),
        kinds=tuple(KINDS[t] for t in raw["layer_types"]),
        d_model=d, n_heads=hq,
        n_kv_heads=int(raw["num_key_value_heads"]),
        head_dim=int(raw.get("head_dim") or d // hq),
        vocab=int(raw["vocab_size"]),
        norm_eps=float(raw["rms_norm_eps"]),
        m_heads=int(raw["mamba_n_heads"]),
        m_head_dim=int(raw["mamba_d_head"]),
        d_state=int(raw["mamba_d_state"]),
        n_groups=int(raw["mamba_n_groups"]),
        d_conv=int(raw["mamba_d_conv"]),
        expand=int(raw["mamba_expand"]),
        chunk_size=int(raw["mamba_chunk_size"]),
        n_experts=int(raw.get("published", {}).get(
            "num_local_experts", raw["num_local_experts"])),
        held=int(raw["num_local_experts"]),
        top_k=int(raw["num_experts_per_tok"]),
        d_ff_expert=int(raw["intermediate_size"]),
        shared_d_ff=int(raw["shared_intermediate_size"]),
        embedding_multiplier=float(raw["embedding_multiplier"]),
        attention_multiplier=float(raw["attention_multiplier"]),
        residual_multiplier=float(raw["residual_multiplier"]),
        logits_scaling=float(raw["logits_scaling"]),
        dtype=raw.get("torch_dtype", "bfloat16"),
    )


def program_config(conf):
    """The program's ArchConfig: the repository's published configuration
    with the depth of the file, checked width by width and layer by layer
    against the file, then holding the file's experts (0 .. held-1)."""
    from benchlib import system
    from repro import configs

    m = conf.dims
    try:
        configs.get(conf.repo_config)
    except KeyError:
        raise CellError(f"{conf.name}: the program has no configuration "
                        f"{conf.repo_config!r}") from None
    from repro.models.config import MambaConfig, MoEConfig

    cfg = system.program_config(
        conf, dict(n_layers=m.n_layers, norm_eps=m.norm_eps, dtype=m.dtype),
        dict(d_model=m.d_model, n_heads=m.n_heads, n_kv_heads=m.n_kv_heads,
             resolved_head_dim=m.head_dim, vocab=m.vocab, family="hybrid",
             tie_embeddings=True, norm="rms", post_block_norm=False,
             qk_norm=False, qkv_bias=False, nope=True, local_window=None,
             mamba=MambaConfig(d_state=m.d_state, d_conv=m.d_conv,
                               expand=m.expand, n_heads=m.m_heads,
                               head_dim=m.m_head_dim, n_groups=m.n_groups,
                               chunk_size=m.chunk_size),
             moe=MoEConfig(num_experts=m.n_experts, top_k=m.top_k,
                           d_ff_expert=m.d_ff_expert,
                           shared_d_ff=m.shared_d_ff, drop_free=True),
             moe_every=1,
             embedding_multiplier=m.embedding_multiplier,
             attention_multiplier=m.attention_multiplier,
             residual_multiplier=m.residual_multiplier,
             logits_scaling=m.logits_scaling))
    kinds = cfg.layer_kinds() * cfg.n_groups
    if kinds != m.kinds:
        raise CellError(f"{conf.name}: the program's layers {kinds} are not "
                        f"the file's {m.kinds}")
    return dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, first_expert=0, experts_held=m.held))


# --- weights ---------------------------------------------------------------

def layer_shapes(m: Dims, kind: str) -> dict:
    """name -> shape of the tensors of one layer of `kind`."""
    d, F, Fs = m.d_model, m.d_ff_expert, m.shared_d_ff
    shapes = {"input_norm": (d,), "post_norm": (d,),
              "router": (d, m.n_experts),
              "w_gate": (m.held, d, F), "w_up": (m.held, d, F),
              "w_down": (m.held, F, d),
              "shared_gate": (d, Fs), "shared_up": (d, Fs),
              "shared_down": (Fs, d)}
    if kind == "mamba":
        H, cd = m.m_heads, m.conv_dim
        shapes.update({
            "in_proj": (d, m.d_inner + cd + H), "conv_w": (m.d_conv, cd),
            "conv_b": (cd,), "dt_bias": (H,), "A_log": (H,), "D": (H,),
            "mamba_norm": (m.d_inner,), "out_proj": (m.d_inner, d)})
    else:
        D = m.head_dim
        shapes.update({"wq": (d, m.n_heads * D), "wk": (d, m.n_kv_heads * D),
                       "wv": (d, m.n_kv_heads * D),
                       "wo": (m.n_heads * D, d)})
    return shapes


def global_shapes(m: Dims) -> dict:
    return {"embed": (m.vocab, m.d_model), "final_norm": (m.d_model,)}


def _experts(key, shape):
    """Experts 0 .. shape[0]-1, each drawn from its own key: N(0, 1/fan_in)
    with fan_in the expert's input width."""
    n, fan_in = shape[0], shape[1]
    draw = lambda e: jax.random.normal(                       # noqa: E731
        jax.random.fold_in(key, e), shape[1:], jnp.float32) * fan_in ** -0.5
    return jax.vmap(draw)(jnp.arange(n))


def _dt_bias(key, shape):
    """The inverse softplus of dt ~ U(0.001, 0.1)."""
    dt = jax.random.uniform(key, shape, jnp.float32, 1e-3, 0.1)
    return dt + jnp.log(-jnp.expm1(-dt))


def init(name: str):
    """Draws of the tensors that weights.py's default does not suit.  The
    embedding is N(0, 0.001^2): at the default 0.02, the tied head and the
    embedding multiplier of 12 put each position's own token some 18
    standard deviations above the rest of the vocabulary, so every
    precision would copy the last token and the comparison could not
    fail."""
    return {
        "A_log": lambda k, s: jnp.log(
            jax.random.uniform(k, s, jnp.float32, 1.0, 16.0)),
        "dt_bias": _dt_bias,
        "D": lambda k, s: jnp.ones(s, jnp.float32),
        "embed": lambda k, s: 0.001 * jax.random.normal(k, s, jnp.float32),
        "w_gate": _experts, "w_up": _experts, "w_down": _experts,
    }.get(name)


def _layer_tensor(key, layer, name, shape, dtype=jnp.bfloat16):
    return W.layer_tensor(key, LAYER_TENSORS, layer, name, shape, dtype,
                          init=init)


def _global_tensor(key, name, shape, dtype=jnp.bfloat16):
    return W.global_tensor(key, GLOBAL_TENSORS, name, shape, dtype,
                           init=init)


# The program's names: (kind, sub-tree, name) -> this module's tensor.
_MIXER = {
    "mamba": {"w_in": "in_proj", "conv_w": "conv_w", "conv_b": "conv_b",
              "dt_bias": "dt_bias", "A_log": "A_log", "D": "D",
              "norm": "mamba_norm", "w_out": "out_proj"},
    "attn": {"wq": "wq", "wk": "wk", "wv": "wv", "wo": "wo"},
}
_FFN = {"router": "router", "w_gate": "w_gate", "w_up": "w_up",
        "w_down": "w_down"}
_SHARED = {"w_gate": "shared_gate", "w_up": "shared_up",
           "w_down": "shared_down"}


def program_tree(key, m: Dims, cfg):
    """The program's parameter pytree (models/model.py layout: layers
    stacked per scanned group, sub-layer i of group g is layer g*G + i)."""
    dt = jnp.dtype(m.dtype)
    G, n_groups = cfg.group_size, cfg.n_groups

    def stacked(i, name):
        kind = m.kinds[i]
        shape = layer_shapes(m, kind)[name]
        return jnp.stack([
            _layer_tensor(key, g * G + i, name, shape,
                          jnp.float32 if name in F32_TENSORS else dt)
            for g in range(n_groups)])

    blocks = {}
    for i in range(G):
        kind = m.kinds[i]
        blocks[f"sub{i}"] = {
            "norm1": stacked(i, "input_norm"),
            "mixer": {n: stacked(i, t) for n, t in _MIXER[kind].items()},
            "norm2": stacked(i, "post_norm"),
            "ffn": {**{n: stacked(i, t) for n, t in _FFN.items()},
                    "shared": {n: stacked(i, t) for n, t in _SHARED.items()}},
        }
    gs = global_shapes(m)
    return {
        "embed": _global_tensor(key, "embed", gs["embed"], dt),
        "final_norm": _global_tensor(key, "final_norm", gs["final_norm"], dt),
        "blocks": blocks,
    }


# --- the plain reference ---------------------------------------------------

def _rows_int8(x):
    """x as int8 codes with one scale per row of its last axis: the
    control's cached state (as the int8 KV pool holds K and V)."""
    return R._kv_int8(x)


def _swiglu(mm, h, gate, up, down):
    return mm(jax.nn.silu(mm(h, gate)) * mm(h, up), down)


@functools.partial(jax.jit, static_argnames=("m", "precision"))
def _mamba(x, w: Dict[str, jax.Array], *, m, precision="float32"):
    """x += 0.22 Mamba-2(rms(x)): the recurrence one token at a time."""
    mm = R.MATMULS[precision]
    state = {"float32": lambda s: s, "int8": _rows_int8}[precision]
    T = x.shape[0]
    di, cd, H, P, N, G = (m.d_inner, m.conv_dim, m.m_heads, m.m_head_dim,
                          m.d_state, m.n_groups)
    h = R.rms(x, w["input_norm"], m.norm_eps)
    zxbcdt = mm(h, w["in_proj"])
    z, xbc, dt = zxbcdt[:, :di], zxbcdt[:, di:di + cd], zxbcdt[:, di + cd:]
    xbc = state(xbc)              # the conv tail the state carries
    xp = jnp.pad(xbc, ((m.d_conv - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(xp[i:i + T] * w["conv_w"][i]
                          for i in range(m.d_conv)) + w["conv_b"])
    xs = xbc[:, :di].reshape(T, H, P)
    Bm = jnp.repeat(xbc[:, di:di + G * N].reshape(T, G, N), H // G, axis=1)
    Cm = jnp.repeat(xbc[:, di + G * N:].reshape(T, G, N), H // G, axis=1)
    dt = jax.nn.softplus(dt + w["dt_bias"])                      # (T, H)
    A = -jnp.exp(w["A_log"])

    def token(hs, t):
        x_t, b_t, c_t, dt_t = t
        hs = (jnp.exp(dt_t * A)[:, None, None] * hs
              + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        hs = state(hs)
        return hs, jnp.einsum("hpn,hn->hp", hs, c_t, precision=R.HI)

    _, y = jax.lax.scan(token, jnp.zeros((H, P, N), jnp.float32),
                        (xs, Bm, Cm, dt))
    y = (y + w["D"][:, None] * xs).reshape(T, di)
    g = (y * jax.nn.silu(z)).reshape(T, G, di // G)
    g = R.rms(g, 1.0, m.norm_eps).reshape(T, di) * w["mamba_norm"]
    return x + m.residual_multiplier * mm(g, w["out_proj"])


@functools.partial(jax.jit, static_argnames=("m", "precision"))
def _attn(x, w: Dict[str, jax.Array], *, m, precision="float32"):
    """x += 0.22 NoPE GQA attention(rms(x)), softmax scale
    attention_multiplier."""
    mm = R.MATMULS[precision]
    T = x.shape[0]
    D, Hq, Hkv = m.head_dim, m.n_heads, m.n_kv_heads
    h = R.rms(x, w["input_norm"], m.norm_eps)
    # causal_attention scales by D ** -0.5; the query carries the rest.
    q = mm(h, w["wq"]).reshape(T, Hq, D) * (m.attention_multiplier * D ** 0.5)
    k = R.KV[precision](mm(h, w["wk"]).reshape(T, Hkv, D))
    v = R.KV[precision](mm(h, w["wv"]).reshape(T, Hkv, D))
    o = R.causal_attention(q, k, v)
    return x + m.residual_multiplier * mm(o.reshape(T, Hq * D), w["wo"])


@functools.partial(jax.jit, static_argnames=("m", "precision"))
def _moe(x, w: Dict[str, jax.Array], *, m, precision="float32"):
    """x += 0.22 (routed experts held here + the shared expert)(rms(x))."""
    mm = R.MATMULS[precision]
    h = R.rms(x, w["post_norm"], m.norm_eps)
    top, idx = jax.lax.top_k(mm(h, w["router"]), m.top_k)        # (T, k)
    gates = jax.nn.softmax(top, axis=-1)
    y = _swiglu(mm, h, w["shared_gate"], w["shared_up"], w["shared_down"])
    for e in range(m.held):
        g = jnp.sum(jnp.where(idx == e, gates, 0.0), axis=-1)     # (T,)
        y = y + g[:, None] * _swiglu(mm, h, w["w_gate"][e], w["w_up"][e],
                                     w["w_down"][e])
    return x + m.residual_multiplier * y


@functools.partial(jax.jit, static_argnames=("m", "kind"))
def _layer_weights(key, layer, *, m, kind):
    return {n: _layer_tensor(key, layer, n, s, jnp.float32 if n in F32_TENSORS
                             else jnp.bfloat16).astype(jnp.float32)
            for n, s in layer_shapes(m, kind).items()}


@functools.partial(jax.jit, static_argnames=("m", "name"))
def _global(key, *, m, name):
    return _global_tensor(key, name, global_shapes(m)[name])


_MIXERS = {"mamba": _mamba, "attn": _attn}


def served_gaps(m: Dims, seed: int, requests: List[tuple],
                control: Optional[str] = None) -> Dict[str, list]:
    """For each (prompt, served tokens): the reference's gap of every
    served token below its best logit at that position, under "served",
    and with `control` ("int8") the control's, under its name
    (reference.compare).  The control is the same forward over the same
    inputs with every product, the head, K and V, the conv inputs and the
    Mamba state in that precision."""
    precisions = ["float32"] + ([control] if control else [])
    key = W.base_key(seed)
    embed = _global(key, m=m, name="embed")
    seqs = [x * m.embedding_multiplier for x in R.inputs(embed, requests)]
    runs = {p: list(seqs) for p in precisions}
    del seqs
    for layer, kind in enumerate(m.kinds):
        w = _layer_weights(key, layer, m=m, kind=kind)
        for p in precisions:
            runs[p] = [_moe(_MIXERS[kind](x, w, m=m, precision=p), w, m=m,
                            precision=p) for x in runs[p]]
        del w
    final_norm = _global(key, m=m, name="final_norm").astype(jnp.float32)
    # The tied head, with the logits' 1/16 folded in (a power of two: exact).
    head = embed.astype(jnp.float32).T * (1.0 / m.logits_scaling)
    del embed

    def logits(x, start, rows, precision):
        return R.logits(x, start, final_norm, head, m.norm_eps, rows=rows,
                        precision=precision)

    return R.compare(requests, runs, control, logits)


# --- work the mathematics needs --------------------------------------------

def attn_flops(m: Dims, ctx: int) -> float:
    """Score and value products of one query over `ctx` keys, all
    attention layers."""
    return 4.0 * m.n_heads * m.head_dim * ctx * m.n_attn


def ssm_flops(m: Dims) -> float:
    """One token's recurrence in one Mamba-2 layer: decay (1), input outer
    product (2) and readout (2) per state element, and the conv (2 per tap
    and channel)."""
    return 5.0 * m.state_elems + 2.0 * m.d_conv * m.conv_dim


def expert_pairs(m: Dims) -> float:
    """Routed pairs of one token that land on the experts held here, as
    the shapes give them: top_k of n_experts, held of which are here."""
    return m.top_k * m.held / m.n_experts


def token_flops(m: Dims) -> float:
    """One token's products in every layer, its attention over its context
    left out: the projections, the recurrence, the router, the shared
    expert and its pairs on the held experts."""
    ffn = 2.0 * (m.router_params + m.shared_params
                 + expert_pairs(m) * m.expert_params)
    return (m.n_mamba * (2.0 * m.mamba_params + ssm_flops(m))
            + m.n_attn * 2.0 * m.attn_params + m.n_layers * ffn)


def head_flops(m: Dims) -> float:
    return 2.0 * m.d_model * m.vocab


def prefill_chunk_flops(m: Dims, start: int, chunk: int) -> float:
    """Useful FLOPs of one prefill chunk of `chunk` tokens at positions
    start .. start+chunk-1: every token's layers, causal attention over
    each token's context, and one head row."""
    ctx_sum = chunk * start + chunk * (chunk + 1) // 2
    return token_flops(m) * chunk + attn_flops(m, 1) * ctx_sum + head_flops(m)


def decode_flops(m: Dims, contexts: Iterable[int]) -> float:
    """Useful FLOPs of one decode step: each active row runs the layers and
    the head, and attends over its context."""
    ctx = list(contexts)
    return ((token_flops(m) + head_flops(m)) * len(ctx)
            + sum(attn_flops(m, c) for c in ctx))


def experts_touched(m: Dims, rows: int) -> int:
    """Held experts a step over `rows` tokens can touch."""
    return min(rows * m.top_k, m.held)


def expert_rows(m: Dims, rows: int) -> int:
    """Rows each touched expert multiplies: the step's pairs spread over
    the router's experts, rounded up."""
    return math.ceil(rows * m.top_k / m.n_experts)


def layer_gemms(m: Dims, kind: str, rows: int) -> List[Tuple[int, int, int]]:
    """(M, K, N) of one layer's products over `rows` tokens."""
    d = m.d_model
    if kind == "mamba":
        mixer = [(rows, d, m.d_inner + m.conv_dim + m.m_heads),
                 (rows, m.d_inner, d)]
    else:
        D = m.head_dim
        mixer = [(rows, d, m.n_heads * D), (rows, d, m.n_kv_heads * D),
                 (rows, d, m.n_kv_heads * D), (rows, m.n_heads * D, d)]
    Fs, F, M = m.shared_d_ff, m.d_ff_expert, expert_rows(m, rows)
    return (mixer + [(rows, d, m.n_experts), (rows, d, Fs), (rows, d, Fs),
                     (rows, Fs, d)]
            + [(M, d, F), (M, d, F), (M, F, d)] * experts_touched(m, rows))


def step_gemms(m: Dims, rows: int,
               head_rows: int) -> List[Tuple[int, int, int]]:
    """Every GeMM of one step: the layers' products over `rows` tokens and
    the head over `head_rows`."""
    out = []
    for kind in m.kinds:
        out += layer_gemms(m, kind, rows)
    return out + [(head_rows, m.d_model, m.vocab)]


def decode_attn_least_s(m: Dims, contexts: Iterable[int],
                        peaks: work.Peaks) -> float:
    """The least time of one step's decode attention, all attention layers:
    the K and V of each active row's live context read once, its query read
    and its output written, against the score and value products."""
    ctx = list(contexts)
    kv = sum(2 * c * m.n_kv_heads * m.head_dim for c in ctx)
    qo = 2 * len(ctx) * m.n_heads * m.head_dim
    nbytes = work.BYTES * (kv + qo) * m.n_attn
    ops = sum(attn_flops(m, c) for c in ctx)
    return max(ops / peaks.flops, nbytes / peaks.hbm_bw)


STATE_BYTES = 4          # the recurrent state h is float32


def layer_state_bytes(m: Dims) -> int:
    """One slot's recurrent state in one Mamba-2 layer: h in float32 and
    the conv tail in the served dtype."""
    return STATE_BYTES * m.state_elems + work.BYTES * m.conv_tail_elems


def state_bytes_per_slot(m: Dims) -> int:
    """One slot's recurrent state over all Mamba-2 layers."""
    return m.n_mamba * layer_state_bytes(m)


def decode_mamba_least_s(m: Dims, rows: int, peaks: work.Peaks) -> float:
    """The least time of one decode step's Mamba-2 mixers over `rows`
    active rows, all Mamba-2 layers: each row's state read and written
    once and the mixer's weights read once, against its products."""
    weights = (m.mamba_params + m.d_conv * m.conv_dim + m.conv_dim
               + m.d_inner) * work.BYTES + 3 * m.m_heads * STATE_BYTES
    nbytes = m.n_mamba * (2 * rows * layer_state_bytes(m) + weights)
    ops = m.n_mamba * rows * (2.0 * m.mamba_params + ssm_flops(m))
    return max(ops / peaks.flops, nbytes / peaks.hbm_bw)


def decode_moe_least_s(m: Dims, rows: int, peaks: work.Peaks) -> float:
    """The least time of one decode step's expert layers over `rows`
    active rows, all layers: the touched experts, the shared expert and the
    router read once, against their products."""
    nbytes = m.n_layers * work.BYTES * (
        experts_touched(m, rows) * m.expert_params + m.shared_params
        + m.router_params)
    ops = m.n_layers * rows * 2.0 * (
        m.router_params + m.shared_params + expert_pairs(m) * m.expert_params)
    return max(ops / peaks.flops, nbytes / peaks.hbm_bw)
