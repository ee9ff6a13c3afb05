"""Sharding rules: DP / FSDP(ZeRO-3) / TP / EP over the production mesh.

Two rule layers:
  * activation rules — logical names used by `repro.parallel.logical.shard`
    annotations inside the models;
  * parameter rules — path-pattern table mapping every parameter in the zoo
    to a PartitionSpec.

Design (see DESIGN.md §7): batch over ("pod","data"); attention heads, MLP
hidden, experts and vocab over "model" (TP/EP); for models above
`fsdp_threshold` parameters the non-model dimension of every weight is
additionally sharded over the data axes (FSDP) so params + optimizer state
fit HBM, with XLA inserting the all-gather-on-use (overlapped by the
scheduler — the paper's input-pre-fetch mechanism at pod scale).
"""

from __future__ import annotations

import dataclasses
import re
from typing import Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.parallel.logical import Rules


@dataclasses.dataclass(frozen=True)
class ParallelPlan:
    """Resolved parallelism decisions for one (arch, mesh) pair.

    Two modes over the same fixed production mesh:
      * "tp"  — tensor parallel over the "model" axis + DP over pod/data,
        with FSDP over the DP axes for models whose replicated state would
        not fit HBM.  For the 12B-480B archs.
      * "dp"  — the model axis joins the batch axes (pure 256/512-way data
        parallel) and parameters are FSDP-sharded over everything.  For the
        <4B archs whose head/ffn dims cannot feed a 16-way TP axis without
        padding waste (gemma3: 4 heads).
    """

    batch_axes: Tuple[str, ...]          # mesh axes carrying data parallelism
    model_axis: Optional[str] = "model"  # None => dp mode (no TP)
    fsdp: bool = False                   # ZeRO-3 parameter sharding
    fsdp_axes: Tuple[str, ...] = ()      # axes used for FSDP
    # Attention sharding strategy.  Head-TP is only collective-free when the
    # KV heads divide the model axis; otherwise GSPMD re-shards the
    # (B, Hkv, G, S, D) tensors on every KV-block-scan step ("involuntary
    # full rematerialization", ~TBs of all-gather per step).  When heads
    # don't divide, we shard attention over the *sequence* instead: q and
    # the attention output are seq-sharded on the model axis, K/V replicate
    # across it, and the attention projections become model-replicated
    # (still FSDP over the data axes).
    attn_seq: bool = False
    # Serving: parameters are *statically* 2D-sharded instead of FSDP-
    # gathered (there is no optimizer state to shard against, and an
    # all-gather of 132-477B expert weights per decoded token is the
    # baseline's dominant cost).  Expert FFN weights spread (E -> model,
    # d_ff_expert -> data); the contraction over the data-sharded d_ff dim
    # becomes a tiny activation psum instead of a weight gather.
    expert_2d: bool = False

    def activation_rules(self) -> Rules:
        b = self.batch_axes if len(self.batch_axes) > 1 else self.batch_axes[0]
        M = self.model_axis
        return {
            "batch": b,
            # Sequence parallelism (Megatron-SP): in attn_seq mode the
            # residual stream / norms / attention all run seq-sharded on the
            # model axis; XLA inserts the all-gather before the TP FFN
            # matmuls and a reduce-scatter after, so redundant compute on
            # the model axis disappears.
            "seq": M if self.attn_seq else None,
            "embed": None,
            "heads": None if self.attn_seq else M,
            "kv_heads": None if self.attn_seq else M,
            "attn_seq": M if self.attn_seq else None,
            "mlp": M,
            "vocab": M,
            "expert": M,
        }


def make_plan(
    mesh: Mesh,
    param_count: int,
    *,
    n_kv_heads: Optional[int] = None,
    tp_threshold: int = 4_000_000_000,
    fsdp_threshold: int = 8_000_000_000,
    force_fsdp: Optional[bool] = None,
    force_mode: Optional[str] = None,
    force_attn_seq: Optional[bool] = None,
    serving: bool = False,
) -> ParallelPlan:
    axes = mesh.axis_names
    dp_axes = tuple(a for a in axes if a in ("pod", "data"))
    mode = force_mode or ("tp" if param_count > tp_threshold else "dp")
    if "model" not in axes or mesh.shape.get("model", 1) == 1:
        mode = "dp" if force_mode is None else mode
    if serving and mode == "tp":
        # Static weight sharding for inference: no optimizer state to
        # co-shard, so FSDP's per-step gathers are pure overhead.  TP for
        # dense weights; 2D (model x data) for MoE experts.  Prefill keeps
        # the seq-sharded attention rule (long sequences); decode forces it
        # off (S_q = 1, and its tiny tensors reshard for free).
        model_size = mesh.shape["model"]
        if force_attn_seq is not None:
            attn_seq = force_attn_seq
        else:
            attn_seq = bool(n_kv_heads) and (n_kv_heads % model_size != 0)
        return ParallelPlan(
            batch_axes=dp_axes, model_axis="model",
            fsdp=True, fsdp_axes=dp_axes,   # static 2nd axis for big weights
            attn_seq=attn_seq, expert_2d=True,
        )
    if mode == "dp":
        if serving:
            # Small-model serving: static TP over the model axis (a <4B
            # model fits 16-way sharded); FSDP's per-token weight gathers
            # are the dp-mode decode baseline's entire cost.
            return ParallelPlan(
                batch_axes=dp_axes, model_axis="model",
                fsdp=False, fsdp_axes=(), attn_seq=False,
            )
        batch_axes = tuple(a for a in axes)
        return ParallelPlan(
            batch_axes=batch_axes, model_axis=None,
            fsdp=True if force_fsdp is None else force_fsdp,
            fsdp_axes=batch_axes,
        )
    fsdp = param_count > fsdp_threshold if force_fsdp is None else force_fsdp
    model_size = mesh.shape["model"]
    if force_attn_seq is not None:
        attn_seq = force_attn_seq
    else:
        attn_seq = bool(n_kv_heads) and (n_kv_heads % model_size != 0)
    return ParallelPlan(
        batch_axes=dp_axes,
        model_axis="model",
        fsdp=fsdp,
        fsdp_axes=dp_axes if fsdp else (),
        attn_seq=attn_seq,
    )


# --- parameter sharding -------------------------------------------------------

# (path regex, spec builder) — first match wins.  `F` is the FSDP axis group
# (or None), "model" the tensor-parallel axis.
def _param_spec(path: str, ndim: int, plan: ParallelPlan) -> P:
    F = plan.fsdp_axes if plan.fsdp else None
    M = plan.model_axis
    # seq-sharded attention: projections replicate over the model axis
    # (FSDP still shards them over data) — see ParallelPlan.attn_seq.
    AM = None if plan.attn_seq else M
    table = [
        # embeddings / unembedding
        (r"embed$", {2: P(M, F)}),
        (r"head$", {2: P(F, M)}),
        (r"projector$", {2: P(F, M)}),
        # attention projections
        (r"(wq|wk|wv)$", {2: P(F, AM)}),
        (r"(bq|bk|bv)$", {1: P(AM)}),
        (r"wo$", {2: P(AM, F)}),
        # MLP (rank-3 = MoE expert weights; 2D-sharded when serving)
        (r"(w_gate|w_up|w_ff_up)$",
         {2: P(F, M), 3: P(M, None, plan.fsdp_axes) if plan.expert_2d else P(M, F, None)}),
        (r"(w_down|w_ff_down)$",
         {2: P(M, F), 3: P(M, plan.fsdp_axes, None) if plan.expert_2d else P(M, None, F)}),
        (r"(b_up|b_down)$", {1: P(M)}),
        # MoE
        (r"router$", {2: P(F, None)}),
        # Mamba
        (r"w_in$", {2: P(F, M)}),
        (r"conv_[wb]$", {1: P(M), 2: P(None, M)}),
        (r"w_x$", {2: P(M, None)}),
        (r"w_dt$", {2: P(None, M)}),
        (r"b_dt$", {1: P(M)}),
        (r"A_log$", {2: P(M, None)}),
        (r"D$", {1: P(M)}),
        (r"w_out$", {2: P(M, F)}),
        # xLSTM
        (r"w_(q|k|v|i|f)$", {2: P(None, M)}),
        (r"w_[izfo]$", {2: P(F, M)}),
        (r"r_[izfo]$", {3: P(None, None, None)}),
        (r"b_[if]$", {1: P(None), 2: P(None, None)}),
        # norms and everything else: replicated
    ]
    for pat, by_rank in table:
        if re.search(pat, path):
            spec = by_rank.get(ndim)
            if spec is not None:
                return spec
    return P(*([None] * ndim))


def _stacked(spec: P, extra_leading: int) -> P:
    """Prepend `extra_leading` None dims (scan-group / vmap stacking)."""
    return P(*([None] * extra_leading + list(spec)))


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
    return "/".join(parts)


def param_sharding(params, mesh: Mesh, plan: ParallelPlan):
    """NamedSharding pytree for a (possibly group-stacked) params pytree.

    Parameters under the top-level "blocks"/"encoder_blocks" keys carry one
    leading stacking dimension (the scan-group axis); the rule table below is
    written against the *unstacked* rank.
    """

    def leaf(path, x):
        p = _path_str(path)
        extra = 1 if p.split("/", 1)[0] in ("blocks", "encoder_blocks") else 0
        base_rank = x.ndim - extra
        if plan.model_axis is None:
            # dp mode: pure FSDP — shard the largest divisible dim (skipping
            # the stacking dim) over all FSDP axes.
            size = 1
            for a in plan.fsdp_axes:
                size *= mesh.shape[a]
            spec_l: list = [None] * x.ndim
            dims = sorted(range(extra, x.ndim), key=lambda d: -x.shape[d])
            for d in dims:
                if x.shape[d] % size == 0 and x.shape[d] >= size:
                    spec_l[d] = plan.fsdp_axes
                    break
            return NamedSharding(mesh, P(*spec_l))
        spec = _param_spec(p, base_rank, plan)
        spec = _stacked(spec, extra)
        # Guard: drop mesh axes that don't divide the dim (GSPMD would pad;
        # we prefer replication for correctness-of-intent on tiny dims).
        fixed = []
        for dim, ax in zip(x.shape, spec):
            if ax is None:
                fixed.append(None)
                continue
            axs = ax if isinstance(ax, tuple) else (ax,)
            size = 1
            for a in axs:
                size *= mesh.shape[a]
            fixed.append(ax if dim % size == 0 else None)
        return NamedSharding(mesh, P(*fixed))

    return jax.tree_util.tree_map_with_path(leaf, params)


def _best_batch_axes(bsz: int, axes: Tuple[str, ...], mesh: Mesh) -> Tuple[str, ...]:
    """Largest contiguous subsequence of `axes` whose size divides `bsz`."""
    best: Tuple[str, ...] = ()
    best_size = 1
    n = len(axes)
    for i in range(n):
        for j in range(i + 1, n + 1):
            sub = axes[i:j]
            size = 1
            for a in sub:
                size *= mesh.shape[a]
            if bsz % size == 0 and size > best_size:
                best, best_size = sub, size
    return best


def batch_sharding(batch, mesh: Mesh, plan: ParallelPlan):
    """Shard every batch leaf on its leading (batch) dimension, using the
    largest divisor subset of the DP axes (decode_32k's batch 128 shards
    16-way on "data" under the 256-chip mesh; long_500k's batch 1 replicates)."""

    def leaf(x):
        if x.ndim == 0:
            return NamedSharding(mesh, P())
        axes = _best_batch_axes(x.shape[0], plan.batch_axes, mesh)
        if not axes:
            return NamedSharding(mesh, P(*([None] * x.ndim)))
        spec0 = axes if len(axes) > 1 else axes[0]
        return NamedSharding(mesh, P(spec0, *([None] * (x.ndim - 1))))

    return jax.tree_util.tree_map(leaf, batch)


def cache_sharding(state, mesh: Mesh, plan: ParallelPlan):
    """Sharding of a paged decode state (models/model.py PagedDecodeState).

    Block tables and lengths put their slots on the batch axes.  KV pools
    (G, num_blocks, H_kv, block_size, D) and their scales split H_kv over
    the model axis where it divides, else replicate: any slot may read any
    block.  Recurrent states (G, slots, ...) put slots on the batch axes and
    their first wide dim on the model axis where it divides.
    """
    from repro.serving.kv_cache import PagedKVCache

    M = plan.model_axis
    model = mesh.shape[M] if M is not None else 0

    def batch(n):
        axes = _best_batch_axes(n, plan.batch_axes, mesh)
        return (axes if len(axes) > 1 else axes[0]) if axes else None

    def pool(x):
        heads = M if model and x.shape[2] % model == 0 else None
        return NamedSharding(mesh, P(None, None, heads, *[None] * (x.ndim - 3)))

    def recurrent(x):
        spec: list = [None, batch(x.shape[1])] + [None] * (x.ndim - 2)
        wide = [d for d in range(2, x.ndim)
                if model and x.shape[d] % model == 0 and x.shape[d] >= model]
        if wide:
            spec[wide[0]] = M
        return NamedSharding(mesh, P(*spec))

    caches = tuple(jax.tree_util.tree_map(
        pool if isinstance(c, PagedKVCache) else recurrent, c)
        for c in state.caches)
    tables, lengths = batch_sharding((state.block_tables, state.lengths),
                                     mesh, plan)
    return state._replace(caches=caches, block_tables=tables, lengths=lengths)
