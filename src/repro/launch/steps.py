"""Step builders: train_step / prefill_step / the paged serving steps, plus
the ShapeDtypeStruct input_specs for every (arch x shape) dry-run cell.

These are the functions the dry-run lowers and the real launchers execute —
one definition, both uses.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.models import model as M
from repro.models.config import ArchConfig
from repro.optim import AdamWConfig, adamw_init, adamw_update, warmup_cosine

S32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32)
F32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32)


def optimizer_config(cfg: ArchConfig) -> AdamWConfig:
    # Sub-fp32 moments for models whose fp32 state would not fit HBM.
    big = cfg.param_count() > 8e9
    return AdamWConfig(state_dtype="bfloat16" if big else "float32")


def make_train_step(cfg: ArchConfig, opt_cfg: AdamWConfig | None = None,
                    base_lr: float = 3e-4, total_steps: int = 100_000):
    opt_cfg = opt_cfg or optimizer_config(cfg)
    sched = warmup_cosine(base_lr, warmup=min(2000, total_steps // 10), total=total_steps)

    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(M.loss_fn)(params, cfg, batch)
        lr = sched(opt_state["count"])
        new_params, new_state = adamw_update(grads, opt_state, params, lr, opt_cfg)
        metrics = {"loss": loss, "lr": lr}
        return new_params, new_state, metrics

    return train_step


def make_prefill_step(cfg: ArchConfig):
    def prefill_step(params, batch):
        # Serving prefill: only the next-token logits leave the step.
        return M.forward(params, cfg, batch, last_only=True)

    return prefill_step


def make_paged_serve_step(cfg: ArchConfig):
    """Decode step over the paged KV cache: every slot at its own length;
    `active` masks slots that are idle or mid-prefill this step."""

    def paged_serve_step(params, state: M.PagedDecodeState, tokens, active):
        return M.paged_decode_step(params, cfg, state, tokens, active)

    return paged_serve_step


def make_paged_verify_step(cfg: ArchConfig):
    """Speculative-decoding verification: score (B, S) drafted tokens — the
    last committed token plus S-1 draft guesses per slot — in one paged
    forward pass and greedily accept the longest matching prefix.

    The same python callable serves every draft bucket S — jit (or the
    engine's warmup) specializes per shape, exactly like the prefill-chunk
    buckets."""

    def paged_verify_step(params, state: M.PagedDecodeState, tokens, active,
                          limits, eos):
        return M.paged_verify_step(params, cfg, state, tokens, active,
                                   limits, eos)

    return paged_verify_step


def make_paged_sample_step(cfg: ArchConfig):
    """Decode step + on-device temperature/top-k/top-p sampling: same trunk
    as the paged serve step, but the head draws from the per-(seed, index)
    PRNG stream instead of handing logits back for a host argmax.  Engaged
    only when a batch contains a non-greedy request — all-greedy batches
    keep dispatching the plain serve step (bitwise-identical paths)."""

    def paged_sample_step(params, state: M.PagedDecodeState, tokens, active,
                          temperature, top_k, top_p, seeds, gen_idx):
        return M.paged_decode_sample_step(params, cfg, state, tokens, active,
                                          temperature, top_k, top_p, seeds,
                                          gen_idx)

    return paged_sample_step


def make_paged_verify_sample_step(cfg: ArchConfig):
    """Speculative verification under stochastic sampling (rejection
    sampling against the drafted point mass); bucketed per draft width S
    exactly like the greedy verify step."""

    def paged_verify_sample_step(params, state: M.PagedDecodeState, tokens,
                                 active, limits, eos, temperature, top_k,
                                 top_p, seeds, gen_idx):
        return M.paged_verify_sample_step(params, cfg, state, tokens, active,
                                          limits, eos, temperature, top_k,
                                          top_p, seeds, gen_idx)

    return paged_verify_sample_step


def make_prefill_chunk_step(cfg: ArchConfig):
    """Multi-token prefill: advance one slot by a (1, C) chunk of prompt.

    The same python callable serves every chunk size C — jit (or the
    engine's AOT bucket compiles) specializes per shape."""

    def prefill_chunk_step(params, state: M.PagedDecodeState, tokens, slot):
        return M.prefill_chunk(params, cfg, state, tokens, slot)

    return prefill_chunk_step


# ---------------------------------------------------------------------------
# ShapeDtypeStruct inputs per (arch x shape) cell — no device allocation.
# ---------------------------------------------------------------------------

def _batch_extras(cfg: ArchConfig, batch: int) -> Dict[str, Any]:
    extras: Dict[str, Any] = {}
    if cfg.family == "encdec":
        extras["frames"] = F32((batch, cfg.encoder_seq, cfg.d_model))
    if cfg.family == "vlm":
        extras["patches"] = F32((batch, cfg.prefix_len, M.VISION_DIM))
    return extras


def params_struct(cfg: ArchConfig):
    return jax.eval_shape(lambda: M.init_model(jax.random.PRNGKey(0), cfg))


def opt_state_struct(cfg: ArchConfig, p_struct, opt_cfg: AdamWConfig):
    return jax.eval_shape(lambda: adamw_init(
        jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype), p_struct),
        opt_cfg,
    ))


def paged_state_struct(cfg: ArchConfig, slots: int, max_seq: int,
                       block_size: int = 16):
    """The decode state an Engine of ``slots`` slots at ``max_seq`` holds:
    ``block_size``-token blocks and the engine's default (worst-case) pool.
    Raises ``init_paged_decode_state``'s NotImplementedError for a family
    paged serving refuses."""
    from repro.serving import kv_cache as kvc

    return jax.eval_shape(lambda: M.init_paged_decode_state(
        cfg, slots,
        num_blocks=kvc.default_pool_blocks(slots, max_seq, block_size),
        block_size=block_size,
        max_blocks_per_slot=kvc.blocks_for(max_seq, block_size)))


def input_specs(cfg: ArchConfig, shape: Dict[str, Any]) -> Dict[str, Any]:
    """Spec dict for one shape cell: what the lowered step consumes.

    train  -> {"batch": {tokens, labels, ...}}
    prefill-> {"batch": {tokens, ...}}
    decode -> {"tokens": (B,1), "active": (B,), "max_seq": S}  (the paged
              state comes from paged_state_struct)
    """
    kind, S, B = shape["kind"], shape["seq_len"], shape["global_batch"]
    if kind == "train":
        return {
            "batch": {
                "tokens": S32((B, S)),
                "labels": S32((B, S)),
                **_batch_extras(cfg, B),
            }
        }
    if kind == "prefill":
        return {"batch": {"tokens": S32((B, S)), **_batch_extras(cfg, B)}}
    if kind == "decode":
        return {"tokens": S32((B, 1)),
                "active": jax.ShapeDtypeStruct((B,), jnp.bool_), "max_seq": S}
    raise ValueError(kind)
