"""Serving engine facade: warmup, request lifecycle, metrics.

Maps the paper's three utilization mechanisms onto the request path:

  * `warmup()` — **configuration pre-loading**: the GeMM tile autotuner and
    the XLA compiler both run before traffic.  Every step the server can
    ever execute (the decode step, each power-of-two prefill-chunk bucket,
    the slot reset) is traced and compiled into the jit cache during
    warmup, so no request ever pays a compile.  Pre-loading covers
    *precision* too: ``Engine(cfg, precision="w8a8")`` calibrates (for the
    calibrated mode), quantizes the weights int8-resident, and compiles
    int8 decode/prefill steps — the paper's int8 deployment datapath, set
    up entirely before traffic (repro.quant).
  * chunked prefill interleaved with decode — **input pre-fetching with
    output buffering**: C prompt tokens stream through one step while
    decode batches drain between chunks; prefill work is proportional to
    real tokens (no padding positions, see serving/prefill.py).
  * the paged KV cache — **programmable strided memory access**: block
    tables address a shared pool, so slot memory tracks actual lengths and
    finished slots hand their blocks to the next request.

Typical use (launch/serve.py is a thin CLI over exactly this):

    eng = Engine(cfg, slots=4, max_seq=256, autotune=True)
    eng.warmup()
    for p in prompts:
        eng.submit(RequestSpec(prompt=p, max_new=16))
    results = eng.run()
    print(eng.metrics.summary())

(`submit(p, max_new=16)` still works through the deprecated legacy shim —
serving/request.py owns the one warning path.)
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dataflow import GemmShape
from repro.models import model as M
from repro.obs import Histogram, MfuMeter, NULL_TRACER, Tracer
from repro.obs import percentile as _obs_percentile
from repro.serving import kv_cache as kvc
from repro.serving.prefill import chunk_buckets, plan_chunks
from repro.serving.request import RequestSpec, as_spec, priority_rank
from repro.serving.scheduler import Phase, Request, Scheduler
from repro.serving.speculative import (
    NgramDrafter,
    bucket_for,
    coerce_spec,
    verify_buckets,
)


# ---------------------------------------------------------------------------
# warmup shape extraction (tile autotuning, the CPL analogue's first half)
# ---------------------------------------------------------------------------

def serving_gemm_shapes(cfg, *, slots: int, chunks: Optional[List[int]] = None
                        ) -> List[GemmShape]:
    """The per-step *dense-projection* GeMMs of the serving path: the shapes
    to pre-tune.

    A decode step runs, per attention layer, the separate q/k/v and output
    projections (models/attention.py: wq (d, hq*hd), wk/wv (d, hkv*hd),
    wo (hq*hd, d)) and — for dense-FFN archs — the two FFN matmuls over
    `slots` token rows, plus the vocab head.  Chunked prefill runs the same
    projections over `C` rows per bucket size C (batch 1), so those M-dims
    are warmed too.  MoE expert matmuls (einsum over stacked expert weights)
    and SSM scans do not route through spec-dispatched ops.gemm, so they are
    not warmed here.
    """
    d, ff, vocab = cfg.d_model, cfg.d_ff, cfg.vocab
    hd = cfg.resolved_head_dim
    hq, hkv = cfg.n_heads, cfg.n_kv_heads
    rows = [slots] + list(chunks or [])
    shapes = []
    for m in rows:
        if cfg.family != "ssm":          # archs with attention layers
            shapes += [
                GemmShape(m, d, hq * hd),    # q projection
                GemmShape(m, d, hkv * hd),   # k / v projections
                GemmShape(m, hq * hd, d),    # attention output projection
            ]
        if cfg.moe is None:              # dense FFN (MoE experts run via einsum)
            shapes += [
                GemmShape(m, d, ff),         # FFN up (and swiglu gate)
                GemmShape(m, ff, d),         # FFN down
            ]
        shapes.append(GemmShape(m, d, vocab))  # LM head
    seen, out = set(), []
    for s in shapes:
        if s not in seen:
            seen.add(s)
            out.append(s)
    return out


def autotune_for_serving(cfg, *, slots: int, mode: str = "analytic",
                         chunks: Optional[List[int]] = None,
                         dtype: Optional[str] = None,
                         backend: str = "pallas",
                         verbose: bool = True) -> None:
    """Warm the tuner cache for this model's shapes and enable tuned dispatch.

    `dtype`/`backend` select the candidate space: a w8a8 engine tunes int8
    tiles for the fused "w8a8" kernel — a *separate* search from the float
    tiles (int8 packs 32 sublanes and twice the tile per VMEM byte, so the
    winners differ; see tuning/candidates.py)."""
    from repro import tuning

    tuner = tuning.Autotuner(mode=mode)
    tuning.set_tuner(tuner)
    shapes = serving_gemm_shapes(cfg, slots=slots, chunks=chunks)
    dtype = dtype or cfg.dtype
    if verbose:
        print(f"autotune[{mode}]: {len(shapes)} GeMM shapes for {cfg.name} "
              f"({dtype}/{backend})")
    for r, s in zip(tuner.warmup(shapes, dtype=dtype, backend=backend), shapes):
        if verbose:
            hit = "cache" if r.from_cache else r.source
            print(f"  {s.M}x{s.K}x{s.N}: tile=({r.spec.tm},{r.spec.tk},"
                  f"{r.spec.tn}) [{hit}]")
    tuning.enable()


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

# Nearest-rank percentile over a possibly-empty sequence (0.0 when empty).
# The definition lives in repro.obs (obs/hist.py), shared with
# Histogram.percentile's rank math; the module-level alias stays for
# back-compat (cluster/metrics.py and tests imported it from here before
# the helper moved into repro.obs).
percentile = _obs_percentile


@dataclasses.dataclass
class RequestMetrics:
    rid: int
    prompt_len: int
    new_tokens: int
    ttft_s: float                 # submit -> first generated token
    latency_s: float              # submit -> finish
    queue_steps: int              # engine ticks spent waiting for a slot
    cached_tokens: int = 0        # prompt tokens served from a shared prefix
    priority: str = "interactive"  # SLO class (repro.serving.request)
    tenant: str = "default"
    preemptions: int = 0          # times this request was swapped out

    @property
    def decode_tok_s(self) -> float:
        """Per-request decode rate: tokens after the first over the time
        after the first (the first token falls out of the final prefill
        chunk, so it belongs to TTFT, not decode)."""
        span = self.latency_s - self.ttft_s
        return (self.new_tokens - 1) / span if span > 0 else 0.0


@dataclasses.dataclass
class EngineMetrics:
    prefill_chunks: int = 0
    prefill_tokens: int = 0
    decode_steps: int = 0
    decode_tokens: int = 0
    decode_time_s: float = 0.0    # wall clock spent in decode ticks only
    aot_steps: int = 0            # executables compiled during warmup
    cold_compiles: int = 0        # steps that missed the warmup cache
    precision: str = "float"      # execution precision (quant/modes.py)
    weight_bytes: int = 0         # resident param bytes (post-quantization)
    weight_bytes_float: int = 0   # param bytes before quantization
    calib_sites: int = 0          # activation sites calibrated in warmup
    peak_blocks_in_use: int = 0
    occupancy_sum: float = 0.0
    occupancy_samples: int = 0
    elapsed_s: float = 0.0
    prefix_lookups: int = 0       # admissions that consulted the prefix cache
    prefix_hits: int = 0          # admissions seeded from a cached prefix
    prefix_hit_tokens: int = 0    # prompt tokens whose prefill was skipped
    spec_ticks: int = 0           # decode ticks that ran batched verification
    spec_draft_tokens: int = 0    # draft tokens proposed to the verifier
    spec_accepted_tokens: int = 0  # draft tokens verification accepted
    preemptions: int = 0          # decode victims swapped out for a higher class
    swap_out_blocks: int = 0      # KV blocks serialized to host memory
    swap_in_blocks: int = 0       # KV blocks restored on re-admission
    swap_time_s: float = 0.0      # wall clock in swap-out + restore transfers
    sampled_tokens: int = 0       # tokens emitted via the sampling head
    kv_precision: str = "float"   # pool residency (serving/kv_cache.py)
    kv_pool_bytes: int = 0        # resident KV pool bytes across all layers
    kv_pool_blocks: int = 0       # pool blocks (incl. the null block)
    kv_bytes_per_block: int = 0   # pool bytes per block across all layers
    kv_slot_capacity: int = 0     # max-length requests the pool can hold
    prefill_time_s: float = 0.0   # wall clock spent in prefill-chunk steps
    requests: List[RequestMetrics] = dataclasses.field(default_factory=list)
    # Streaming percentile sketches (repro.obs.hist): fed on every finish,
    # bounded regardless of how long the engine lives.  The raw `requests`
    # list stays for exact/offline analysis but may be capped
    # (Engine(request_log=N)); once entries are dropped, the histograms
    # become the percentile source of truth.
    requests_dropped: int = 0
    ttft_hist: Histogram = dataclasses.field(default_factory=Histogram)
    latency_hist: Histogram = dataclasses.field(default_factory=Histogram)
    tok_s_hist: Histogram = dataclasses.field(default_factory=Histogram)
    # Live utilization gauges (repro.obs.mfu), owned/installed by the engine.
    mfu: Optional[MfuMeter] = None

    def note_request(self, rm: RequestMetrics,
                     log_limit: Optional[int] = None) -> None:
        """Record one finished request: feed the streaming histograms and
        append to the raw log, trimming it to `log_limit` entries (oldest
        first) when set."""
        self.ttft_hist.add(rm.ttft_s)
        self.latency_hist.add(rm.latency_s)
        self.tok_s_hist.add(rm.decode_tok_s)
        self.requests.append(rm)
        if log_limit is not None and len(self.requests) > log_limit:
            drop = len(self.requests) - log_limit
            del self.requests[:drop]
            self.requests_dropped += drop

    @property
    def finished_requests(self) -> int:
        """Total requests finished (raw log length + trimmed entries)."""
        return len(self.requests) + self.requests_dropped

    @property
    def mean_occupancy(self) -> float:
        return self.occupancy_sum / max(1, self.occupancy_samples)

    @property
    def throughput_tok_s(self) -> float:
        """Decode throughput over decode-tick time only — dividing by the
        total elapsed time would fold prefill ticks into the denominator
        and understate prompt-heavy workloads."""
        return self.decode_tokens / self.decode_time_s if self.decode_time_s else 0.0

    def ttft_percentile(self, q: float) -> float:
        """Nearest-rank TTFT percentile: exact over the raw log while it is
        complete, histogram-backed (within Histogram.rel_error) once the
        capped log has dropped entries."""
        if self.requests and not self.requests_dropped:
            return percentile([r.ttft_s for r in self.requests], q)
        return self.ttft_hist.percentile(q)

    def latency_percentile(self, q: float) -> float:
        if self.requests and not self.requests_dropped:
            return percentile([r.latency_s for r in self.requests], q)
        return self.latency_hist.percentile(q)

    def decode_tok_s_percentile(self, q: float) -> float:
        if self.requests and not self.requests_dropped:
            return percentile([r.decode_tok_s for r in self.requests], q)
        return self.tok_s_hist.percentile(q)

    @property
    def prefix_hit_rate(self) -> float:
        return self.prefix_hits / max(1, self.prefix_lookups)

    @property
    def acceptance_rate(self) -> float:
        """Fraction of drafted tokens that survived verification."""
        return self.spec_accepted_tokens / max(1, self.spec_draft_tokens)

    @property
    def decode_tok_per_tick(self) -> float:
        """Mean committed tokens per decode tick, summed across slots: one
        token per *active slot* per tick without speculation; up to
        slots x (accepted + 1) with it — the utilization metric batched
        verification moves."""
        return self.decode_tokens / max(1, self.decode_steps)

    def summary(self) -> str:
        n = self.finished_requests
        if self.requests and not self.requests_dropped:
            ttft = np.mean([r.ttft_s for r in self.requests])
            lat = np.mean([r.latency_s for r in self.requests])
        else:
            ttft, lat = self.ttft_hist.mean, self.latency_hist.mean
        out = (
            f"requests={n} prefill_chunks={self.prefill_chunks} "
            f"prefill_tokens={self.prefill_tokens} "
            f"decode_steps={self.decode_steps} "
            f"decode={self.decode_tokens} tok ({self.throughput_tok_s:.1f} tok/s) "
            f"ttft={ttft*1e3:.0f}ms "
            f"(p50={self.ttft_percentile(50)*1e3:.0f}ms "
            f"p95={self.ttft_percentile(95)*1e3:.0f}ms) "
            f"latency={lat*1e3:.0f}ms "
            f"req_tok_s_p50={self.decode_tok_s_percentile(50):.1f} "
            f"p95={self.decode_tok_s_percentile(95):.1f} "
            f"kv_occupancy={self.mean_occupancy:.0%} "
            f"peak_blocks={self.peak_blocks_in_use} "
            f"warmed={self.aot_steps} cold_compiles={self.cold_compiles}"
        )
        if self.kv_pool_bytes:
            out += (
                f" kv_pool={self.kv_pool_bytes / 2**20:.1f}MiB "
                f"({self.kv_pool_blocks} blk x "
                f"{self.kv_bytes_per_block / 2**10:.1f}KiB, "
                f"{self.kv_precision}) "
                f"slots@max_seq={self.kv_slot_capacity}"
            )
        if self.prefix_lookups:
            out += (
                f" prefix_hits={self.prefix_hits}/{self.prefix_lookups} "
                f"({self.prefix_hit_tokens} tok reused)"
            )
        if self.spec_ticks:
            out += (
                f" spec_ticks={self.spec_ticks}/{self.decode_steps} "
                f"accept={self.acceptance_rate:.0%} "
                f"tok/tick={self.decode_tok_per_tick:.2f}"
            )
        if self.preemptions:
            out += (
                f" preemptions={self.preemptions} "
                f"(swap out={self.swap_out_blocks} blk "
                f"in={self.swap_in_blocks} blk "
                f"{self.swap_time_s * 1e3:.0f}ms)"
            )
        if self.sampled_tokens:
            out += f" sampled={self.sampled_tokens} tok"
        if self.precision != "float":
            saved = (1.0 - self.weight_bytes / self.weight_bytes_float
                     if self.weight_bytes_float else 0.0)
            out += (
                f" precision={self.precision} "
                f"weights={self.weight_bytes / 2**20:.1f}MiB "
                f"({saved:.0%} smaller)"
            )
            if self.calib_sites:
                out += f" calib_sites={self.calib_sites}"
        if self.mfu is not None:
            frag = self.mfu.summary()
            if frag:
                out += " " + frag
        return out

    def as_dict(self) -> dict:
        """JSON-serializable snapshot (launch/serve.py --metrics-json):
        scalar gauges, percentile sketches, and the per-phase utilization
        figures."""
        return {
            "requests": self.finished_requests,
            "requests_dropped_from_log": self.requests_dropped,
            "prefill_chunks": self.prefill_chunks,
            "prefill_tokens": self.prefill_tokens,
            "prefill_time_s": self.prefill_time_s,
            "decode_steps": self.decode_steps,
            "decode_tokens": self.decode_tokens,
            "decode_time_s": self.decode_time_s,
            "throughput_tok_s": self.throughput_tok_s,
            "ttft_p50_s": self.ttft_percentile(50),
            "ttft_p95_s": self.ttft_percentile(95),
            "latency_p50_s": self.latency_percentile(50),
            "latency_p95_s": self.latency_percentile(95),
            "req_tok_s_p50": self.decode_tok_s_percentile(50),
            "req_tok_s_p95": self.decode_tok_s_percentile(95),
            "mean_occupancy": self.mean_occupancy,
            "peak_blocks_in_use": self.peak_blocks_in_use,
            "precision": self.precision,
            "kv_precision": self.kv_precision,
            "kv_pool_bytes": self.kv_pool_bytes,
            "prefix_hits": self.prefix_hits,
            "prefix_lookups": self.prefix_lookups,
            "spec_ticks": self.spec_ticks,
            "acceptance_rate": self.acceptance_rate,
            "preemptions": self.preemptions,
            "swap_out_blocks": self.swap_out_blocks,
            "swap_in_blocks": self.swap_in_blocks,
            "swap_time_s": self.swap_time_s,
            "sampled_tokens": self.sampled_tokens,
            "aot_steps": self.aot_steps,
            "cold_compiles": self.cold_compiles,
            "ttft_hist": self.ttft_hist.to_dict(),
            "latency_hist": self.latency_hist.to_dict(),
            "tok_s_hist": self.tok_s_hist.to_dict(),
            "mfu": self.mfu.as_dict() if self.mfu is not None else None,
        }


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------

class Engine:
    """Continuous-batching serving engine over the paged decode state."""

    def __init__(
        self,
        cfg,
        params=None,
        *,
        slots: int = 4,
        max_seq: int = 256,
        block_size: int = 16,
        num_blocks: Optional[int] = None,
        max_chunk: int = 64,
        autotune: bool = False,
        tune_mode: str = "analytic",
        precision: str = "float",
        kv_precision: str = "float",
        calib_batches=None,
        max_queue: Optional[int] = None,
        prefix_cache=False,
        speculative=False,
        sampling: bool = False,
        preempt: bool = False,
        trace=False,
        trace_flow: bool = True,
        request_log: Optional[int] = None,
        seed: int = 0,
        verbose: bool = False,
    ):
        from repro.launch import steps as steps_lib

        if precision != "float":
            from repro.quant import modes as _qmodes

            if precision not in _qmodes.MODES:
                raise ValueError(
                    f"unknown precision {precision!r}; known: {_qmodes.MODES}")
        self.precision = precision
        if kv_precision not in ("float", "int8"):
            raise ValueError(
                f"unknown kv_precision {kv_precision!r}; known: float, int8")
        # Orthogonal to `precision` (weight/activation GeMMs): int8 KV keeps
        # the *pool* int8-resident with per-(block, position, head) scales;
        # the decode kernel dequantizes in-VMEM (kernels/flash_decode.py).
        self.kv_precision = kv_precision
        self._calib_batches = calib_batches
        self._seed = seed
        self.cfg = cfg
        self.params = (params if params is not None
                       else M.init_model(jax.random.PRNGKey(seed), cfg))
        self.slots, self.max_seq = slots, max_seq
        self.block_size = block_size
        self.max_blocks_per_slot = kvc.blocks_for(max_seq, block_size)
        self.num_blocks = num_blocks or kvc.default_pool_blocks(
            slots, max_seq, block_size)
        # No prompt can exceed max_seq, so larger buckets would only be
        # compiled, never dispatched.
        self.max_chunk = min(max_chunk, max_seq)
        self.autotune = autotune
        self.tune_mode = tune_mode
        self.verbose = verbose

        # Speculative decoding (serving/speculative.py): a model-free
        # prompt-lookup drafter proposes up to spec.k tokens per request per
        # tick; one batched verify step scores them all.  False/None -> off,
        # True -> defaults, int -> draft length K, SpecConfig -> as given.
        self.spec = coerce_spec(speculative)
        self.drafter = NgramDrafter(self.spec) if self.spec else None

        # Stochastic sampling (temperature/top-k/top-p, models/model.py
        # sampling section).  The flag only controls *warmup*: a sampling
        # RequestSpec on a sampling=False engine still works, it just pays
        # one cold compile for the sample step.  All-greedy batches always
        # dispatch the plain greedy steps, so greedy traffic stays bitwise
        # identical whatever this flag says.
        self.sampling = bool(sampling)
        # KV-swap preemption: an interactive arrival may evict a decoding
        # batch-class request by serializing its blocks to host memory and
        # restoring them on re-admission.  Attention-only stacks only —
        # recurrent (SSM/xLSTM) per-slot state is not block-addressable, so
        # a swap round trip would silently drop it.
        self.preempt = bool(preempt)
        if self.preempt and any(
                k not in ("attn", "attn_local") for k in cfg.layer_kinds()):
            raise ValueError(
                "preempt requires an attention-only stack; "
                f"{cfg.name} has kinds {cfg.layer_kinds()}")
        self._swapped: Dict[int, tuple] = {}   # rid -> (payload, n_blocks)

        self.scheduler = Scheduler(slots, max_chunk=max_chunk, max_queue=max_queue)
        self.alloc = kvc.BlockAllocator(self.num_blocks, block_size)
        self.tables = kvc.BlockTables(slots, self.max_blocks_per_slot)
        # Prompt-prefix reuse (cluster/prefix_cache.py): requests whose
        # prompts share full, block-aligned prefixes fork the already-written
        # KV blocks (refcounted) and prefill only the uncached suffix.
        # Limited to attention-only stacks — a recurrent (SSM/xLSTM) layer's
        # state is not captured by KV blocks, so a seeded prefix would skip
        # its scan.
        self.prefix_cache = None
        if prefix_cache:
            if any(k not in ("attn", "attn_local") for k in cfg.layer_kinds()):
                raise ValueError(
                    "prefix_cache requires an attention-only stack; "
                    f"{cfg.name} has kinds {cfg.layer_kinds()}")
            if prefix_cache is True or isinstance(prefix_cache, int):
                from repro.cluster.prefix_cache import PrefixCache

                # True: unbounded (pool pressure evicts); int: max_blocks.
                mb = None if prefix_cache is True else int(prefix_cache)
                self.prefix_cache = PrefixCache(self.alloc, max_blocks=mb)
            else:
                # Caller-built cache (e.g. a subclass wired to eng.alloc
                # post-construction): block ids only mean anything inside
                # the allocator that issued them.
                if prefix_cache.alloc is not self.alloc:
                    raise ValueError(
                        "prefix_cache is bound to a different allocator; "
                        "pass True (or a max_blocks int) and let the engine "
                        "build its own, or construct the cache from "
                        "engine.alloc")
                self.prefix_cache = prefix_cache
        self._prefix_match: Dict[int, tuple] = {}  # rid -> (blocks, toks, fresh)
        self._seeded: Dict[int, int] = {}          # rid -> forked block count
        self.state = M.init_paged_decode_state(
            cfg, slots, num_blocks=self.num_blocks, block_size=block_size,
            max_blocks_per_slot=self.max_blocks_per_slot,
            kv_precision=kv_precision,
        )
        self.metrics = EngineMetrics()
        # Live utilization gauges (repro.obs.mfu): a few float adds per tick,
        # so they stay on unconditionally — summary() always carries a
        # per-phase utilization/MFU figure.
        self.mfu = MfuMeter(cfg)
        self.metrics.mfu = self.mfu
        # Raw request-log cap: None keeps every RequestMetrics (exact
        # percentiles, benchmark-friendly); an int bounds the log for
        # long-lived serving and flips percentiles onto the histograms.
        self._request_log = request_log
        # Span/event tracing (repro.obs.trace): off by default — NULL_TRACER
        # makes every record call a no-op method dispatch.  Pass True for a
        # fresh ring, or a Tracer to aggregate several engines into one
        # export (cluster/replica.py names one per replica).
        if isinstance(trace, Tracer):
            self.tracer = trace
        elif trace:
            self.tracer = Tracer(name=f"engine[{cfg.name}]")
        else:
            self.tracer = NULL_TRACER
        tc = self.tracer.intern
        self._ev_tick = tc("tick")
        self._ev_sched = tc("sched")
        self._ev_prefill = tc("prefill")
        self._ev_decode = tc("decode")
        self._ev_verify = tc("verify")
        self._ev_draft = tc("draft")
        self._ev_reset = tc("reset")
        self._ev_kv_in_use = tc("kv_blocks_in_use")
        self._ev_kv_reserved = tc("kv_blocks_reserved")
        self._ev_queue = tc("queue_depth")
        self._ev_req_queued = tc("queued")
        self._ev_req_prefill = tc("req_prefill")
        self._ev_req_decode = tc("req_decode")
        # Request-flow tracing (cross-lane arrows + annotated instants) on
        # top of the spans above.  `trace_flow=False` restores the pre-flow
        # event set — the A/B baseline benchmarks/obs_bench.py measures
        # flow overhead against.
        self._flow = bool(trace_flow) and self.tracer.enabled
        self._ev_submit = tc("submit")
        self._ev_flow = tc("req")            # one flow chain per request
        self._ev_shed = tc("shed")
        self._ev_prefix_hit = tc("prefix_hit")
        self._ev_evict = tc("cache_evict")
        self._ev_preempt = tc("preempt")
        self._ev_restore = tc("restore")
        self._account_kv_pools()

        # The decode state (KV pools included) is *donated* to every step:
        # XLA updates the pools in place instead of copying them per tick.
        # Without donation each step memcpys the whole pool (tens of MB for
        # even small configs) — measured ~1000x slower for the update itself
        # on CPU, and the copies saturate memory bandwidth, which is exactly
        # the resource replica threads must share (cluster/replica.py).
        # Every call site immediately reassigns self.state from the step's
        # return, so the consumed buffers are never touched again.
        self._decode_fn = jax.jit(
            steps_lib.make_paged_serve_step(cfg), donate_argnums=(1,))
        self._chunk_fn = jax.jit(
            steps_lib.make_prefill_chunk_step(cfg), donate_argnums=(1,))
        self._verify_fn = jax.jit(
            steps_lib.make_paged_verify_step(cfg), donate_argnums=(1,))
        self._sample_fn = jax.jit(
            steps_lib.make_paged_sample_step(cfg), donate_argnums=(1,))
        self._verify_sample_fn = jax.jit(
            steps_lib.make_paged_verify_sample_step(cfg), donate_argnums=(1,))
        # Prefill first token under sampling: the final chunk's (1, 1, V)
        # logits feed the same sample_tokens head the decode step uses, so
        # one seed stream covers every generated position.  No donation —
        # logits are a fresh output, not the threaded state.
        self._sample1_fn = jax.jit(
            lambda lg, t, k, p, s, i: M.sample_tokens(
                lg[:, -1], jnp.reshape(s, (1,)), jnp.reshape(i, (1,)),
                jnp.reshape(t, (1,)), jnp.reshape(k, (1,)),
                jnp.reshape(p, (1,))))
        self._reset_fn = jax.jit(
            lambda state, mask: M.reset_slots(cfg, state, mask),
            donate_argnums=(0,))
        self._warmed: set = set()                # step shapes compiled so far
        self._slot_used = [False] * slots        # occupied at least once
        # Scalar construction (jnp.int32) costs ~0.7 ms on CPU jax; slot ids
        # are a fixed set, so build them once.
        self._slot_ids = [jnp.int32(s) for s in range(slots)]
        self._last_token = np.zeros((slots,), np.int32)
        self._reserved: Dict[int, int] = {}      # rid -> blocks reserved
        self._step = 0
        self._t0: Optional[float] = None
        self._submit_t: Dict[int, float] = {}
        self._first_tok_t: Dict[int, float] = {}
        self.results: Dict[int, np.ndarray] = {}

    def share_steps_from(self, other: "Engine") -> None:
        """Reuse another engine's jitted step callables (and their compile
        caches).  Only valid across engines of the same config — same
        traces, same shapes; ReplicaPool uses this so a pool compiles each
        step shape once, and benchmarks/tests use it to not re-pay warmup
        per engine.  The single place that knows the step-field list."""
        self._decode_fn = other._decode_fn
        self._chunk_fn = other._chunk_fn
        self._verify_fn = other._verify_fn
        self._sample_fn = other._sample_fn
        self._verify_sample_fn = other._verify_sample_fn
        self._sample1_fn = other._sample1_fn
        self._reset_fn = other._reset_fn

    def _account_kv_pools(self) -> None:
        """KV-pool residency accounting (metrics): total pool bytes across
        every attention layer (scales included for int8 pools), per-block
        cost, and how many max_seq-length requests the pool can hold at
        once (the null block never serves data)."""
        pools = [
            leaf for leaf in jax.tree_util.tree_leaves(
                self.state.caches,
                is_leaf=lambda x: isinstance(x, kvc.PagedKVCache))
            if isinstance(leaf, kvc.PagedKVCache)
        ]
        m = self.metrics
        m.kv_precision = self.kv_precision
        m.kv_pool_bytes = sum(kvc.pool_bytes(p) for p in pools)
        m.kv_pool_blocks = self.num_blocks
        m.kv_bytes_per_block = m.kv_pool_bytes // self.num_blocks
        m.kv_slot_capacity = (self.num_blocks - 1) // self.max_blocks_per_slot

    # -- warmup: the configuration-pre-loading analogue ----------------------

    def warmup(self) -> None:
        """Autotune GeMM tiles and trace+compile every step shape before
        traffic: the decode step, each prefill-chunk bucket, the slot reset.

        Each step is invoked once on dummy inputs (outputs discarded — the
        steps are functional), populating the jit executable cache; serve
        time then always dispatches through jit's C++ fast path.  An AOT
        ``.lower().compile()`` executable would also pre-compile, but its
        Python-side call path re-validates the params pytree per call
        (measured ~4 ms/step on CPU, double the decode step itself).

        With ``precision != "float"`` warmup additionally covers the paper's
        deployment precision: (optionally) calibrate activation scales,
        quantize the weights int8-resident *once*, and trace every step
        inside the precision context — so the compiled executables are int8
        end to end and serving never quantizes a weight again."""
        buckets = chunk_buckets(self.max_chunk)
        warm_code = self.tracer.intern("warmup")
        self.tracer.begin(warm_code)
        if self.autotune:
            w8a8 = self.precision != "float"
            autotune_for_serving(
                self.cfg, slots=self.slots, mode=self.tune_mode,
                chunks=buckets, verbose=self.verbose,
                dtype="int8" if w8a8 else None,
                backend="w8a8" if w8a8 else "pallas")
            # Decode-attention design point (tuning/decode.py), bound at
            # trace time like the precision mode: every step traced below
            # bakes in the tuned FlashDecodeSpec.  Shares the tuner cache
            # autotune_for_serving just installed.
            from repro import tuning
            from repro.kernels import flash_decode as _fd

            dspec = tuning.tune_decode_for_serving(
                self.cfg, slots=self.slots, block_size=self.block_size,
                max_blocks=self.max_blocks_per_slot, mode=self.tune_mode,
                verbose=self.verbose)
            if dspec is not None:
                _fd.set_decode_spec(dspec)
        if self.precision != "float":
            self._quantize_weights()
        tokens = jnp.zeros((self.slots, 1), jnp.int32)
        active = jnp.zeros((self.slots,), bool)
        slot0 = self._slot_ids[0]
        # The steps donate their state input, so warmup *threads* the state
        # through every call instead of discarding outputs, then rebuilds a
        # fresh zero state (the chunk steps advanced slot 0's length).
        state = self.state
        with self._precision_ctx():
            _, state = self._decode_fn(self.params, state, tokens, active)
            self._warmed.add("decode")
            logits1 = None
            for c in buckets:
                logits1, state = self._chunk_fn(
                    self.params, state, jnp.zeros((1, c), jnp.int32), slot0)
                self._warmed.add(f"chunk{c}")
            zt = np.zeros((self.slots,), np.float32)
            zk = np.zeros((self.slots,), np.int32)
            op = np.ones((self.slots,), np.float32)
            if self.sampling:
                _, state = self._sample_fn(self.params, state, tokens, active,
                                           zt, zk, op, zk, zk)
                self._warmed.add("decode_sample")
                # Warm the prefill-token sampler on real chunk logits so the
                # compiled executable matches serve-time dtype exactly.
                self._sample1_fn(logits1, np.float32(0.0), np.int32(0),
                                 np.float32(1.0), np.int32(0), np.int32(0))
                self._warmed.add("sample1")
            if self.spec is not None:
                # Every verify width the drafter can produce (speculative
                # K buckets), compiled before traffic like the chunk sizes.
                lim = jnp.ones((self.slots,), jnp.int32)
                no_eos = jnp.full((self.slots,), -1, jnp.int32)
                for s in verify_buckets(self.spec.k):
                    _, _, state = self._verify_fn(
                        self.params, state,
                        jnp.zeros((self.slots, s), jnp.int32), active,
                        lim, no_eos)
                    self._warmed.add(f"verify{s}")
                    if self.sampling:
                        _, _, state = self._verify_sample_fn(
                            self.params, state,
                            jnp.zeros((self.slots, s), jnp.int32), active,
                            lim, no_eos, zt, zk, op, zk, zk)
                        self._warmed.add(f"verify_sample{s}")
            state = self._reset_fn(state, jnp.zeros((self.slots,), bool))
            self._warmed.add("reset")
            jax.block_until_ready(state)
        self.state = M.init_paged_decode_state(
            self.cfg, self.slots, num_blocks=self.num_blocks,
            block_size=self.block_size,
            max_blocks_per_slot=self.max_blocks_per_slot,
            kv_precision=self.kv_precision)
        self.metrics.aot_steps = len(self._warmed)
        self.tracer.end(warm_code)
        if self.verbose:
            extra = (f" + verify {verify_buckets(self.spec.k)}"
                     if self.spec is not None else "")
            print(f"warmup: {len(self._warmed)} step shapes compiled "
                  f"(decode + chunks {buckets}{extra} + reset)"
                  + (f" [{self.precision}]" if self.precision != "float" else ""))

    def _precision_ctx(self):
        """Context the engine traces its steps under.  Trace-time dispatch:
        the precision mode binds when a step is traced (quant/modes.py), so
        warmup and any cold compile enter this context; executing the
        already-compiled steps needs no context."""
        import contextlib

        if self.precision == "float":
            return contextlib.nullcontext()
        from repro.quant import modes as qmodes

        return qmodes.precision(self.precision)

    def _quantize_weights(self) -> None:
        """Calibrate (for "w8a8-calibrated") and swap the float params for
        the int8-resident pytree; the float copy is dropped — the memory
        saving is real, not additive."""
        from repro import quant

        scales = None
        if self.precision == "w8a8-calibrated":
            batches = self._calib_batches
            if batches is None:
                batches = quant.synthetic_batches(
                    self.cfg, n=2, batch=2,
                    seq=min(32, self.max_seq), seed=self._seed)
            scales = quant.collect_scales(self.params, self.cfg, batches)
            self.metrics.calib_sites = len(scales)
            if self.verbose:
                print(f"calibrated {len(scales)} activation sites "
                      f"({scales.observer}, {scales.batches} batches)")
        self.metrics.weight_bytes_float = quant.weight_bytes(self.params)
        self.params = quant.quantize_params(
            self.params, cfg=self.cfg, scales=scales)
        self.metrics.weight_bytes = quant.weight_bytes(self.params)
        self.metrics.precision = self.precision
        if self.verbose:
            mb = 2**20
            print(f"quantized {quant.quantized_leaf_count(self.params)} "
                  f"weights int8-resident: "
                  f"{self.metrics.weight_bytes_float / mb:.1f}MiB -> "
                  f"{self.metrics.weight_bytes / mb:.1f}MiB")

    def _run_compiled(self, key: str, fn, *args):
        if key not in self._warmed:
            self.metrics.cold_compiles += 1
            self._warmed.add(key)
            with self._precision_ctx():   # cold trace: bind the precision
                return fn(*args)
        return fn(*args)

    def prompt_logits(self, prompt) -> np.ndarray:
        """Next-token logits (vocab,) of one prompt, computed by this
        engine's own compiled prefill-chunk steps (kernels, precision and
        decode spec as bound at warmup) on a scratch state of the engine's
        shapes: the logits the serving path takes a request's first token
        from, for comparison against a reference forward.  The live state
        and slots are untouched; after warmup() nothing compiles here."""
        prompt = np.asarray(prompt, np.int32)
        if not 0 < len(prompt) <= self.max_seq:
            raise ValueError(
                f"prompt of {len(prompt)} tokens; need 1..{self.max_seq}")
        state = M.init_paged_decode_state(
            self.cfg, self.slots, num_blocks=self.num_blocks,
            block_size=self.block_size,
            max_blocks_per_slot=self.max_blocks_per_slot,
            kv_precision=self.kv_precision)
        tables = np.zeros((self.slots, self.max_blocks_per_slot), np.int32)
        n = kvc.blocks_for(len(prompt), self.block_size)
        tables[0, :n] = np.arange(1, n + 1)       # slot 0, blocks 1..n
        state = state._replace(block_tables=jnp.asarray(tables))
        start, logits = 0, None
        for c in plan_chunks(len(prompt), self.max_chunk):
            logits, state = self._run_compiled(
                f"chunk{c}", self._chunk_fn, self.params, state,
                jnp.asarray(prompt[None, start:start + c]), self._slot_ids[0])
            start += c
        return np.asarray(logits)[0, -1]

    # -- request lifecycle ---------------------------------------------------

    def submit(self, request, max_new: Optional[int] = None, *,
               eos_token: Optional[int] = None,
               trace_id: Optional[int] = None) -> Optional[Request]:
        """Queue a request: a ``RequestSpec``, or the legacy
        ``(prompt, max_new)`` form (deprecated, shimmed through
        ``repro.serving.request.as_spec``).  The spec's ``trace_id`` (or
        the keyword, for legacy callers) threads an externally-minted id
        (the router's cluster-wide request id) into this request's flow
        chain and lifecycle spans; engine-local submissions mint their own,
        namespaced by the tracer's pid so ids never collide across replica
        lanes in one export."""
        spec = as_spec(request, max_new, eos_token=eos_token,
                       trace_id=trace_id)
        if spec.prompt_len + spec.max_new > self.max_seq:
            raise ValueError(
                f"prompt {spec.prompt_len} + max_new {spec.max_new} exceeds "
                f"max_seq {self.max_seq}")
        if (kvc.blocks_for(spec.prompt_len + spec.max_new, self.block_size)
                > self.num_blocks - 1):
            raise ValueError(
                f"request needs more KV blocks than the whole pool "
                f"({self.num_blocks - 1}); raise num_blocks")
        req = self.scheduler.submit(spec, step=self._step)
        tr = self.tracer
        if req is not None:
            req.trace_id = (int(spec.trace_id) if spec.trace_id is not None
                            else (tr.pid << 24) + req.rid)
            self._submit_t[req.rid] = time.monotonic()
            if self._flow:
                # Flow events bind to the duration slice open at their
                # timestamp, so the chain's first link sits in a tiny
                # "submit" slice (a step when the router already started
                # the chain in its admit slice).
                tr.begin(self._ev_submit)
                if spec.trace_id is None:
                    tr.flow_start(self._ev_flow, req.trace_id)
                else:
                    tr.flow_step(self._ev_flow, req.trace_id)
                tr.end(self._ev_submit)
            tr.async_begin(self._ev_req_queued, req.trace_id)
            tr.counter(self._ev_queue, len(self.scheduler.queue))
        elif self._flow:
            tr.instant(self._ev_shed, len(self.scheduler.queue))
        return req

    def _can_admit(self, req: Request) -> bool:
        need = kvc.blocks_for(req.prompt_len + req.max_new, self.block_size)
        if req.swapped:
            # Preempted victim re-admitting: its cache already diverged from
            # any shared prefix (it decoded past the prompt), so the bytes
            # are restored verbatim into fresh private blocks — no prefix
            # fork, full worst-case reservation like a fresh admit.
            return self.alloc.can_reserve(need)
        if self.prefix_cache is None:
            return self.alloc.can_reserve(need)
        # Prefix path: match full blocks of an already-prefilled identical
        # prompt prefix, fork them (refcount, zero KV bytes moved), and
        # reserve only the *fresh* worst case.  Under pool pressure the
        # cache gives blocks back (LRU) before we refuse admission.  The
        # fork happens *before* eviction so an eviction sweep that reaches
        # our own matched nodes can only drop the cache's refs — the blocks
        # stay alive under ours.
        blocks, tokens = self.prefix_cache.lookup(req.prompt)
        if blocks:
            kvc.fork_blocks(self.alloc, blocks)
        n_fresh = need - len(blocks)
        if not self.alloc.can_reserve(n_fresh):
            shortfall = n_fresh - self.alloc.available
            if self._flow:
                self.tracer.instant(self._ev_evict, shortfall)
            self.prefix_cache.evict(shortfall)
            if not self.alloc.can_reserve(n_fresh):
                if blocks:
                    self.alloc.free(blocks)     # un-fork: admission refused
                return False
        req.cached_tokens = tokens
        self._prefix_match[req.rid] = (blocks, tokens, n_fresh)
        return True

    def _admit(self) -> None:
        self._admit_once()
        if not self.preempt:
            return
        # Preemption sweep: while a queued request outranks running decode
        # work, swap the lowest-class, youngest decoding victim out and
        # retry admission.  Bounded by the slot count (each pass frees at
        # most one slot, and victims must strictly outrank the head).
        for _ in range(self.slots):
            victim = self._pick_victim()
            if victim is None:
                break
            self._swap_out(victim)
            self._admit_once()

    def _admit_once(self) -> None:
        to_reset, seeds, restores = [], [], []
        for slot, req in self.scheduler.admit(self._can_admit):
            # Request lifecycle track: the queued span ends here, the prefill
            # span opens (closed on the prompt-complete prefill chunk) — or,
            # for a restored victim, the decode span reopens directly.
            self.tracer.async_end(self._ev_req_queued, req.trace_id)
            if req.swapped:
                self.tracer.async_begin(self._ev_req_decode, req.trace_id)
                n = kvc.blocks_for(req.prompt_len + req.max_new,
                                   self.block_size)
                if not self.alloc.reserve(n):
                    raise RuntimeError(
                        f"reservation of {n} blocks failed post-admit")
                self._reserved[req.rid] = n
                self._seeded[req.rid] = 0   # restored blocks are private
                restores.append((slot, req))
                if self._slot_used[slot]:
                    to_reset.append(slot)
                self._slot_used[slot] = True
                continue
            self.tracer.async_begin(self._ev_req_prefill, req.trace_id)
            blocks, ptoks, n_fresh = self._prefix_match.pop(
                req.rid, ((), 0, None))
            n = (n_fresh if n_fresh is not None else
                 kvc.blocks_for(req.prompt_len + req.max_new, self.block_size))
            if not self.alloc.reserve(n):   # _can_admit just vouched for this
                raise RuntimeError(f"reservation of {n} blocks failed post-admit")
            self._reserved[req.rid] = n
            self._seeded[req.rid] = len(blocks)
            if self.prefix_cache is not None:
                self.metrics.prefix_lookups += 1
                if blocks:
                    self.metrics.prefix_hits += 1
                    self.metrics.prefix_hit_tokens += ptoks
                    if self._flow:
                        self.tracer.instant(self._ev_prefix_hit, ptoks)
                    seeds.append((slot, list(blocks), ptoks))
            # A *refilled* slot needs its recurrent state and length zeroed
            # (the rest of the batch keeps decoding undisturbed); a
            # never-used slot is already zeroed — no step needed.
            if self._slot_used[slot]:
                to_reset.append(slot)
            self._slot_used[slot] = True
        if to_reset:
            mask = np.zeros((self.slots,), bool)
            mask[to_reset] = True
            self.tracer.begin(self._ev_reset)
            self.state = self._run_compiled(
                "reset", self._reset_fn, self.state, jnp.asarray(mask))
            self.tracer.end(self._ev_reset)
        if seeds:
            # Install the forked prefix *after* any reset: the slot's table
            # row starts with the shared blocks and its length starts at the
            # (block-aligned) cached-token count, so every later KV write —
            # prefill of the suffix, then decode — lands at positions >= the
            # shared boundary, i.e. only ever in refcount-1 blocks.
            lengths = np.array(self.state.lengths)
            for slot, blocks, ptoks in seeds:
                self.tables.seed(slot, blocks)
                lengths[slot] = ptoks
            self.state = self.state._replace(lengths=jnp.asarray(lengths))
        if restores:
            self._restore(restores)

    # -- KV-swap preemption --------------------------------------------------

    def _pick_victim(self) -> Optional[Request]:
        """The decoding request to evict for the queue head: strictly lower
        class than the head, latest-submitted first (it has done the least
        work and will re-queue behind no one of its own class).  None when
        the head would gain nothing (no queue, or no lower-class victim —
        preemption never reorders within a class)."""
        head = self.scheduler.next_queued()
        if head is None:
            return None
        head_rank = priority_rank(head.priority)
        victims = [
            r for r in self.scheduler.slots
            if r is not None and r.phase is Phase.DECODE and r.out_tokens
            and priority_rank(r.priority) > head_rank
        ]
        if not victims:
            return None
        return max(victims, key=lambda r: (priority_rank(r.priority),
                                           r.submit_step, r.rid))

    def _swap_out(self, victim: Request) -> None:
        """Serialize the victim's KV blocks to host memory, release its
        blocks + reservation (the accounting mirror of _finish), and return
        it to the front of its class queue."""
        t0 = time.monotonic()
        slot = victim.slot
        ids = list(self.tables.blocks[slot])
        payload = kvc.swap_out_blocks(self.state.caches, ids)
        self._swapped[victim.rid] = (payload, len(ids))
        # Reservation unwind mirrors _finish: seeded (forked-prefix) blocks
        # were never reserved, so only fresh draws count against it.
        fresh = len(ids) - self._seeded.pop(victim.rid, 0)
        unused = max(0, self._reserved.pop(victim.rid, fresh) - fresh)
        self.scheduler.preempt(victim)
        self.tables.release(slot, self.alloc, unreserve=unused)
        self.metrics.preemptions += 1
        self.metrics.swap_out_blocks += len(ids)
        self.metrics.swap_time_s += time.monotonic() - t0
        tr = self.tracer
        tr.async_end(self._ev_req_decode, victim.trace_id)
        tr.async_begin(self._ev_req_queued, victim.trace_id)
        if self._flow:
            tr.instant(self._ev_preempt, victim.trace_id)

    def _restore(self, restores) -> None:
        """Swap preempted requests' KV payloads back into freshly-allocated
        blocks; runs after the reset step (which zeroed the slot) so the
        restored lengths/tables are what the next step sees."""
        t0 = time.monotonic()
        lengths = np.array(self.state.lengths)
        caches = self.state.caches
        for slot, req in restores:
            payload, n_blocks = self._swapped.pop(req.rid)
            ids = self.alloc.alloc(n_blocks)
            self.tables.seed(slot, ids)
            caches = kvc.swap_in_blocks(caches, ids, payload)
            # Device length between ticks is one behind req.length: the
            # newest emitted token is the *next* step's input — its KV is
            # written when it is fed, exactly as if never preempted.
            lengths[slot] = req.length - 1
            self._last_token[slot] = req.out_tokens[-1]
            req.swapped = False
            self.metrics.swap_in_blocks += n_blocks
            if self._flow:
                self.tracer.instant(self._ev_restore, req.trace_id)
        self.state = self.state._replace(
            caches=caches, lengths=jnp.asarray(lengths))
        self.metrics.swap_time_s += time.monotonic() - t0

    def _sync_tables(self) -> None:
        if self.tables.dirty:
            self.state = self.state._replace(block_tables=self.tables.array())

    def _finish(self, req: Request) -> None:
        slot = self.scheduler.release(req)
        drawn = len(self.tables.blocks[slot])
        # Seeded (forked-prefix) blocks were never reserved — only the fresh
        # draws count against this request's reservation.
        fresh_drawn = drawn - self._seeded.pop(req.rid, 0)
        unused = max(0, self._reserved.pop(req.rid, fresh_drawn) - fresh_drawn)
        self.tables.release(slot, self.alloc, unreserve=unused)
        self.results[req.rid] = np.asarray(req.out_tokens, np.int32)
        if self.drafter is not None:
            # Committed stream into the drafter corpus: greedy decoding is
            # deterministic, so a later repeat/templated request re-generates
            # this stream and the drafter proposes its true continuation.
            self.drafter.remember(
                np.concatenate([req.prompt, self.results[req.rid]]))
        now = time.monotonic()
        t_submit = self._submit_t.pop(req.rid)   # fully consumed here; a
        t_first = self._first_tok_t.pop(req.rid, now)  # long-lived engine
        self.metrics.note_request(RequestMetrics(  # must not leak these
            rid=req.rid, prompt_len=req.prompt_len,
            new_tokens=len(req.out_tokens),
            ttft_s=t_first - t_submit,
            latency_s=now - t_submit,
            queue_steps=(req.first_token_step or self._step) - req.submit_step,
            cached_tokens=req.cached_tokens,
            priority=req.priority, tenant=req.tenant,
            preemptions=req.preemptions,
        ), self._request_log)
        if self._flow:
            # Lands inside the enclosing tick slice (_record_token runs
            # after the phase span closed, before the tick ends) — the
            # arrowhead points at the tick that finished the request.
            self.tracer.flow_end(self._ev_flow, req.trace_id)
        self.tracer.async_end(self._ev_req_decode, req.trace_id)

    def _sampling_args(self, reqs: List[Request]):
        """Per-slot sampling-knob arrays for a decode/verify batch, or None
        when every request in it is greedy — the all-greedy fast path keeps
        dispatching the plain compiled steps, so greedy traffic is bitwise
        identical with or without sampling support.  Greedy rows inside a
        mixed batch get temperature 0 and emit argmax on device."""
        if all(r.sampling.is_greedy for r in reqs):
            return None
        temp = np.zeros((self.slots,), np.float32)
        top_k = np.zeros((self.slots,), np.int32)
        top_p = np.ones((self.slots,), np.float32)
        seeds = np.zeros((self.slots,), np.int32)
        gen_idx = np.zeros((self.slots,), np.int32)
        for r in reqs:
            sp = r.sampling
            temp[r.slot] = max(sp.temperature, 0.0)
            top_k[r.slot] = sp.top_k
            top_p[r.slot] = sp.top_p
            seeds[r.slot] = r.sample_seed
            gen_idx[r.slot] = len(r.out_tokens)
        return temp, top_k, top_p, seeds, gen_idx

    def _record_token(self, req: Request, token: int) -> None:
        if req.first_token_step is None:
            self._first_tok_t[req.rid] = time.monotonic()
        self.scheduler.on_token(req, token, self._step)
        self._last_token[req.slot if req.slot >= 0 else 0] = token
        if req.phase is Phase.FINISHED:
            self._finish(req)

    # -- speculative decode: draft -> verify -> rollback ---------------------

    def _decode_speculative(self, reqs: List[Request]) -> bool:
        """One speculative decode tick over the decoding slots: the n-gram
        drafter proposes per-request continuations, one batched verify step
        scores every drafted position, and rejected-position KV blocks are
        rolled back.  Returns False (without touching the device) when no
        request drafted anything — the caller falls through to the plain
        decode step, so incompressible traffic pays zero speculative
        overhead beyond the host-side lookup."""
        drafts: Dict[int, np.ndarray] = {}
        self.tracer.begin(self._ev_draft)     # host-side n-gram lookups
        for r in reqs:
            if r.remaining > 1:    # a 1-token budget can't use a draft
                # remaining - 1: the bonus token always rides along, so the
                # last draft a request could accept is its (remaining-1)-th —
                # drafting more only widens the verify GEMM for nothing.
                d = self.drafter.draft(r.context,
                                       k=min(self.spec.k, r.remaining - 1))
                if len(d):
                    drafts[r.rid] = d
        self.tracer.end(self._ev_draft)
        if not drafts:
            return False
        width = bucket_for(max(len(d) for d in drafts.values()), self.spec.k)
        tokens = np.zeros((self.slots, width), np.int32)
        limits = np.zeros((self.slots,), np.int32)
        eos = np.full((self.slots,), -1, np.int32)
        active = np.zeros((self.slots,), bool)
        for r in reqs:
            d = drafts.get(r.rid)
            nd = 0 if d is None else len(d)
            # Real draft positions need covered blocks (writes at
            # r.length - 1 .. r.length - 1 + nd); padding columns beyond the
            # draft resolve to the null block and need none.
            self.tables.ensure(r.slot, r.length + nd, self.alloc)
            tokens[r.slot, 0] = self._last_token[r.slot]
            if nd:
                tokens[r.slot, 1:1 + nd] = d
            limits[r.slot] = min(nd + 1, r.remaining)
            eos[r.slot] = -1 if r.eos_token is None else r.eos_token
            active[r.slot] = True
        self._sync_tables()
        samp = self._sampling_args(reqs)
        t_dec = time.monotonic()
        # numpy args go straight into the jitted call: the C++ fast path
        # converts them in ~µs, where a standalone jnp.asarray dispatches an
        # un-jitted XLA copy (~100-700µs each on CPU — real money against a
        # ~1ms verify step).
        self.tracer.begin(self._ev_verify)
        if self._flow:
            for r in reqs:
                self.tracer.flow_step(self._ev_flow, r.trace_id)
        if samp is None:
            greedy, n_new, self.state = self._run_compiled(
                f"verify{width}", self._verify_fn, self.params, self.state,
                tokens, active, limits, eos)
        else:
            greedy, n_new, self.state = self._run_compiled(
                f"verify_sample{width}", self._verify_sample_fn, self.params,
                self.state, tokens, active, limits, eos, *samp)
        greedy, n_new = np.asarray(greedy), np.asarray(n_new)
        self.tracer.end(self._ev_verify)
        dt_verify = time.monotonic() - t_dec
        self.metrics.decode_time_s += dt_verify
        emitted = 0
        for r in reqs:
            slot, n = r.slot, int(n_new[r.slot])
            drafted = len(drafts.get(r.rid, ()))
            self.scheduler.on_spec(r, drafted, max(0, n - 1))
            self.metrics.spec_draft_tokens += drafted
            self.metrics.spec_accepted_tokens += max(0, n - 1)
            for t in greedy[slot, :n]:
                self._record_token(r, int(t))
            emitted += n
            # Rollback: blocks drawn for rejected draft positions go back to
            # the pool (and this request's reservation).  A finished request
            # released everything already; an accept-all tick may legally
            # need *more* blocks than it holds (covered by next tick's
            # ensure), hence the guard.
            if r.phase is not Phase.FINISHED:
                held = len(self.tables.blocks[slot])
                if kvc.blocks_for(r.length, self.block_size) < held:
                    _, pair = self.tables.rewind(slot, r.length, self.alloc)
                    # The engine only ever speculates past the shared-prefix
                    # boundary, so divergence cannot trigger here.
                    assert pair is None, "spec rewind crossed a shared block"
        self.metrics.decode_steps += 1
        self.metrics.decode_tokens += emitted
        self.metrics.spec_ticks += 1
        if samp is not None:
            self.metrics.sampled_tokens += emitted
        # Verify rows: every slot runs the widened step (padding included).
        self.mfu.note("verify", tokens=emitted, rows=self.slots * width,
                      time_s=dt_verify)
        return True

    # -- the serve loop ------------------------------------------------------

    def tick(self) -> bool:
        """Admit, then execute one scheduler action.  Returns False when no
        work remains."""
        tr = self.tracer
        tr.begin(self._ev_tick)
        tr.begin(self._ev_sched)      # host scheduling: admit + pick action
        self._admit()
        action = self.scheduler.next_action()
        tr.end(self._ev_sched)
        if action is None:
            tr.end(self._ev_tick)
            return self.scheduler.has_work
        self._step += 1
        if action[0] == "prefill":
            _, req, chunk = action
            self.tables.ensure(req.slot, req.prefilled + chunk, self.alloc)
            self._sync_tables()
            tokens = jnp.asarray(
                req.prompt[None, req.prefilled:req.prefilled + chunk])
            tr.begin(self._ev_prefill)
            if self._flow:
                tr.flow_step(self._ev_flow, req.trace_id)
            t_pre = time.monotonic()
            logits, self.state = self._run_compiled(
                f"chunk{chunk}", self._chunk_fn,
                self.params, self.state, tokens, self._slot_ids[req.slot])
            # Sync so the span/MFU time covers the device step, not just its
            # dispatch.  Chunks are state-dependent (the next chunk consumes
            # this one's KV writes), so total prefill wall time is unchanged.
            logits = jax.block_until_ready(logits)
            dt_pre = time.monotonic() - t_pre
            tr.end(self._ev_prefill)
            self.scheduler.on_prefill(req, chunk, self._step)
            self.metrics.prefill_chunks += 1
            self.metrics.prefill_tokens += chunk
            self.metrics.prefill_time_s += dt_pre
            self.mfu.note("prefill", tokens=chunk, rows=chunk, time_s=dt_pre)
            if req.phase is Phase.DECODE:
                # Prompt complete: close the request's prefill span, open its
                # decode span (closed in _finish).
                tr.async_end(self._ev_req_prefill, req.trace_id)
                tr.async_begin(self._ev_req_decode, req.trace_id)
            if req.phase is Phase.DECODE and self.prefix_cache is not None:
                # Prompt fully in the pool: publish its full blocks for
                # later requests (the cache takes its own refs; the partial
                # tail block keeps receiving decode writes and is excluded).
                n_full = req.prompt_len // self.block_size
                if n_full:
                    self.prefix_cache.insert(
                        req.prompt[: n_full * self.block_size],
                        self.tables.blocks[req.slot][:n_full])
            if req.phase is Phase.DECODE:
                # Prompt complete: the chunk's last logits yield the first
                # generated token (no separate step for it).  Index on the
                # numpy copy — slicing a device array dispatches un-jitted
                # primitives that would compile tiny kernels at serve time.
                if req.sampling.is_greedy:
                    self._record_token(
                        req, int(np.argmax(np.asarray(logits)[0, -1])))
                else:
                    sp = req.sampling
                    tok = self._run_compiled(
                        "sample1", self._sample1_fn, logits,
                        np.float32(sp.temperature), np.int32(sp.top_k),
                        np.float32(sp.top_p), np.int32(req.sample_seed),
                        np.int32(len(req.out_tokens)))
                    self.metrics.sampled_tokens += 1
                    self._record_token(req, int(np.asarray(tok)[0]))
        elif self.spec is not None and self._decode_speculative(action[1]):
            pass                              # spec tick ran (metrics inside)
        else:
            _, reqs = action
            # The step writes at position r.length - 1 (the last recorded
            # token's KV goes in on the step that consumes it), so covering
            # r.length tokens suffices — +1 would draw blocks a step early.
            for r in reqs:
                self.tables.ensure(r.slot, r.length, self.alloc)
            self._sync_tables()
            # numpy args feed the jitted call directly — see the note in
            # _decode_speculative; an explicit jnp.asarray here costs more
            # than the decode step's own dispatch.
            tokens = self._last_token[:, None]
            active = np.zeros((self.slots,), bool)
            active[[r.slot for r in reqs]] = True
            samp = self._sampling_args(reqs)
            t_dec = time.monotonic()
            tr.begin(self._ev_decode)
            if self._flow:
                for r in reqs:
                    tr.flow_step(self._ev_flow, r.trace_id)
            if samp is None:
                logits, self.state = self._run_compiled(
                    "decode", self._decode_fn, self.params, self.state,
                    tokens, active)
                # np.asarray blocks on the result — the span covers the step.
                next_tok = np.argmax(np.asarray(logits)[:, -1], axis=-1)
            else:
                sampled, self.state = self._run_compiled(
                    "decode_sample", self._sample_fn, self.params, self.state,
                    tokens, active, *samp)
                next_tok = np.asarray(sampled)
                self.metrics.sampled_tokens += len(reqs)
            tr.end(self._ev_decode)
            dt_dec = time.monotonic() - t_dec
            self.metrics.decode_time_s += dt_dec
            # Decode rows: all slots execute (padding rows included) —
            # tokens counts only the active requests' commits.
            self.mfu.note("decode", tokens=len(reqs), rows=self.slots,
                          time_s=dt_dec)
            for r in reqs:
                self._record_token(r, int(next_tok[r.slot]))
            self.metrics.decode_steps += 1
            self.metrics.decode_tokens += len(reqs)
        self.metrics.peak_blocks_in_use = max(
            self.metrics.peak_blocks_in_use, self.alloc.in_use)
        self.metrics.occupancy_sum += self.alloc.occupancy()
        self.metrics.occupancy_samples += 1
        tr.counter(self._ev_kv_in_use, self.alloc.in_use)
        tr.counter(self._ev_kv_reserved, self.alloc.reserved)
        tr.end(self._ev_tick)
        return True

    def run(self, max_ticks: Optional[int] = None) -> Dict[int, np.ndarray]:
        """Drive the loop until the queue and all slots drain."""
        self._t0 = time.monotonic()
        ticks = 0
        while self.scheduler.has_work:
            if max_ticks is not None and ticks >= max_ticks:
                break
            if not self.tick():
                break
            ticks += 1
        self.metrics.elapsed_s += time.monotonic() - self._t0
        return self.results
