"""Serving engine benchmark: engine decode throughput, float vs w8a8
(int8-resident) decode throughput, and the int8 quality delta.

Paper artifact: none directly — this measures the serving-path analogues of
the paper's mechanisms (EXPERIMENTS.md §Serving, §Quantization).  The
headline row is the w8a8-vs-float decode-throughput delta (the paper's int8
deployment precision carried through the serving stack).

Output rows (CSV via benchmarks/run.py):
  serving/decode_tok_s          float decode throughput, tokens/s
  serving/decode_tok_s_w8a8     w8a8 decode throughput, tokens/s (derived =
                                the float row: the delta the gate requires)
  serving/w8a8_decode_speedup   w8a8-vs-float decode-throughput ratio
                                (int8 datapath effect on this host)
  serving/w8a8_weight_savings   int8-resident weight-memory saving fraction
  serving/w8a8_nll_delta        end-to-end quality delta (quant NLL - float
                                NLL on held-out synthetic batches, via
                                quant/report.py)

All engines are pre-compiled (Engine.warmup) and decode timings are
best-of-N interleaved, so rows measure steady-state dispatch, not compiles
or shared-host noise.  NOTE: the w8a8 throughput ratio is *host-dependent* —
on CPU (xla int8 matmul) int8 usually loses to f32; on TPU the int8 MXU
path is the paper's regime.  The row exists to keep the number measured,
whatever it is.

Expected runtime: ~2 min on CPU (dominated by warmup compiles).
REPRO_BENCH_FAST=1 (or `benchmarks/run.py --fast` / `make bench-smoke`)
shrinks prompts/iterations to a smoke run of the same code paths.
"""

from __future__ import annotations

import os

import numpy as np

from repro import configs
from repro.serving.engine import Engine
from repro.tuning import env_truthy

FAST = env_truthy(os.environ.get("REPRO_BENCH_FAST"))

ARCH = "gemma3-1b"
PROMPT_LEN = 16 if FAST else 64
SLOTS = 2 if FAST else 4
GEN_LEN = 8 if FAST else 16
ITERS = 2 if FAST else 5
MAX_CHUNK = 16 if FAST else 64


def _decode_run(eng, prompts, gen_len):
    """Submit all prompts, run to completion; returns decode-tick seconds."""
    t0_tokens, t0_time = eng.metrics.decode_tokens, eng.metrics.decode_time_s
    for p in prompts:
        eng.submit(p, max_new=gen_len)
    eng.run()
    return (eng.metrics.decode_tokens - t0_tokens,
            eng.metrics.decode_time_s - t0_time)


def _quality_rows(cfg):
    """Float-vs-w8a8 NLL on held-out synthetic batches (quant/report.py)."""
    import jax

    from repro import quant
    from repro.models import model as M

    params = M.init_model(jax.random.PRNGKey(0), cfg)
    qparams = quant.quantize_params(params, cfg=cfg)
    rng = np.random.default_rng(7)
    batches = []
    for _ in range(1 if FAST else 2):
        toks = rng.integers(0, cfg.vocab, size=(2, 32)).astype(np.int32)
        batches.append({"tokens": toks, "labels": np.roll(toks, -1, axis=1)})
    return quant.quality_delta(params, qparams, cfg, batches, mode="w8a8")


def run():
    cfg = configs.get_smoke(ARCH)
    max_seq = PROMPT_LEN + GEN_LEN + 1
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, size=PROMPT_LEN).astype(np.int32)
               for _ in range(SLOTS)]

    # float vs w8a8 decode throughput, engines interleaved per iteration so
    # host load spikes hit both alike
    f_eng = Engine(cfg, slots=SLOTS, max_seq=max_seq, block_size=16,
                   max_chunk=MAX_CHUNK)
    q_eng = Engine(cfg, slots=SLOTS, max_seq=max_seq, block_size=16,
                   max_chunk=MAX_CHUNK, precision="w8a8")
    f_eng.warmup()
    q_eng.warmup()
    f_best = q_best = 0.0
    for _ in range(ITERS):
        toks, secs = _decode_run(f_eng, prompts, GEN_LEN)
        f_best = max(f_best, toks / secs if secs else 0.0)
        toks, secs = _decode_run(q_eng, prompts, GEN_LEN)
        q_best = max(q_best, toks / secs if secs else 0.0)

    delta = _quality_rows(cfg)
    savings = (1.0 - q_eng.metrics.weight_bytes
               / max(q_eng.metrics.weight_bytes_float, 1))

    return [
        {"name": "serving/decode_tok_s",
         "value": round(f_best, 1), "derived": ""},
        {"name": "serving/decode_tok_s_w8a8",
         "value": round(q_best, 1), "derived": round(f_best, 1)},
        {"name": "serving/w8a8_decode_speedup",
         "value": round(q_best / f_best, 3) if f_best else "",
         "derived": "host-dependent (int8 MXU on TPU)"},
        {"name": "serving/w8a8_weight_savings",
         "value": round(savings, 3), "derived": "~0.66 (int8 + f32 scales)"},
        {"name": "serving/w8a8_nll_delta",
         "value": round(delta["delta_nll"], 5),
         "derived": round(delta["float_nll"], 5)},
    ]


def rows():
    return run()


if __name__ == "__main__":
    print("name,value,derived")
    for r in rows():
        print(f"{r['name']},{r['value']},{r['derived']}")
