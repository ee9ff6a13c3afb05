#!/usr/bin/env python3
"""Bring-up smoke run: the serving path on a TPU at published widths.

  python chip_smoke.py              # one chip
  python chip_smoke.py --chips 4    # four one-chip replicas behind a router

One chip.  gemma3-1b as published (``configs.get``: 26 layers, d_model 1152,
head_dim 256, MQA, 5:1 local:global windows of 512, tied 262k vocab; random
weights from ``--seed``) goes through the normal serving entry points: an
``Engine`` built the way ``launch/serve.py`` builds one (8 slots, max_seq
1024, 16-token blocks, analytic autotune, so the tuned decode-kernel spec is
the one that runs), warmup, then 8 greedy requests of 300-900 prompt tokens
(crossing the 512-token window) and 32 new tokens each.  A second phase
serves 4 of them again with w8a8 weights and an int8 KV pool.  Each phase
fails unless:

  * the backend is the TPU and the lowered decode and prefill-chunk steps
    contain the Pallas kernels (``tpu_custom_call``): the paged flash-decode
    kernel and the GeMM of the phase's precision;
  * every request returns its 32 tokens, and no step compiled after warmup;
  * for two prompts, the engine's next-token logits match a kernel-free
    float32 reference forward within the bound stated beside it below.

Four chips.  Only the replica path users run with ``serve --replicas``: a
``ReplicaPool`` of 4 engines, one pinned to each chip, behind a ``Router``,
serves a greedy trace; one replica serves the same trace; the outputs must
be token-identical, and each replica's params and KV pools must sit on its
own chip.

Lines before the last are bring-up readings (device, peak device memory,
warmup and serve seconds), not measurements.  The last line is one JSON
object, printed only when every check passed:

  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

The script exits nonzero without that line when JAX finds no TPU, when the
repository's ``src/`` is missing, or when a check fails.  Everything runs in
this one process (a chip belongs to one process); it starts no other.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

ARCH = "gemma3-1b"
SLOTS, MAX_SEQ, BLOCK, CHUNK = 8, 1024, 16, 64
N_REQUESTS, PROMPT_LENS, MAX_NEW = 8, (300, 900), 32
N_W8A8 = 4
N_LOGIT_PROMPTS = 2

# Bounds on the relative L2 error ||kernel - ref|| / ||ref|| of one prompt's
# next-token logits (262144 of them) against the float32 reference forward
# (same weights upcast, XLA ops only, matmul precision "highest").
#
# bf16 phase: the weights are bf16 in both paths (the upcast is exact), so
# the error is activation rounding alone: projection outputs, the residual
# stream and the KV pool are rounded to bf16 (relative step 2^-8) a few
# times per layer.  At these widths on the CPU (XLA paths, 8192-token vocab)
# it measured 0.010-0.012 at 6 layers and 0.012-0.014 at 13, growing about
# as the square root of depth: ~0.02 expected at 26.  The bound leaves more
# than 2x over that; a wrong mask, window or block walk moves the logits by
# order 1.
FLOAT_BOUND = 0.05
# w8a8 + int8-KV phase: weights per output channel, activations per row and
# K/V per token are rounded to 127 levels.  Same CPU measurement: 0.045 at
# 6 layers, 0.054-0.058 at 13, ~0.08 expected at 26; the bound allows 3x.
W8A8_BOUND = 0.25

REPLICAS = 4
REPLICA_REQUESTS, REPLICA_PROMPT_LENS, REPLICA_MAX_NEW = 8, (64, 200), 16
REPLICA_MAX_SEQ, REPLICA_CHUNK = 256, 32


class CheckFailed(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)


def make_trace(vocab: int, n: int, lens, max_new: int, seed: int):
    """n greedy requests with seeded prompt lengths in lens (inclusive)."""
    import numpy as np

    from repro.serving.request import RequestSpec

    rng = np.random.default_rng(seed)
    sizes = rng.integers(lens[0], lens[1] + 1, size=n)
    return [RequestSpec(prompt=rng.integers(0, vocab, size=int(s))
                        .astype(np.int32), max_new=max_new) for s in sizes]


def step_kernels(eng):
    """Lower the decode and 64-token chunk steps the way the engine traced
    them (same step builders, same trace-time bindings) and return the
    names of the Pallas kernels in each program."""
    import contextlib
    import re

    import jax
    import jax.numpy as jnp

    from repro.launch import steps as steps_lib

    ctx = contextlib.nullcontext()
    if eng.precision != "float":
        from repro.quant import modes as qmodes

        ctx = qmodes.precision(eng.precision)
    with ctx:
        decode = jax.jit(steps_lib.make_paged_serve_step(eng.cfg)).lower(
            eng.params, eng.state, jnp.zeros((eng.slots, 1), jnp.int32),
            jnp.zeros((eng.slots,), bool)).as_text()
        chunk = jax.jit(steps_lib.make_prefill_chunk_step(eng.cfg)).lower(
            eng.params, eng.state, jnp.zeros((1, eng.max_chunk), jnp.int32),
            jnp.int32(0)).as_text()
    def names(text):
        if "tpu_custom_call" not in text:
            return set()
        return set(re.findall(r'kernel_name = "([^"]+)"', text))

    return names(decode), names(chunk)


def reference_logits(cfg, params, prompts):
    """Next-token logits of each prompt from the model's plain forward in
    float32: params upcast, every GeMM and attention as XLA ops
    (``ops.set_default_backend("xla")``, restored after), matmul precision
    "highest"."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops
    from repro.models import model as M

    cfg32 = dataclasses.replace(cfg, dtype="float32")
    p32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    fwd = jax.jit(lambda p, t: M.forward(p, cfg32, {"tokens": t},
                                         last_only=True))
    prev = ops.get_default_backend()
    ops.set_default_backend("xla")
    try:
        with jax.default_matmul_precision("highest"):
            return [np.asarray(fwd(p32, jnp.asarray(p[None])))[0, -1]
                    for p in prompts]
    finally:
        ops.set_default_backend(prev)


def rel_err(got, want) -> float:
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def serve_phase(name, cfg, params, trace, refs, bound, *, precision="float",
                kv_precision="float"):
    """Warm an Engine as serve.main builds it, serve `trace`, and check
    completeness, compiles, kernels and logits.  Returns the engine."""
    import jax
    import numpy as np

    from repro.serving.engine import Engine

    eng = Engine(cfg, params=params, slots=SLOTS, max_seq=MAX_SEQ,
                 block_size=BLOCK, max_chunk=CHUNK, autotune=True,
                 precision=precision, kv_precision=kv_precision,
                 verbose=True)
    t0 = time.perf_counter()
    eng.warmup()
    t_warm = time.perf_counter() - t0
    gemm = "_gemm_kernel" if precision == "float" else "_dequant_gemm_kernel"
    for step, kernels in zip(("decode", "chunk"), step_kernels(eng)):
        print(f"{name}: {step} step kernels {sorted(kernels)}", flush=True)
        check({"_decode_kernel", gemm} <= kernels,
              f"{name}: the {step} step lacks the flash-decode or {gemm} "
              f"Pallas kernel: {sorted(kernels)}")
    for spec in trace:
        check(eng.submit(spec) is not None, f"{name}: a request was shed")
    t0 = time.perf_counter()
    results = eng.run()
    t_serve = time.perf_counter() - t0
    check(len(results) == len(trace),
          f"{name}: {len(results)} of {len(trace)} requests finished")
    short = {rid: len(t) for rid, t in results.items()
             if len(t) != trace[0].max_new}
    check(not short, f"{name}: requests short of {trace[0].max_new} "
                     f"tokens: {short}")
    check(eng.metrics.cold_compiles == 0,
          f"{name}: {eng.metrics.cold_compiles} steps compiled after warmup")
    got = [eng.prompt_logits(spec.prompt) for spec in trace[:len(refs)]]
    errs = [rel_err(g, ref) for g, ref in zip(got, refs)]
    top1 = [int(np.argmax(g) == np.argmax(ref)) for g, ref in zip(got, refs)]
    stats = jax.devices()[0].memory_stats() or {}
    print(f"[bring-up reading] {name}: warmup {t_warm:.1f}s, serve "
          f"{t_serve:.1f}s for {len(trace)} requests, peak device memory "
          f"{stats.get('peak_bytes_in_use', 0) / 2**30:.2f} GiB; "
          f"{eng.metrics.summary()}", flush=True)
    print(f"{name}: logits rel err vs f32 reference {errs} "
          f"(bound {bound}), top-1 agrees {top1}", flush=True)
    check(all(e < bound for e in errs),
          f"{name}: logits rel err {errs} over the bound {bound}")
    return eng


def one_chip(seed: int) -> None:
    import jax

    from repro import configs
    from repro.models import model as M

    cfg = configs.get(ARCH)
    params = M.init_model(jax.random.PRNGKey(seed), cfg)
    trace = make_trace(cfg.vocab, N_REQUESTS, PROMPT_LENS, MAX_NEW, seed)
    refs = reference_logits(
        cfg, params, [s.prompt for s in trace[:N_LOGIT_PROMPTS]])
    eng = serve_phase("bf16", cfg, params, trace, refs, FLOAT_BOUND)
    del eng
    serve_phase("w8a8+int8kv", cfg, params, trace[:N_W8A8], refs, W8A8_BOUND,
                precision="w8a8", kv_precision="int8")


def four_chips(seed: int) -> None:
    import jax

    from repro import cluster, configs
    from repro.models import model as M

    devices = jax.devices()
    check(len(devices) == REPLICAS,
          f"--chips {REPLICAS} needs {REPLICAS} devices, found {len(devices)}")
    cfg = configs.get(ARCH)
    params = M.init_model(jax.random.PRNGKey(seed), cfg)
    trace = make_trace(cfg.vocab, REPLICA_REQUESTS, REPLICA_PROMPT_LENS,
                       REPLICA_MAX_NEW, seed)
    kw = dict(params=params, slots=SLOTS, max_seq=REPLICA_MAX_SEQ,
              block_size=BLOCK, max_chunk=REPLICA_CHUNK, autotune=True)

    def serve(pool):
        t0 = time.perf_counter()
        pool.warmup(verbose=True)
        t_warm = time.perf_counter() - t0
        pool.start()
        router = cluster.Router(pool, policy="round-robin")
        t0 = time.perf_counter()
        try:
            handles = [router.submit(spec) for spec in trace]
            check(all(h is not None for h in handles), "a request was shed")
            router.drain(timeout=600)
            out = [h.result().tolist() for h in handles]
        finally:
            router.close()
        print(f"[bring-up reading] {len(pool)} replica(s): warmup "
              f"{t_warm:.1f}s, serve {time.perf_counter() - t0:.1f}s",
              flush=True)
        return out

    pool = cluster.ReplicaPool(cfg, REPLICAS, **kw)
    got = serve(pool)
    for r in pool.replicas:
        held = set()
        for leaf in jax.tree_util.tree_leaves((r.engine.params,
                                               r.engine.state)):
            held |= leaf.devices()
        check(held == {r.device},
              f"replica {r.idx}: state on {held}, pinned to {r.device}")
    pinned = {r.device for r in pool.replicas}
    check(len(pinned) == REPLICAS, f"replicas share devices: {pinned}")
    single = cluster.ReplicaPool(cfg, 1, devices=[devices[0]], **kw)
    # Same config on the same chip as replica 0: reuse its compiled steps.
    single.engines[0].share_steps_from(pool.engines[0])
    want = serve(single)
    check(all(len(t) == REPLICA_MAX_NEW for t in got),
          "a replica request came back short")
    diff = [i for i, (a, b) in enumerate(zip(got, want)) if a != b]
    check(not diff, f"requests {diff} differ between 4 replicas and 1")
    print(f"replicas: {REPLICAS} distinct devices "
          f"{sorted(str(d) for d in pinned)}, {len(got)} requests "
          f"token-identical to 1 replica", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, REPLICAS])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    if jax.default_backend() != "tpu":
        print(f"chip_smoke: JAX found no TPU (backend "
              f"{jax.default_backend()!r})", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repository's src/ is not beside this "
              f"script: {e}", file=sys.stderr)
        return 2
    enable_compile_cache()
    os.environ.setdefault("REPRO_TUNE_CACHE",
                          os.path.join(ROOT, ".jax_cache", "tunecache.json"))
    dev = jax.devices()[0]
    print(f"[bring-up reading] device {dev.platform} {dev.device_kind} "
          f"x{len(jax.devices())}", flush=True)
    try:
        if args.chips == 1:
            one_chip(args.seed)
        else:
            four_chips(args.seed)
    except CheckFailed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
