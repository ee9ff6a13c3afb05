"""Serving launcher: thin CLI over the serving engine (repro.serving).

The engine maps the paper's three utilization mechanisms onto the request
path — warmup (autotune + AOT compile) as configuration pre-loading, chunked
prefill interleaved with decode as input pre-fetching with output buffering,
and the paged KV cache as programmable strided memory access.  See
EXPERIMENTS.md §Serving for the mechanism table and measured speedups.

  PYTHONPATH=src python -m repro.launch.serve --arch gemma3-1b --requests 8 \
      --autotune --compare-prefill

``--size full`` serves the arch's published config (``configs.get``) instead
of its reduced CPU preset; run it on the accelerator:

  PYTHONPATH=src python -m repro.launch.serve --arch gemma3-1b --size full \
      --autotune --prompt-len 512 --gen-len 32

``--compare-prefill`` additionally times the legacy token-by-token prefill
loop (decode steps over a padded batch) against the engine's chunked prefill
on the same prompts and prints the wall-clock speedup.

``--precision w8a8`` serves through the paper's int8 deployment datapath:
warmup calibrates/quantizes the weights int8-resident and compiles int8
decode/prefill steps (see repro.quant and EXPERIMENTS.md §Quantization).
"""

from __future__ import annotations

import argparse
import json
import time
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.launch import steps as steps_lib
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as M
from repro.serving.engine import (  # re-exported for back-compat
    Engine,
    autotune_for_serving,
    serving_gemm_shapes,
)
from repro.serving.request import PRIORITIES, RequestSpec, SamplingParams

__all__ = ["Engine", "autotune_for_serving", "serving_gemm_shapes",
           "token_by_token_prefill", "serve_cluster", "main"]


def _parse_class_mix(spec: str):
    """'interactive=0.7,batch=0.3' -> (('interactive', 0.7), ('batch', 0.3));
    empty string -> None (all-interactive traffic)."""
    if not spec:
        return None
    mix = []
    for part in spec.split(","):
        name, _, w = part.partition("=")
        name = name.strip()
        if name not in PRIORITIES:
            raise SystemExit(f"--priority-classes: unknown class {name!r}; "
                             f"expected one of {PRIORITIES}")
        mix.append((name, float(w) if w else 1.0))
    return tuple(mix)


def _sampling_from_args(args) -> SamplingParams:
    """CLI sampling knobs -> SamplingParams (temperature 0 = greedy)."""
    return SamplingParams(temperature=args.temperature, top_k=args.top_k,
                          top_p=args.top_p,
                          seed=args.seed if args.seed >= 0 else None)


def warm_token_by_token(cfg, params, slots: int, max_seq: int):
    """Compile the baseline's decode step and build its initial state
    *before* any timed region — the same footing the engine gets from
    Engine.warmup().  Returns (jitted step, initial decode state) to pass
    into token_by_token_prefill."""
    serve_step = jax.jit(steps_lib.make_serve_step(cfg))
    state = M.init_decode_state(params, cfg, slots, max_seq)
    out, _ = serve_step(params, state, jnp.zeros((slots, 1), jnp.int32))
    jax.block_until_ready(out)
    return serve_step, state


def token_by_token_prefill(cfg, params, prompts: List[np.ndarray], *,
                           max_seq: int, warmed=None):
    """The pre-engine prefill path, kept as the comparison baseline: pad all
    prompts to the batch max and feed them through the decode step one token
    at a time (short prompts burn dead steps on their padding positions).

    Pass `warmed` from warm_token_by_token() when timing this, so the
    measurement is steady-state dispatch — not the jit trace+compile or the
    dense cache allocation.  Returns (last logits, state, step call count).
    """
    slots = len(prompts)
    if warmed is None:
        warmed = warm_token_by_token(cfg, params, slots, max_seq)
    serve_step, state = warmed
    maxlen = max(len(p) for p in prompts)
    padded = np.zeros((slots, maxlen), np.int32)
    for i, p in enumerate(prompts):
        padded[i, :len(p)] = p
    last = None
    for t in range(maxlen):
        last, state = serve_step(params, state, jnp.asarray(padded[:, t:t + 1]))
    jax.block_until_ready(last)
    return last, state, maxlen


def compare_prefill(cfg, params, prompts: List[np.ndarray], *, slots: int,
                    max_seq: int, block_size: int = 16, num_blocks=None,
                    max_chunk: int = 64, iters: int = 3):
    """Time legacy token-by-token prefill vs the engine's chunked prefill on
    the same prompts; returns (t_legacy_s, t_chunked_s).

    Both paths are pre-compiled (warm_token_by_token / Engine.warmup) and
    the iterations *interleave* legacy/chunked runs, each side reported as
    its best-of-`iters` — so shared-host load spikes hit both paths alike
    and the ratio measures steady-state step-count/batching effects.
    Engine iterations after the first refill previously-used slots —
    steady-state serving, slot resets included.  The one comparison harness
    behind both the ``--compare-prefill`` CLI flag and
    benchmarks/serving_bench.py.
    """
    if params is None:
        params = M.init_model(jax.random.PRNGKey(0), cfg)
    warmed = warm_token_by_token(cfg, params, slots, max_seq)
    eng = Engine(cfg, params=params, slots=slots, max_seq=max_seq,
                 block_size=block_size, num_blocks=num_blocks,
                 max_chunk=max_chunk)
    eng.warmup()

    def legacy():
        token_by_token_prefill(cfg, params, prompts[:slots],
                               max_seq=max_seq, warmed=warmed)

    def chunked():
        # max_new=1: the first token falls out of the final chunk, so each
        # run is pure prefill.
        for p in prompts[:slots]:
            eng.submit(RequestSpec(prompt=p, max_new=1))
        eng.run()

    t_legacy, t_chunked = float("inf"), float("inf")
    for _ in range(iters):
        t_legacy = min(t_legacy, _timed(legacy))
        t_chunked = min(t_chunked, _timed(chunked))
    return t_legacy, t_chunked


def _timed(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def _build_recorder(args, *, metadata=None):
    """FlightRecorder for --incident-dir (None when the flag is off)."""
    if not getattr(args, "incident_dir", ""):
        return None
    from repro.obs import FlightRecorder

    # min_interval_s: a shed storm writes one bundle per reason per second,
    # not one per refused request.
    return FlightRecorder(args.incident_dir, min_interval_s=1.0,
                          metadata=metadata)


def _evaluate_slo(args, snapshot, recorder, engines):
    """--slo post-run evaluation: print the burn-rate report, capture
    breach bundles, and run the built-in engine pressure triggers."""
    report = None
    if getattr(args, "slo", ""):
        from repro.obs import SloMonitor, parse_slo_spec

        monitor = SloMonitor(parse_slo_spec(args.slo))
        report = monitor.observe(snapshot)
        print(f"slo: {report.summary()}")
        if recorder is not None:
            recorder.record_breaches(report)
    if recorder is not None:
        for e in engines:
            recorder.check_engine(e)
        if recorder.incidents:
            print(f"incidents: {len(recorder.incidents)} bundle(s) -> "
                  + ", ".join(recorder.incidents))
    return report


def serve_cluster(cfg, args) -> None:
    """Multi-replica serving (repro.cluster): pool + router + traffic.

    Exits nonzero (SystemExit) when any request resolved with an error."""
    from repro import cluster

    max_seq = args.prompt_len + args.gen_len + 1
    sampling = _sampling_from_args(args)
    class_mix = _parse_class_mix(args.priority_classes)
    pool = cluster.ReplicaPool(
        cfg, args.replicas, slots=args.slots or 2, max_seq=max_seq,
        block_size=args.block_size, num_blocks=args.kv_blocks or None,
        max_chunk=args.chunk, autotune=args.autotune,
        tune_mode=args.tune_mode, precision=args.precision,
        kv_precision=args.kv_precision,
        prefix_cache=args.prefix_cache,
        speculative=args.draft_k if args.speculative else False,
        sampling=not sampling.is_greedy, preempt=args.preempt,
        trace=bool(args.trace_out))
    # Router lane for the distributed trace: admission/shed/route events
    # live on their own pid above the replica lanes, and every request's
    # flow arrow starts here.
    router_tracer = None
    if args.trace_out:
        from repro.obs import Tracer

        router_tracer = Tracer(name="router", pid=args.replicas)
    recorder = _build_recorder(
        args, metadata={"arch": cfg.name, "replicas": args.replicas})
    if recorder is not None:
        if router_tracer is not None:
            recorder.add_tracer(router_tracer)
        for i, e in enumerate(pool.engines):
            recorder.attach_engine(e, name=f"replica{i}")
    t0 = time.time()
    pool.warmup(verbose=True)
    print(f"warmup: {args.replicas} replicas in {time.time() - t0:.1f}s "
          f"(steps compiled once, shared)")
    trace = cluster.mixed_traffic(
        cfg.vocab, n=args.requests, seed=0,
        max_prompt=args.prompt_len, max_new=(2, args.gen_len),
        class_mix=class_mix, tenants=args.tenants)
    pool.start()
    router = cluster.Router(pool, policy=args.router_policy,
                            max_pending=args.max_pending or None,
                            tracer=router_tracer, recorder=recorder)
    t0 = time.time()
    handles, shed = cluster.replay(
        trace, router.submit,
        sampling=None if sampling.is_greedy else sampling)
    router.drain()
    elapsed = time.time() - t0
    failed = [h for h in handles if h.error is not None]
    m = cluster.aggregate(pool, router, elapsed_s=elapsed)
    print(f"cluster[{args.router_policy}]: {m.summary()}")
    for i, e in enumerate(pool.engines):
        print(f"  replica[{i}]: {e.metrics.summary()}")
    router.close()
    pool.stop()     # replica threads must be parked before reading the rings
    _evaluate_slo(args, cluster.slo_snapshot(m), recorder, pool.engines)
    if args.trace_out:
        doc = pool.export_trace(
            args.trace_out, metadata={"arch": cfg.name,
                                      "replicas": args.replicas},
            extra_tracers=[router_tracer] if router_tracer else ())
        print(f"trace: {len(doc['traceEvents'])} events -> {args.trace_out} "
              f"(open in https://ui.perfetto.dev)")
    if args.metrics_json:
        with open(args.metrics_json, "w") as f:
            json.dump({"cluster": m.as_dict(),
                       "replicas": [e.metrics.as_dict()
                                    for e in pool.engines]}, f, indent=2)
        print(f"metrics: {args.metrics_json}")
    if failed:
        raise SystemExit(
            f"{len(failed)} of {len(handles)} requests failed; first: "
            f"request {failed[0].crid} on replica {failed[0].replica}: "
            f"{failed[0].error!r}")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-1b", choices=configs.list_archs())
    ap.add_argument("--size", default="smoke", choices=["smoke", "full"],
                    help="smoke: the arch's reduced preset (CPU-sized); "
                         "full: its published config")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--slots", type=int, default=0,
                    help="decode batch slots (default: --requests)")
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--chunk", type=int, default=64,
                    help="max prefill chunk (power-of-two buckets)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="KV cache block size in tokens")
    ap.add_argument("--kv-blocks", type=int, default=0,
                    help="KV pool blocks (default: worst-case for --slots)")
    ap.add_argument("--autotune", action="store_true",
                    help="pre-tune this model's GeMM tiles before serving")
    ap.add_argument("--tune-mode", default="analytic",
                    choices=["analytic", "wallclock"])
    ap.add_argument("--precision", default="float",
                    choices=["float", "w8a8", "w8a8-calibrated"],
                    help="execution precision: w8a8 quantizes weights "
                         "int8-resident at warmup and serves through the "
                         "paper's int8 datapath (repro.quant)")
    ap.add_argument("--kv-precision", default="float",
                    choices=["float", "int8"],
                    help="KV pool residency: int8 keeps the paged pool "
                         "int8-resident (per-block scales, in-kernel "
                         "dequant) — ~half the pool bytes per token")
    ap.add_argument("--compare-prefill", action="store_true",
                    help="time legacy token-by-token prefill vs the engine")
    ap.add_argument("--replicas", type=int, default=1,
                    help=">1 serves through repro.cluster: a replica pool "
                         "behind an async router")
    ap.add_argument("--router-policy", default="round-robin",
                    choices=["round-robin", "least-loaded", "prefix-affinity"],
                    help="cluster load-balancing policy (with --replicas)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="reuse prefilled KV blocks across requests sharing "
                         "a prompt prefix (attention-only archs)")
    ap.add_argument("--speculative", action="store_true",
                    help="self-speculative decoding: a prompt-lookup n-gram "
                         "drafter proposes tokens and one batched verify "
                         "step scores them (greedy-token-identical; see "
                         "README §Speculative)")
    ap.add_argument("--draft-k", type=int, default=4,
                    help="max drafted tokens per request per tick "
                         "(with --speculative)")
    ap.add_argument("--max-pending", type=int, default=0,
                    help="cluster backpressure: in-flight request bound "
                         "(0 = unbounded; overflow is shed)")
    ap.add_argument("--priority-classes", default="",
                    help="SLO class mix for generated traffic, e.g. "
                         "'interactive=0.7,batch=0.3' (empty = all "
                         "interactive); classes drive admission order, "
                         "class-aware shedding, and --preempt victims")
    ap.add_argument("--tenants", type=int, default=1,
                    help="spread generated traffic over N synthetic tenant "
                         "ids (per-tenant fairness accounting in the router)")
    ap.add_argument("--preempt", action="store_true",
                    help="let interactive arrivals preempt decoding batch "
                         "requests: the victim's KV blocks swap to host "
                         "memory and restore on re-admission (attention-only "
                         "archs)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy argmax, the "
                         "default; >0 samples on-device with per-request "
                         "PRNG streams)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="keep only the k highest-probability tokens "
                         "(0 = disabled)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling mass (1.0 = disabled)")
    ap.add_argument("--seed", type=int, default=-1,
                    help="sampling PRNG seed shared by all requests "
                         "(-1 = derive per-request from the request id)")
    ap.add_argument("--trace-out", default="",
                    help="write a Chrome-trace/Perfetto JSON of the run "
                         "(per-request lifecycle spans + per-tick phases; "
                         "see README §Observability)")
    ap.add_argument("--metrics-json", default="",
                    help="write the metrics snapshot (scalar gauges, "
                         "percentile histograms, per-phase MFU) as JSON")
    ap.add_argument("--slo", default="",
                    help="SLO spec evaluated after the run, e.g. "
                         "'ttft_p95=0.25,latency_p95=1.0,shed_rate=0.05,"
                         "mfu_floor=1e-6' (multi-window burn rates; see "
                         "README §Observability)")
    ap.add_argument("--incident-dir", default="",
                    help="flight-recorder output directory: sheds, SLO "
                         "breaches, and allocator/spec pressure write "
                         "self-contained JSON incident bundles here")
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = (configs.get(args.arch) if args.size == "full"
           else configs.get_smoke(args.arch))
    if args.replicas > 1:
        return serve_cluster(cfg, args)
    sampling = _sampling_from_args(args)
    class_mix = _parse_class_mix(args.priority_classes)
    slots = args.slots or args.requests
    max_seq = args.prompt_len + args.gen_len + 1
    eng = Engine(
        cfg, slots=slots, max_seq=max_seq,
        block_size=args.block_size,
        num_blocks=args.kv_blocks or None,
        max_chunk=args.chunk,
        autotune=args.autotune, tune_mode=args.tune_mode,
        precision=args.precision,
        kv_precision=args.kv_precision,
        prefix_cache=args.prefix_cache,
        speculative=args.draft_k if args.speculative else False,
        sampling=not sampling.is_greedy, preempt=args.preempt,
        trace=bool(args.trace_out),
        verbose=True,
    )
    t0 = time.time()
    eng.warmup()
    t_warm = time.time() - t0

    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(0, cfg.vocab, size=rng.integers(4, args.prompt_len + 1))
        for _ in range(args.requests)
    ]
    # Class assignment draws from its own stream so labelling never
    # perturbs the prompt draws above (same rule as cluster.traffic).
    crng = np.random.default_rng(0x5EED)
    names = [c for c, _ in (class_mix or ())]
    weights = np.asarray([w for _, w in (class_mix or ())], np.float64)
    if names:
        weights = weights / weights.sum()
    for p in prompts:
        prio = (PRIORITIES[0] if not names
                else names[int(crng.choice(len(names), p=weights))])
        eng.submit(RequestSpec(prompt=p, max_new=args.gen_len,
                               sampling=sampling, priority=prio))
    t0 = time.time()
    results = eng.run()
    t_serve = time.time() - t0

    gen = np.stack([results[rid] for rid in sorted(results)])
    pool_tokens = (eng.num_blocks - 1) * eng.block_size
    dense_tokens = slots * max_seq
    print(f"arch={cfg.name} slots={slots} precision={args.precision} "
          f"warmup {t_warm*1e3:.0f}ms serve {t_serve*1e3:.0f}ms")
    print(f"engine: {eng.metrics.summary()}")
    print(f"kv pool: {eng.num_blocks - 1} blocks x {eng.block_size} tokens "
          f"= {pool_tokens} tokens shared "
          f"(dense would pin {dense_tokens} = slots x max_seq per layer)")
    print("sample continuations:", gen[:2, :8].tolist())

    recorder = _build_recorder(args, metadata={"arch": cfg.name})
    if recorder is not None:
        recorder.attach_engine(eng)
    from repro.obs import engine_snapshot

    _evaluate_slo(args, engine_snapshot(eng), recorder, [eng])

    if args.trace_out:
        from repro.obs import write_chrome_trace

        doc = write_chrome_trace(args.trace_out, [eng.tracer],
                                 metadata={"arch": cfg.name})
        print(f"trace: {len(doc['traceEvents'])} events -> {args.trace_out} "
              f"(open in https://ui.perfetto.dev)")
    if args.metrics_json:
        with open(args.metrics_json, "w") as f:
            json.dump(eng.metrics.as_dict(), f, indent=2)
        print(f"metrics: {args.metrics_json}")

    if args.compare_prefill:
        t_legacy, t_chunked = compare_prefill(
            cfg, eng.params, prompts, slots=slots, max_seq=max_seq,
            block_size=args.block_size, num_blocks=args.kv_blocks or None,
            max_chunk=args.chunk)
        print(f"prefill: token-by-token {t_legacy*1e3:.0f}ms vs chunked "
              f"{t_chunked*1e3:.0f}ms -> {t_legacy / t_chunked:.1f}x speedup")
    return gen


if __name__ == "__main__":
    main()
