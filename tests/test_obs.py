"""Observability tests (repro.obs): ring-buffer tracer semantics, Chrome-
trace export validity from a real traced serving run, histogram percentile
parity with the engine's nearest-rank definition, per-phase MFU accounting,
and the tracing-overhead bound the subsystem is allowed to cost."""

import json
import time

import numpy as np
import pytest

from repro import configs
from repro.obs import (
    Histogram,
    MfuMeter,
    NULL_TRACER,
    NullTracer,
    Tracer,
    chrome_trace_events,
    nearest_rank_index,
    trace_document,
    write_chrome_trace,
)
from repro import obs
from repro.serving.engine import Engine, percentile

ARCH = "gemma3-1b"


# ---------------------------------------------------------------------------
# tracer ring
# ---------------------------------------------------------------------------


def test_tracer_records_and_decodes():
    tr = Tracer(capacity=64, name="t")
    a, g = tr.intern("phase"), tr.intern("gauge")
    assert tr.intern("phase") == a          # idempotent interning
    tr.begin(a)
    tr.counter(g, 7.5)
    tr.end(a)
    tr.async_begin(tr.intern("req"), 42)
    tr.async_end(tr.intern("req"), 42)
    evs = tr.events()
    assert [e["ph"] for e in evs] == ["B", "C", "E", "b", "e"]
    assert evs[1]["value"] == 7.5
    assert evs[3]["id"] == 42
    assert evs[0]["ts_ns"] <= evs[-1]["ts_ns"]
    assert tr.dropped == 0 and tr.recorded == 5 and len(tr) == 5


def test_tracer_ring_wraps_and_counts_dropped():
    tr = Tracer(capacity=8)
    c = tr.intern("x")
    for i in range(20):
        tr.counter(c, float(i))
    assert len(tr) == 8
    assert tr.recorded == 20 and tr.dropped == 12
    # ring holds the most recent events, oldest first
    assert [e["value"] for e in tr.events()] == [float(i) for i in range(12, 20)]
    tr.clear()
    assert len(tr) == 0 and tr.events() == []


def test_null_tracer_is_inert():
    assert not NULL_TRACER.enabled
    NULL_TRACER.begin(NULL_TRACER.intern("x"))
    NULL_TRACER.counter(0, 1.0)
    with NULL_TRACER.span("y"):
        pass
    assert len(NULL_TRACER) == 0 and NULL_TRACER.events() == []


def test_spans_reach_a_recording_profiler(monkeypatch):
    """While a profiler session records, begin/end also open and close an
    annotation (metadata from either end); the next poll closes any an
    exception left open, and nothing is annotated once it stops."""
    from repro.obs import trace as trace_mod

    log = []

    class Fake:
        on = True

        def __init__(self, name, **meta):
            self.name, self.meta = name, dict(meta)

        @staticmethod
        def is_enabled():
            return Fake.on

        def set_metadata(self, **meta):
            self.meta.update(meta)

        def __enter__(self):
            log.append(("enter", self.name))

        def __exit__(self, *exc):
            log.append(("exit", self.name, self.meta))

    monkeypatch.setattr(trace_mod, "TraceAnnotation", Fake)
    for tr in (Tracer(capacity=16), NullTracer()):
        log.clear()
        a, b = tr.intern("a"), tr.intern("b")
        assert tr.poll_profiler()
        tr.begin(a, {"rows": 2})
        tr.begin(b)
        tr.end(b, {"n": 1})
        tr.end(a)
        tr.begin(b)                          # left open, as by an exception
        Fake.on = False
        assert not tr.poll_profiler()
        tr.begin(a)
        tr.end(a)
        Fake.on = True
        assert log == [("enter", "a"), ("enter", "b"),
                       ("exit", "b", {"n": 1}), ("exit", "a", {"rows": 2}),
                       ("enter", "b"), ("exit", "b", {})]


def test_span_contextmanager_balances_on_exception():
    tr = Tracer(capacity=16)
    with pytest.raises(RuntimeError):
        with tr.span("work"):
            raise RuntimeError("boom")
    assert [e["ph"] for e in tr.events()] == ["B", "E"]


# ---------------------------------------------------------------------------
# histograms
# ---------------------------------------------------------------------------


def test_percentile_helper_is_the_shared_definition():
    """One nearest-rank definition across the repo: the engine and the
    serving package both re-export repro.obs.percentile (the PR-9 dedupe),
    and its rank math matches the index helper the histogram uses."""
    from repro import serving
    from repro.serving import engine as engine_mod

    assert engine_mod.percentile is obs.percentile
    assert serving.percentile is obs.percentile      # lazy re-export
    vals = [5.0, 1.0, 3.0, 2.0, 4.0]
    assert obs.percentile(vals, 50) == 3.0           # nearest rank, not interp
    assert obs.percentile(vals, 100) == 5.0
    assert obs.percentile(vals, 0) == 1.0
    assert obs.percentile([], 95) == 0.0
    assert obs.percentile(iter(vals), 95) == 5.0     # any iterable
    assert nearest_rank_index(50, 5) == 2
    assert nearest_rank_index(0, 5) == 0             # clamped low
    assert nearest_rank_index(100, 5) == 4
    assert nearest_rank_index(99, 1) == 0


def test_histogram_count_above():
    h = Histogram()
    assert h.count_above(1.0) == 0
    for v in (0.5, 0.5, 2.0, 3.0, 100.0):
        h.add(v)
    # bucket representatives keep small-vs-large separable at rel_error
    assert h.count_above(1.0) == 3
    assert h.count_above(0.01) == 5
    assert h.count_above(1e9) == 0
    # underflow bucket represents as h.min (never above a real threshold)
    h2 = Histogram()
    h2.add(0.0)
    h2.add(5.0)
    assert h2.count_above(1.0) == 1


def test_histogram_empty_and_single_value():
    h = Histogram()
    assert h.percentile(50) == 0.0 and h.mean == 0.0
    h.add(3.25)
    # single observation: clamped to [min, max] -> exact
    assert h.percentile(50) == pytest.approx(3.25)
    assert h.percentile(99) == pytest.approx(3.25)
    assert h.count == 1 and h.mean == pytest.approx(3.25)


def test_histogram_matches_nearest_rank_within_rel_error():
    rng = np.random.default_rng(0)
    vals = np.concatenate([
        rng.lognormal(-3.0, 1.0, size=400),       # latency-like spread
        rng.uniform(1e-4, 1e-1, size=100),
    ])
    h = Histogram()
    for v in vals:
        h.add(float(v))
    for q in (5, 25, 50, 90, 95, 99, 100):
        exact = percentile(vals, q)
        approx = h.percentile(q)
        assert approx == pytest.approx(exact, rel=h.rel_error), q


def test_histogram_merge_equals_single_feed():
    rng = np.random.default_rng(1)
    a_vals, b_vals = rng.lognormal(0, 1, 200), rng.lognormal(0.5, 0.7, 150)
    one = Histogram()
    for v in np.concatenate([a_vals, b_vals]):
        one.add(float(v))
    a, b = Histogram(), Histogram()
    for v in a_vals:
        a.add(float(v))
    for v in b_vals:
        b.add(float(v))
    a.merge(b)
    assert a.count == one.count and a.total == pytest.approx(one.total)
    assert a.counts == one.counts
    for q in (50, 95, 99):
        assert a.percentile(q) == one.percentile(q)


def test_histogram_merge_rejects_mismatched_bucketing():
    with pytest.raises(ValueError, match="bucketing"):
        Histogram().merge(Histogram(growth=2.0))


def test_histogram_dict_roundtrip():
    h = Histogram()
    for v in (0.0, 1e-12, 0.5, 2.0, 2.0, 1e6):   # incl. underflow bucket
        h.add(v)
    h2 = Histogram.from_dict(json.loads(json.dumps(h.to_dict())))
    assert h2.count == h.count and h2.counts == h.counts
    assert h2.percentile(50) == h.percentile(50)
    assert h2.min == h.min and h2.max == h.max


# ---------------------------------------------------------------------------
# traced serving run: export validity + instrumentation coverage
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def traced_run():
    cfg = configs.get_smoke(ARCH)
    eng = Engine(cfg, slots=2, max_seq=64, block_size=4, max_chunk=8,
                 trace=True, speculative=True)
    eng.warmup()
    rng = np.random.default_rng(0)
    for _ in range(5):
        p = rng.integers(0, cfg.vocab, size=int(rng.integers(4, 12)))
        eng.submit(p, max_new=int(rng.integers(2, 8)))
    eng.run()
    return eng


def test_trace_export_is_valid_chrome_trace(traced_run, tmp_path):
    path = tmp_path / "trace.json"
    write_chrome_trace(str(path), [traced_run.tracer],
                       metadata={"arch": traced_run.cfg.name})
    doc = json.loads(path.read_text())          # valid JSON on disk
    evs = doc["traceEvents"]
    assert doc["metadata"]["arch"] == traced_run.cfg.name
    assert evs, "traced run exported no events"
    # B/E spans nest properly per (pid, tid)
    stacks = {}
    for e in evs:
        key = (e["pid"], e["tid"])
        if e["ph"] == "B":
            stacks.setdefault(key, []).append(e["name"])
        elif e["ph"] == "E":
            assert stacks[key], f"E without B for {e['name']}"
            assert stacks[key].pop() == e["name"]
    assert all(not s for s in stacks.values()), stacks
    # async request spans balance per (name, id) and carry the request cat
    open_spans = {}
    for e in evs:
        if e["ph"] in ("b", "e"):
            assert e["cat"] == "request"
            k = (e["name"], e["id"])
            open_spans[k] = open_spans.get(k, 0) + (1 if e["ph"] == "b" else -1)
            assert open_spans[k] in (0, 1), k
    assert all(v == 0 for v in open_spans.values()), open_spans
    # timestamps are non-negative microseconds from the common origin
    assert min(e["ts"] for e in evs if "ts" in e) >= 0.0


def test_trace_covers_lifecycle_and_phases(traced_run):
    names = {e["name"] for e in chrome_trace_events([traced_run.tracer])}
    # per-tick phase spans
    assert {"engine.tick", "engine.admit", "engine.schedule", "engine.stage",
            "engine.dispatch", "engine.readback", "engine.pick",
            "engine.commit", "engine.warmup"} <= names
    # per-request lifecycle async spans
    assert {"queued", "req_prefill", "req_decode"} <= names
    # counters
    assert {"kv_blocks_in_use", "kv_blocks_reserved", "queue_depth"} <= names


def test_trace_document_counts_dropped():
    tr = Tracer(capacity=4)
    c = tr.intern("x")
    for i in range(10):
        tr.counter(c, i)
    doc = trace_document([tr])
    assert doc["metadata"]["dropped_events"] == 6


def test_untraced_engine_records_nothing(traced_run):
    cfg = configs.get_smoke(ARCH)
    eng = Engine(cfg, slots=2, max_seq=32, block_size=4, max_chunk=8)
    eng.share_steps_from(traced_run)
    eng.warmup()
    eng.submit([1, 2, 3, 4], max_new=3)
    eng.run()
    assert isinstance(eng.tracer, NullTracer) and not eng.tracer.enabled
    assert eng.tracer is not NULL_TRACER        # its own: one owner thread
    assert chrome_trace_events([eng.tracer]) == []


def test_flow_events_connect_each_request(traced_run):
    """Tentpole acceptance: every finished request is reconstructable by
    trace id — one connected flow chain (``s`` -> ``t``... -> ``f``) named
    "req" with ``cat="flow"``, ids namespaced ``(pid << 24) + rid``."""
    evs = chrome_trace_events([traced_run.tracer])
    flows = [e for e in evs if e.get("cat") == "flow"]
    assert flows, "flow-traced run exported no flow events"
    assert all(e["name"] == "req" for e in flows)
    want = {(traced_run.tracer.pid << 24) + r.rid
            for r in traced_run.metrics.requests}
    by_id = {}
    for e in flows:
        by_id.setdefault(e["id"], []).append(e)
    assert set(by_id) == want
    for fid, chain in by_id.items():
        phs = [e["ph"] for e in chain]
        assert phs[0] == "s" and phs[-1] == "f", (fid, phs)
        assert set(phs[1:-1]) <= {"t"}, (fid, phs)
        assert chain[-1]["bp"] == "e"           # bind f to preceding slice
        ts = [e["ts"] for e in chain]
        assert ts == sorted(ts)


def test_flow_events_bind_to_open_slices(traced_run):
    """Perfetto draws a flow arrow only when the s/t/f event lands inside a
    duration slice open on that thread at that ts; replay the stream and
    require nonzero B/E depth at every flow event."""
    depth = {}
    for e in chrome_trace_events([traced_run.tracer]):
        key = (e.get("pid"), e.get("tid"))
        if e["ph"] == "B":
            depth[key] = depth.get(key, 0) + 1
        elif e["ph"] == "E":
            depth[key] = depth.get(key, 0) - 1
        elif e["ph"] in ("s", "t", "f"):
            assert depth.get(key, 0) > 0, e


def test_shed_and_prefix_hit_instants():
    """Shed decisions and prefix-cache hits surface as annotated instant
    events ("i", thread-scoped) in the trace."""
    cfg = configs.get_smoke(ARCH)
    eng = Engine(cfg, slots=2, max_seq=32, block_size=4, max_chunk=8,
                 trace=True, prefix_cache=True, max_queue=1)
    eng.warmup()
    rng = np.random.default_rng(0)
    prefix = rng.integers(0, cfg.vocab, size=8).astype(np.int32)
    p1 = np.concatenate([prefix, rng.integers(0, cfg.vocab, size=3)]).astype(np.int32)
    p2 = np.concatenate([prefix, rng.integers(0, cfg.vocab, size=4)]).astype(np.int32)
    assert eng.submit(p1, max_new=3) is not None
    # queue cap 1: a second pre-tick submit must shed (-> "shed" instant)
    assert eng.submit(p2, max_new=3) is None
    eng.run()
    # p1's full blocks are cached at finish; resubmitting p2 hits the prefix
    assert eng.submit(p2, max_new=3) is not None
    eng.run()
    inst = [e for e in chrome_trace_events([eng.tracer]) if e["ph"] == "i"]
    names = {e["name"] for e in inst}
    assert {"shed", "prefix_hit"} <= names
    assert all(e["s"] == "t" for e in inst)
    hit = [e for e in inst if e["name"] == "prefix_hit"]
    assert hit[0]["args"]["value"] >= 4          # tokens served from cache


def test_cache_evict_instant_under_pool_pressure():
    cfg = configs.get_smoke(ARCH)
    eng = Engine(cfg, slots=1, max_seq=16, block_size=4, num_blocks=5,
                 max_chunk=4, prefix_cache=True, trace=True)
    eng.warmup()
    rng = np.random.default_rng(1)
    for _ in range(3):
        eng.submit(rng.integers(0, cfg.vocab, size=9).astype(np.int32),
                   max_new=3)
        eng.run()
    evs = chrome_trace_events([eng.tracer])
    evict = [e for e in evs if e["ph"] == "i" and e["name"] == "cache_evict"]
    assert evict, "pool pressure produced no cache_evict instant"
    assert evict[0]["args"]["value"] > 0         # blocks short at admission


def test_tracing_overhead_under_two_percent(traced_run):
    """The acceptance bar: per-tick tracing cost < 2% of a decode tick.

    Asserted analytically — measured per-event ring cost x the most events
    any tick of the traced smoke run recorded, plus the tick's one look at
    the profiler, against the engine's own measured mean tick — so the test
    is robust to host-load noise that an A/B wall-clock diff would flake
    on."""
    events_per_tick = max(events_per_tick_of(traced_run.tracer))
    tr = Tracer(capacity=1 << 14)
    code = tr.intern("bench")
    n = 5000
    best_ns = poll_ns = float("inf")
    for _ in range(3):                     # best-of-3: dodge load spikes
        t0 = time.perf_counter_ns()
        for _ in range(n):
            tr.begin(code)
            tr.end(code)
        t1 = time.perf_counter_ns()
        for _ in range(n):
            tr.poll_profiler()
        t2 = time.perf_counter_ns()
        best_ns = min(best_ns, (t1 - t0) / (2 * n))
        poll_ns = min(poll_ns, (t2 - t1) / n)
    m = traced_run.metrics
    tick_s = m.decode_time_s / max(1, m.decode_steps)
    overhead = (events_per_tick * best_ns + poll_ns) * 1e-9 / tick_s
    assert overhead < 0.02, (
        f"tracing costs {overhead:.2%} of a {tick_s * 1e6:.0f}us decode tick "
        f"({events_per_tick} events of {best_ns:.0f}ns)")


def events_per_tick_of(tracer):
    """Ring events recorded within each engine.tick span, its own B/E
    included (flow steps, counters, nested spans)."""
    out, n = [], None
    for e in tracer.events():
        if e["name"] == "engine.tick" and e["ph"] == "B":
            n = 0
        if n is not None:
            n += 1
        if e["name"] == "engine.tick" and e["ph"] == "E":
            out.append(n)
            n = None
    assert out, "the traced run recorded no tick"
    return out


# ---------------------------------------------------------------------------
# engine spans on the profiler's clock
# ---------------------------------------------------------------------------

TICK_ORDER = ["engine.admit", "engine.schedule", "engine.stage",
              "engine.dispatch", "engine.readback", "engine.pick",
              "engine.commit"]


def _kv_blocks(eng):
    """Pool blocks the flash-decode kernel fetches in a decode step: per
    slot, whole steps of ``blocks_per_step`` table columns while a step
    starts at or before the row's last key and its first entry is not the
    null block, each step clipped at the row's table."""
    from repro.kernels.flash_decode import blocks_per_step
    from repro.serving.kv_cache import NULL_BLOCK

    P = blocks_per_step(eng.block_size, eng.max_blocks_per_slot)
    total = 0
    for slot, row in enumerate(eng.tables.table):
        r = eng.scheduler.slots[slot]
        keys = 0 if r is None else (
            r.length if r.out_tokens else r.prefilled + 1)
        col = 0
        while (col < len(row) and col * eng.block_size < keys
               and row[col] != NULL_BLOCK):
            total += min(P, len(row) - col)
            col += P
    return total


def _recording_actions(eng):
    """Wrap the scheduler's next_action to log what each tick ran, as the
    dispatch span's metadata should carry it ({} for a tick that ran no
    step)."""
    ran, inner = [], eng.scheduler.next_action

    def next_action():
        a = inner()
        if a is None:
            ran.append({})
        elif a[0] == "prefill":
            ran.append({"chunk": a[2], "start": a[1].prefilled})
        else:
            ran.append({"rows": len(a[1]),
                        "ctx_tokens": sum(r.length for r in a[1])})
        return a

    eng.scheduler.next_action = next_action
    return ran


def _host_spans(trace_dir):
    """engine.* events of the profile under trace_dir: (name, start, end,
    metadata), by start."""
    import glob
    import os

    from jax.profiler import ProfileData

    [path] = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                       recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("engine."):
                    out.append((e.name, e.start_ns, e.end_ns,
                                dict(iter(e.stats))))
    return sorted(out, key=lambda x: (x[1], -x[2]))


@pytest.fixture(scope="module")
def profiled_run(traced_run, tmp_path_factory):
    """An untraced engine (ring off) served under the JAX profiler."""
    import jax

    cfg = configs.get_smoke(ARCH)
    eng = Engine(cfg, slots=2, max_seq=64, block_size=4, max_chunk=8)
    eng.share_steps_from(traced_run)
    eng.warmup()
    ran = _recording_actions(eng)
    rng = np.random.default_rng(3)
    for n in (11, 5, 7):
        eng.submit(rng.integers(0, cfg.vocab, size=n), max_new=4)
    trace_dir = str(tmp_path_factory.mktemp("profile"))
    jax.profiler.start_trace(trace_dir)
    try:
        eng.run()
    finally:
        jax.profiler.stop_trace()
    return eng, ran, _host_spans(trace_dir)


def test_engine_spans_reach_the_profiler(profiled_run):
    """With the ring off, each tick still writes engine.tick and its phases
    into the profiler's trace, nested in the tick, in the order the tick
    runs them; the dispatch span carries what the scheduler ran."""
    eng, ran, spans = profiled_run
    assert len(eng.tracer) == 0                  # the ring stayed off
    ticks = [s for s in spans if s[0] == "engine.tick"]
    assert len(ticks) == len(ran) > 0
    dispatched = []
    for _, a, b, _ in ticks:
        inner = [s for s in spans if s[0] != "engine.tick"
                 and a <= s[1] and s[2] <= b]
        # the tick's own phases: not nested in another phase
        top = [s for s in inner if not any(
            o is not s and o[1] <= s[1] and s[2] <= o[2] for o in inner)]
        names = [s[0] for s in top]
        assert names == [n for n in TICK_ORDER if n in names], names
        assert names[:4] == TICK_ORDER[:4]
        assert {"engine.readback", "engine.commit"} <= set(names)
        assert all(x[2] <= y[1] for x, y in zip(top, top[1:]))
        dispatched += [s[3] for s in top if s[0] == "engine.dispatch"]
    assert dispatched == [a for a in ran if a]
    assert any("chunk" in d for d in ran) and any("rows" in d for d in ran)
    admits = [s[3] for s in spans if s[0] == "engine.admit"]
    assert sum(d["admitted"] for d in admits) == 3
    assert admits[0]["queued"] == 1              # 2 slots, 3 requests


def test_decode_stage_counts_the_blocks_the_kernel_walks(tmp_path):
    """kv_blocks, on the engine.stage span of each decode tick, is what the
    flash-decode kernel fetches in that step: 8 blocks of 4 tokens per
    32-key step here, so rows crossing 32 and 64 keys add a step, and an
    idle slot with a null table adds none."""
    import jax

    from repro.kernels.flash_decode import blocks_per_step

    cfg = configs.get_smoke(ARCH)
    eng = Engine(cfg, slots=3, max_seq=96, block_size=4, max_chunk=16)
    assert blocks_per_step(eng.block_size, eng.max_blocks_per_slot) == 8
    eng.warmup()
    want, run = [], eng._run_compiled

    def run_compiled(key, fn, *args):
        if key == "decode":
            want.append(_kv_blocks(eng))
        return run(key, fn, *args)

    eng._run_compiled = run_compiled
    rng = np.random.default_rng(5)
    for n, new in ((29, 40), (6, 4)):
        eng.submit(rng.integers(0, cfg.vocab, size=n), max_new=new)
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.run()
    finally:
        jax.profiler.stop_trace()
    got = [s[3]["kv_blocks"] for s in _host_spans(str(tmp_path))
           if s[0] == "engine.stage" and "kv_blocks" in s[3]]
    assert got == want
    assert {8, 16, 24} <= set(got) and max(got) == 24


def test_no_annotation_without_a_profile(traced_run, monkeypatch):
    """With the profiler off, an untraced engine creates no annotation:
    its spans cost one attribute test each."""
    from repro.obs import trace as trace_mod

    made = []
    real = trace_mod.TraceAnnotation

    class Counting(real):
        def __init__(self, *a, **kw):
            made.append(a)
            super().__init__(*a, **kw)

    monkeypatch.setattr(trace_mod, "TraceAnnotation", Counting)
    cfg = configs.get_smoke(ARCH)
    eng = Engine(cfg, slots=2, max_seq=32, block_size=4, max_chunk=8)
    eng.share_steps_from(traced_run)
    eng.warmup()
    eng.submit([1, 2, 3, 4, 5], max_new=3)
    eng.run()
    assert eng.metrics.decode_steps > 0
    assert made == []


# ---------------------------------------------------------------------------
# engine metrics: histogram percentiles, request-log capping
# ---------------------------------------------------------------------------


def test_engine_metrics_percentiles_follow_raw_log_until_dropped():
    from repro.serving.engine import EngineMetrics, RequestMetrics

    m = EngineMetrics()
    for i, t in enumerate([0.010, 0.020, 0.200]):
        m.note_request(RequestMetrics(
            rid=i, prompt_len=4, new_tokens=5, ttft_s=t,
            latency_s=t + 0.1, queue_steps=0))
    # complete log: exact nearest-rank over the raw list
    assert m.ttft_percentile(50) == pytest.approx(0.020)
    assert m.finished_requests == 3 and m.requests_dropped == 0
    # cap the log: the histogram becomes the percentile source of truth
    m2 = EngineMetrics()
    for i, t in enumerate([0.010, 0.020, 0.200]):
        m2.note_request(RequestMetrics(
            rid=i, prompt_len=4, new_tokens=5, ttft_s=t,
            latency_s=t + 0.1, queue_steps=0), 2)
    assert len(m2.requests) == 2 and m2.requests_dropped == 1
    assert m2.finished_requests == 3
    assert m2.ttft_percentile(50) == pytest.approx(
        0.020, rel=m2.ttft_hist.rel_error)
    assert "requests=3" in m2.summary()


def test_engine_as_dict_is_json_serializable(traced_run):
    d = traced_run.metrics.as_dict()
    json.dumps(d)
    assert d["requests"] == traced_run.metrics.finished_requests
    assert d["ttft_hist"]["count"] == d["requests"]
    assert d["mfu"]["phases"]["decode"]["steps"] > 0


# ---------------------------------------------------------------------------
# MFU / utilization gauges
# ---------------------------------------------------------------------------


V5E = "TPU v5 lite"


def test_mfu_meter_accounting_and_merge():
    cfg = configs.get_smoke(ARCH)
    a = MfuMeter(cfg, device_kind=V5E)
    assert a.utilization("decode") == 0.0 and a.mfu("decode") == 0.0
    a.note("decode", tokens=2, rows=4, time_s=1e-3)
    a.note("decode", tokens=2, rows=4, time_s=1e-3)
    a.note("prefill", tokens=8, rows=8, time_s=2e-3)
    assert list(a.active_phases()) == ["prefill", "decode"]
    st = a.phases["decode"]
    assert st.steps == 2 and st.tokens == 4 and st.rows == 8
    assert st.flops == pytest.approx(4 * a.flops_per_token)
    assert 0.0 < a.utilization("decode") <= 1.0 or a.utilization("decode") > 0
    assert a.mfu("decode") == pytest.approx(
        st.flops / (st.time_s * a.peak_flops))
    # bound is memoized and monotone in rows
    assert a.step_bound_s(4) == a.step_bound_s(4)
    assert a.step_bound_s(64) >= a.step_bound_s(4)
    b = MfuMeter(cfg, device_kind=V5E)
    b.note("decode", tokens=1, rows=4, time_s=5e-4)
    merged = MfuMeter.merged([a, b])
    assert merged.phases["decode"].steps == 3
    assert merged.phases["decode"].tokens == 5
    assert merged.phases["prefill"].steps == 1
    assert MfuMeter.merged([]) is None
    frag = a.summary()
    assert "util[decode]=" in frag and "mfu[prefill]=" in frag
    json.dumps(a.as_dict())


def test_mfu_meter_unknown_device_not_measured():
    """A device without published peaks reports MFU and utilization as not
    measured (None), never against another chip's peaks."""
    m = MfuMeter(configs.get_smoke(ARCH), device_kind="cpu")
    assert not m.measurable and m.peak_flops is None
    m.note("decode", tokens=2, rows=4, time_s=1e-3)
    assert m.utilization("decode") is None and m.mfu("decode") is None
    assert m.phases["decode"].tokens == 2 and m.phases["decode"].bound_s == 0
    assert "mfu[decode]=n/m" in m.summary()
    d = json.loads(json.dumps(m.as_dict()))
    assert d["phases"]["decode"]["mfu"] is None and d["device_kind"] == "cpu"
    merged = MfuMeter.merged([m])
    assert merged.device_kind == "cpu" and merged.mfu("decode") is None


def test_engine_mfu_phases_populated(traced_run):
    """The engine's meter follows the device it runs on: phases are
    accounted, and on a device without published peaks (this CPU host) the
    gauges read not measured."""
    import jax

    mfu = traced_run.mfu
    assert mfu.device_kind == jax.devices()[0].device_kind
    active = set(mfu.active_phases())
    assert {"prefill", "decode"} <= active
    for p in active:
        st = mfu.phases[p]
        assert st.time_s > 0 and st.steps > 0 and st.flops > 0
        if mfu.measurable:
            assert st.bound_s > 0 and 0 < mfu.mfu(p) < 1
        else:
            assert mfu.utilization(p) is None and mfu.mfu(p) is None
    assert "util[decode]=" in traced_run.metrics.summary()


# ---------------------------------------------------------------------------
# satellite counters: allocator, scheduler, drafter
# ---------------------------------------------------------------------------


def test_allocator_traffic_counters(traced_run):
    alloc = traced_run.alloc
    s = alloc.stats()
    assert s["total_allocated"] == s["total_freed"]   # drained engine
    assert s["in_use"] == 0 and s["reserved"] == 0
    assert 0 < s["peak_in_use"] <= alloc.num_blocks - 1
    assert alloc.reserved == 0


def test_scheduler_and_drafter_counters(traced_run):
    sched = traced_run.scheduler
    assert sched.admitted_total == 5
    assert sched.peak_queue_depth >= 1
    d = traced_run.drafter
    assert d.draft_calls > 0
    assert 0 <= d.draft_hits <= d.draft_calls
    assert 0.0 <= d.hit_rate <= 1.0
    if d.draft_hits:
        assert d.drafted_tokens >= d.draft_hits


# ---------------------------------------------------------------------------
# cluster: per-replica tracers in one export
# ---------------------------------------------------------------------------


def test_replica_pool_trace_multi_pid(tmp_path):
    from repro import cluster

    cfg = configs.get_smoke(ARCH)
    pool = cluster.ReplicaPool(cfg, 2, slots=2, max_seq=32, block_size=4,
                               max_chunk=8, trace=True)
    pool.warmup()
    rng = np.random.default_rng(0)
    for i in range(4):
        h = cluster.ClusterRequest(i, rng.integers(0, cfg.vocab, size=6), 3)
        pool.submit_to(i % 2, h)
    pool.run_sync(max_ticks=500)
    path = tmp_path / "cluster_trace.json"
    doc = pool.export_trace(str(path), metadata={"replicas": 2})
    evs = json.loads(path.read_text())["traceEvents"]
    pids = {e["pid"] for e in evs}
    assert pids == {0, 1}                  # one process lane per replica
    for pid in pids:                       # both replicas actually traced
        assert any(e["ph"] == "B" and e["name"] == "engine.tick"
                   and e["pid"] == pid
                   for e in evs)
    names = {e["args"]["name"] for e in evs if e["ph"] == "M"
             and e["name"] == "process_name"}
    assert names == {f"replica0[{cfg.name}]", f"replica1[{cfg.name}]"}
    assert doc["metadata"]["replicas"] == 2


def test_replica_pool_without_trace_refuses_export(tmp_path):
    from repro import cluster

    cfg = configs.get_smoke(ARCH)
    pool = cluster.ReplicaPool(cfg, 1, slots=2, max_seq=32, block_size=4)
    with pytest.raises(RuntimeError, match="trace=True"):
        pool.export_trace(str(tmp_path / "x.json"))
