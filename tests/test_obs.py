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
    assert {"tick", "sched", "prefill", "decode", "warmup"} <= names
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
    assert eng.tracer is NULL_TRACER
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


def test_flow_events_gated_by_trace_flow(traced_run):
    cfg = configs.get_smoke(ARCH)
    eng = Engine(cfg, slots=2, max_seq=32, block_size=4, max_chunk=8,
                 trace=True, trace_flow=False)
    eng.share_steps_from(traced_run)
    eng.warmup()
    eng.submit([1, 2, 3, 4], max_new=3)
    eng.run()
    evs = chrome_trace_events([eng.tracer])
    assert evs                                   # still span-traced
    assert not [e for e in evs if e["ph"] in ("s", "t", "f", "i")]


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

    Asserted analytically — measured per-event ring cost x the events a
    decode tick records, against the engine's own measured mean tick — so
    the test is robust to host-load noise that an A/B wall-clock diff
    (benchmarks/obs_bench.py keeps that measurement) would flake on."""
    tr = Tracer(capacity=1 << 14)
    code = tr.intern("bench")
    n = 5000
    best_ns = float("inf")
    for _ in range(3):                     # best-of-3: dodge load spikes
        t0 = time.perf_counter_ns()
        for _ in range(n):
            tr.begin(code)
            tr.end(code)
        best_ns = min(best_ns, (time.perf_counter_ns() - t0) / (2 * n))
    m = traced_run.metrics
    tick_s = m.decode_time_s / max(1, m.decode_steps)
    # a plain decode tick records: tick B/E + sched B/E + decode B/E
    # + 2 KV counters = 8 events; per-request flow steps add one per
    # active slot and spec ticks add draft/verify spans
    events_per_tick = 14
    overhead = events_per_tick * best_ns * 1e-9 / tick_s
    assert overhead < 0.02, (
        f"tracing costs {overhead:.2%} of a {tick_s * 1e6:.0f}us decode tick "
        f"({best_ns:.0f}ns/event)")


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
        assert any(e["ph"] == "B" and e["name"] == "tick" and e["pid"] == pid
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
