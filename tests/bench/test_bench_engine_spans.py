"""The engine's spans and the step programs' layer-kind scopes, as the
per-layer readers take them from a profile: on hand-made events, and on a
smoke engine driven through the open loop under the profiler on the CPU,
where the dispatch spans' counts must equal the open loop's own tick log."""

import dataclasses
import json
import os
import types

import numpy as np
import pytest

import conftest
from benchlib import cells, drive, engine_spans as E, trace
from benchlib.traffic import Arrival

MS = 1e6      # ns


def test_kind_of_reads_the_innermost_scope():
    assert E.kind_of("jit(paged_serve_step)/while/body/closed_call/attn/"
                     "pallas_call:") == "attn"
    assert E.kind_of("jit(prefill_chunk_step)/unembed/dot_general") == \
        "unembed"
    assert E.kind_of("jit(paged_serve_step)/while/body/squeeze:") == ""
    assert E.kind_of("jit(step)/while/body/moe/ffn/mul") == "ffn"
    assert E.kind_of("") == ""


def test_leaf_time_counts_each_instant_once():
    ops = [("while", 0, 100, ""),
           ("a", 10, 30, "attn"), ("f", 30, 60, "ffn"),
           ("slice", 59, 70, ""),     # overlaps by clock noise: it wins
           ("u", 95, 110, "unembed")]               # clipped at 100
    t = E.leaf_time(ops, 0, 100)
    assert t == {"": 10 + 11 + 25, "attn": 20, "ffn": 29, "unembed": 5}
    assert sum(t.values()) == 100


def traced(with_engine=True, with_kinds=True):
    k = (lambda x: x) if with_kinds else (lambda x: "")
    host = [("bench.trace_window", 0, 100 * MS),
            ("bench.tick", 10 * MS, 50 * MS),
            ("bench.poll", 50 * MS, 52 * MS),
            ("bench.tick", 60 * MS, 95 * MS)]
    engine = [
        ("engine.tick", 11 * MS, 49 * MS, {}),
        ("engine.admit", 11 * MS, 12 * MS, {"admitted": 0, "queued": 3}),
        ("engine.schedule", 12 * MS, 13 * MS, {}),
        ("engine.stage", 13 * MS, 14 * MS, {}),
        ("engine.dispatch", 14 * MS, 16 * MS, {"rows": 2, "ctx_tokens": 70}),
        ("engine.readback", 16 * MS, 42 * MS, {}),
        ("engine.pick", 42 * MS, 45 * MS, {}),
        ("engine.commit", 45 * MS, 48 * MS, {}),
        ("engine.tick", 61 * MS, 94 * MS, {}),
        ("engine.admit", 61 * MS, 64 * MS, {"admitted": 1, "queued": 2}),
        ("engine.reset", 62 * MS, 63 * MS, {}),
        ("engine.schedule", 64 * MS, 65 * MS, {}),
        ("engine.stage", 65 * MS, 66 * MS, {}),
        ("engine.dispatch", 66 * MS, 67 * MS, {"chunk": 64, "start": 0}),
        ("engine.readback", 67 * MS, 92 * MS, {}),
        ("engine.commit", 92 * MS, 93 * MS, {}),
    ] if with_engine else []
    programs = [("jit_paged_serve_step(1)", 15 * MS, 40 * MS),
                ("jit_prefill_chunk_step(2)", 67 * MS, 90 * MS)]
    ops = [("while.2", 15 * MS, 38 * MS, ""),
           ("fusion.1", 16 * MS, 26 * MS, k("attn")),
           ("fusion.2", 26 * MS, 34 * MS, k("ffn")),
           ("custom-call.9", 38 * MS, 40 * MS, k("unembed")),
           ("fusion.7", 67 * MS, 90 * MS, k("attn"))]
    return E.Traced(host=host, engine=engine, ops=ops, programs=programs)


def test_host_time_before_and_after_the_step():
    r = E.reduce(traced())
    assert [t.kind for t in r.ticks] == ["decode", "prefill"]
    assert r.ticks[0].meta == {"rows": 2, "ctx_tokens": 70}
    # decode: readback 16-42 less busy 15-40, then pick and commit
    assert E.token_host_ms(r) == pytest.approx(2 + 3 + 3)
    assert r.ticks[0].after_by_span == pytest.approx(
        {"engine.readback": 2e-3, "engine.pick": 3e-3,
         "engine.commit": 3e-3})
    # admit..dispatch: 11-15 idle in the decode tick, 61-67 in the chunk's
    assert E.host_prep_ms(r) == pytest.approx((4 + 6) / 2)


def test_decode_step_by_layer_kind():
    r = E.reduce(traced())
    assert r.decode_steps == 1
    assert r.decode_program_s == pytest.approx(25e-3)
    assert E.decode_kind_ms(r, "attn") == pytest.approx(10)
    assert E.decode_kind_ms(r, "ffn") == pytest.approx(8)
    assert E.decode_kind_ms(r, "unembed") == pytest.approx(2)
    assert E.decode_kind_ms(r, "") == pytest.approx(1 + 4)   # the loop's own
    assert sum(r.decode_by_kind.values()) == pytest.approx(
        r.decode_program_s)


def test_idle_labelled_by_the_innermost_engine_span():
    r = E.reduce(traced())
    idle = dict(r.idle_by_host)
    assert idle["engine.readback"] == pytest.approx(2e-3 + 2e-3)
    assert idle["engine.pick"] == pytest.approx(3e-3)
    assert idle["engine.reset"] == pytest.approx(1e-3)
    assert idle["engine.admit"] == pytest.approx(1e-3 + 2e-3)
    assert idle["engine.tick"] == pytest.approx(1e-3 + 1e-3)  # the glue
    assert idle["bench.tick before its first device op"] == \
        pytest.approx(1e-3 + 1e-3)
    assert "bench.tick after its last device op" in idle      # 49-50, 94-95
    assert sum(idle.values()) == pytest.approx(
        0.1 - (25e-3 + 23e-3))


def test_a_program_without_spans_or_scopes_reads_none():
    r = E.reduce(traced(with_engine=False, with_kinds=False))
    assert r.ticks == []
    assert E.token_host_ms(r) is None and E.host_prep_ms(r) is None
    assert E.decode_kind_ms(r, "attn") is None
    assert E.decode_kind_ms(r, "") is None


def test_recording_round_trips_as_json():
    tr = traced()
    assert E.Traced.from_json(tr.to_json()) == tr


# ---------------------------------------------------------------------------
# seven ticks recorded on the chip (testdata/nemo-chat-engine-spans.json)
# ---------------------------------------------------------------------------

DATA = os.path.join(conftest.CHIP, "testdata", "nemo-chat-engine-spans.json")


@pytest.fixture(scope="module")
def recorded():
    with open(DATA) as f:
        tr = E.Traced.from_json(json.load(f))
    return tr, E.reduce(tr)


def test_recorded_fixture_is_small():
    assert os.path.getsize(DATA) < 1 << 20


def test_recorded_ticks_and_their_counts(recorded):
    _, r = recorded
    assert [t.kind for t in r.ticks] == ["decode", "prefill"] + ["decode"] * 5
    assert r.ticks[1].meta == {"chunk": 256, "start": 768}
    assert r.ticks[2].meta == {"rows": 8, "ctx_tokens": 15310}


def test_recorded_metric_values(recorded):
    _, r = recorded
    assert E.token_host_ms(r) == pytest.approx(7.177933)   # median of 6
    assert E.host_prep_ms(r) == pytest.approx(0.887576857)
    assert r.decode_steps == 6
    assert E.decode_kind_ms(r, "attn") == pytest.approx(53.3236265)
    assert E.decode_kind_ms(r, "ffn") == pytest.approx(10.569513333)
    assert E.decode_kind_ms(r, "") == pytest.approx(37.736402333)
    assert E.decode_kind_ms(r, "unembed") == pytest.approx(4.025260833)
    # every instant of the decode programs belongs to one kind, or none
    assert sum(r.decode_by_kind.values()) == pytest.approx(
        r.decode_program_s, rel=0.02)


def test_recorded_idle_labels(recorded):
    _, r = recorded
    idle = dict(r.idle_by_host)
    assert idle["engine.readback"] == pytest.approx(0.023930421)
    assert idle["engine.pick"] == pytest.approx(0.021505588)
    assert idle["engine.dispatch"] == pytest.approx(0.003201718)
    # what the harness labels after a tick's last op is nearly all named
    assert idle["bench.tick after its last device op"] < 0.001 * sum(
        idle.values())


def test_recorded_host_time_accounts_for_host_ms_per_tick(recorded):
    """Before-step spans over every tick and after-step spans over decode
    ticks cover the host time per bench.tick, less the glue between spans
    and the chunk tick's own after-step time."""
    tr, r = recorded
    _, a, b = tr.host[0]
    ticks = [h for h in tr.host if h[0] == "bench.tick"]
    ev = trace.Events(ops=[], programs=tr.programs, host=tr.host)
    dt = trace.device_skew(ev, ticks)
    busy = trace.union(trace.clip([(o[1] + dt, o[2] + dt) for o in tr.ops],
                                  a, b))
    host = sum((y - x) - trace.overlap(busy, x, y) for _, x, y in ticks)
    after = sum(t.after_s for t in r.ticks if t.kind == "decode")
    spans = 1e3 * after + E.host_prep_ms(r) * len(r.ticks)
    assert len(ticks) == len(r.ticks) == 7
    assert 0.9 * host * 1e-6 < spans < host * 1e-6


def test_recorded_token_host_ms_ignores_one_stalled_tick(recorded):
    """Starting the profiler can stall one tick's readback for seconds
    (2.5 s in a traced chat run on the chip): the median over decode ticks
    moves by at most one neighbour, where a mean would grow 60 times."""
    _, r = recorded
    dec = [i for i, t in enumerate(r.ticks) if t.kind == "decode"]
    before = E.token_host_ms(r)
    stalled = list(r.ticks)
    stalled[dec[0]] = dataclasses.replace(r.ticks[dec[0]], after_s=2.5)
    got = E.token_host_ms(dataclasses.replace(r, ticks=stalled))
    after = sorted(1e3 * r.ticks[i].after_s for i in dec)
    assert before == pytest.approx(7.177933)
    assert after[2] <= got <= after[4] < 8


# ---------------------------------------------------------------------------
# a smoke engine under the profiler (CPU: host spans only)
# ---------------------------------------------------------------------------


def _drive_profiled(engine, tmp_path, monkeypatch, cell):
    import jax

    monkeypatch.setattr(cells, "ROOT", str(tmp_path))
    trace_dir = str(tmp_path / ".bench_trace" / cell)
    rng = np.random.default_rng(5)
    # Requests due through the whole window, so the traced middle holds
    # prefill chunks and decode ticks.
    arrivals = [Arrival(t=0.08 * i, prompt=rng.integers(0, 250, size=n)
                        .astype(np.int32), max_new=m)
                for i, (n, m) in enumerate([(40, 30), (9, 40), (70, 20),
                                            (20, 60), (33, 45)] * 3)]
    from benchlib import system

    spec = (system.request_spec if not isinstance(engine, FakeEngine)
            else (lambda p, n: (p, n)))
    win = drive.Driver(engine, arrivals, spec).run(
        warm_in_s=0.0, seconds=1.0, trace_s=0.6,
        profiler=(lambda: jax.profiler.start_trace(trace_dir),
                  jax.profiler.stop_trace))
    return win, trace_dir


@pytest.fixture(scope="module")
def smoke_engine():
    from repro import configs
    from repro.serving.engine import Engine

    eng = Engine(configs.get_smoke("mistral-nemo-12b"), slots=4, max_seq=256,
                 block_size=16, max_chunk=32)
    eng.warmup()
    return eng


def test_dispatch_counts_equal_the_open_loops_tick_log(smoke_engine,
                                                       tmp_path,
                                                       monkeypatch):
    win, trace_dir = _drive_profiled(smoke_engine, tmp_path, monkeypatch,
                                     "smoke-spans")
    tr = E.load_dir(trace_dir)
    assert tr.ops == []                          # the CPU has no TPU plane
    r = E.reduce(tr)
    logged = win.ticks[win.trace_tick0:win.trace_tick1]
    assert len(r.ticks) == len(logged) > 5
    for got, want in zip(r.ticks, logged):
        assert got.kind == want.kind
        if want.kind == "decode":
            assert got.meta == {"rows": len(want.contexts),
                                "ctx_tokens": sum(want.contexts)}
        elif want.kind == "prefill":
            assert got.meta == {"chunk": want.chunk, "start": want.start}
    assert {t.kind for t in r.ticks} >= {"decode", "prefill"}
    ctx = types.SimpleNamespace(trace=object(), cell={"name": "smoke-spans"})
    got = E.reading(ctx)
    assert E.token_host_ms(got) > 0 and E.host_prep_ms(got) > 0
    assert E.decode_kind_ms(got, "attn") is None      # no device operation


class FakeEngine:
    """The open loop's engine without spans: one token per tick."""

    def __init__(self):
        self.scheduler = types.SimpleNamespace(slots=[None, None],
                                               has_work=False)
        self.queue, self.rid = [], 0

    def submit(self, spec):
        self.rid += 1
        q = types.SimpleNamespace(rid=self.rid, prefilled=0, out_tokens=[],
                                  phase=types.SimpleNamespace(name="DECODE"),
                                  prompt_len=len(spec[0]), max_new=spec[1])
        q.prefilled = q.prompt_len
        self.queue.append(q)
        self.scheduler.has_work = True
        return q

    def tick(self):
        drive.time.sleep(0.002)
        slots = self.scheduler.slots
        for i, q in enumerate(slots):
            if q is None and self.queue:
                slots[i] = self.queue.pop(0)
        for i, q in enumerate(slots):
            if q is not None:
                q.out_tokens.append(1)
                if len(q.out_tokens) == q.max_new:
                    q.phase.name = "FINISHED"
                    slots[i] = None
        self.scheduler.has_work = bool(self.queue) or any(slots)


def test_a_run_without_engine_spans_reads_none(tmp_path, monkeypatch):
    _drive_profiled(FakeEngine(), tmp_path, monkeypatch, "no-spans")
    ctx = types.SimpleNamespace(trace=object(), cell={"name": "no-spans"})
    assert E.reading(ctx) is None
    untraced = types.SimpleNamespace(trace=None, cell={"name": "no-spans"})
    assert E.reading(untraced) is None
