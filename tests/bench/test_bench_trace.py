"""The trace reduction against six ticks recorded on one TPU v5e
(testdata/nemo-chat-six-ticks.json): busy union, idle share, per-kernel
sums per step, the device clock's skew and the idle gaps' labels."""

import json
import os

import pytest

import conftest
from benchlib import drive, readings, trace, work

DATA = os.path.join(conftest.CHIP, "testdata", "nemo-chat-six-ticks.json")


@pytest.fixture(scope="module")
def recorded():
    with open(DATA) as f:
        d = json.load(f)
    ev = trace.Events.from_json(d["events"])
    ticks = [drive.Tick(0.0, 0.0, t["kind"], t["chunk"], t["start"],
                        tuple(t["contexts"])) for t in d["ticks"]]
    win = drive.Window(records=[], ticks=ticks, t_origin=0.0, t_start=0.0,
                       t_end=1.0, trace_tick0=0)
    return ev, win


def test_fixture_is_small():
    assert os.path.getsize(DATA) < 1 << 20


def test_busy_union_and_idle(recorded):
    ev, win = recorded
    r = trace.reduce(ev, win)
    assert r.window_s == pytest.approx(0.638997595)
    assert r.busy_s == pytest.approx(0.597149594)
    assert r.wait_s == 0.0
    assert 100 * (1 - r.busy_s / r.window_s) == pytest.approx(6.549, abs=1e-3)


def test_device_clock_skew(recorded):
    ev, _ = recorded
    spans = [h for h in ev.host if h[0] == "bench.tick"]
    assert trace.device_skew(ev, spans) == pytest.approx(665746.0)


def test_per_step_kernel_sums(recorded):
    ev, win = recorded
    r = trace.reduce(ev, win)
    kinds = [t.tick.kind for t in r.ticks]
    assert kinds == ["decode", "prefill"] * 3
    dec, pre = r.ticks[0], r.ticks[1]
    assert dec.program_s == pytest.approx(0.10565407)
    assert dec.gemm_s == pytest.approx(0.015781535)
    assert dec.decode_kernel_s == pytest.approx(0.045337873)
    assert pre.program_s == pytest.approx(0.091005316)
    assert pre.gemm_s == pytest.approx(0.045232795)
    assert pre.decode_kernel_s == pytest.approx(0.001398079)
    last = r.ticks[5]
    assert last.tick.chunk == 256
    assert last.gemm_s == pytest.approx(0.035395403)


def test_idle_gap_labels(recorded):
    ev, win = recorded
    r = trace.reduce(ev, win)
    idle = dict(r.idle_by_host)
    assert idle["bench.tick after its last device op"] == \
        pytest.approx(0.037933264)
    assert idle["bench.tick before its first device op"] == \
        pytest.approx(0.002347654)
    assert idle["bench.poll"] == pytest.approx(0.001025861)
    assert sum(idle.values()) == pytest.approx(r.window_s - r.busy_s)


def test_shares_stay_under_100(recorded):
    ev, win = recorded

    class Ctx:
        arch, dims = conftest_arch()
        peaks = work.PEAKS["TPU v5 lite"]
        trace = trace.reduce(ev, win)

    for f in (readings.decode_mfu, readings.prefill_mfu,
              readings.flash_decode_roofline):
        assert 0 < f(Ctx) < 100
    for kinds in (("prefill",), ("prefill", "decode")):
        assert 0 < readings.gemm_roofline(Ctx, kinds) < 100
    assert 0 < readings.device_idle_pct(Ctx) < 100
    assert readings.host_ms_per_tick(Ctx) == pytest.approx(
        1e3 * sum(t.span_s - t.busy_s for t in Ctx.trace.ticks) / 6)


def conftest_arch():
    from benchlib import cells
    conf = cells.load_config("mistral-nemo-12b-d8")
    return conf.arch, conf.dims


def test_readings_are_pinned(recorded):
    """The work counts behind each share read on the recorded ticks, as
    they read before the counts moved into arch/dense_gqa.py."""
    ev, win = recorded

    class Ctx:
        arch, dims = conftest_arch()
        peaks = work.PEAKS["TPU v5 lite"]
        trace = trace.reduce(ev, win)

    assert readings.decode_mfu(Ctx) == 0.8729904837155593
    assert readings.prefill_mfu(Ctx) == 2.0934230960574345
    assert readings.flash_decode_roofline(Ctx) == 3.293768358566829
    assert readings.gemm_roofline(Ctx, ("prefill",)) == 16.949313408526706
    assert readings.gemm_roofline(Ctx, ("decode",)) == 44.54020926712777
    assert readings.gemm_roofline(Ctx, ("prefill", "decode")) == \
        24.48756168349558
