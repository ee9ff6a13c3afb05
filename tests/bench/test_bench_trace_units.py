"""Trace reduction on hand-made events: busy union, waits, per-tick kernel
sums, and idle gaps labelled by the host span open during them."""

import pytest

from benchlib import drive, trace

MS = 1e6      # ns


def events():
    host = [("bench.trace_window", 0, 100 * MS),
            ("bench.tick", 10 * MS, 40 * MS),
            ("bench.poll", 40 * MS, 42 * MS),
            ("bench.wait_arrival", 42 * MS, 60 * MS),
            ("bench.tick", 60 * MS, 90 * MS)]
    programs = [("jit_prefill_chunk_step", 12 * MS, 30 * MS),
                ("jit_paged_serve_step", 64 * MS, 80 * MS)]
    ops = [("fusion.1", 12 * MS, 14 * MS, ""),
           ("custom-call.2", 14 * MS, 24 * MS, "gemm"),
           ("custom-call.3", 22 * MS, 30 * MS, "flash_decode"),  # overlaps
           ("custom-call.2", 64 * MS, 70 * MS, "gemm"),
           ("custom-call.3", 72 * MS, 80 * MS, "flash_decode"),
           ("fusion.9", 95 * MS, 105 * MS, "")]          # clipped at 100
    return trace.Events(ops=ops, programs=programs, host=host)


def window():
    ticks = [drive.Tick(0, 0, "decode", contexts=(5,)),     # before tracing
             drive.Tick(0, 0, "prefill", chunk=64, start=0),
             drive.Tick(0, 0, "decode", contexts=(64, 10))]
    return drive.Window(records=[], ticks=ticks, t_origin=0, t_start=0,
                        t_end=1, trace_start=0, trace_tick0=1)


def test_union_and_overlap():
    u = trace.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert u == [(0, 3), (5, 8)]
    assert trace.overlap(u, 2, 6) == pytest.approx(2)


def test_reduce_pins_busy_waits_and_ticks():
    r = trace.reduce(events(), window())
    assert r.window_s == pytest.approx(0.100)
    # busy: [12, 30] + [64, 70] + [72, 80] + [95, 100] = 18 + 6 + 8 + 5 ms
    assert r.busy_s == pytest.approx(0.037)
    assert r.wait_s == pytest.approx(0.018)
    assert r.busy_in_wait_s == 0
    a, b = r.ticks
    assert a.tick.kind == "prefill" and b.tick.kind == "decode"
    assert a.program_s == pytest.approx(0.018)
    assert a.gemm_s == pytest.approx(0.010)
    assert a.decode_kernel_s == pytest.approx(0.008)
    assert a.busy_s == pytest.approx(0.018) and a.span_s == pytest.approx(0.030)
    assert b.gemm_s == pytest.approx(0.006)
    assert b.decode_kernel_s == pytest.approx(0.008)


def test_idle_gaps_are_labelled():
    r = trace.reduce(events(), window())
    idle = dict(r.idle_by_host)
    assert idle["bench.tick before its first device op"] == pytest.approx(
        0.002 + 0.004)
    assert idle["bench.tick after its last device op"] == pytest.approx(
        0.010 + 0.010)
    assert idle["bench.wait_arrival"] == pytest.approx(0.018)
    assert sum(idle.values()) == pytest.approx(r.window_s - r.busy_s)
    bd = r.breakdown()
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_tick_count_mismatch_is_an_error():
    w = window()
    w.trace_tick0 = 0
    with pytest.raises(RuntimeError, match="traced ticks"):
        trace.reduce(events(), w)


GEMM = ('%closed_call.235 = f32[8,1024]{1,0:T(8,128)S(1)} custom-call('
        'bf16[8,5120]{1,0:T(8,128)(2,1)S(1)} %fusion.147, '
        'bf16[5120,1024]{1,0:T(8,128)(2,1)S(1)} %dynamic-slice.57), '
        'custom_call_target="tpu_custom_call", operand_layout_constraints={}')
DECODE = ('%closed_call.205 = (f32[32,16,8,8,128]{4,3,2,1,0}, f32[32,16,8,8]'
          '{3,2,1,0}, f32[32,16,8,8]{3,2,1,0}) custom-call(s32[32,256]{1,0} '
          '%copy-done.7, s32[32]{0} %copy-done.36, bf16[32,8,8,128]{3,2,1,0}'
          ' %fusion.167, bf16[4097,8,16,128]{3,2,1,0} %copy.188, '
          'bf16[4097,8,16,128]{3,2,1,0} %copy.190), '
          'custom_call_target="tpu_custom_call"')


def test_kernels_known_by_operands():
    assert trace.kernel_of(GEMM) == "gemm"
    assert trace.kernel_of(DECODE) == "flash_decode"
    assert trace.kernel_of("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %a)") == ""
    alloc = ('%custom-call.28 = bf16[2,4097,8,16,128]{4,3,2,1,0} '
             'custom-call(), custom_call_target="AllocateBuffer"')
    assert trace.kernel_of(alloc) == ""
