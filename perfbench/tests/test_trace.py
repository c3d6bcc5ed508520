"""The trace reduction on a small synthetic trace, and the peaks table."""

from types import SimpleNamespace as NS

import pytest

from harness import peaks, trace


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def planes():
    host = NS(name="/host:CPU", lines=[NS(name="main", events=[
        ev("bench.window", 1000, 1000),
        ev("bench.ingest", 1000, 300),
        ev("bench.train_tick", 1300, 500),
        ev("bench.push", 1800, 200),
        ev("unrelated", 0, 5000),
    ])])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[
            ev("jit__ftrl_program(123)", 1400, 300),
            ev("jit__lookup_program(9)", 1850, 100),
            ev("jit__ftrl_program(77)", 2500, 50)]),     # after the window
        NS(name="XLA Ops", events=[
            ev("%body.3 = (s32[8,1,128]{2,1,0:T(1,128)}, s32[8,1,128]"
               "{2,1,0:T(1,128)}) custom-call(s32[1] %b)", 1400, 200),
            ev("%fusion = f32[8]", 1550, 150),           # overlaps the while
            ev("%fusion.2 = f32[8]", 1850, 100),
            ev("%copy = f32[8]", 900, 200),              # starts before
        ])])
    return [host, dev, NS(name="/device:TPU:1", lines=[])]


def test_busy_union_and_idle():
    r = trace.reduce_planes(planes())
    assert r.window_ns == (1000.0, 2000.0)
    assert r.devices == 1                      # the idle plane is not averaged
    # union: [1000,1100] (clipped copy) + [1400,1700] + [1850,1950]
    assert r.busy_ns == pytest.approx(100 + 300 + 100)
    assert r.window_s == pytest.approx(1e-6)
    assert 1 - r.busy_s / r.window_s == pytest.approx(0.5)


def test_programs_and_ops():
    r = trace.reduce_planes(planes())
    assert r.modules_ns == {"jit__ftrl_program": 300.0,
                            "jit__lookup_program": 100.0}
    assert r.module_ns(r"^jit__ftrl_program$") == 300.0
    from harness import bench
    probe = bench.reader("probe_roofline.train").__globals__["OP"]
    assert r.op_ns(probe) == 200.0
    assert r.op_ns(r"^%fusion") == 250.0


def test_idle_gaps_by_span_and_breakdown():
    r = trace.reduce_planes(planes())
    # gaps: [1100,1400] (ingest 200 ns of it, train_tick 100),
    # [1700,1850] (train_tick 100, push 50), [1950,2000] (push)
    assert r.idle_by_span_ns == {"bench.ingest": 300.0,
                                 "bench.train_tick": 150.0,
                                 "bench.push": 50.0}
    b = r.breakdown()
    assert b["device_ops"][0] == ["jit__ftrl_program", pytest.approx(3e-7)]
    assert b["idle_gaps"][0] == ["bench.ingest", pytest.approx(3e-7)]
    assert len(b["idle_gaps"]) <= 10 and len(b["device_ops"]) <= 10


def test_no_window_span_is_an_error():
    p = planes()
    p[0].lines[0].events.pop(0)
    with pytest.raises(ValueError):
        trace.reduce_planes(p)


def test_union_and_gaps_helpers():
    assert trace.union([(5, 7), (0, 2), (1, 3), (6, 9)]) == [[0, 3], [5, 9]]
    assert trace.gaps([[2, 3], [5, 6]], 0, 10) == [(0, 2), (3, 5), (6, 10)]


def test_peaks_table():
    p = peaks.for_kind("TPU v5 lite")
    assert p["flops_bf16"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(peaks.UnknownDevice):
        peaks.for_kind("TPU v4")
