"""Trace reduction on a small recorded trace: busy time is the union of
device operations between the bench markers, programs are summed from the
module line, and idle gaps carry the host span they fell in."""
import types

import pytest

from harness import trace


def ev(name, start, dur):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur)


def line(name, events):
    return types.SimpleNamespace(name=name, events=events)


def recorded():
    ms = 1_000_000
    host = types.SimpleNamespace(name="/host:CPU", lines=[line("python", [
        ev(trace.OPEN, 0, 0),
        ev("bench.step", 0, 40 * ms),
        ev("bench.generate", 40 * ms, 10 * ms),
        ev("bench.step", 50 * ms, 50 * ms),
        ev(trace.CLOSE, 100 * ms, 0),
        ev("bench.step", 100 * ms, 30 * ms),       # after the close
    ])])
    device = types.SimpleNamespace(name="/device:TPU:0", lines=[
        line("XLA Modules", [ev("jit__prefill_fn", 5 * ms, 25 * ms),
                             ev("jit__chunk_fn", 60 * ms, 30 * ms),
                             ev("jit__chunk_fn", 105 * ms, 10 * ms)]),
        line("XLA Ops", [ev("fusion.1", 5 * ms, 20 * ms),
                         ev("fusion.2", 20 * ms, 10 * ms),   # overlaps
                         ev("while.3", 60 * ms, 30 * ms),
                         ev("while.3", 105 * ms, 10 * ms)]),
    ])
    return [host, device]


def test_reduce_recorded_trace():
    s = trace.reduce_planes(recorded())
    assert s.window_s == pytest.approx(0.1)
    # Union of [5, 30] and [60, 90] ms; the op after the close is left out.
    assert s.busy_s == pytest.approx(0.055)
    assert s.programs == {"jit__prefill_fn": (1, pytest.approx(0.025)),
                          "jit__chunk_fn": (1, pytest.approx(0.030))}
    gaps = dict((round(sec, 6), name) for name, sec in s.gaps)
    # [30, 60] ms: midpoint 45 ms lies in bench.generate.
    assert gaps[0.03] == "bench.generate"
    assert gaps[0.005] == "bench.step"
    assert s.top_ops[0] == ("while.3", pytest.approx(0.03))


def test_program_kinds():
    from harness import adapter

    s = trace.reduce_planes(recorded())
    assert s.program(adapter.program_kind, "prefill") == (1, pytest.approx(0.025))
    assert s.program(adapter.program_kind, "decode") == (1, pytest.approx(0.03))


def test_missing_markers_is_an_error():
    planes = recorded()
    planes[0].lines[0].events = planes[0].lines[0].events[1:]
    with pytest.raises(ValueError):
        trace.reduce_planes(planes)


def test_op_names_drop_the_hlo_text():
    assert trace.op_name("%while.41 = (s32[], bf16[8,16]) while(%tuple.89), "
                         "condition=%c, body=%b") == "%while.41"
    assert trace.op_name("while.3") == "while.3"
