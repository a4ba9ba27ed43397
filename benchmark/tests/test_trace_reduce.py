"""The trace reduction: on hand-made planes, and on a small trace recorded
on the TPU v5e (`small.xplane.pb`: one warm-up dispatch outside the window
annotation, then three dispatches of a 2048^2 matmul + tanh + sum, 10 ms of
sleep after each, inside it)."""

import os
from collections import namedtuple

import pytest

from benchmark import trace_reduce

Event = namedtuple("Event", "name start_ns duration_ns stats")
Line = namedtuple("Line", "name events")
Plane = namedtuple("Plane", "name lines")


def ev(name, start, dur, **stats):
    return Event(name, start, dur, list(stats.items()))


def planes():
    ops = Line("XLA Ops", [
        ev("%while.1 = ...", 100, 400),        # holds the two below
        ev("%fusion.1 = f32[8]", 120, 100),
        ev("%fusion.2 = f32[8]", 300, 150),
        ev("%copy.1 = f32[8]", 1000, 200),
        ev("%late = f32[8]", 5000, 100),       # outside the window
    ])
    modules = Line("XLA Modules", [ev("jit_f", 100, 1100)])
    host = Line("main", [
        ev(trace_reduce.WINDOW, 0, 2000),
        ev("PjitFunction(f)", 90, 300),
        ev("np.asarray(jax.Array)", 600, 350),
        ev("inner", 700, 100),
    ])
    return [Plane("/device:TPU:0", [modules, ops]), Plane("/host:CPU", [host])]


def test_busy_is_the_union_inside_the_window():
    red = trace_reduce.reduce_planes(planes(), window_s=99.0)
    assert red["window_s"] == pytest.approx(2000e-9)  # the annotation's own length
    assert red["busy_s"] == pytest.approx((400 + 200) * 1e-9)
    assert red["n_devices"] == 1


def test_self_time_takes_the_body_out_of_the_while():
    ops = dict(trace_reduce.reduce_planes(planes(), 1.0)["device_ops"])
    assert ops["while.1_..."] == pytest.approx(150e-9)
    assert ops["fusion.2_f32_8_"] == pytest.approx(150e-9)
    assert "late_f32_8_" not in ops


def test_gaps_are_named_by_the_innermost_host_span_open():
    gaps = dict(trace_reduce.reduce_planes(planes(), 1.0)["idle_gaps"])
    # one gap, 500..1000; at its middle (750) `inner` is the shortest span open
    assert gaps == {"inner": pytest.approx(500e-9)}


def test_without_the_annotation_the_whole_trace_counts():
    pl = planes()
    pl[1] = Plane("/host:CPU", [Line("main", pl[1].lines[0].events[1:])])
    red = trace_reduce.reduce_planes(pl, window_s=3.0)
    assert red["window_s"] == 3.0
    assert red["busy_s"] == pytest.approx(700e-9)


def test_cpu_rehearsal_falls_back_to_host_events_with_an_hlo_op():
    host = Line("tf_XLA", [ev("dot.1", 10, 50, hlo_op="dot.1"), ev("Listener", 0, 5)])
    red = trace_reduce.reduce_planes([Plane("/host:CPU", [host])], 1.0)
    assert red["busy_s"] == pytest.approx(50e-9)
    assert red["device_ops"] == [["dot.1", pytest.approx(50e-9)]]


def test_no_device_events_reads_zero():
    red = trace_reduce.reduce_planes([Plane("/host:CPU", [])], 1.0)
    assert red["busy_s"] == 0.0 and red["device_ops"] == []


RECORDED = os.path.join(os.path.dirname(__file__), "small.xplane.pb")


def test_recorded_tpu_trace():
    """Device timestamps lead the host's by 1 to 1.6 ms in this trace (the
    device shows a dispatch 0.9 ms before the host enqueues it), so of the
    three dispatches inside the annotation the first falls just before its
    start: two are counted, 90 us each, with one 11.8 ms gap between them.
    On a window of seconds that skew is 0.03%."""
    red = trace_reduce.reduce_file(RECORDED, window_s=1.0)
    assert red["n_devices"] == 1
    assert red["window_s"] == pytest.approx(0.034742032)
    assert red["busy_s"] == pytest.approx(0.00018007)
    name, seconds = red["device_ops"][0]
    assert name.startswith("fusion_f32_") and seconds == pytest.approx(0.000180038)
    assert [n for n, _ in red["device_ops"][1:]] == [
        trace_reduce.clean(n) for n in (
            "%copy-start = (f32[2048,2048]{1,0:T(8,128)S(1)}, f32[2048,2048]{1,0:T(8,128)}, u32[]{:S(2)})",
            "%copy-done = f32[2048,2048]{1,0:T(8,128)S(1)} copy-done((f32[2048,2048]{1,0:T(8,128)S(1)}, f32[2048,2048]"
        )]
    assert red["idle_gaps"] == [["_no_host_span_", pytest.approx(0.011815555)]]
