"""``trace_reduce`` on the small trace recorded on the chip (three calls of
a jitted program with one flash-attention custom call each, under the span
``small_span``; ``benchmarks/tools/record_small_trace.py``): busy union,
idle share, module durations, custom-call durations, against a plain second
pass over the raw events and the numbers the recording printed."""
import os

import pytest

from benchmarks import trace_reduce
from conftest import ROOT

SMALL = os.path.join(ROOT, "benchmarks", "testdata", "small_trace.xplane.pb")


@pytest.fixture(scope="module")
def trace():
    return trace_reduce.read(SMALL, span_names={"small_span"})


def _raw(line_name):
    from jax.profiler import ProfileData

    data = ProfileData.from_file(SMALL)
    plane = next(p for p in data.planes if p.name == "/device:TPU:0")
    line = next(l for l in plane.lines if l.name == line_name)
    return [(e.name, e.start_ns, e.duration_ns) for e in line.events]


def test_union_merges_overlaps_and_keeps_gaps():
    total, merged = trace_reduce._union([("a", 0.0, 2.0), ("b", 1.0, 2.0), ("c", 5.0, 1.0), ("d", 5.5, 0.25)])
    assert total == 4.0 and merged == [(0.0, 3.0), (5.0, 6.0)]
    assert trace_reduce._union([]) == (0.0, [])


def test_module_durations_are_the_three_calls(trace):
    durations = trace.module_durations("jit_small_step")
    assert len(durations) == 3
    # as the recording printed them: 13520, 13531, 13530 ns
    assert [round(d * 1e9) for d in durations] == [13520, 13531, 13530]
    assert trace.module_durations("no_such_module") == []


def test_busy_union_and_idle_share_against_a_second_pass(trace):
    ops = _raw("XLA Ops")
    assert len(ops) == 27 and len(trace.devices) == 1
    covered = set()
    for _, start, dur in ops:  # nanosecond grid: a slow, plain union
        covered.update(range(int(start), int(start + dur)))
    lo = min(s for _, s, _ in ops)
    hi = max(s + d for _, s, d in ops)
    assert trace.busy_s() == pytest.approx(len(covered) * 1e-9, rel=2e-3)
    assert trace.window_s() == pytest.approx((hi - lo) * 1e-9, rel=1e-9)
    # three 13.5 us calls spread over 1.57 ms: the device is idle nearly all of it
    assert 0.95 < trace.idle_share() < 0.99
    assert trace.idle_share() == pytest.approx(1 - trace.busy_s() / trace.window_s())


def test_custom_call_durations_are_the_flash_kernels(trace):
    raw = [d for n, _, d in _raw("XLA Ops") if 'custom_call_target="tpu_custom_call"' in n]
    assert len(raw) == 3
    assert trace.custom_call_s() == pytest.approx(sum(raw) * 1e-9)
    # the kernel is most of each call: 10446 of 13520 ns in the first
    assert round(raw[0]) == 10446
    top = trace.top_ops(3)
    assert top[0][0] == "branch_0_fun tpu_custom_call"
    assert top[0][1] == pytest.approx(trace.custom_call_s())


def test_idle_gaps_are_named_by_the_host_span_that_covers_them(trace):
    assert [n for n, _, _ in trace.host_spans] == ["small_span"] * 3
    gaps = trace.idle_gaps(2)
    assert len(gaps) == 2 and all(length > 5e-4 for _, length in gaps)
    # the device waits while the host is between two spans, or inside one
    assert {name for name, _ in gaps} <= {"small_span", "host_untraced"}


def test_trace_without_device_events_reads_as_nothing():
    empty = trace_reduce.Trace([], [], 0.0)
    assert empty.idle_share() is None and empty.busy_s() == 0.0 and empty.idle_gaps() == []
