"""Tests of ``host_clock.py``: the clock offset's bounds and the idle gaps
named by host activity, on hand-made events with a known offset and on
the prefill trace recorded on a TPU v5e (``testdata/``).

  PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip
"""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import host_clock as hc  # noqa: E402
import trace_reduce as tr  # noqa: E402

RECORDED = HERE / "testdata" / "phi3-medium-14b.prefill.xplane.pb"
OFFSET = 50_000                      # host time = device time + OFFSET


def made(runs, gap_spans=()):
    """One device running ``jit_a`` over each ``(start, end)`` of
    ``runs``; the host launches each run a little before it starts and
    hears it end a little after, on a clock ``OFFSET`` ns ahead, and runs
    the ``(name, start, end)`` spans of ``gap_spans`` (device times)."""
    events = []
    for s, e in runs:
        events += [("XLA Modules", "jit_a(1)", s, e - s),
                   ("XLA Ops", "%fusion.1 = f32[] fusion()", s, e - s)]
    d = tr.from_events(1e-3, {"/device:TPU:0": events}).devices[
        "/device:TPU:0"]
    launch_lag, done_lag = (300, 100, 200), (200, 50, 400)
    host = [hc.HostEvent("bench.window", "python3", 0, 10**6)]
    for (s, e), a, b in zip(runs, launch_lag, done_lag):
        host += [hc.HostEvent(hc.LAUNCH, "main", s + OFFSET - a,
                              s + OFFSET - a + 20),
                 hc.HostEvent(hc.DONE, "futex", e + OFFSET + b,
                              e + OFFSET + b + 30)]
    host += [hc.HostEvent(n, "python3", s + OFFSET, e + OFFSET)
             for n, s, e in gap_spans]
    return d, sorted(host, key=lambda e: (e.start_ns, -e.end_ns))


RUNS = [(1_000, 5_000), (9_000, 13_000), (13_100, 17_000)]


def test_known_offset_is_recovered():
    d, host = made(RUNS)
    lo, hi = hc.clock_bounds(d, host)
    # the least launch lag and the least done lag bound the offset
    assert (lo, hi) == (OFFSET - 100, OFFSET + 50)
    assert lo <= OFFSET <= hi


def test_gaps_are_named_by_the_host_and_short_ones_unresolved():
    d, host = made(RUNS, [("PjitFunction(jit(a))", 6_000, 9_000),
                          ("bench.step", 5_000, 9_000)])
    bounds = hc.clock_bounds(d, host)
    named = dict(hc.named_gaps(d, host, bounds))
    # the 4 us gap, on the host's clock 25 ns before the true one (the
    # midpoint of the bounds): python between the calls, the done of the
    # run before, dispatch, the launch of the run after, each instant to
    # the innermost event
    pair = " (jit_a -> jit_a)"
    assert named == pytest.approx({
        "bench.window" + pair: 25e-9, hc.DONE + pair: 30e-9,
        "bench.step" + pair: 970e-9, "PjitFunction(jit(a))" + pair: 2955e-9,
        hc.LAUNCH + pair: 20e-9,
        # 100 ns, under the 150 ns resolution
        "jit_a -> jit_a unresolved": 100e-9})
    assert sum(named.values()) == pytest.approx(
        sum(s for s, _ in tr.gaps(d)))


def test_no_offset_without_pairs_or_with_contradicting_ones():
    d, host = made(RUNS)
    assert hc.clock_bounds(d, host[:-1]) is None       # a done missing
    named = hc.named_gaps(d, host, (10.0, 5.0))        # lo > hi
    assert all(name.endswith(" unresolved") for name, _ in named)


@pytest.fixture(scope="module")
def recorded():
    return tr.load(str(RECORDED)), hc.load_host(str(RECORDED))


def test_recorded_clock_bounds(recorded):
    """Three runs of ``jit_prefill``: each starts 1.093-1.185 ms after
    the host's launch on the host's clock, and ends 2.303-2.894 ms before
    the host hears of it."""
    trace, host = recorded
    (d,) = trace.devices.values()
    lo, hi = hc.clock_bounds(d, host)
    assert lo * 1e-6 == pytest.approx(1.185, abs=0.01)
    assert hi * 1e-6 == pytest.approx(2.303, abs=0.01)


def test_recorded_gaps_get_host_names(recorded):
    """The two gaps between the three calls are longer than the
    resolution, and every instant of them gets a host event's name; the
    other gaps, between ops of one run, last a few ns."""
    trace, host = recorded
    (d,) = trace.devices.values()
    bounds = hc.clock_bounds(d, host)
    calls = [g for g in hc._gap_spans(d) if g[1] - g[0] > 1e3]
    assert len(calls) == 2
    assert all(g[1] - g[0] > bounds[1] - bounds[0] for g in calls)
    named = hc.named_gaps(d, host, bounds, n=999)
    resolved = {n: s for n, s in named if not n.endswith(" unresolved")}
    assert sum(resolved.values()) == pytest.approx(
        sum(g[1] - g[0] for g in calls) * 1e-9)
    assert all(n.endswith(" (jit_prefill -> jit_prefill)") and
               not n.startswith("no host span") for n in resolved)


def test_recorded_dispatch(recorded):
    _, host = recorded
    # three calls: 0.690, 0.703 and 0.987 ms, each span nested in another
    # of the same name
    assert hc.dispatch_ms(host, "jit_prefill") == pytest.approx(0.70288)
    assert hc.dispatch_ms(host, "jit_decode") is None


def test_host_events_lie_in_the_window(recorded):
    trace, host = recorded
    (window,) = [e for e in host if e.name == tr.WINDOW_SPAN]
    assert (window.end_ns - window.start_ns) * 1e-9 == trace.window_s
    assert all(window.start_ns <= e.start_ns and e.end_ns <= window.end_ns
               for e in host)
    assert {"bench.step", hc.LAUNCH, hc.DONE} <= {e.name for e in host}
