"""The host's side of a profiler trace, put on the device's clock.

  python3 benchmarks/chip/host_clock.py trace.xplane.pb

prints, as one JSON object, the clock offset's bounds, the device's idle
gaps named by what the host was doing in them, and the median dispatch
time of each program.  It reads any ``.xplane.pb`` whose host spans include
the benchmark's ``bench.window`` (``run.py``, ``record.py``), on any
machine: nothing here needs a chip.

The host's events are the lines of the plane ``/host:CPU``: the
benchmark's spans (``bench.window``, ``bench.step``), JAX's dispatch of a
jitted program (``PjitFunction(jit(<name>))``) and the TPU runtime's own
(``tpu::System::Execute`` launches a program run,
``tpu::System::Execute=>Done`` is the host hearing that it ended).  The
trace gives host and device times on clocks whose offset it does not
record.  Each program run bounds it: with ``host = device + offset``, the
device cannot start a run before the host launched it, and the host
cannot hear of its end before it ended, so

    lo = max over runs of (launch start - run start)  <=  offset
    hi = min over runs of (done start - run end)      >=  offset

The n-th launch and the n-th done in the window are paired with the n-th
run on the device: the runtime's launch carries no stat that the device's
``run_id`` shares, and one device runs its programs in the order they were
launched.  Where the counts differ, nothing is paired.  A window where
``lo > hi`` has pairs that contradict each other (a wrong pairing, or
clocks that drift apart within the window): no offset is taken from it.
Otherwise the host's events are shifted by the midpoint, and ``hi - lo``
is the resolution of any attribution made with them.
"""

from __future__ import annotations

import json
import statistics
import sys
from collections import defaultdict
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Optional, Tuple

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import trace_reduce  # noqa: E402

LAUNCH = "tpu::System::Execute"
DONE = "tpu::System::Execute=>Done"


@dataclass(frozen=True)
class HostEvent:
    name: str
    thread: str          # the host line, e.g. "python3" or "main/330"
    start_ns: float
    end_ns: float


def load_host(path: str) -> List[HostEvent]:
    """The host's events that lie inside the ``bench.window`` span, that
    span included; none without it."""
    from jax.profiler import ProfileData
    events = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            events += [HostEvent(e.name, line.name, e.start_ns, e.end_ns)
                       for line in plane.lines for e in line.events]
    window = [e for e in events if e.name == trace_reduce.WINDOW_SPAN]
    if not window:
        return []
    lo, hi = window[0].start_ns, window[0].end_ns
    return sorted((e for e in events if lo <= e.start_ns and e.end_ns <= hi),
                  key=lambda e: (e.start_ns, -e.end_ns))


def clock_bounds(d: trace_reduce.DeviceTrace, host: List[HostEvent]
                 ) -> Optional[Tuple[float, float]]:
    """``(lo, hi)`` in ns: the bounds of ``host - device`` time that the
    device's program runs and the host's launches and dones give; None
    where their counts differ or there is no run."""
    launches = sorted(e.start_ns for e in host if e.name == LAUNCH)
    dones = sorted(e.start_ns for e in host if e.name == DONE)
    runs = sorted(d.modules)
    if not runs or not len(runs) == len(launches) == len(dones):
        return None
    lo = max(t - run[0] for t, run in zip(launches, runs))
    hi = min(t - run[1] for t, run in zip(dones, runs))
    return lo, hi


def _gap_spans(d: trace_reduce.DeviceTrace):
    """``(start, end, name)`` of each idle stretch between the first and
    the last op, named as ``trace_reduce.gaps`` names it."""
    end, before = None, None
    for op in d.ops:
        if end is not None and op.start_ns > end:
            yield end, op.start_ns, f"{before or '?'} -> {op.module or '?'}"
        if end is None or op.end_ns > end:
            end, before = op.end_ns, op.module


def _split(start: float, end: float, covering: List[HostEvent]):
    """``(seconds, event)`` pieces of ``[start, end)``, each instant given
    to the innermost (shortest) event that covers it, or to None."""
    cuts = sorted({start, end} | {t for e in covering
                                  for t in (e.start_ns, e.end_ns)
                                  if start < t < end})
    for a, b in zip(cuts, cuts[1:]):
        over = [e for e in covering if e.start_ns <= a and b <= e.end_ns]
        inner = min(over, key=lambda e: e.end_ns - e.start_ns, default=None)
        yield (b - a) * 1e-9, inner


def named_gaps(d: trace_reduce.DeviceTrace, host: List[HostEvent],
               bounds: Optional[Tuple[float, float]], n: int = 10
               ) -> List[List]:
    """Idle time summed by what the host was doing, largest first, as
    ``[name, seconds]``.  With the host's events shifted onto the device's
    clock (by the midpoint of ``bounds``), each instant of a gap goes to
    the innermost host event that covers it, so a gap is named by the
    event that covers most of it, and split where several do: for example
    ``PjitFunction(jit(prefill)) (jit_prefill -> jit_prefill)``.  A gap
    shorter than the resolution ``hi - lo``, or any gap where there are
    no bounds or ``lo > hi``, keeps ``trace_reduce.gaps``' name with
    `` unresolved`` added."""
    total = defaultdict(float)
    usable = bounds is not None and bounds[0] <= bounds[1]
    mid = (bounds[0] + bounds[1]) / 2 if usable else None
    for start, end, pair in _gap_spans(d):
        if not usable or end - start < bounds[1] - bounds[0]:
            total[f"{pair} unresolved"] += (end - start) * 1e-9
            continue
        a, b = start + mid, end + mid          # the gap on the host's clock
        covering = [e for e in host if e.start_ns < b and e.end_ns > a]
        for sec, e in _split(a, b, covering):
            total[f"{e.name if e else 'no host span'} ({pair})"] += sec
    return [[k, v] for k, v in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def dispatch_ms(host: List[HostEvent], module: str) -> Optional[float]:
    """Median milliseconds of JAX's dispatch of the program ``module``
    (``jit_prefill`` -> spans ``PjitFunction(jit(prefill))``, or
    ``PjitFunction(prefill)`` for a function jitted inside JAX), counting
    each outermost span once; None without such a span."""
    fn = module.removeprefix("jit_")
    names = {f"PjitFunction(jit({fn}))", f"PjitFunction({fn})"}
    spans, last_end = [], defaultdict(lambda: float("-inf"))
    for e in host:                             # sorted by start
        if e.name in names and e.start_ns >= last_end[e.thread]:
            spans.append(e.end_ns - e.start_ns)
            last_end[e.thread] = e.end_ns
    return statistics.median(spans) * 1e-6 if spans else None


def summary(path: str) -> Dict:
    """What the command line prints for one trace."""
    trace = trace_reduce.load(path)
    host = load_host(path)
    out = {"window_s": trace.window_s, "devices": {}, "dispatch_ms": {}}
    for name, d in sorted(trace.devices.items()):
        bounds = clock_bounds(d, host)
        out["devices"][name] = {
            "runs": len(d.modules),
            "clock_offset_ms": None if bounds is None else
            [bounds[0] * 1e-6, bounds[1] * 1e-6],
            "idle_gaps": named_gaps(d, host, bounds)}
        for module in {m for _, _, m in d.modules}:
            out["dispatch_ms"][module] = dispatch_ms(host, module)
    return out


if __name__ == "__main__":
    if len(sys.argv) != 2:
        sys.exit(__doc__.split("\n\n")[1])
    print(json.dumps(summary(sys.argv[1])))
