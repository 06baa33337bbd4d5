"""From a profiler trace to the numbers the per-layer metrics read.

The JAX profiler writes an ``.xplane.pb``.  On a TPU each chip is a plane
``/device:TPU:<n>``; its line ``XLA Modules`` holds one event per program
run and its line ``XLA Ops`` one event per HLO instruction run, named by
the instruction's text (``%fusion.141 = bf16[...] fusion(...)``).  Host
threads are lines of the plane ``/host:CPU``; the benchmark's own spans
(``jax.profiler.TraceAnnotation``) appear there by name.

Host and device timestamps are not aligned to better than about a
millisecond, so nothing here compares a host time with a device time: the
traced window's length is the host span ``bench.window``, and busy time is
the union of the device's own op intervals.  An idle gap is named by the
programs on either side of it.
"""

from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
_INSTR = re.compile(r"%?([\w.\-]+)")
_MODULE = re.compile(r"^(.*?)(\(\d+\))?$")


@dataclass
class Op:
    start_ns: float
    end_ns: float
    instr: str           # HLO instruction name, e.g. "fusion.141"
    module: str          # program name, e.g. "jit_prefill"
    self_ns: float = 0.0  # duration less that of the ops nested in it


@dataclass
class DeviceTrace:
    name: str
    ops: List[Op] = field(default_factory=list)
    modules: List[Tuple[float, float, str]] = field(default_factory=list)


@dataclass
class Trace:
    window_s: Optional[float]
    devices: Dict[str, DeviceTrace]


def module_name(event_name: str) -> str:
    """``jit_prefill(12694211510846059035)`` -> ``jit_prefill``."""
    return _MODULE.match(event_name).group(1)


def from_events(window_s, device_events) -> Trace:
    """Build a ``Trace`` from plain tuples: ``device_events`` maps a device
    name to ``(line, name, start_ns, duration_ns)`` tuples."""
    devices = {}
    for dev, events in device_events.items():
        d = DeviceTrace(dev)
        for line, name, start, dur in events:
            if line == "XLA Modules":
                d.modules.append((start, start + dur, module_name(name)))
            elif line == "XLA Ops":
                d.ops.append(Op(start, start + dur,
                                _INSTR.match(name).group(1), ""))
        d.modules.sort()
        d.ops.sort(key=lambda o: (o.start_ns, -o.end_ns))
        _attach_modules(d)
        _self_times(d.ops)
        devices[dev] = d
    return Trace(window_s, devices)


def _attach_modules(d: DeviceTrace) -> None:
    """Give each op the program whose run contains its start."""
    i = 0
    for op in d.ops:
        while i + 1 < len(d.modules) and d.modules[i + 1][0] <= op.start_ns:
            i += 1
        if d.modules and d.modules[i][0] <= op.start_ns <= d.modules[i][1]:
            op.module = d.modules[i][2]


def _self_times(ops: List[Op]) -> None:
    """A ``while`` or ``call`` op is an event that spans the ops of its
    body on the same line; give each op the time no nested op covers, so
    that summing self times counts every instant once."""
    stack: List[Op] = []
    for op in ops:
        op.self_ns = op.end_ns - op.start_ns
        while stack and stack[-1].end_ns <= op.start_ns:
            stack.pop()
        if stack and op.end_ns <= stack[-1].end_ns:
            stack[-1].self_ns -= op.end_ns - op.start_ns
        stack.append(op)


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` written by ``jax.profiler``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    window_s = None
    device_events = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            device_events[plane.name] = [
                (line.name, e.name, e.start_ns, e.duration_ns)
                for line in plane.lines
                if line.name in ("XLA Modules", "XLA Ops")
                for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW_SPAN:
                        window_s = e.duration_ns * 1e-9
    return from_events(window_s, device_events)


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------

def union_ns(intervals) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def busy_s(d: DeviceTrace) -> float:
    """Seconds in which some operation ran on this device."""
    return union_ns((o.start_ns, o.end_ns) for o in d.ops) * 1e-9


def mean_busy_s(trace: Trace) -> float:
    return sum(busy_s(d) for d in trace.devices.values()) / \
        max(1, len(trace.devices))


def idle_share(trace: Trace) -> Optional[float]:
    """1 - busy / window, averaged over the devices; None without a
    window or without device ops."""
    if not trace.window_s or not trace.devices:
        return None
    busy = mean_busy_s(trace)
    if busy <= 0:
        return None
    return 1.0 - busy / trace.window_s


def gaps(d: DeviceTrace) -> List[Tuple[float, str]]:
    """Idle stretches between the first and the last op, each named by the
    programs that ran before and after it."""
    out = []
    end, before = None, None
    for op in d.ops:
        if end is not None and op.start_ns > end:
            out.append(((op.start_ns - end) * 1e-9,
                        f"{before or '?'} -> {op.module or '?'}"))
        if end is None or op.end_ns > end:
            end, before = op.end_ns, op.module
    return out


def top_ops(d: DeviceTrace, scope: Optional[Dict[str, str]] = None,
            n: int = 10) -> List[List]:
    """The ``n`` instructions with the most device self time, as
    ``[name, seconds]``; ``scope`` (instruction -> op_name, from the
    compiled HLO) adds what the instruction computes."""
    total = defaultdict(float)
    for op in d.ops:
        total[(op.module, op.instr)] += op.self_ns * 1e-9
    rows = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    out = []
    for (module, instr), sec in rows:
        what = (scope or {}).get(instr, "")
        what = "/".join(what.split("/")[-2:]) if what else ""
        out.append([f"{module}:{instr}" + (f" {what}" if what else ""), sec])
    return out


def top_gaps(d: DeviceTrace, n: int = 10) -> List[List]:
    """Idle time summed by what lay on either side, largest first."""
    total = defaultdict(float)
    for sec, name in gaps(d):
        total[name] += sec
    return [[k, v] for k, v in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def scope_seconds(d: DeviceTrace, module: str, instrs) -> float:
    """Device self seconds of the ops of ``module`` whose instruction is in
    ``instrs``."""
    instrs = set(instrs)
    return sum(o.self_ns for o in d.ops
               if o.module == module and o.instr in instrs) * 1e-9


# ---------------------------------------------------------------------------
# the compiled HLO: which instruction belongs to which named scope
# ---------------------------------------------------------------------------

_COMP = re.compile(r"^(?:ENTRY )?%([\w.\-]+) .*\{\s*$")
_INST = re.compile(r"^\s+(?:ROOT )?%([\w.\-]+) = (.*)$")
_OPNAME = re.compile(r'op_name="([^"]*)"')


def op_names(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> the ``op_name`` of its metadata (the JAX name
    stack, which holds every ``jax.named_scope`` around it).  A fusion
    carries the op_name of the instruction it was built around."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INST.match(line)
        if m:
            op = _OPNAME.search(m.group(2))
            out[m.group(1)] = op.group(1) if op else ""
    return out


def in_scope(hlo_text: str, scope: str) -> List[str]:
    """Instructions whose op_name lies under the named scope ``scope``."""
    key = f"/{scope}/"
    return [k for k, v in op_names(hlo_text).items() if key in f"{v}/"]
