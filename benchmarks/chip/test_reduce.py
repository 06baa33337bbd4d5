"""Tests of the trace-to-metric reductions (``trace_reduce.py``): on
hand-made events, and on a short trace of the prefill program recorded on a
TPU v5e with ``record.py`` (2 of Phi-3-medium's layers, the cell's batch
and prompt length), with the compiled HLO beside it.

  PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip
"""

from __future__ import annotations

import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import trace_reduce as tr  # noqa: E402

RECORDED = HERE / "testdata" / "phi3-medium-14b.prefill"


def made(events, window_s=1e-5):
    """One device; ``events`` are (line, name, start_ns, duration_ns)."""
    return tr.from_events(window_s, {"/device:TPU:0": events})


def test_union_counts_overlap_once():
    assert tr.union_ns([(0, 10), (5, 20), (30, 40), (40, 41)]) == 31
    assert tr.union_ns([]) == 0


def test_busy_idle_and_gaps():
    t = made([("XLA Modules", "jit_a(1)", 0, 4000),
              ("XLA Ops", "%fusion.1 = f32[] fusion()", 0, 3000),
              ("XLA Ops", "%dot.2 = f32[] dot()", 2000, 2000),
              ("XLA Modules", "jit_b(2)", 6000, 1000),
              ("XLA Ops", "%copy.3 = f32[] copy()", 6000, 1000)])
    d = t.devices["/device:TPU:0"]
    assert [o.module for o in d.ops] == ["jit_a", "jit_a", "jit_b"]
    assert [o.instr for o in d.ops] == ["fusion.1", "dot.2", "copy.3"]
    assert tr.busy_s(d) == pytest.approx(5e-6)
    assert tr.idle_share(t) == pytest.approx(0.5)
    assert tr.gaps(d) == [(pytest.approx(2e-6), "jit_a -> jit_b")]
    assert tr.top_ops(d)[0] == ["jit_a:fusion.1", pytest.approx(3e-6)]
    assert tr.scope_seconds(d, "jit_a", {"dot.2"}) == pytest.approx(2e-6)


def test_nested_ops_count_once():
    """A loop op spans its body's ops on the same line."""
    t = made([("XLA Modules", "jit_a(1)", 0, 100),
              ("XLA Ops", "%while.1 = () while()", 0, 100),
              ("XLA Ops", "%fusion.2 = f32[] fusion()", 10, 30),
              ("XLA Ops", "%dot.3 = f32[] dot()", 50, 40),
              ("XLA Ops", "%add.4 = f32[] add()", 60, 10)])
    d = t.devices["/device:TPU:0"]
    self_ns = {o.instr: o.self_ns for o in d.ops}
    assert self_ns == {"while.1": 30, "fusion.2": 30, "dot.3": 30,
                       "add.4": 10}
    assert sum(self_ns.values()) * 1e-9 == pytest.approx(tr.busy_s(d))
    assert tr.top_ops(d)[0][0] in ("jit_a:while.1", "jit_a:fusion.2",
                                   "jit_a:dot.3")


def test_no_window_or_no_ops_reads_nothing():
    assert tr.idle_share(made([], window_s=1.0)) is None
    assert tr.idle_share(made([("XLA Ops", "%a = f32[] a()", 0, 5)],
                              window_s=None)) is None


HLO = """HloModule jit_prefill
%fused_computation.1 (p: f32[2]) -> f32[2] {
  %p = f32[2]{0} parameter(0)
  ROOT %exp.1 = f32[2]{0} exponential(%p), metadata={op_name="jit(prefill)/while/body/attention_kernel/exp"}
}
ENTRY %main (a: f32[2]) -> f32[2] {
  %a = f32[2]{0} parameter(0)
  %fusion.1 = f32[2]{0} fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(prefill)/while/body/attention_kernel/exp"}
  ROOT %dot.2 = f32[2]{0} multiply(%fusion.1, %a), metadata={op_name="jit(prefill)/while/body/mul"}
}
"""


def test_scope_from_hlo_metadata():
    assert tr.op_names(HLO)["dot.2"] == "jit(prefill)/while/body/mul"
    assert sorted(tr.in_scope(HLO, "attention_kernel")) == ["exp.1",
                                                            "fusion.1"]
    assert tr.in_scope(HLO, "attention") == []


@pytest.fixture(scope="module")
def recorded():
    return (tr.load(str(RECORDED) + ".xplane.pb"),
            Path(str(RECORDED) + ".hlo.txt").read_text())


def test_recorded_busy_matches_a_mask_of_the_ops(recorded):
    trace, _ = recorded
    (d,) = trace.devices.values()
    assert d.ops and d.modules and trace.window_s
    lo = min(o.start_ns for o in d.ops)
    mask = np.zeros(int(max(o.end_ns for o in d.ops) - lo) // 100 + 2, bool)
    for o in d.ops:           # 100 ns cells, independently of union_ns
        mask[int((o.start_ns - lo) // 100):int((o.end_ns - lo) // 100) + 1] \
            = True
    busy = tr.busy_s(d)
    assert busy == pytest.approx(mask.sum() * 1e-7, rel=2e-2)
    assert 0 < busy < trace.window_s
    assert tr.idle_share(trace) == pytest.approx(1 - busy / trace.window_s)


def test_recorded_attention_scope(recorded):
    trace, hlo = recorded
    (d,) = trace.devices.values()
    instrs = tr.in_scope(hlo, "attention_kernel")
    assert instrs
    att = tr.scope_seconds(d, "jit_prefill", instrs)
    assert 0 < att < tr.busy_s(d)
    # self times add up to the busy time: no instant is counted twice
    total = sum(o.self_ns for o in d.ops) * 1e-9
    assert total == pytest.approx(tr.busy_s(d), rel=1e-3)
    assert all(o.module == "jit_prefill" for o in d.ops)


def test_recorded_breakdown(recorded):
    trace, hlo = recorded
    (d,) = trace.devices.values()
    ops = tr.top_ops(d, tr.op_names(hlo))
    assert 0 < len(ops) <= 10
    assert [s for _, s in ops] == sorted((s for _, s in ops), reverse=True)
    assert all(name.startswith("jit_prefill:") for name, _ in ops)
    idle = tr.top_gaps(d)
    assert sum(s for _, s in idle) <= trace.window_s
