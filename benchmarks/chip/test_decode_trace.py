"""The decode cell's device-trace metrics on a short trace of the decode
program recorded on a TPU v5e with ``record.py`` (2 of StarCoder2-7B's
layers, the cell's batch, prompts and cache), with the compiled HLO beside
it, and a ``--trace 1`` run of the decode cell that reads them.

  PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip
"""

from __future__ import annotations

import dataclasses
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parents[1] / "src"))

import cell as cells  # noqa: E402
import host_clock as hc  # noqa: E402
import run as bench  # noqa: E402
import trace_reduce as tr  # noqa: E402
import work  # noqa: E402

CELL = "starcoder2-7b.decode"
RECORDED = HERE / "testdata" / CELL
MODULE = "jit_decode"


@pytest.fixture(scope="module")
def recorded():
    """The reader's context for the recorded window: its steps ran at the
    positions that follow the prompts and the warm-up steps, one step per
    run of ``jit_decode``."""
    trace = tr.load(f"{RECORDED}.xplane.pb")
    hlo = Path(f"{RECORDED}.hlo.txt").read_text()
    c = cells.load(CELL)
    s = cells.sizes(dict(c.config, num_hidden_layers=2))
    t = c.traffic
    (d,) = trace.devices.values()
    first = t["prompt_len"] + t["warmup_steps"]
    steps = sum(m == MODULE for _, _, m in d.modules)
    load = {}
    for pos in range(first, first + steps):
        load = work.add(load, work.decode_step(s, t["batch"], pos))
    peaks = cells.read_json(HERE / "peaks.json")["TPU v5 lite"]
    return SimpleNamespace(trace=trace, hlo={MODULE: hlo}, work=load,
                           peaks=peaks, chips=1, window_s=trace.window_s)


def read(name, ctx):
    return bench.load_module(bench.reader(name)).read(ctx)


def test_recorded_decode_runs(recorded):
    """Each step runs ``jit_decode`` and, before it, the conversion of its
    position to a device scalar."""
    (d,) = recorded.trace.devices.values()
    names = [m for _, _, m in sorted(d.modules)]
    assert names[1::2] == [MODULE] * (len(names) // 2) and len(names) > 20
    assert set(names[::2]) == {"jit_convert_element_type"}


def test_recorded_attention_roofline(recorded):
    """Below the roofline: the ops under ``attention_kernel`` take longer
    than the live KV's bytes need at the peak bandwidth."""
    value = read("attention_roofline.decode", recorded)
    assert 0 < value <= 100


def test_recorded_scope_shares_add_up(recorded):
    """Each part's share of ``jit_decode``'s self time and the unscoped
    share add up to all of it."""
    unscoped = read("unscoped_share.decode", recorded)
    assert 0 < unscoped < 100
    scopes = bench.load_module(bench.reader("unscoped_share.decode")).SCOPES
    hlo = recorded.hlo[MODULE]
    (d,) = recorded.trace.devices.values()
    total = sum(o.self_ns for o in d.ops if o.module == MODULE) * 1e-9
    parts = {s: 100 * tr.scope_seconds(d, MODULE, tr.in_scope(hlo, s)) /
             total for s in scopes}
    assert all(v > 0 for v in parts.values()), parts
    assert sum(parts.values()) + unscoped == pytest.approx(100, abs=1e-6)


def test_recorded_host_side():
    """Two programs a step, each paired with its launch and done; every
    idle gap lasts a few microseconds, under the clock bounds' width."""
    path = f"{RECORDED}.xplane.pb"
    (d,) = tr.load(path).devices.values()
    host = hc.load_host(path)
    lo, hi = hc.clock_bounds(d, host)
    assert 0 < lo < hi < lo + 1e6
    named = hc.named_gaps(d, host, (lo, hi))
    assert [n for n, _ in named] == ["jit_decode -> jit_decode unresolved"]
    assert hc.dispatch_ms(host, MODULE) == pytest.approx(0.389955)
    assert hc.dispatch_ms(host, "jit_convert_element_type") == \
        pytest.approx(0.308675)


def test_traced_decode_run_reports_per_layer(monkeypatch):
    """A ``--trace 1`` run of the decode cell end to end, with the recorded
    trace and HLO read in place of the CPU's: every per-layer metric the
    decode cell lists comes out."""
    from test_bench import cpu, tiny_cell
    trace = tr.load(f"{RECORDED}.xplane.pb")
    hlo = {MODULE: Path(f"{RECORDED}.hlo.txt").read_text()}
    monkeypatch.setattr(bench.trace_reduce, "load", lambda path: trace)
    mod = bench.load_module(HERE / "kinds" / "decode.py")
    real = mod.Run.__init__

    def init(self, cell, seed):
        real(self, cell, seed)
        self.hlo = hlo
    monkeypatch.setattr(mod.Run, "__init__", init)
    load = bench.load_module
    monkeypatch.setattr(bench, "load_module",
                        lambda p: mod if p.parent.name == "kinds" else load(p))
    c = tiny_cell("decode", "gelu_pytorch_tanh")
    c = dataclasses.replace(c, per_layer=cells.load(CELL).per_layer)
    peaks = cells.read_json(HERE / "peaks.json")["TPU v5 lite"]
    out = bench.run_cell(c, 2**32 + 9, 0.3, True, cpu(), peaks)
    assert out["correct"], out
    assert set(out["metrics"]) == {m["name"] for m in c.per_layer}
    assert {"attention_roofline.decode", "unscoped_share.decode"} <= \
        set(out["metrics"])
    assert 0 < out["device"]["busy_s"] <= out["device"]["window_s"]
