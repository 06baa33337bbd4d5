"""Run one cell of the on-chip benchmark once.

  python3 benchmarks/chip/run.py --workload starcoder2-7b.decode \
      --seed 1234 --seconds 10 --trace 0

The cell, its model configuration, its traffic mix and its per-layer
metrics are found by name from ``BENCHMARK.json`` (see ``cell.py``).  The
run makes its weights and inputs from ``--seed``, sets up (compiles,
warms up), measures for ``--seconds``, then checks what the window served
against the float32 reference of its model's family.  With ``--trace 0``
it reports the cell's end-to-end metrics; with ``--trace 1`` it traces the
window with the JAX profiler and reports the cell's per-layer metrics.
The last line of standard output is one JSON object; the numbers that
decide ``correct``, those the cell's file limits, are the last lines of
standard error.

It runs only on a TPU whose ``device_kind`` is in ``peaks.json``; anywhere
else it exits 2 and prints no result.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from types import SimpleNamespace  # noqa: E402
from typing import Optional  # noqa: E402

from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parents[1] / "src"))

import cell as cells  # noqa: E402
import check  # noqa: E402
from cell import load_module  # noqa: E402
import trace_reduce  # noqa: E402


class NoChip(RuntimeError):
    pass


def find_chips(cell) -> tuple:
    """The devices the cell runs on and their peaks; raises ``NoChip``
    unless JAX finds enough TPUs of a kind ``peaks.json`` lists."""
    import jax
    devices = jax.devices()
    kind = devices[0].device_kind
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devices[0].platform} ({kind})")
    if len(devices) < cell.chips:
        raise NoChip(f"the cell needs {cell.chips} chips, JAX found "
                     f"{len(devices)}")
    peaks = cells.read_json(HERE / "peaks.json")
    if kind not in peaks:
        raise NoChip(f"device_kind {kind!r} is not in peaks.json")
    return devices[:cell.chips], peaks[kind]


class CompileCounter:
    """Counts the programs JAX compiles (or fetches from its cache) while
    it is on."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.on, self.count = False, 0

        def listen(event, duration, **kw):
            if self.on and event == self.EVENT:
                self.count += 1
        jax.monitoring.register_event_duration_secs_listener(listen)


def measure(run, seconds: float, trace: bool, counter: CompileCounter,
            keep: Optional[Path] = None):
    """The window, traced or not.  Returns (window result, Trace or None);
    ``keep`` is where to copy the trace file."""
    import jax
    counter.on = True
    if not trace:
        out = run.window(seconds)
        counter.on = False
        return out, None
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp, profiler_options=opts)
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            out = run.window(seconds)
        jax.profiler.stop_trace()
        counter.on = False
        path = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)[0]
        if keep is not None:
            shutil.copy(path, keep)
        return out, trace_reduce.load(path)


def memory_peak(devices) -> int:
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)


def reader(name: str) -> Path:
    """The reader of a per-layer metric: ``metrics/<name>.py``, or, for a
    quantity split by the end-to-end metric it moves (``<quantity>.<part>``),
    ``metrics/<quantity>.py`` where the part has no reader of its own."""
    own = HERE / "metrics" / f"{name}.py"
    return own if own.exists() else HERE / "metrics" / \
        f"{name.split('.')[0]}.py"


def per_layer(cell, ctx) -> dict:
    """Each per-layer metric of the cell, read by its ``reader``; a reader
    that finds nothing to read returns None and is left out."""
    out = {}
    for m in cell.per_layer:
        value = load_module(reader(m["name"])).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = cells.load(args.workload)
    try:
        devices, peaks = find_chips(cell)
    except NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices, peaks)
    print(json.dumps(result), flush=True)
    return 0


def run_cell(cell, seed: int, seconds: float, trace: bool, devices,
             peaks: dict) -> dict:
    """Set up, measure and check one run; returns the result line."""
    import jax
    from repro.launch.common import init_compile_cache
    init_compile_cache()
    # the decode step compiles in under a second, which JAX's default
    # threshold would leave out of the persistent cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    counter = CompileCounter()

    kind = load_module(HERE / "kinds" / f"{cell.traffic['kind']}.py")
    run = kind.Run(cell, seed)
    setup_s = time.perf_counter() - T0
    out, tr = measure(run, seconds, trace, counter)
    print(f"compiles in window: {counter.count}", file=sys.stderr)
    peak = memory_peak(devices)
    hlo = run.hlo
    run.release()

    sample = check.sample(run.requests(), cell.spec["check"]["requests"],
                          seed)
    s = cells.sizes(cell.config)
    limits = cell.limits
    compared = {k: v for k, v in
                check.compare(s, cells.prng_key(seed), sample).items()
                if k in limits}
    correct = check.judge(compared, limits) and out["failed"] == 0

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"]}
    if trace:
        used = {f"/device:TPU:{d.id}" for d in devices}
        tr.devices = {k: v for k, v in tr.devices.items() if k in used}
        ctx = SimpleNamespace(cell=cell, sizes=s, peaks=peaks,
                              chips=len(devices), window_s=out["window_s"],
                              work=out["work"], trace=tr, hlo=hlo)
        result["metrics"] = per_layer(cell, ctx)
        device["busy_s"] = trace_reduce.mean_busy_s(tr)
        device["window_s"] = tr.window_s
        first = tr.devices[min(tr.devices)]
        names = {}
        for text in hlo.values():
            names.update(trace_reduce.op_names(text))
        result["breakdown"] = {
            "device_ops": trace_reduce.top_ops(first, names),
            "idle_gaps": trace_reduce.top_gaps(first)}
    else:
        measured = dict(out["e2e"], setup_s=setup_s)
        result["metrics"] = {m["name"]: {"value": measured[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
    result["device"] = device
    result["served_tokens_compared"] = int(sum(len(r) for r in sample))
    result["compiles_in_window"] = counter.count
    result["compared"] = {k: {"value": v, "limit": limits[k]}
                          for k, v in compared.items()}
    for k, v in compared.items():
        ok = math.isfinite(v) and v <= limits[k]
        print(f"compared {k} {v!r} limit {limits[k]!r} "
              f"{'ok' if ok else 'FAILED'}", file=sys.stderr)
    return result


if __name__ == "__main__":
    sys.exit(main())
