"""Trace-workload throughput benchmark (tracked PR-over-PR).

Runs the reference *multi-collective* workload — a 2-step training loop on
8 ranks (fwd comp -> ring all-reduce of gradients -> optimizer comp,
chained across steps) — through ``simulate(trace, infra, fidelity=...)``
at all three fidelity tiers, and writes ``results/BENCH_trace.json`` with
one row per tier (time_ns, events, wall) so the workload seam's perf and
determinism are visible across PRs.

Determinism gates: per-tier results are identical across wall trials, the
fine tier stays FIFO-certified, and every tier respects the trace's
dependency order.

Run:  PYTHONPATH=src python benchmarks/trace_throughput.py
"""

from __future__ import annotations

import json
import os
import sys
import time

# simulator-only entry points pin the CPU because they must never take
# the chip (set before anything imports jax)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.core.backends import FineConfig, simulate          # noqa: E402
from repro.core.chakra import ExecutionTrace                  # noqa: E402
from repro.sweep import (SweepSpec, payload,                  # noqa: E402
                         register_suite, register_sweep, run_sweep)

RESULTS = os.path.join(os.path.dirname(__file__), "..", "results")

NRANKS = 8
STEPS = 2
GRAD_BYTES = 1 << 16          # 64 KiB per-rank gradient shard
FWD_FLOPS = 2e8
OPT_FLOPS = 5e7
COLL_WGS = 2

#: wall-clock trials per tier; minimum reported (shared-CPU bench boxes)
WALL_TRIALS = 2


def training_loop_trace(nranks: int = NRANKS, steps: int = STEPS,
                        grad_bytes: int = GRAD_BYTES,
                        fwd_flops: float = FWD_FLOPS,
                        opt_flops: float = OPT_FLOPS) -> ExecutionTrace:
    """The tracked trace: a small data-parallel training loop."""
    et = ExecutionTrace(num_ranks=nranks)
    prev = {r: None for r in range(nranks)}
    for s in range(steps):
        fwd = {r: et.comp(r, f"fwd{s}.r{r}", flops=fwd_flops,
                          bytes_moved=grad_bytes,
                          deps=[prev[r]] if prev[r] else None)
               for r in range(nranks)}
        ar = et.coll(s, "all_reduce", grad_bytes, "ring",
                     deps_by_rank={r: [fwd[r]] for r in range(nranks)})
        prev = {r: et.comp(r, f"opt{s}.r{r}", flops=opt_flops, deps=[ar[r]])
                for r in range(nranks)}
    return et


def run_tier(fidelity: str) -> dict:
    wall = None
    sims = set()
    for _ in range(WALL_TRIALS):
        trace = training_loop_trace()
        cfg = FineConfig(coll_workgroups=COLL_WGS) if fidelity == "fine" \
            else None
        t0 = time.perf_counter()
        r = simulate(trace, fidelity=fidelity, config=cfg)
        trial = time.perf_counter() - t0
        wall = trial if wall is None else min(wall, trial)
        # dependency order must hold at every tier
        for n in trace.nodes:
            for d in n.deps:
                assert r.node_times[n.nid][0] >= r.node_times[d][1] - 1e-9, \
                    f"{fidelity}: node {n.nid} ran before dep {d}"
        sims.add((r.time_ns, r.events, tuple(r.per_rank_done_ns)))
    assert len(sims) == 1, f"{fidelity} trials disagree: {sims}"
    return {
        "fidelity": fidelity,
        "time_ns": r.time_ns,
        "per_rank_done_ns": r.per_rank_done_ns,
        "events": r.events,
        "wall_s": round(wall, 3),
        "wall_trials": WALL_TRIALS,
        "events_per_s": round(r.events / wall) if wall > 0 else None,
        "sim_ns_per_wall_s": round(r.time_ns / wall) if wall > 0 else None,
    }


def _run_point(coords: dict, tier: str) -> dict:
    return run_tier(tier)


SWEEP = register_sweep(SweepSpec(
    name="trace_throughput",
    points=[{}],
    run_point=_run_point,
    tiers=("analytic", "coarse", "fine"),
))


@register_suite("trace_throughput")
def suite() -> dict:
    """Driver-facing run: same tiers and gates via the sweep runner;
    writes an *untracked* report so the committed BENCH_trace baseline
    stays pristine."""
    res = run_sweep(SWEEP, jobs=0, fresh=True, progress=False,
                    out=os.path.join(RESULTS, "sweeps",
                                     "trace_throughput.jsonl"))
    assert not res.failed, res.failed[0]
    rows = {r["tier"]: payload(r) for r in res.rows}
    assert rows["analytic"]["events"] <= rows["coarse"]["events"] \
        < rows["fine"]["events"], "fidelity must buy event detail"
    out = {"tiers": {fid: {k: v for k, v in row.items()
                           if k != "per_rank_done_ns"}
                     for fid, row in rows.items()}}
    os.makedirs(RESULTS, exist_ok=True)
    path = os.path.join(RESULTS, "trace_throughput_suite.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    fine = rows["fine"]
    print(f"trace_throughput,{fine['wall_s'] * 1e6:.0f},"
          f"events={fine['events']}")
    return out


def main() -> None:
    rows = {fid: run_tier(fid) for fid in ("analytic", "coarse", "fine")}
    assert rows["analytic"]["events"] <= rows["coarse"]["events"] \
        < rows["fine"]["events"], "fidelity must buy event detail"
    out = {
        "workload": {"kind": "training_loop_trace", "nranks": NRANKS,
                     "steps": STEPS, "grad_bytes": GRAD_BYTES,
                     "fwd_flops": FWD_FLOPS, "opt_flops": OPT_FLOPS,
                     "coll_workgroups": COLL_WGS, "noc": "default"},
        "tiers": {fid: {k: v for k, v in row.items()
                        if k != "per_rank_done_ns"}
                  for fid, row in rows.items()},
    }
    os.makedirs(RESULTS, exist_ok=True)
    path = os.path.join(RESULTS, "BENCH_trace.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out, indent=1))
    print(f"\nwrote {path}")


if __name__ == "__main__":
    main()
