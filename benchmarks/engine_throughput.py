"""Event-engine throughput benchmark (tracked PR-over-PR).

Runs the reference workload — a fine-grained 8-rank 1 MiB ring all-reduce
on the default NoC — through the three fabric scheduling modes:

* ``classic``  — the seed's two-events-per-hop reference implementation;
* ``exact``    — one event per hop + sound lookahead chaining;
* ``coalesce`` — ``exact`` + train coalescing (the default).

Asserts that the fast paths are bit-exact against each other and FIFO-
certified (``order_violations == 0``), then writes ``results/
BENCH_engine.json`` with events, wall time, events/s and simulated-ns per
wall-second so the perf trajectory is visible across PRs.

Run:  PYTHONPATH=src python benchmarks/engine_throughput.py [--quick]
      [--profile]   (cProfile the default-mode run, print top 25 by cumtime)
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

# simulator-only entry points pin the CPU because they must never take
# the chip (set before anything imports jax)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.core import collectives as C                        # noqa: E402
from repro.core.backends import simulate                       # noqa: E402
from repro.core.cluster import Cluster, NocConfig              # noqa: E402
from repro.sweep import (SweepSpec, payload,                   # noqa: E402
                         register_suite, register_sweep, run_sweep)

RESULTS = os.path.join(os.path.dirname(__file__), "..", "results")

NRANKS = 8
SIZE = 1 << 20          # 1 MiB
NWG = 1
PROTOCOL = "put"

#: the scheduling-mode grid (name -> run_mode arguments); declared as
#: explicit sweep points so the suite and main() drive the same spec
MODE_POINTS = (
    {"name": "classic", "mode": "classic", "bulk": "on", "ledger": "on"},
    {"name": "exact", "mode": "exact", "bulk": "on", "ledger": "on"},
    {"name": "coalesce", "mode": "coalesce", "bulk": "on", "ledger": "on"},
    {"name": "coalesce_bulk_off", "mode": "coalesce", "bulk": "off",
     "ledger": "on"},
    {"name": "coalesce_ledger_off", "mode": "coalesce", "bulk": "on",
     "ledger": "off"},
    {"name": "coalesce_ledger_auto", "mode": "coalesce", "bulk": "on",
     "ledger": "auto"},
    {"name": "exact_ledger_off", "mode": "exact", "bulk": "on",
     "ledger": "off"},
)

#: seed baseline on this workload (measured at the fast-path PR; the seed
#: predates BENCH_engine.json, so its numbers are pinned here once)
SEED_BASELINE = {"events": 9_864_416, "wall_s": 23.32}


#: wall-clock trials per mode; min and median are both reported (the CI
#: boxes run shared-CPU, so single samples swing by 30% — the median is
#: what the smoke test gates on; sim results are identical across trials
#: and asserted so)
WALL_TRIALS = 3


def run_mode(mode: str, size: int, bulk: str = "on", ledger: str = "on"):
    walls = []
    sims = set()
    for _ in range(WALL_TRIALS):
        cluster = Cluster(NRANKS, noc=NocConfig(fabric_mode=mode,
                                                bulk_emission=bulk,
                                                fabric_ledger=ledger))
        t0 = time.perf_counter()
        r = simulate(C.ring_all_reduce(NRANKS, size, NWG, PROTOCOL),
                     fidelity="fine", cluster=cluster, check="off")
        walls.append(time.perf_counter() - t0)
        sims.add((r.time_ns, r.events, cluster.fabric.order_violations))
    assert len(sims) == 1, f"trials disagree on sim results: {sims}"
    wall = min(walls)
    med = statistics.median(walls)
    return {
        "mode": mode,
        "bulk_emission": bulk,
        "fabric_ledger": ledger,
        "time_ns": r.time_ns,
        "per_rank_done_ns": r.per_rank_done_ns,
        "events": r.events,
        "wall_s": round(wall, 3),
        "wall_median_s": round(med, 3),
        "wall_stddev_s": round(statistics.stdev(walls), 3)
        if len(walls) > 1 else 0.0,
        "wall_trials": WALL_TRIALS,
        "events_per_s": round(r.events / wall) if wall > 0 else None,
        "sim_ns_per_wall_s": round(r.time_ns / wall) if wall > 0 else None,
        "order_violations": cluster.fabric.order_violations,
        "ledger": cluster.fabric.ledger_counters(),
    }


def _run_point(coords: dict, tier: str) -> dict:
    return run_mode(coords["mode"], coords["size"], bulk=coords["bulk"],
                    ledger=coords["ledger"])


SWEEP = register_sweep(SweepSpec(
    name="engine_throughput",
    points=[dict(p, size=SIZE) for p in MODE_POINTS],
    run_point=_run_point,
))


def measure(size: int, jobs: int = 0) -> dict:
    """All mode rows at ``size``, via the sweep runner (inline by default
    so wall-clock numbers are unperturbed by process scheduling)."""
    pts = [dict(p, size=size) for p in MODE_POINTS]
    res = run_sweep(SWEEP, jobs=jobs, fresh=True, progress=False,
                    out=os.path.join(RESULTS, "sweeps",
                                     "engine_throughput.jsonl"),
                    points=pts)
    assert not res.failed, res.failed[0]
    return {r["point"]["name"]: payload(r) for r in res.rows}


def check_rows(rows: dict) -> None:
    """Cross-mode correctness gates (bit-exactness + FIFO certification)."""
    exact, coal, classic = rows["exact"], rows["coalesce"], rows["classic"]
    nobulk = rows["coalesce_bulk_off"]
    noled, noled_ex = rows["coalesce_ledger_off"], rows["exact_ledger_off"]
    assert coal["time_ns"] == exact["time_ns"], \
        "coalesced result must be bit-exact vs the un-coalesced path"
    assert coal["per_rank_done_ns"] == exact["per_rank_done_ns"]
    assert coal["order_violations"] == 0, \
        "FIFO monitor must certify the coalesced run"
    assert classic["time_ns"] == exact["time_ns"], \
        "fast path must reproduce the reference schedule"
    assert nobulk["time_ns"] == coal["time_ns"], \
        "bulk wavefront emission must be timing-neutral"
    assert nobulk["per_rank_done_ns"] == coal["per_rank_done_ns"]
    assert nobulk["order_violations"] == 0
    assert noled["time_ns"] == coal["time_ns"] \
        and noled_ex["time_ns"] == coal["time_ns"], \
        "reservation ledgers must be timing-neutral"
    assert noled["per_rank_done_ns"] == coal["per_rank_done_ns"]
    assert noled["order_violations"] == 0 and noled_ex["order_violations"] == 0
    auto = rows["coalesce_ledger_auto"]
    assert auto["time_ns"] == coal["time_ns"], \
        "the adaptive per-link probe policy must be timing-neutral"
    assert auto["per_rank_done_ns"] == coal["per_rank_done_ns"]
    assert auto["order_violations"] == 0
    assert coal["events"] < noled["events"], \
        "ledger chaining must strictly reduce heap events"


@register_suite("engine_throughput")
def suite() -> dict:
    """Quick-size engine run for the benchmark driver: same modes, same
    gates, 1/8th buffer; writes an *untracked* report so the committed
    BENCH_engine baselines stay pristine."""
    rows = measure(SIZE // 8)
    check_rows(rows)
    out = {
        "workload": {"collective": "ring_all_reduce", "nranks": NRANKS,
                     "size_bytes": SIZE // 8, "nworkgroups": NWG,
                     "protocol": PROTOCOL, "noc": "default"},
        "modes": {m: {k: v for k, v in row.items()
                      if k != "per_rank_done_ns"}
                  for m, row in rows.items()},
    }
    os.makedirs(RESULTS, exist_ok=True)
    path = os.path.join(RESULTS, "engine_throughput_suite.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    coal = rows["coalesce"]
    print(f"engine_throughput,{coal['wall_s'] * 1e6:.0f},"
          f"events={coal['events']}")
    return out


def profile_run(size: int) -> None:
    """cProfile one default-mode simulation; print the top 25 by cumtime."""
    import cProfile
    import pstats

    cluster = Cluster(NRANKS, noc=NocConfig())
    wl = C.ring_all_reduce(NRANKS, size, NWG, PROTOCOL)
    prof = cProfile.Profile()
    prof.enable()
    simulate(wl, fidelity="fine", cluster=cluster, check="off")
    prof.disable()
    pstats.Stats(prof).sort_stats("cumulative").print_stats(25)
    print(json.dumps(cluster.fabric.ledger_counters(), indent=1))


def main() -> None:
    size = SIZE if "--quick" not in sys.argv else SIZE // 8
    if "--profile" in sys.argv:
        profile_run(size)
        return
    rows = measure(size)
    check_rows(rows)
    classic, coal = rows["classic"], rows["coalesce"]

    out = {
        "workload": {"collective": "ring_all_reduce", "nranks": NRANKS,
                     "size_bytes": size, "nworkgroups": NWG,
                     "protocol": PROTOCOL, "noc": "default"},
        "modes": {m: {k: v for k, v in row.items()
                      if k != "per_rank_done_ns"}
                  for m, row in rows.items()},
        "event_ratio_vs_classic": round(classic["events"] / coal["events"], 2),
        "wall_speedup_vs_classic": round(classic["wall_s"] / coal["wall_s"], 2),
    }
    if size == SIZE:
        out["seed_baseline"] = SEED_BASELINE
        out["event_ratio_vs_seed"] = round(
            SEED_BASELINE["events"] / coal["events"], 2)
        out["wall_speedup_vs_seed"] = round(
            SEED_BASELINE["wall_s"] / coal["wall_s"], 2)

    os.makedirs(RESULTS, exist_ok=True)
    # --quick runs must not clobber the committed full-size baseline (the
    # bench smoke test compares against it)
    name = "BENCH_engine.json" if size == SIZE else "BENCH_engine_quick.json"
    path = os.path.join(RESULTS, name)
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out, indent=1))
    print(f"\nwrote {path}")


if __name__ == "__main__":
    main()
