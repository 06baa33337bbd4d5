"""Serving tail-latency benchmark (tracked PR-over-PR).

Runs two seeded serving scenarios — Poisson-traffic continuous batching
on a 4-way TP group, and disaggregated prefill/decode with KV-cache p2p
transfers — through ``simulate()`` at all three fidelity tiers, and
writes ``results/BENCH_serving.json`` with per-tier tail-latency rows
(p50/p99/p999, mean, max, goodput).

Determinism gates: every scenario is built and simulated twice from the
same seed and both passes must agree bit-for-bit (arrival streams, trace
shape, per-tier time_ns and every latency percentile).

Run:  PYTHONPATH=src python benchmarks/serving_tail_latency.py
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

from repro.serve import (PoissonArrivals, ServingModel,   # noqa: E402
                         continuous_batching, disaggregated,
                         generate_requests)
from repro.sweep import (SweepSpec, payload,              # noqa: E402
                         register_suite, register_sweep, run_sweep)

RESULTS = os.path.join(os.path.dirname(__file__), "..", "results")

SEED = 20260808
N_REQUESTS = 48
RATE_RPS = 2000.0
PROMPT_TOKENS = (16, 64)
DECODE_TOKENS = (4, 24)

#: toy per-token serving costs — small enough that the fine tier finishes
#: in seconds, large enough that comp, all-reduce and KV transfer all
#: contribute to the critical path
MODEL = ServingModel("bench_toy", flops_per_token=2e6, weight_bytes=1e6,
                     coll_bytes_per_token=4096, kv_bytes_per_token=2048)

TIERS = ("analytic", "coarse", "fine")


def build_scenarios():
    reqs = generate_requests(PoissonArrivals(RATE_RPS), n=N_REQUESTS,
                             seed=SEED, prompt_tokens=PROMPT_TOKENS,
                             decode_tokens=DECODE_TOKENS)
    return {
        "continuous_batching": continuous_batching(MODEL, reqs, tp=4),
        "disaggregated": disaggregated(MODEL, reqs, prefill_ranks=2,
                                       decode_ranks=2),
    }


def run_scenario(scen) -> dict:
    rows = {}
    for fid in TIERS:
        t0 = time.perf_counter()
        r = scen.simulate(fidelity=fid, check="off")
        wall = time.perf_counter() - t0
        s = r.latency
        rows[fid] = {
            "time_ns": r.time_ns,
            "events": r.events,
            "wall_s": round(wall, 3),
            "p50_ns": s.p50_ns,
            "p99_ns": s.p99_ns,
            "p999_ns": s.p999_ns,
            "mean_ns": s.mean_ns,
            "max_ns": s.max_ns,
            "goodput_rps": s.goodput_rps,
        }
    return rows


def _run_point(coords: dict, tier: str) -> dict:
    scen = build_scenarios()[coords["scenario"]]
    r = scen.simulate(fidelity=tier, check="off")
    s = r.latency
    return {"time_ns": r.time_ns, "events": r.events,
            "p50_ns": s.p50_ns, "p99_ns": s.p99_ns, "p999_ns": s.p999_ns,
            "mean_ns": s.mean_ns, "max_ns": s.max_ns,
            "goodput_rps": s.goodput_rps}


SWEEP = register_sweep(SweepSpec(
    name="serving_tail_latency",
    axes={"scenario": ("continuous_batching", "disaggregated")},
    run_point=_run_point,
    tiers=TIERS,
))


@register_suite("serving_tail_latency")
def suite() -> dict:
    """Driver-facing run: scenario x tier through the sweep runner; writes
    an *untracked* report so the committed BENCH_serving baseline stays
    pristine."""
    res = run_sweep(SWEEP, jobs=0, fresh=True, progress=False,
                    out=os.path.join(RESULTS, "sweeps",
                                     "serving_tail_latency.jsonl"))
    assert not res.failed, res.failed[0]
    out: dict = {"scenarios": {}}
    for r in res.rows:
        scen = r["point"]["scenario"]
        out["scenarios"].setdefault(scen, {})[r["tier"]] = payload(r)
    os.makedirs(RESULTS, exist_ok=True)
    path = os.path.join(RESULTS, "serving_tail_latency_suite.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    p99s = {n: tiers["fine"]["p99_ns"]
            for n, tiers in out["scenarios"].items()}
    print("serving_tail_latency,0," + ";".join(
        f"{n}_p99_us={v / 1e3:.1f}" for n, v in sorted(p99s.items())))
    return out


def main() -> None:
    passes = []
    for _ in range(2):                        # same-seed replay gate
        scens = build_scenarios()
        passes.append({name: run_scenario(s) for name, s in scens.items()})
    stable = {n: {f: {k: v for k, v in row.items() if k != "wall_s"}
                  for f, row in tiers.items()}
              for n, tiers in passes[0].items()}
    stable2 = {n: {f: {k: v for k, v in row.items() if k != "wall_s"}
                   for f, row in tiers.items()}
               for n, tiers in passes[1].items()}
    assert stable == stable2, "same-seed serving runs must be bit-identical"

    scens = build_scenarios()
    out = {
        "workload": {
            "kind": "serving_scenarios", "seed": SEED,
            "n_requests": N_REQUESTS, "rate_rps": RATE_RPS,
            "prompt_tokens": list(PROMPT_TOKENS),
            "decode_tokens": list(DECODE_TOKENS),
            "model": {"flops_per_token": MODEL.flops_per_token,
                      "weight_bytes": MODEL.weight_bytes,
                      "coll_bytes_per_token": MODEL.coll_bytes_per_token,
                      "kv_bytes_per_token": MODEL.kv_bytes_per_token},
            "trace_nodes": {n: len(s.trace.nodes)
                            for n, s in scens.items()},
        },
        "scenarios": passes[0],
    }
    os.makedirs(RESULTS, exist_ok=True)
    path = os.path.join(RESULTS, "BENCH_serving.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out, indent=1))
    print(f"\nwrote {path}")


if __name__ == "__main__":
    main()
