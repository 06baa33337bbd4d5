"""Readings that set a cell's limits: the program's compared numbers on many
seeds (the lower readings) and the control's on the same requests (the
upper readings), in one process so that set-up compiles once.

  python3 benchmarks/chip/control.py --workload starcoder2-7b.decode \
      --seeds 1,2,3 --seconds 10

For each seed it runs the cell's own set-up and window at the cell's own
load, samples the requests as a run does, and prints one JSON line with
every number ``check.py`` computes (``logit_rel_rms``,
``median_logit_rel_rms``, ``max_logit_gap``), whichever of them the
cell's file limits, for the program (``program``), for the control
(``control``: the reference computed with float8 operands put in the
program's place) and, for ``max_logit_gap``, for each served token
altered to the next id (``altered``), so that a cell's limits can be set
from them.  The benchmark's runs never run this.  With ``--witness`` it
also compares the program's weights with the ones the reference makes
from the same seed (the family's ``witness``).
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parents[1] / "src"))

import cell as cells  # noqa: E402
import check  # noqa: E402
import run as bench  # noqa: E402


def readings(s: dict, key, reqs) -> dict:
    """The program's, the control's and the altered tokens' numbers."""
    t0 = time.perf_counter()
    ref = check.reference_logits(s, key, reqs)
    program = check.numbers(ref, [r.logits for r in reqs],
                            [r.served for r in reqs])
    t1 = time.perf_counter()
    control = check.control(s, key, reqs, ref=ref)
    altered = check.numbers(ref, [r.logits for r in reqs],
                            [(r.served + 1) % s["vocab"] for r in reqs])
    return {"program": program, "control": control,
            "altered": {"max_logit_gap": altered["max_logit_gap"]},
            "reference_s": t1 - t0}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--witness", action="store_true")
    args = ap.parse_args(argv)
    cell = cells.load(args.workload)
    try:
        bench.find_chips(cell)
    except bench.NoChip as e:
        print(f"control.py: {e}", file=sys.stderr)
        return 2
    from repro.launch.common import init_compile_cache
    import jax
    init_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    kind = bench.load_module(HERE / "kinds" / f"{cell.traffic['kind']}.py")
    s = cells.sizes(cell.config)
    for seed in (int(x) for x in args.seeds.split(",")):
        t0 = time.perf_counter()
        key = cells.prng_key(seed)
        run = kind.Run(cell, seed)
        w = (cells.family(s["family"]).witness(run.params, key, s)
             if args.witness else None)
        out = run.window(args.seconds)
        run.release()
        reqs = check.sample(run.requests(), cell.spec["check"]["requests"],
                            seed)
        del run
        print(json.dumps({
            "seed": seed, **readings(s, key, reqs),
            "tokens": int(sum(len(r) for r in reqs)),
            "witness_max_abs": w, "e2e": out["e2e"],
            "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
