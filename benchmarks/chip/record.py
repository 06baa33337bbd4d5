"""Record a small trace of one cell's programs for the reduction tests.

  python3 benchmarks/chip/record.py --workload phi3-medium-14b.prefill \
      --layers 2 --seconds 0.5 --out benchmarks/chip/testdata

Runs the cell's set-up at ``--layers`` layers, traces a short window and
writes ``<cell>.xplane.pb`` and ``<cell>.hlo.txt`` (the compiled programs'
HLO, which maps instructions to named scopes).  On a TPU only.
"""

from __future__ import annotations

import argparse
import dataclasses
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parents[1] / "src"))

import cell as cells  # noqa: E402
import run as bench  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--layers", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=0.5)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    cell = cells.load(args.workload)
    cell = dataclasses.replace(cell, config=dict(
        cell.config, num_hidden_layers=args.layers))
    try:
        bench.find_chips(cell)
    except bench.NoChip as e:
        print(f"record.py: {e}", file=sys.stderr)
        return 2
    from repro.launch.common import init_compile_cache
    init_compile_cache()
    kind = bench.load_module(HERE / "kinds" / f"{cell.traffic['kind']}.py")
    run = kind.Run(cell, 0)
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    bench.measure(run, args.seconds, True, bench.CompileCounter(),
                  keep=out / f"{cell.name}.xplane.pb")
    (out / f"{cell.name}.hlo.txt").write_text(
        "\n".join(run.hlo.values()))
    return 0


if __name__ == "__main__":
    sys.exit(main())
