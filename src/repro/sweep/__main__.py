"""CLI: ``python -m repro.sweep NAME [--jobs N] [--tier T] ...``

Runs a registered sweep (``--list`` shows them), streaming JSONL rows to
``results/sweeps/<name>.jsonl`` and caching point results under
``results/sweep_cache`` (override with ``--cache`` or $REPRO_SWEEP_CACHE).
Exit status is 1 if any point finished as timeout/error.
"""

from __future__ import annotations

import argparse
import os
import sys

# simulator-only entry points pin the CPU because they must never take
# the chip
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def main(argv=None) -> int:
    from . import registry
    from .runner import SweepRunner

    ap = argparse.ArgumentParser(
        prog="python -m repro.sweep",
        description="Run a registered DSE sweep (sharded, cached, "
                    "tier-escalating).")
    ap.add_argument("name", nargs="?", help="registered sweep name")
    ap.add_argument("--list", action="store_true",
                    help="list registered sweeps and exit")
    ap.add_argument("--jobs", type=int, default=0, metavar="N",
                    help="worker processes (0 = run inline in this process)")
    ap.add_argument("--tier", choices=("fine", "coarse", "analytic"),
                    help="force one tier (disables escalation)")
    ap.add_argument("--out", help="JSONL output path "
                    "(default results/sweeps/<name>.jsonl)")
    ap.add_argument("--cache", help="cache directory "
                    "(default results/sweep_cache or $REPRO_SWEEP_CACHE)")
    ap.add_argument("--no-cache", action="store_true",
                    help="neither read nor write the point cache")
    ap.add_argument("--fresh", action="store_true",
                    help="ignore cache and any existing JSONL rows")
    ap.add_argument("--timeout", type=float, metavar="S",
                    help="per-point timeout (default: the spec's)")
    ap.add_argument("--retries", type=int, metavar="N",
                    help="crash retries per point (default: the spec's)")
    args = ap.parse_args(argv)

    if args.list:
        for name in registry.sweep_names():
            spec = registry.SWEEPS[name]
            esc = (f"  escalate {spec.escalate.prefilter}->"
                   f"{spec.escalate.final}" if spec.escalate else "")
            print(f"{name}: {len(spec.grid())} points{esc}")
        return 0
    if not args.name:
        ap.error("sweep name required (or --list)")

    try:
        spec = registry.resolve(args.name)
    except KeyError as e:
        print(e.args[0], file=sys.stderr)
        return 2

    runner = SweepRunner(spec, jobs=args.jobs, out=args.out,
                         cache=args.cache,
                         use_cache=not args.no_cache, fresh=args.fresh,
                         timeout_s=args.timeout, retries=args.retries)
    result = runner.run(tier=args.tier)
    c = result.counts()
    print(f"{spec.name}: {len(result.rows)} rows -> {result.out_path}  "
          f"(ok={c['ok']} timeout={c['timeout']} error={c['error']} "
          f"cached={c['cached']})  {result.wall_s:.2f}s")
    return 1 if (c["timeout"] or c["error"]) else 0


if __name__ == "__main__":
    sys.exit(main())
