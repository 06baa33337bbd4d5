"""Child-process side of the sweep runner.

Each grid point runs in its own worker process: the parent sends only
picklable primitives — (module name, sweep name, coordinate dict, tier) —
and the worker *re-imports the spec and rebuilds the point* from scratch.
That keeps the parent/child contract trivially serializable (no pickling
of Programs, backends, or closures) and doubles as a determinism check:
the worker recomputes the point's content-addressed key and the parent
compares it against its own — a mismatch means ``build`` is
nondeterministic and the cache would lie.

``JAX_PLATFORMS=cpu`` is pinned before anything imports jax: simulator-only
entry points pin the CPU because they must never take the chip.
"""

from __future__ import annotations

import os
import sys
import time
import traceback

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def execute_point(spec_module: str, spec_name: str, coords: dict,
                  tier: str) -> dict:
    """Run one (point, tier); returns the result fields for its JSONL row.

    Importable from both parent (``jobs=0`` inline mode) and worker
    processes — the single definition of "run a point" so escalated fine
    results are bit-identical to direct ``simulate()`` calls.
    """
    from . import registry
    spec = registry.resolve(spec_name, module=spec_module)
    key, _prov = spec.fingerprint(coords, tier)

    if spec.run_point is not None:
        t0 = time.perf_counter()
        fields = spec.run_point(coords, tier)
        if not isinstance(fields, dict):
            raise TypeError(f"sweep {spec.name!r}: run_point must return a "
                            f"dict, got {type(fields).__name__}")
        fields.setdefault("sim_wallclock_s", time.perf_counter() - t0)
        fields["key"] = key
        return fields

    from ..core.backends import simulate
    ps = spec.build(coords, tier)
    t0 = time.perf_counter()
    res = simulate(ps.workload, ps.infra, fidelity=tier, config=ps.config,
                   check=ps.check, **ps.run_kw)
    wall = time.perf_counter() - t0
    fields = {
        "key": key,
        # verbatim, not coerced: rows must be bit-identical to a direct
        # simulate() call (time_ns is int on most backends, float on some)
        "time_ns": res.time_ns,
        "events": int(getattr(res, "events", 0)),
        "fidelity": getattr(res, "fidelity", tier),
        "sim_wallclock_s": wall,
    }
    if ps.metrics is not None:
        extra = ps.metrics(res)
        if extra:
            fields.update(extra)
    return fields


def _child_entry(conn, spec_module: str, spec_name: str, coords: dict,
                 tier: str, parent_path: list) -> None:
    """multiprocessing target: run the point, ship the outcome, exit.

    With the ``spawn`` start method the child gets a fresh interpreter, so
    the parent's ``sys.path`` (src layout, benchmarks dir) rides along.
    """
    for p in parent_path:
        if p not in sys.path:
            sys.path.append(p)
    try:
        fields = execute_point(spec_module, spec_name, coords, tier)
        conn.send(("ok", fields))
    except BaseException:
        try:
            conn.send(("error", traceback.format_exc()))
        except BaseException:
            pass
    finally:
        try:
            conn.close()
        except BaseException:
            pass
