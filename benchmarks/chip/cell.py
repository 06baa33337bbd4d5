"""One cell of the benchmark: its entry in ``BENCHMARK.json`` and the files
that entry names, found by name under this directory:

- ``configs/<config>.json``: the model's sizes as the cell runs them, and
  its family;
- ``families/<family>.py``: what belongs to the model's architecture (see
  ``families/__init__.py``);
- ``traffic/<traffic>.json``: the parameters of the traffic mix;
- ``cells/<cell>.json``: how many requests the check samples and the
  limits of the numbers that decide ``correct``, which are the numbers
  compared (``check.unjudged`` says which sets ``load`` refuses).

A new cell adds files and entries; nothing here names a cell or a
family.
"""

from __future__ import annotations

import functools
import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Optional

import numpy as np

HERE = Path(__file__).resolve().parent
REPO = HERE.parents[1]
FAMILIES = HERE / "families"


def read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    spec: dict
    end_to_end: tuple
    per_layer: tuple

    @property
    def limits(self) -> dict:
        return {k: float(v["limit"]) for k, v in self.spec["limits"].items()}


def reports(metric: dict, cell: str, e2e_names) -> bool:
    """Whether ``cell`` reports ``metric``: listed in its ``workloads``, or,
    without that key, every cell that reports the metric it moves (an
    end-to-end metric without the key is reported by every cell)."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in e2e_names


def load(workload: str, bench: Optional[dict] = None) -> Cell:
    bench = read_json(REPO / "BENCHMARK.json") if bench is None else bench
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json "
                         f"has {[w['name'] for w in bench['workloads']]}")
    config = next(c for c in bench["configs"] if c["name"] == entry["config"])
    config = read_json(REPO / config["file"])
    family(config["family"])        # stops before set-up where it has no file
    spec = read_json(HERE / "cells" / f"{workload}.json")
    import check                    # imports this module
    why = check.unjudged(spec["limits"])
    if why:
        raise SystemExit(f"cells/{workload}.json {why}")
    e2e = tuple(m for m in bench["end_to_end"] if reports(m, workload, ()))
    names = {m["name"] for m in e2e}
    per_layer = tuple(m for m in bench["per_layer"]
                      if reports(m, workload, names))
    return Cell(name=workload, chips=int(entry["chips"]),
                config=config,
                traffic=read_json(HERE / "traffic" /
                                  f"{entry['traffic']}.json"),
                spec=spec,
                end_to_end=e2e, per_layer=per_layer)


# ---------------------------------------------------------------------------
# seeds: every input and every weight of a run comes from --seed
# ---------------------------------------------------------------------------

def rng(seed: int, stream: str) -> np.random.Generator:
    """A numpy generator for one named stream of inputs; any whole seed,
    however large, and a different stream for each name."""
    return np.random.default_rng(
        [int(seed) % (1 << 64), *stream.encode()])


def prng_key(seed: int):
    """The JAX key the weights are made from.  ``PRNGKey`` keeps only the
    low 32 bits of a large seed, so the high bits are folded in."""
    import jax
    s = int(seed) % (1 << 64)
    return jax.random.fold_in(jax.random.PRNGKey(s & 0xFFFFFFFF), s >> 32)


# ---------------------------------------------------------------------------
# the model's family: everything that depends on its architecture
# ---------------------------------------------------------------------------

def family(name: str):
    """The module of the family ``name``, ``families/<name>.py`` (loaded
    once); stops when there is no such file."""
    return _family_module(FAMILIES, name)


@functools.cache
def _family_module(families: Path, name: str):
    path = families / f"{name}.py"
    if not path.is_file():
        raise SystemExit(f"model family {name!r}: no file {path}")
    return load_module(path)


def sizes(config: dict) -> dict:
    """The configuration's sizes, as its family names them."""
    return family(config["family"]).sizes(config)


def arch_config(cell: Cell):
    """The program's ``ArchConfig`` for this cell's model and depth."""
    return family(cell.config["family"]).arch_config(cell.config,
                                                     cell.config["name"])
