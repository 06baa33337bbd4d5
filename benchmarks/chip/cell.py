"""One cell of the benchmark: its entry in ``BENCHMARK.json`` and the files
that entry names, found by name under this directory:

- ``configs/<config>.json``: the model's sizes as the cell runs them;
- ``traffic/<traffic>.json``: the parameters of the traffic mix;
- ``cells/<cell>.json``: how many requests the check samples and the
  limits of the numbers that decide ``correct``.

A new cell adds files and entries; nothing here names a cell.
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from typing import Optional

import numpy as np

HERE = Path(__file__).resolve().parent
REPO = HERE.parents[1]


def read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


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
    e2e = tuple(m for m in bench["end_to_end"] if reports(m, workload, ()))
    names = {m["name"] for m in e2e}
    per_layer = tuple(m for m in bench["per_layer"]
                      if reports(m, workload, names))
    return Cell(name=workload, chips=int(entry["chips"]),
                config=read_json(REPO / config["file"]),
                traffic=read_json(HERE / "traffic" /
                                  f"{entry['traffic']}.json"),
                spec=read_json(HERE / "cells" / f"{workload}.json"),
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
# the model as the program is given it
# ---------------------------------------------------------------------------

ACTIVATIONS = {"gelu_pytorch_tanh": "gelu", "silu": "swiglu"}


def sizes(config: dict) -> dict:
    """The configuration's sizes under plain names; ``window`` is the
    sliding window (0: none)."""
    eps = config.get("rms_norm_eps", config.get("norm_epsilon"))
    d, h = config["hidden_size"], config["num_attention_heads"]
    return {"layers": int(config["num_hidden_layers"]), "d_model": d,
            "heads": h,
            "kv_heads": config["num_key_value_heads"],
            "head_dim": config.get("head_dim", d // h),
            "d_ff": config["intermediate_size"],
            "vocab": config["vocab_size"],
            "act": ACTIVATIONS[config["hidden_act"]],
            "rope_theta": float(config["rope_theta"]), "eps": float(eps),
            "window": int(config.get("sliding_window") or 0),
            "dtype": config["torch_dtype"]}


def arch_config(cell: Cell):
    """The program's ``ArchConfig`` for this cell's model and depth."""
    from repro.configs.base import ArchConfig
    s = sizes(cell.config)
    return ArchConfig(
        name=cell.config["name"], family="dense", n_layers=s["layers"],
        d_model=s["d_model"], n_heads=s["heads"], n_kv_heads=s["kv_heads"],
        d_ff=s["d_ff"], vocab=s["vocab"], head_dim=s["head_dim"],
        act=s["act"], norm_eps=s["eps"], rope_theta=s["rope_theta"],
        window=s["window"], dtype=s["dtype"])
