"""Sharding plans: map every parameter / batch / cache leaf to a
PartitionSpec over the production mesh (DESIGN.md §6).

Plan summary (axes: optional 'pod' (pure DP), 'data' (DP/FSDP), 'model'
(TP/EP/SP)):

* weights — Megatron pattern: column-parallel matrices shard their output
  dim over 'model', row-parallel their input dim; the other large dim
  shards over 'data' (FSDP / ZeRO-3: GSPMD all-gathers per layer inside the
  scan).  Optimizer moments inherit parameter specs => ZeRO sharded states.
* expert weights — grok "tp": per-expert hidden over 'model';
  moonshot "ep": expert dim over 'model' (all-to-all dispatch).
* batch — (pod, data) on the batch dim.
* decode caches — batch over 'data' when divisible, sequence/window over
  'model' (sequence-parallel decode: softmax partials are the only
  cross-device traffic).
"""

from __future__ import annotations

from typing import Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..configs.base import ArchConfig

# parameter-name classification
_COL_PARALLEL = {"wq", "wk", "wv", "wg", "wu", "cm_k", "w_x", "w_y", "w_a",
                 "w_i", "wr", "cm_r", "maa_w1", "dec_w1"}
_ROW_PARALLEL = {"wo", "wd", "cm_v", "w_out"}


def dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _div(n: int, mesh: Mesh, axis) -> bool:
    if isinstance(axis, tuple):
        size = int(np.prod([mesh.shape[a] for a in axis]))
    else:
        size = mesh.shape[axis]
    return n % size == 0


def _maybe(axis, dim: int, mesh: Mesh):
    """Use ``axis`` only if it divides dim."""
    return axis if _div(dim, mesh, axis) else None


def param_spec(path: Tuple[str, ...], shape: Tuple[int, ...],
               cfg: ArchConfig, mesh: Mesh, plan: str = "default") -> P:
    """Spec for one parameter leaf, given its dict path and shape."""
    name = path[-1]
    if plan == "fsdp":
        return _fsdp_spec(path, shape, mesh)
    stacked = path[0] in ("layers", "macro", "tail", "enc", "dec") or \
        (len(path) > 1 and path[0] in ("rec1", "rec2", "attn"))
    lead = (None,) if stacked and len(shape) >= 2 else ()
    body = shape[1:] if lead else shape
    nd = len(body)

    # expert tensors (E, D, F) / (E, F, D)
    if name in ("wg", "wu", "wd") and nd == 3 and cfg.moe:
        E, a, b = body
        if cfg.moe.sharding == "ep":
            return P(*lead, _maybe("model", E, mesh),
                     _maybe("data", a, mesh), None)
        return P(*lead, None, _maybe("data", a, mesh),
                 _maybe("model", b, mesh))
    if name == "router" and nd == 2:
        return P(*lead, _maybe("data", body[0], mesh), None)
    if name == "embed":
        return P(_maybe("model", shape[0], mesh),
                 _maybe("data", shape[1], mesh))
    if name == "lm_head":
        return P(_maybe("data", shape[0], mesh),
                 _maybe("model", shape[1], mesh))
    if name == "connector":
        return P(_maybe("data", shape[0], mesh),
                 _maybe("model", shape[1], mesh))
    if nd == 2:
        a, b = body
        if name in _ROW_PARALLEL:
            return P(*lead, _maybe("model", a, mesh), _maybe("data", b, mesh))
        if name in _COL_PARALLEL:
            return P(*lead, _maybe("data", a, mesh), _maybe("model", b, mesh))
        # misc 2-D (loras, conv weights, bonus): shard the bigger dim on data
        if a >= b:
            return P(*lead, _maybe("data", a, mesh), None)
        return P(*lead, None, _maybe("data", b, mesh))
    if nd == 1 and body[0] >= 4096:
        return P(*lead, _maybe("model", body[0], mesh))
    if nd == 0:
        return P()
    return P(*lead, *([None] * nd))


def _fsdp_spec(path: Tuple[str, ...], shape: Tuple[int, ...],
               mesh: Mesh) -> P:
    """Pure FSDP: shard the largest dimension over the whole (data, model)
    device plane; everything else replicated (ZeRO-3)."""
    if not shape:
        return P()
    big = max(range(len(shape)), key=lambda i: shape[i])
    axes = tuple(a for a in ("data", "model") if a in mesh.axis_names)
    size = int(np.prod([mesh.shape[a] for a in axes]))
    spec = [None] * len(shape)
    if shape[big] % size == 0:
        spec[big] = axes
    elif shape[big] % mesh.shape.get("data", 1) == 0 and "data" in \
            mesh.axis_names:
        spec[big] = "data"
    return P(*spec)


def _tree_specs_with_path(tree, fn):
    def walk(node, path):
        if isinstance(node, dict):
            return {k: walk(v, path + (k,)) for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            t = [walk(v, path + (str(i),)) for i, v in enumerate(node)]
            return type(node)(t)
        return fn(path, node)
    return walk(tree, ())


def params_specs(abstract_params, cfg: ArchConfig, mesh: Mesh,
                 plan: str = "default"):
    return _tree_specs_with_path(
        abstract_params,
        lambda p, leaf: param_spec(p, leaf.shape, cfg, mesh, plan))


def state_specs(abstract_state, cfg: ArchConfig, mesh: Mesh,
                plan: str = "default"):
    """Specs for the full train state: optimizer moments inherit the
    parameter layout (ZeRO); step counter replicated."""
    out = {}
    for key, sub in abstract_state.items():
        if key == "params":
            out[key] = params_specs(sub, cfg, mesh, plan)
        elif key == "opt":
            out[key] = {
                "m": params_specs(sub["m"], cfg, mesh, plan),
                "v": params_specs(sub["v"], cfg, mesh, plan),
                "step": P(),
            }
        elif key == "residual":
            out[key] = params_specs(sub, cfg, mesh, plan)
        else:
            out[key] = _tree_specs_with_path(sub, lambda p, l: P())
    return out


def batch_specs(abstract_batch, cfg: ArchConfig, mesh: Mesh,
                plan: str = "default"):
    """Batch dim over (pod, data); under fsdp additionally sequence over
    'model' (sequence-parallel inputs)."""
    dp = dp_axes(mesh)

    def one(path, leaf):
        b = leaf.shape[0]
        ax = dp if _div(b, mesh, dp) else \
            ("data",) if _div(b, mesh, "data") else None
        rest = [None] * (len(leaf.shape) - 1)
        if plan == "fsdp" and len(leaf.shape) >= 2 and \
                _div(leaf.shape[1], mesh, "model"):
            rest[0] = "model"
        return P(ax, *rest)

    return _tree_specs_with_path(abstract_batch, one)


def cache_specs(abstract_cache, cfg: ArchConfig, mesh: Mesh):
    """Decode caches: batch over 'data' if divisible; the long axis
    (cache sequence / window / state heads) over 'model'."""

    from ..models import api       # models import this package's hints

    def one(path, leaf):
        shape = leaf.shape
        name = path[-1]
        if len(shape) == 0:
            return P()
        spec = [None] * len(shape)
        # leading L (stacked layers) then batch
        bdim = 1 if len(shape) >= 2 else 0
        if _div(shape[bdim], mesh, "data"):
            spec[bdim] = "data"
        if name in ("k", "v", "mk", "mv") and len(shape) == 5:
            seq = api.kv_seq_axis(cfg) if name in ("k", "v") else 2
            if _div(shape[seq], mesh, "model"):
                spec[seq] = "model"        # cache sequence (SP decode)
        elif name == "S" and len(shape) == 5:
            if _div(shape[2], mesh, "model"):
                spec[2] = "model"          # rwkv heads
        elif name in ("h", "conv", "x_tm", "x_cm"):
            if _div(shape[-1], mesh, "model"):
                spec[-1] = "model"         # feature dim
        return P(*spec)

    return _tree_specs_with_path(abstract_cache, one)


def to_named(spec_tree, mesh: Mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def with_sharding(abstract_tree, spec_tree, mesh: Mesh):
    """Attach shardings to ShapeDtypeStructs (for .lower())."""
    return jax.tree.map(
        lambda sds, spec: jax.ShapeDtypeStruct(
            sds.shape, sds.dtype, sharding=NamedSharding(mesh, spec)),
        abstract_tree, spec_tree,
        is_leaf=lambda x: isinstance(x, (jax.ShapeDtypeStruct, P)))
