"""What the launch entry points share: model selection and the compile
cache.  Nothing here runs at import; each ``main()`` calls it."""

from __future__ import annotations

import argparse
import dataclasses
import os
import re
from pathlib import Path
from typing import Optional

import jax

from ..configs.base import ArchConfig, get, reduced

CHECKOUT = Path(__file__).resolve().parents[3]


def init_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is left to JAX.  Otherwise the
    cache lives at ``<checkout>/.jax_cache``: a fixed path, because a
    directory that moves between runs never hits.

    The cache key includes the programs' metadata.  Without it, two
    programs that differ only in their named scopes share a key, and the
    one compiled second is handed the first's executable, whose HLO
    carries the first's op_names: a device trace read through the scopes
    (``models/layers.py:SCOPES``) would then read the wrong program.  The
    metadata's source paths are made relative to the checkout, so that
    the same program in another checkout still hits."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(f"{CHECKOUT}{os.sep}"))
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def add_model_args(ap: argparse.ArgumentParser, default_arch: str) -> None:
    ap.add_argument("--arch", default=default_arch)
    ap.add_argument("--reduced", action="store_true",
                    help="tiny widths for CPU tests (configs.base.reduced)")
    ap.add_argument("--layers", type=int, default=None,
                    help="keep N layers; cuts depth only, never a width")


def model_config(arch: str, *, tiny: bool = False,
                 layers: Optional[int] = None) -> ArchConfig:
    cfg = get(arch)
    if tiny:
        cfg = reduced(cfg)
    if layers is not None:
        if layers < 1:
            raise ValueError(f"--layers must be >= 1, got {layers}")
        cfg = dataclasses.replace(cfg, n_layers=layers)
    return cfg
