"""The named scopes of the serve programs (``models/layers.SCOPES``) in
their compiled HLO, where the device trace's readers look for them: every
part is there, the decode attention and the decode step's cache write
included, and the benchmark's own list of them is the program's; and the
drivers' compile cache never hands a program another's op_names."""

import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get, reduced
from repro.launch import serve
from repro.models import api, layers

ROOT = Path(__file__).resolve().parents[1]
CHIP = ROOT / "benchmarks" / "chip"
sys.path.append(str(CHIP))

import trace_reduce  # noqa: E402

# the decode step takes its long-cache attention branch from 4096 cached
# positions on (models/layers.py:attention)
MAX_LEN, PROMPT, BATCH = 4096, 16, 2


@pytest.fixture(scope="module")
def programs():
    """Instruction -> op_name of the compiled prefill and decode programs
    of a tiny StarCoder2 (GELU MLP, grouped KV heads, sliding window)."""
    cfg = reduced(get("starcoder2-7b"))
    params = jax.eval_shape(lambda k: api.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    prefill, decode = serve.build(cfg, MAX_LEN)
    batch = {"tokens": jax.ShapeDtypeStruct((BATCH, PROMPT), jnp.int32)}
    cache, _, tok = jax.eval_shape(prefill, params, batch)
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    return {"prefill": trace_reduce.op_names(
                prefill.lower(params, batch).compile().as_text()),
            "decode": trace_reduce.op_names(
                decode.lower(params, cache, tok, pos).compile().as_text())}


def under(names, scope):
    return [i for i, op in names.items() if f"/{scope}/" in f"{op}/"]


def unscoped_reader():
    path = CHIP / "metrics" / "unscoped_share.decode.py"
    spec = importlib.util.spec_from_file_location("unscoped_share", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_benchmark_reads_the_programs_scopes(programs):
    """A scope renamed, added or dropped in the program without the
    reader's list would move ``unscoped_share.decode``."""
    listed = unscoped_reader().SCOPES
    assert sorted(listed) == sorted(layers.SCOPES)
    for scope in listed:
        assert under(programs["decode"], scope), scope
    for scope in set(listed) - {"kv_cache_write"}:
        assert under(programs["prefill"], scope), scope


def test_decode_attention_and_cache_write_are_scoped(programs):
    names = programs["decode"]
    assert under(names, "attention_kernel")
    writes = [names[i] for i in under(names, "kv_cache_write")]
    assert any(op.endswith("dynamic_update_slice") for op in writes)


def test_scopes_do_not_nest(programs):
    """Each instruction lies under at most one part, so the parts' shares
    of a program's device time and the unscoped share add up to all of
    it."""
    for names in programs.values():
        for op in names.values():
            assert sum(f"/{s}/" in f"{op}/" for s in layers.SCOPES) <= 1, op


def test_scope_refuses_a_name_outside_the_table():
    with pytest.raises(ValueError):
        layers.scope("attention")


# one program, under the named scope argv[1], through the drivers' cache;
# prints its HLO and the number of entries in the cache
CACHED = """
import os, sys
import jax, jax.numpy as jnp
from repro.launch.common import init_compile_cache
from repro.models import layers
init_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
def f(x):
    with jax.named_scope(sys.argv[1]):
        return layers.rmsnorm(x, jnp.zeros(8))
print(jax.jit(f).lower(jnp.ones(8)).compile().as_text())
print(len(os.listdir(os.environ["JAX_COMPILATION_CACHE_DIR"])))
"""


def test_compile_cache_keeps_programs_apart_by_their_scopes(tmp_path):
    """Programs that differ only in a named scope, compiled one after the
    other through the persistent cache, each keep their own op_names; the
    same program in a second checkout of the source finds the first's
    entry."""
    cache = tmp_path / "cache"
    checkouts = []
    for name in ("a", "b"):
        shutil.copytree(ROOT / "src" / "repro", tmp_path / name / "src" /
                        "repro", ignore=shutil.ignore_patterns("__pycache__"))
        checkouts.append(tmp_path / name / "src")

    def compile_in(src, scope):
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(src),
                   JAX_COMPILATION_CACHE_DIR=str(cache))
        out = subprocess.run([sys.executable, "-c", CACHED, scope], env=env,
                             capture_output=True, text=True, timeout=300,
                             check=True).stdout.splitlines()
        return "\n".join(out[:-1]), int(out[-1])

    first, n1 = compile_in(checkouts[0], "first_scope")
    second, n2 = compile_in(checkouts[0], "second_scope")
    again, n3 = compile_in(checkouts[1], "second_scope")
    assert "/first_scope/" in first
    assert "/second_scope/" in second and "/first_scope/" not in second
    assert "/second_scope/" in again
    assert n1 < n2 == n3
