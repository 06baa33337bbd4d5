"""The Pallas kernels and the serve driver's decode step compiled ahead of
time for a described TPU v5e, at the widths of the models that would run
them.  Nothing runs: the TPU compiler refuses what interpret mode accepts
(tiling, unsupported primitives, VMEM limits), and shows how the compiled
program holds its buffers; each compile takes a few seconds.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""

import dataclasses
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention
from repro.kernels.rg_lru import rg_lru_scan
from repro.kernels.rwkv6_wkv import wkv6
from repro.launch import serve
from repro.launch.common import model_config
from repro.models import api


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", prev)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile()


BF16, F32 = jnp.bfloat16, jnp.float32


@pytest.mark.parametrize("name,fn,shapes", [
    # StarCoder2-7B: 36 query and 4 KV heads of 128
    ("flash_attention prefill T=2048",
     lambda q, k, v: flash_attention(q, k, v, causal=True),
     [((1, 36, 2048, 128), BF16), ((1, 4, 2048, 128), BF16),
      ((1, 4, 2048, 128), BF16)]),
    ("flash_attention decode T=1",
     lambda q, k, v: flash_attention(q, k, v, causal=False, kv_len=1000),
     [((8, 36, 1, 128), BF16), ((8, 4, 1024, 128), BF16),
      ((8, 4, 1024, 128), BF16)]),
    # rwkv6-7b: 64 heads of 64, chunk 32
    ("wkv6", lambda r, k, v, w, u: wkv6(r, k, v, w, u, chunk=32),
     [((1, 64, 2048, 64), BF16)] * 3 + [((1, 64, 2048, 64), F32),
                                        ((64, 64), F32)]),
    # recurrentgemma-9b: RG-LRU width 4096
    ("rg_lru_scan", rg_lru_scan,
     [((2, 2048, 4096), BF16), ((2, 2048, 4096), BF16),
      ((2, 4096), F32)]),
])
def test_kernel_compiles_for_v5e(one_chip, name, fn, shapes):
    compiled = _compile(fn, one_chip, *shapes)
    assert "tpu_custom_call" in compiled.as_text(), name


@pytest.fixture(scope="module")
def decode_program(one_chip):
    """The serve driver's decode step at StarCoder2-7B widths (batch 16, a
    4096 cache), compiled once per depth: ``decode_program(layers)`` gives
    (compiled, params, cache) with the arguments as shapes."""
    done = {}

    def build(layers):
        if layers in done:
            return done[layers]
        cfg = dataclasses.replace(model_config("starcoder2-7b",
                                               layers=layers), window=4096)
        B, S = 16, 4096
        _, decode = serve.build(cfg, S)

        def on_chip(tree):
            return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=one_chip), tree)

        params = on_chip(jax.eval_shape(lambda k: api.init_params(k, cfg),
                                        jax.random.PRNGKey(0)))
        cache = on_chip(jax.eval_shape(lambda: api.init_cache(cfg, B, S)))
        compiled = decode.lower(
            params, cache, jax.ShapeDtypeStruct((B, 1), jnp.int32,
                                                sharding=one_chip),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)).compile()
        done[layers] = compiled, params, cache
        return done[layers]

    return build


def test_decode_writes_the_donated_cache_in_place(decode_program):
    """The decode step, 2 layers deep, hands the donated cache back as its
    output and makes no second one: no whole-cache copy or buffer in the
    entry computation."""
    compiled, params, cache = decode_program(2)
    hlo = compiled.as_text()

    # outputs (logits, cache k, cache v, token); the cache's parameters
    # follow the weights'
    n = len(jax.tree.leaves(params))
    alias = re.search(r"input_output_alias=\{(.*?)\}, entry", hlo).group(1)
    assert f"{{1}}: ({n}, {{}}" in alias, alias
    assert f"{{2}}: ({n + 1}, {{}}" in alias, alias

    stacked = "bf16[{},{},{},{},{}]".format(*cache["k"].shape)
    entry = hlo[hlo.index("\nENTRY"):].split("\n}\n")[0]
    for line in entry.splitlines():
        if f"= {stacked}" not in line:
            continue
        assert " copy(" not in line and "AllocateBuffer" not in line, line
        assert not re.match(r"\s*(ROOT )?%copy", line), line
    assert compiled.memory_analysis().temp_size_in_bytes < 0.2e9


# 16: the depth of the benchmark's decode cell, where the compiler has more
# to place in on-chip memory than at 2
@pytest.mark.parametrize("layers", [2, 16])
def test_decode_scan_reads_each_layer_in_place(decode_program, layers):
    """The layer scan's attention reads each layer's K and V where they lie
    in the stacked cache, or stages them in on-chip memory (memory space
    S(1)): no instruction of the scan body makes a whole layer's K or V in
    HBM, and the program's scratch holds less than one of them."""
    compiled, _, cache = decode_program(layers)
    hlo = compiled.as_text()
    body = re.search(r" while\(.*?body=%([\w.\-]+)", hlo).group(1)
    comp = hlo[hlo.index(f"\n%{body} "):].split("\n}\n")[0]
    slab = math.prod(cache["k"].shape[1:])      # elements of a layer's K
    for line in comp.splitlines()[1:]:
        m = re.match(r"\s*(ROOT )?%\S+ = bf16\[([\d,]+)\]\{([^}]*)\}", line)
        if m and math.prod(map(int, m.group(2).split(","))) == slab:
            assert "S(1)" in m.group(3), line
    assert compiled.memory_analysis().temp_size_in_bytes < slab * 2
