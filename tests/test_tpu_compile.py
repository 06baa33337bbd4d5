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


def test_decode_writes_the_donated_cache_in_place(one_chip):
    """The decode step at StarCoder2-7B widths (2 layers, batch 16, a 4096
    cache) hands the donated cache back as its output and makes no second
    one: no whole-cache copy or buffer in the entry computation."""
    cfg = dataclasses.replace(model_config("starcoder2-7b", layers=2),
                              window=4096)
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
