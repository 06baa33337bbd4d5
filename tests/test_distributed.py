"""Distributed layer: sharding plans, checkpoint/restore + elastic remesh,
data pipeline, gradient compression."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ShapeConfig, get, reduced
from repro.data.pipeline import PipelineConfig, TokenPipeline
from repro.distributed import hints
from repro.distributed import sharding as shard
from repro.distributed.checkpoint import CheckpointManager
from repro.launch.mesh import auto_mesh
from repro.models import api
from repro.optim import adamw
from repro.optim.adamw import AdamWConfig
from repro.train.step import init_train_state, make_train_step


def test_param_specs_cover_full_llama_tree():
    cfg = get("llama3-8b")
    mesh = auto_mesh((1, 1), ("data", "model"))
    abs_params = jax.eval_shape(
        lambda k: api.init_params(k, cfg), jax.random.PRNGKey(0))
    specs = shard.params_specs(abs_params, cfg, mesh)
    flat_p = jax.tree.leaves(abs_params)
    flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_p) == len(flat_s)
    for p, s in zip(flat_p, flat_s):
        assert len(s) <= len(p.shape)


def test_param_specs_divisible_on_production_mesh_shapes():
    """Every spec'd axis must divide the dimension it shards (16x16)."""
    for arch in ("llama3-8b", "grok-1-314b", "moonshot-v1-16b-a3b",
                 "rwkv6-7b", "recurrentgemma-9b", "gemma-2b"):
        cfg = get(arch)
        # emulate the 16x16 divisibility question without 256 devices:
        # param_spec uses _div against the REAL mesh, so build specs with a
        # fake mesh object exposing shape 16/16
        class FakeMesh:
            shape = {"data": 16, "model": 16}
            axis_names = ("data", "model")
        abs_params = jax.eval_shape(
            lambda k: api.init_params(k, cfg), jax.random.PRNGKey(0))

        def check(path, leaf):
            spec = shard.param_spec(
                tuple(p for p in path), leaf.shape, cfg, FakeMesh())
            for dim, ax in zip(leaf.shape[len(leaf.shape) - len(spec):]
                               if len(spec) < len(leaf.shape) else leaf.shape,
                               spec):
                pass
            # re-walk: spec aligns right-to-left with shape when stacked
            offset = len(leaf.shape) - len(spec)
            for i, ax in enumerate(spec):
                if ax is None:
                    continue
                axes = ax if isinstance(ax, tuple) else (ax,)
                size = 1
                for a in axes:
                    size *= FakeMesh.shape[a]
                dim = leaf.shape[offset + i]
                assert dim % size == 0, \
                    f"{arch} {path}: dim {dim} not divisible by {size}"
            return leaf

        shard._tree_specs_with_path(abs_params, check)


def test_constrain_noop_without_mesh():
    x = jnp.ones((4, 8))
    assert hints.constrain(x, "dp", "model") is x


def test_constrain_drops_indivisible_axes():
    mesh = auto_mesh((1, 1), ("data", "model"))
    with hints.use_mesh(mesh):
        x = jnp.ones((3, 5))
        y = hints.constrain(x, "data", "model")  # 3 % 1 == 0 -> kept
        assert y.shape == x.shape


def test_sharded_train_step_runs_on_cpu_mesh():
    cfg = reduced(get("llama3-8b"))
    mesh = auto_mesh((1, 1), ("data", "model"))
    shape = ShapeConfig("t", 32, 2, "train")
    with hints.use_mesh(mesh):
        state = init_train_state(jax.random.PRNGKey(0), cfg)
        shard.state_specs(jax.eval_shape(lambda: state), cfg, mesh)
        step = jax.jit(make_train_step(cfg, AdamWConfig()))
        batch = {k: jnp.asarray(v)
                 for k, v in api.make_batch(cfg, shape).items()}
        with mesh:
            state, m = step(state, batch)
    assert np.isfinite(float(m["loss"]))


# ------------------------------------------------------------- checkpoint
def test_checkpoint_save_restore_round_trip(tmp_path):
    cm = CheckpointManager(str(tmp_path), keep=2)
    tree = {"a": {"w": jnp.arange(6.0).reshape(2, 3)},
            "step": jnp.asarray(7)}
    cm.save(10, tree)
    step, back = cm.restore()
    assert step == 10
    np.testing.assert_array_equal(np.asarray(back["a"]["w"]),
                                  np.arange(6.0).reshape(2, 3))


def test_checkpoint_keep_n_rotation(tmp_path):
    cm = CheckpointManager(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        cm.save(s, {"x": jnp.ones((2,)) * s})
    assert cm.all_steps() == [3, 4]


def test_checkpoint_partial_write_not_visible(tmp_path):
    cm = CheckpointManager(str(tmp_path), keep=3)
    cm.save(5, {"x": jnp.ones((4,))})
    # simulate a crashed writer: leftover tmp dir must not surface
    os.makedirs(os.path.join(str(tmp_path), ".tmp_crashed"), exist_ok=True)
    assert cm.all_steps() == [5]


def test_elastic_restore_resharding(tmp_path):
    """Checkpoint under one sharding, restore under another (elastic)."""
    cm = CheckpointManager(str(tmp_path))
    x = jnp.arange(16.0).reshape(4, 4)
    cm.save(1, {"w": x})
    mesh2 = auto_mesh((1, 1), ("data", "model"))
    from jax.sharding import NamedSharding
    sh = {"w": NamedSharding(mesh2, P("data", None))}
    _, tree = cm.restore(shardings=sh)
    np.testing.assert_array_equal(np.asarray(tree["w"]), np.asarray(x))
    assert tree["w"].sharding == sh["w"]


def test_checkpoint_resume_training_continues(tmp_path):
    cfg = reduced(get("gemma-2b"))
    shape = ShapeConfig("t", 32, 2, "train")
    step = jax.jit(make_train_step(cfg, AdamWConfig(lr=1e-3)))
    state = init_train_state(jax.random.PRNGKey(0), cfg)
    batch = {k: jnp.asarray(v) for k, v in api.make_batch(cfg, shape).items()}
    state, _ = step(state, batch)
    cm = CheckpointManager(str(tmp_path))
    cm.save(1, state)
    _, state2 = cm.restore()
    s1, m1 = step(state, batch)
    s2, m2 = step(state2, batch)
    assert float(m1["loss"]) == pytest.approx(float(m2["loss"]), rel=1e-5)


# ---------------------------------------------------------------- pipeline
def test_pipeline_deterministic_and_restart_safe():
    cfg = reduced(get("llama3-8b"))
    shape = ShapeConfig("t", 16, 4, "train")
    p1 = TokenPipeline(cfg, shape, seed=3)
    b5 = p1.batch_at(5)
    p2 = TokenPipeline(cfg, shape, seed=3)
    np.testing.assert_array_equal(b5["tokens"], p2.batch_at(5)["tokens"])
    # host sharding slices the batch
    ph = TokenPipeline(cfg, shape, PipelineConfig(host_count=2, host_index=1),
                       seed=3)
    np.testing.assert_array_equal(ph.batch_at(5)["tokens"],
                                  b5["tokens"][2:])


def test_pipeline_prefetch_delivers_in_order():
    cfg = reduced(get("gemma-2b"))
    shape = ShapeConfig("t", 16, 2, "train")
    p = TokenPipeline(cfg, shape, PipelineConfig(prefetch=2), seed=1)
    p.start()
    got = [p.get()["tokens"] for _ in range(3)]
    p.stop()
    for i, g in enumerate(got):
        np.testing.assert_array_equal(g, p.batch_at(i)["tokens"])


# ------------------------------------------------------------ compression
def test_gradient_compression_error_feedback_converges():
    """int8+EF gradient compression must still train (loss decreases)."""
    cfg = reduced(get("gemma-2b"))
    shape = ShapeConfig("t", 32, 2, "train")
    step = jax.jit(make_train_step(cfg, AdamWConfig(lr=2e-3),
                                   compress_grads=True))
    state = init_train_state(jax.random.PRNGKey(0), cfg,
                             compress_grads=True)
    batch = {k: jnp.asarray(v) for k, v in api.make_batch(cfg, shape).items()}
    losses = []
    for _ in range(8):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.1


def test_compress_int8_bounded_error():
    g = jnp.asarray(np.random.default_rng(0).normal(size=(64, 64)))
    q, s = adamw.compress_int8(g)
    back = adamw.decompress_int8(q, s)
    assert q.dtype == jnp.int8
    assert float(jnp.max(jnp.abs(back - g))) <= float(s) * 0.5 + 1e-6
