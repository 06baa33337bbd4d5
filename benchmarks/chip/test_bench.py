"""CPU tests of the benchmark harness at tiny widths: the harness's own
functions drive the program's serving entries, the float32 reference
agrees with them, and a run whose timed path is broken underneath comes
out not correct.

  PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip
"""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parents[1] / "src"))

import cell as cells  # noqa: E402
import check  # noqa: E402
import run as bench  # noqa: E402

TINY = {"name": "tiny", "family": "dense_gqa",
        "hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "vocab_size": 256, "num_hidden_layers": 2, "rope_theta": 10000.0,
        "rms_norm_eps": 1e-5,
        "torch_dtype": "bfloat16"}
TRAFFIC = {
    "decode": {"kind": "decode", "batch": 4, "prompt_len": 16,
               "max_len": 40, "prefill_group": 2, "warmup_steps": 2,
               "ahead_steps": 8},
    "prefill": {"kind": "prefill", "batch": 2, "prompt_len": 24,
                "max_len": 32, "warmup_calls": 1},
}
# the cell of each kind, whose limits the tiny runs are held to
CELLS = {"decode": "starcoder2-7b.decode",
         "prefill": "phi3-medium-14b.prefill"}
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def limits(kind: str) -> dict:
    return cells.read_json(HERE / "cells" / f"{CELLS[kind]}.json")["limits"]


def median_row(own: dict) -> dict:
    """A cell's limits moved from the worst row to each request's median
    row, as a routed model's cell has them: the worst row's limit put on
    the median row."""
    return {"median_logit_rel_rms": own["logit_rel_rms"],
            "max_logit_gap": own["max_logit_gap"]}


def tiny_cell(kind: str, act: str, window: int = 0,
              judged_by: str = "worst_row") -> cells.Cell:
    config = dict(TINY, hidden_act=act, sliding_window=window)
    own = limits(kind)
    spec = {"check": {"requests": 3},
            "limits": own if judged_by == "worst_row" else median_row(own)}
    e2e = ({"name": f"{kind}_tokens_per_s", "unit": "tokens/s"},
           {"name": "setup_s", "unit": "s"})
    return cells.Cell(name=f"tiny.{kind}", chips=1, config=config,
                      traffic=TRAFFIC[kind], spec=spec, end_to_end=e2e,
                      per_layer=())


def cpu():
    import jax
    return jax.devices()[:1]


# the prefill runs a sliding window shorter than its prompts
CASES = [("decode", "gelu_pytorch_tanh", 0), ("prefill", "silu", 15)]


@pytest.mark.parametrize("kind,act,window", CASES)
def test_run_is_correct(kind, act, window):
    out = bench.run_cell(tiny_cell(kind, act, window), 2**33 + 7, 0.5,
                         False, cpu(), PEAKS)
    assert out["correct"], out
    assert out["compiles_in_window"] == 0
    assert out["metrics"][f"{kind}_tokens_per_s"]["value"] > 0
    assert list(out)[-1] == "compared"
    if kind == "decode":     # the window wrapped past max_len at least once
        assert out["served_tokens_compared"] > 3 * 24


def _break(kind: str, fault: str, monkeypatch):
    """Break the timed path underneath, where the tokens are produced:
    ``altered`` serves the next id in place of each token; ``half_batch``
    computes the first half of the batch and serves its logits and tokens
    to the second half as well; ``state_unchanged`` makes the decode step hand
    back the cache it was given, without the new position's key and
    value."""
    if fault == "state_unchanged":
        from repro.launch import serve
        real_step = serve.make_serve_step

        def make_serve_step(cfg):
            step = real_step(cfg)
            return lambda params, cache, token, pos: (
                step(params, cache, token, pos)[0], cache)
        monkeypatch.setattr(serve, "make_serve_step", make_serve_step)
        return
    mod = bench.load_module(HERE / "kinds" / f"{kind}.py")
    real = mod.Run.__init__

    def init(self, cell, seed):
        real(self, cell, seed)
        prog = getattr(self, kind)
        vocab = self.cfg.vocab

        def broken(*args):
            out = list(prog(*args))
            if fault == "altered":
                out[-1] = (out[-1] + 1) % vocab
            else:                   # the logits, then the token
                for i in (1 if kind == "prefill" else 0, -1):
                    half = out[i].shape[0] // 2
                    out[i] = out[i].at[half:].set(out[i][:half])
            return tuple(out)
        setattr(self, kind, broken)
    monkeypatch.setattr(mod.Run, "__init__", init)
    load = bench.load_module
    monkeypatch.setattr(bench, "load_module",
                        lambda p: mod if p.parent.name == "kinds" else load(p))


FAULTS = [("decode", "gelu_pytorch_tanh", "altered"),
          ("decode", "gelu_pytorch_tanh", "half_batch"),
          ("decode", "gelu_pytorch_tanh", "state_unchanged"),
          ("prefill", "silu", "altered"),
          ("prefill", "silu", "half_batch")]


@pytest.mark.parametrize("kind,act,fault", FAULTS)
def test_broken_path_is_not_correct(kind, act, fault, monkeypatch):
    _break(kind, fault, monkeypatch)
    c = tiny_cell(kind, act)
    # every served request is compared, so the broken half is among them
    c = cells.Cell(**{**c.__dict__, "spec": dict(
        c.spec, check={"requests": 10**6})})
    out = bench.run_cell(c, 11, 0.3, False, cpu(), PEAKS)
    assert not out["correct"], out


@pytest.mark.parametrize("judged_by", ["worst_row", "median_row"])
@pytest.mark.parametrize("seed", [5, 2**31 + 3, 77])
@pytest.mark.parametrize("kind,act,window", CASES)
def test_control_is_not_correct(kind, act, window, seed, judged_by):
    """The reference computed with float8 operands, put in the program's
    place at the rows a run samples, is judged not correct under the
    cell's own limits, where the program is judged correct; and so under
    the same limits put on the median row."""
    c = tiny_cell(kind, act, window, judged_by)
    mod = bench.load_module(HERE / "kinds" / f"{kind}.py")
    run = mod.Run(c, seed)
    run.window(0.3)
    run.release()
    reqs = check.sample(run.requests(), c.spec["check"]["requests"], seed)
    s = cells.sizes(c.config)
    key = cells.prng_key(seed)
    program = check.compare(s, key, reqs)
    control = check.control(s, key, reqs)
    assert check.judge(program, c.limits), program
    assert not check.judge(control, c.limits), control


def test_traced_run_reports_per_layer(monkeypatch):
    """A ``--trace 1`` run end to end, with the trace and the HLO recorded
    on a TPU (``testdata/``) read in place of the CPU's: every per-layer
    metric of the prefill cell, the device's busy and window seconds and
    the breakdown come out."""
    import trace_reduce
    recorded = HERE / "testdata" / "phi3-medium-14b.prefill"
    trace = trace_reduce.load(f"{recorded}.xplane.pb")
    hlo = {"jit_prefill": Path(f"{recorded}.hlo.txt").read_text()}
    monkeypatch.setattr(bench.trace_reduce, "load", lambda path: trace)
    mod = bench.load_module(HERE / "kinds" / "prefill.py")
    real = mod.Run.__init__

    def init(self, cell, seed):
        real(self, cell, seed)
        self.hlo = hlo
    monkeypatch.setattr(mod.Run, "__init__", init)
    load = bench.load_module
    monkeypatch.setattr(bench, "load_module",
                        lambda p: mod if p.parent.name == "kinds" else load(p))
    c = tiny_cell("prefill", "silu", 15)
    c = cells.Cell(**{**c.__dict__, "per_layer": cells.load(
        CELLS["prefill"]).per_layer})
    out = bench.run_cell(c, 3, 0.3, True, cpu(), PEAKS)
    assert out["correct"], out
    assert set(out["metrics"]) == {m["name"] for m in c.per_layer}
    assert 0 < out["device"]["busy_s"] <= out["device"]["window_s"]
    assert out["breakdown"]["device_ops"] and out["breakdown"]["idle_gaps"]


# a family file of its own name that is the dense GQA decoder
NEW_FAMILY = """from cell import HERE, load_module

_dense = load_module(HERE / "families" / "dense_gqa.py")
sizes, arch_config, row_logits = (_dense.sizes, _dense.arch_config,
                                  _dense.row_logits)
witness, prefill, decode_step = (_dense.witness, _dense.prefill,
                                 _dense.decode_step)
"""


@pytest.mark.parametrize("judged_by", ["worst_row", "median_row"])
@pytest.mark.parametrize("fault", [None, "altered"])
def test_new_family_from_its_own_file(fault, judged_by, tmp_path,
                                      monkeypatch, capsys):
    """A configuration that names a family whose file lies in a directory
    of its own runs end to end, with the harness looking up families
    there only, and its check holds: correct as it is, not correct with
    the served tokens altered.  It compares and prints exactly the numbers
    its cell's file limits, whether the worst row or, as a routed model's
    cell does, each request's median row."""
    (tmp_path / "tiny_family.py").write_text(NEW_FAMILY)
    monkeypatch.setattr(cells, "FAMILIES", tmp_path)
    with pytest.raises(SystemExit):
        cells.family("dense_gqa")
    if fault:
        _break("decode", fault, monkeypatch)
    c = tiny_cell("decode", "gelu_pytorch_tanh", judged_by=judged_by)
    c = cells.Cell(**{**c.__dict__,
                      "config": dict(c.config, family="tiny_family")})
    assert cells.sizes(c.config)["family"] == "tiny_family"
    capsys.readouterr()
    out = bench.run_cell(c, 2**32 + 5, 0.3, False, cpu(), PEAKS)
    assert out["correct"] is (fault is None), out
    assert set(out["compared"]) == set(c.limits)
    printed = [line.split()[1] for line in capsys.readouterr().err.splitlines()
               if line.startswith("compared ")]
    assert sorted(printed) == sorted(c.limits)


def test_insert_writes_every_leaf_of_a_nested_cache():
    """The decode set-up's insert of a group's prefilled cache reaches
    every leaf of a cache that nests two kinds of state, each with the
    batch on axis 1, and leaves the other requests' rows as they were."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    mod = bench.load_module(HERE / "kinds" / "decode.py")
    gen = np.random.default_rng(3)

    def cache(batch):
        return {"latent": gen.normal(size=(2, batch, 8, 5)),
                "kv": {"k": gen.normal(size=(2, batch, 8, 2, 3)),
                       "v": gen.normal(size=(2, batch, 8, 2, 3))}}
    whole, part = cache(6), cache(2)
    out = jax.jit(mod._insert)(whole, part, jnp.asarray(2, jnp.int32))
    for got, was, new in zip(jax.tree.leaves(out), jax.tree.leaves(whole),
                             jax.tree.leaves(part)):
        want = was.copy()
        want[:, 2:4] = new
        np.testing.assert_array_equal(np.asarray(got), want.astype(got.dtype))
