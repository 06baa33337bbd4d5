"""chip_smoke.py's phases on the CPU at the tiny widths, and the script's
refusal to run without a TPU."""

import dataclasses
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.configs import get, reduced
from repro.launch import serve
from repro.launch.common import model_config
from repro.launch.mesh import make_device_mesh
from repro.models import api

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_serve_phase_prefill_matches_first_decode_step(smoke):
    res = smoke.serve_phase(reduced(get("starcoder2-7b")), batch=2,
                            prompt_len=16, gen=4, max_len=32)
    check = res["logits_check"]
    assert check["rel_rms_err"] <= check["tol"] == smoke.LOGIT_TOL["float32"]
    assert res["median_decode_token_s"] > 0


def test_serve_phase_catches_a_decode_at_the_wrong_position(smoke,
                                                            monkeypatch):
    def off_by_one(cfg):
        def step(params, cache, token, pos):
            return api.decode_step(params, cfg, token, pos + 1, cache)
        return step

    monkeypatch.setattr(serve, "make_serve_step", off_by_one)
    with pytest.raises(smoke.PhaseFailed, match="disagree"):
        smoke.serve_phase(reduced(get("starcoder2-7b")), batch=2,
                          prompt_len=16, gen=2, max_len=32)


def test_train_phase_losses_finite_and_improving(smoke):
    res = smoke.train_phase(reduced(get("starcoder2-7b")), make_device_mesh(),
                            batch=2, seq=32, steps=10, lr=1e-3)
    assert len(res["losses"]) == 10 and res["improved"]
    assert res["compile_s"] > 0 and res["median_step_s"] > 0


def test_layers_cut_depth_and_no_width():
    full = get("starcoder2-7b")
    cut = model_config("starcoder2-7b", layers=2)
    assert cut.n_layers == 2
    assert {f.name for f in dataclasses.fields(full)
            if getattr(full, f.name) != getattr(cut, f.name)} == {"n_layers"}
    assert model_config("starcoder2-7b") == full
    with pytest.raises(ValueError):
        model_config("starcoder2-7b", layers=0)


@pytest.mark.parametrize("args", [[], ["--chips", "4"]])
def test_chip_smoke_refuses_the_cpu(args):
    env = {"PATH": "/usr/bin:/bin", "HOME": os.environ.get("HOME", "/"),
           "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py"), *args],
                       capture_output=True, text=True, timeout=300, env=env,
                       cwd=ROOT)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "no TPU" in r.stderr
    for line in r.stdout.splitlines():
        assert "ok" not in json.loads(line)


def test_compile_cache_left_to_the_environment(monkeypatch, tmp_path):
    from repro.launch import common
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert common.init_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == prev
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = common.init_compile_cache()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
