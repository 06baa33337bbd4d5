"""End-to-end behaviour tests: training driver, serving driver, and the
framework -> simulator integration."""

import json
import os
import subprocess
import sys



def _run(mod, *args, timeout=400):
    # the drivers run at published widths unless told otherwise: pin the
    # CPU and pass --reduced so these runs stay tiny
    env = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": "/root",
           "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu")}
    return subprocess.run(
        [sys.executable, "-m", mod, *args], capture_output=True, text=True,
        timeout=timeout, env=env, cwd=".")


def test_train_driver_end_to_end(tmp_path):
    r = _run("repro.launch.train", "--arch", "gemma-2b", "--reduced",
             "--steps", "20", "--batch", "2", "--seq", "32",
             "--ckpt-dir", str(tmp_path), "--ckpt-every", "10")
    assert r.returncode == 0, r.stdout + r.stderr
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last["improved"] is True
    # checkpoints rotated and present
    import os
    assert any(d.startswith("step_") for d in os.listdir(tmp_path))


def test_train_driver_resume(tmp_path):
    r1 = _run("repro.launch.train", "--arch", "gemma-2b", "--reduced",
              "--steps", "10", "--batch", "2", "--seq", "32",
              "--ckpt-dir", str(tmp_path), "--ckpt-every", "5")
    assert r1.returncode == 0, r1.stdout + r1.stderr
    # resume for a meaningful number of steps: the driver's exit code
    # asserts the loss improved, and a 3-4 step tail is noise-dominated
    r2 = _run("repro.launch.train", "--arch", "gemma-2b", "--reduced",
              "--steps", "24", "--batch", "2", "--seq", "32",
              "--ckpt-dir", str(tmp_path), "--resume")
    assert r2.returncode == 0, r2.stdout + r2.stderr
    assert "resumed from step 10" in r2.stdout


def test_serve_driver_end_to_end():
    r = _run("repro.launch.serve", "--arch", "gemma-2b", "--reduced",
             "--batch", "2", "--prompt-len", "16", "--gen", "7")
    assert r.returncode == 0, r.stdout + r.stderr
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["decode_tok_per_s"] > 0
    assert len(out["sample_tokens"]) == 8


def test_framework_to_simulator_prediction():
    """A synthetic dry-run record flows through the prediction pipeline."""
    from repro.analysis.predict import predict_cell, simulate_cell_fine
    cell = {
        "arch": "llama3-8b", "shape": "train_4k", "status": "ok",
        "roofline": {"compute_s": 1.3, "memory_s": 2.0,
                     "collective_s": 0.5},
        "collectives": {"all-gather": 2e10, "all-reduce": 3e10,
                        "reduce-scatter": 0.0, "all-to-all": 0.0,
                        "collective-permute": 0.0,
                        "total_wire_bytes": 5e10,
                        "op_counts": {"all-gather": 10, "all-reduce": 5}},
    }
    pred = predict_cell(cell)
    assert pred["step_no_overlap_s"] >= pred["step_full_overlap_s"]
    assert pred["step_full_overlap_s"] >= 2.0  # at least the compute bound
    fine = simulate_cell_fine(cell, ranks=4, layers=2)
    assert fine["sim_time_per_layer_us"] > 0
    assert fine["events"] > 0
