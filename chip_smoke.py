"""Smoke test of the model path on a TPU: StarCoder2-7B at its published
widths, with depth cut to fit one 16 GB v5e chip, through the serve and
train drivers' own functions.

  python chip_smoke.py             # one chip: serve, then train
  python chip_smoke.py --chips 4   # only the sharded train step on a
                                   # (data=2, model=2) mesh vs one device

Each phase prints one JSON line; the last line is
``{"ok": true, "device": {...}}``.  Without a TPU, or when any phase
fails, the script exits non-zero and never prints that line.  Everything
runs in this one process, which holds the chip.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "starcoder2-7b"
SEED = 0
# One chip: bf16 weights of 24 layers (11.3 GB) plus a 0.4 GB KV cache,
# and 2 layers of training state (bf16 weights, f32 Adam moments: 8.9 GB)
# plus 3.5 GB of gradients and activations (AOT memory_analysis for v5e).
SERVE = dict(layers=24, batch=8, prompt_len=512, gen=32, max_len=1024)
TRAIN = dict(layers=2, batch=8, seq=512, steps=60, lr=1e-4)
# The published model uses LayerNorm with biases and a 4096-token sliding
# window; the repo's dense transformer does not.
DEPARTURES = ["RMSNorm instead of LayerNorm", "no biases",
              "full attention instead of the 4096-token sliding window"]

# Prefill of prompt + first token against the first decode step, as the
# RMS of the logit difference over the RMS of the prefill logits.  bf16
# keeps 8 significant bits (unit roundoff 2**-8 = 3.9e-3); prefill and
# decode round at different points (a 513-row batch against one row, a
# causal mask against a cache with a length mask), and ~6 roundings a layer
# over 24 layers compound to about sqrt(144) * 3.9e-3 = 4.7e-2.  A wrong
# position, cache slot or mask gives errors of order 1.  f32 (the CPU
# tests) has unit roundoff 6e-8, so 1e-4 leaves a wide margin.
LOGIT_TOL = {"bfloat16": 5e-2, "float32": 1e-4}
# Sharded against one-device first-step loss, relative.  Sharding changes
# only the order of f32 sums and where bf16 partial products are rounded;
# the mean over 4096 tokens averages those roundings out.
LOSS_TOL = 1e-3


class PhaseFailed(RuntimeError):
    pass


def emit(obj) -> None:
    print(json.dumps(obj), flush=True)


def peak_bytes(devices):
    """``peak_bytes_in_use`` per device, where the backend reports it."""
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices]


def serve_phase(cfg, *, batch, prompt_len, gen, max_len):
    """Greedy generation through ``launch.serve.generate``, then the check
    that prefill of prompt + first token gives the first decode step's
    logits."""
    import jax
    from repro.configs.base import ShapeConfig
    from repro.launch import serve
    from repro.models import api
    from repro.train.step import make_prefill_step

    params = jax.jit(api.init_params, static_argnums=1)(
        jax.random.PRNGKey(SEED), cfg)
    shape = ShapeConfig("smoke", prompt_len, batch, "prefill")
    prompts = {"tokens": api.make_batch(cfg, shape, SEED)["tokens"]}
    out = serve.generate(cfg, params, prompts, prompt_len=prompt_len,
                         gen=gen, max_len=max_len)
    ext = np.concatenate([prompts["tokens"], out["tokens"][:, :1]], axis=1)
    _, logits = jax.jit(make_prefill_step(cfg))(params, {"tokens": ext})
    want = np.asarray(logits[:, -1], np.float32)
    got = out["first_decode_logits"][:, 0]
    rel_rms = float(np.sqrt(np.mean((got - want) ** 2) /
                            np.mean(want ** 2)))
    tol = LOGIT_TOL[cfg.dtype]
    res = {"phase": "serve", "layers": cfg.n_layers, "batch": batch,
           "prompt_len": prompt_len, "gen": gen, "max_len": max_len,
           "compile_s": out["compile_s"], "prefill_s": out["prefill_s"],
           "median_decode_token_s": statistics.median(out["decode_step_s"]),
           "logits_check": {"rel_rms_err": rel_rms, "tol": tol,
                            "max_abs_err": float(np.max(np.abs(got - want))),
                            "max_abs_logit": float(np.max(np.abs(want)))}}
    if not (np.isfinite(got).all() and np.isfinite(want).all()):
        raise PhaseFailed(f"non-finite logits: {res}")
    if not rel_rms <= tol:
        raise PhaseFailed(f"prefill and decode logits disagree: {res}")
    return res


def train_phase(cfg, mesh, *, batch, seq, steps, lr):
    """``steps`` steps of ``launch.train.train``; every loss finite and the
    loss improved as the driver reports it."""
    from repro.configs.base import ShapeConfig
    from repro.launch import train

    _, out = train.train(cfg, mesh, ShapeConfig("smoke", seq, batch, "train"),
                         steps=steps, lr=lr, log_every=0)
    res = {"phase": "train", "layers": cfg.n_layers, "batch": batch,
           "seq": seq, "steps": steps, "lr": lr, "losses": out["losses"],
           "improved": out["improved"], "compile_s": out["compile_s"],
           "median_step_s": out["median_step_s"]}
    if not np.isfinite(out["losses"]).all():
        raise PhaseFailed(f"non-finite loss: {res}")
    if not out["improved"]:
        raise PhaseFailed(f"loss did not improve: {res}")
    return res


def sharded_phase(cfg, devices, *, batch, seq, lr):
    """The train step on a (data=2, model=2) mesh against one device: the
    parameters must be spread over all four, and the first-step losses
    must agree."""
    import jax
    from repro.configs.base import ShapeConfig
    from repro.launch import train
    from repro.launch.mesh import make_device_mesh

    shape = ShapeConfig("smoke", seq, batch, "train")
    mesh = make_device_mesh(2, 2, devices=devices)
    state, out4 = train.train(cfg, mesh, shape, steps=1, lr=lr, log_every=0)
    held = {d: 0 for d in mesh.devices.flat}
    total = 0
    for leaf in jax.tree.leaves(state["params"]):
        total += leaf.nbytes
        for s in leaf.addressable_shards:
            held[s.device] += s.data.nbytes
    del state
    res = {"phase": "sharded_train", "mesh": {"data": 2, "model": 2},
           "layers": cfg.n_layers, "batch": batch, "seq": seq,
           "param_bytes": total,
           "param_bytes_per_device": [held[d] for d in mesh.devices.flat],
           "peak_bytes_in_use_per_device": peak_bytes(mesh.devices.flat),
           "compile_s": out4["compile_s"], "step_s": out4["step_s"][0]}
    _, out1 = train.train(cfg, make_device_mesh(devices=devices[:1]), shape,
                          steps=1, lr=lr, log_every=0)
    l4, l1 = out4["losses"][0], out1["losses"][0]
    res.update(loss_sharded=l4, loss_one_device=l1,
               loss_rel_diff=abs(l4 - l1) / abs(l1), loss_tol=LOSS_TOL)
    if min(held.values()) == 0 or max(held.values()) > total / 2:
        raise PhaseFailed(f"parameters not spread over 4 devices: {res}")
    if not res["loss_rel_diff"] <= LOSS_TOL:
        raise PhaseFailed(f"sharded and one-device losses disagree: {res}")
    return res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU; JAX found {dev.platform} "
              f"({dev.device_kind})", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} devices", file=sys.stderr)
        return 1

    from repro.launch.common import init_compile_cache, model_config
    cache = init_compile_cache()
    cfg = model_config(ARCH)
    emit({"phase": "config", "model": ARCH, "device_kind": dev.device_kind,
          "devices": len(devices), "compile_cache": cache,
          "widths": {"d_model": cfg.d_model, "n_heads": cfg.n_heads,
                     "n_kv_heads": cfg.n_kv_heads, "head_dim": cfg.hd,
                     "d_ff": cfg.d_ff, "vocab": cfg.vocab, "act": cfg.act,
                     "rope_theta": cfg.rope_theta, "dtype": cfg.dtype},
          "published_layers": cfg.n_layers, "departures": DEPARTURES})
    if args.chips == 4:
        t = TRAIN
        emit(sharded_phase(model_config(ARCH, layers=t["layers"]),
                           devices[:4], batch=t["batch"], seq=t["seq"],
                           lr=t["lr"]))
    else:
        s = SERVE
        res = serve_phase(model_config(ARCH, layers=s["layers"]),
                          batch=s["batch"], prompt_len=s["prompt_len"],
                          gen=s["gen"], max_len=s["max_len"])
        emit({**res, "peak_bytes_in_use": peak_bytes([dev])[0]})

        from repro.launch.mesh import make_device_mesh
        t = TRAIN
        res = train_phase(model_config(ARCH, layers=t["layers"]),
                          make_device_mesh(devices=[dev]), batch=t["batch"],
                          seq=t["seq"], steps=t["steps"], lr=t["lr"])
        emit({**res, "peak_bytes_in_use": peak_bytes([dev])[0]})
    emit({"ok": True, "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(devices)}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
