"""The numbers that decide ``correct`` (``check.py``) and the limits a
cell's file may set (``cell.load``).

``testdata/routed_rows.npz`` holds readings of Moonlight-16B-A3B's decode
(27 layers, 8 of 64 experts held, batch 48, 2048-token prompts) on a TPU
v5e, each row's ``rel_rms`` as float32:

- ``program``, ``control``: every served row of 6 sampled requests on each
  of 3 seeds (2147500202, 4294967502, 6442451102), 3,918 rows, for the
  program and for the float8 control at the same rows; ``rows``: how many
  rows each of the 18 requests has, in order;
- ``worst_program``, ``worst_control``: the worst row of each of 6 sampled
  requests on each of 12 other seeds (3000000101 to 3000000112).

  PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip
"""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parents[1] / "src"))

import cell as cells  # noqa: E402
import check  # noqa: E402


def requests_with(errors):
    """Reference logits, program logits and served tokens of requests
    whose rows have the given ``rel_rms``, one list of errors a request."""
    gen = np.random.default_rng(0)
    ref = [gen.normal(size=(len(e), 64)) for e in errors]
    got = [r * (1 + np.asarray(e)[:, None]) for r, e in zip(ref, errors)]
    return ref, got, [r.argmax(axis=-1) for r in ref]


GOOD = [[0.01, 0.02, 0.03], [0.05, 0.04, 0.06, 0.01], [0.001]]


@pytest.mark.parametrize("errors,worst,median", [
    # each request's own median (0.02, 0.045, 0.001), the largest of them;
    # the median of all rows pooled would be 0.025
    (GOOD, 0.06, 0.045),
    # one request wrong at every row raises it
    (GOOD + [[0.5, 0.4, 0.6]], 0.6, 0.5),
    # one bad row in a request does not; the worst row sees it
    ([GOOD[0] + [0.9]] + GOOD[1:], 0.9, 0.045)])
def test_median_row_is_per_request(errors, worst, median):
    got = check.numbers(*requests_with(errors))
    assert set(got) == set(check.NUMBERS)
    assert got["logit_rel_rms"] == pytest.approx(worst)
    assert got["median_logit_rel_rms"] == pytest.approx(median)
    assert got["max_logit_gap"] == 0


def test_routed_rows_separate_by_the_median_row_only():
    """On a routed model's chip readings no limit on a row's error passes
    the program and fails the control, and none on a request's worst row;
    the median row of each request separates them."""
    d = np.load(HERE / "testdata" / "routed_rows.npz")
    assert d["program"].max() >= d["control"].min()
    assert d["worst_program"].max() >= d["worst_control"].min()
    cut = np.cumsum(d["rows"])[:-1]
    program = [np.median(x) for x in np.split(d["program"], cut)]
    control = [np.median(x) for x in np.split(d["control"], cut)]
    assert len(program) == 18
    assert max(program) <= 0.07 < 0.32 <= min(control)


BAD_LIMITS = {
    "no_logits": (["max_logit_gap"], "logits go unjudged"),
    "no_tokens": (["median_logit_rel_rms"], "served tokens go unjudged"),
    "unknown": (["logit_rel_rms", "max_logit_gap", "mean_logit_rel_rms"],
                "does not compute"),
}


@pytest.mark.parametrize("names,refused", [
    *BAD_LIMITS.values(),
    (["median_logit_rel_rms", "max_logit_gap"], None),
    (["logit_rel_rms", "median_logit_rel_rms", "max_logit_gap"], None)],
    ids=[*BAD_LIMITS, "median", "both"])
def test_load_refuses_a_cell_left_unjudged(names, refused, tmp_path,
                                            monkeypatch):
    """``cell.load`` stops, before set-up, at a cell whose file limits a
    set of numbers that leaves the logits or the served tokens unjudged,
    or names a number ``check.py`` does not compute."""
    (tmp_path / "cells").mkdir()
    (tmp_path / "traffic").mkdir()
    shutil.copy(HERE / "traffic" / "decode.json", tmp_path / "traffic")
    spec = {"check": {"requests": 6},
            "limits": {n: {"limit": 0.1} for n in names}}
    (tmp_path / "cells" / "x.decode.json").write_text(json.dumps(spec))
    monkeypatch.setattr(cells, "HERE", tmp_path)
    bench = {"configs": [{"name": "x", "file":
                          "benchmarks/chip/configs/starcoder2-7b.json"}],
             "workloads": [{"name": "x.decode", "config": "x",
                            "traffic": "decode", "chips": 1}],
             "end_to_end": [], "per_layer": []}
    if refused is None:
        assert set(cells.load("x.decode", bench).limits) == set(names)
        return
    with pytest.raises(SystemExit, match=refused):
        cells.load("x.decode", bench)
