"""The dense GQA family (``families/dense_gqa.py``) gives the numbers the
harness gave before the family files were split out of it: the work of
both configurations at their published sizes, and the reference's logits.

The constants were recorded by running the harness's ``work.py`` and
``reference.py`` as they stood before the split, on a CPU, with the same
calls these tests make; the logits are in
``testdata/dense_gqa.row_logits.npz`` with the tokens and rows they were
read at.

  PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip
"""

from __future__ import annotations

import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parents[1] / "src"))

import cell as cells  # noqa: E402
import work  # noqa: E402
from test_bench import TINY  # noqa: E402

# (configuration, batch of its cell) -> what -> position or prompt length
# -> (flops, bytes, attention_flops, attention_bytes)
RECORDED = {
    ("starcoder2-7b", 16): {
        "prefill": {
            1024: (116283018313728.0, 7935623168.0, 2476317081600.0,
                   5368709120.0),
            1480: (169654314074112.0, 8174698496.0, 5171293716480.0,
                   7759462400.0),
            4095: (494666684301312.0, 9545711616.0, 39572754923520.0,
                   21469593600.0)},
        "decode_step": {
            1024: (123216592896.0, 7936819200.0, 4836556800.0,
                   542113792.0),
            1480: (125368270848.0, 8175894528.0, 6988234752.0,
                   781189120.0),
            4095: (137707388928.0, 9546907648.0, 19327352832.0,
                   2152202240.0)}},
    ("phi3-medium-14b", 4): {
        "prefill": {
            1024: (28348516925440.0, 7353794560.0, 429916160000.0,
                   1048576000.0),
            1480: (41248311869440.0, 7447183360.0, 897794048000.0,
                   1515520000.0),
            4095: (116794644234240.0, 7982735360.0, 5151444172800.0,
                   4193280000.0)},
        "decode_step": {
            1024: (29415997440.0, 7354245120.0, 839680000.0, 210739200.0),
            1480: (29789552640.0, 7447633920.0, 1213235200.0, 304128000.0),
            4095: (30253219840.0, 7563550720.0, 1676902400.0,
                   420044800.0)}},
}
KEYS = ("flops", "bytes", "attention_flops", "attention_bytes")
WORK = [(config, batch, what, pos)
        for (config, batch), by_what in RECORDED.items()
        for what, by_pos in by_what.items() for pos in by_pos]


@pytest.mark.parametrize("config,batch,what,pos", WORK)
def test_work_is_as_recorded(config, batch, what, pos):
    s = cells.sizes(cells.read_json(HERE / "configs" / f"{config}.json"))
    assert s["family"] == "dense_gqa"
    got = getattr(work, what)(s, batch, pos)
    assert got == dict(zip(KEYS, RECORDED[(config, batch)][what][pos]))


LOGITS = HERE / "testdata" / "dense_gqa.row_logits.npz"
SEED = 2**33 + 7


@pytest.mark.parametrize("quant", [None, "fp8"])
@pytest.mark.parametrize("act,window", [("gelu_pytorch_tanh", 0),
                                        ("silu", 15)])
def test_reference_logits_are_as_recorded(act, window, quant):
    """The reference and its float8 control, bit for bit."""
    recorded = np.load(LOGITS)
    s = cells.sizes(dict(TINY, hidden_act=act, sliding_window=window))
    (got,) = cells.family(s["family"]).row_logits(
        s, cells.prng_key(SEED), [(recorded["tokens"], recorded["rows"])],
        quant=quant)
    np.testing.assert_array_equal(got, recorded[f"{act}.{window}.{quant}"])
