"""The comparison that decides ``correct`` for a served model.

Once the window has closed and the program's state is freed, a sample of
the requests the window served, drawn from the seed with the longest among
them, is run through the float32 reference of the model's family
(``families/<family>.py``: ``row_logits``) over each prompt with its
served tokens.  Three numbers are computed, each over every served token
of the sample, from ``rel_rms``: at each served row, the root mean square
of the program's logits less the reference's, over the vocabulary, as a
share of the root mean square of the reference's.

- ``logit_rel_rms``: the largest ``rel_rms`` over the rows, the worst
  row.  It sees the program's arithmetic at every position, whether or
  not rounding moved the largest logit.
- ``median_logit_rel_rms``: for each request, the median of its rows'
  ``rel_rms``; the largest over the requests.  Taken request by request,
  not pooled, so that one wrong request (a bad cache slot, say) still
  shows.
- ``max_logit_gap``: how far the served token's reference logit lies
  below the reference's best logit at its row; the largest over the rows.
  The tokens are greedy, so a token that the program altered after its
  logits were made lies far below, while one that rounding moved lies
  within a rounding's width.

A cell's file (``cells/<cell>.json``) limits the numbers that judge it,
and only those are compared and printed: one of the two ``rel_rms``
numbers and ``max_logit_gap``, so that neither the arithmetic nor the
served tokens go unjudged (``unjudged``).  A dense model's cell limits the
worst row.  A routed (mixture-of-experts) model's cell limits the median
row instead: bfloat16 rounding flips which experts a token is routed to,
and the flips multiply, each one changing what later layers and later
positions (through the cache) see.  On Moonlight-16B-A3B's decode at
batch 48 (TPU v5e), 23% of the (token, layer) routings differed from the
float32 reference's; the program's worst row per request read 0.274-0.519
over 12 seeds and the float8 control's 0.472-0.656, so no limit on it
passes the one and fails the other, while the median row per request read
0.026-0.067 against the control's 0.324-0.391 (``testdata/routed_rows.npz``).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Sequence

import numpy as np

from cell import family, rng

NUMBERS = ("logit_rel_rms", "median_logit_rel_rms", "max_logit_gap")
REL_RMS = ("logit_rel_rms", "median_logit_rel_rms")


@dataclass
class Request:
    """One served answer: the full token sequence (prompt, then every
    token fed back), and at each row of ``rows`` the token the program
    served there and the logits (rows, vocab) it served it from."""
    tokens: np.ndarray
    rows: np.ndarray
    served: np.ndarray
    logits: np.ndarray

    def __len__(self):
        return len(self.served)


def sample(requests: Sequence[Request], k: int, seed: int) -> List[Request]:
    """``k`` requests drawn from the seed, always with the one that served
    the most tokens."""
    order = rng(seed, "check").permutation(len(requests))
    longest = max(order, key=lambda i: len(requests[i]))
    rest = [i for i in order if i != longest]
    return [requests[i] for i in [longest] + rest[:max(0, k - 1)]]


def rel_rms(got: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """Per row: RMS of ``got - ref`` over RMS of ``ref``."""
    got, ref = got.astype(np.float64), ref.astype(np.float64)
    return np.sqrt(np.mean((got - ref) ** 2, axis=-1) /
                   np.mean(ref ** 2, axis=-1))


def token_gaps(ref: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """Per row: the best reference logit less that of ``tokens``."""
    return ref.max(axis=-1) - np.take_along_axis(
        ref, tokens[:, None].astype(np.int64), axis=-1)[:, 0]


def numbers(ref: Sequence[np.ndarray], logits: Sequence[np.ndarray],
            served: Sequence[np.ndarray]) -> Dict[str, float]:
    """The compared numbers of logits and served tokens against the
    reference's logits, request by request."""
    rel = [rel_rms(g, r) for g, r in zip(logits, ref)]
    return {"logit_rel_rms": float(max(x.max() for x in rel)),
            "median_logit_rel_rms": float(max(np.median(x) for x in rel)),
            "max_logit_gap": float(max(token_gaps(r, t).max()
                                       for r, t in zip(ref, served)))}


def reference_logits(s: dict, key, requests: Sequence[Request],
                     quant=None) -> List[np.ndarray]:
    return family(s["family"]).row_logits(
        s, key, [(r.tokens, r.rows) for r in requests], quant=quant)


def compare(s: dict, key, requests: Sequence[Request]) -> Dict[str, float]:
    """The program's numbers, every one of ``NUMBERS``."""
    ref = reference_logits(s, key, requests)
    return numbers(ref, [r.logits for r in requests],
                   [r.served for r in requests])


def unjudged(limits: Iterable[str]) -> Optional[str]:
    """Why a cell limited by ``limits`` (the numbers' names) would go
    unjudged, or None: it names a number not computed here, or limits
    neither of the ``rel_rms`` numbers (the arithmetic) or not
    ``max_logit_gap`` (the served tokens)."""
    limits = set(limits)
    if limits - set(NUMBERS):
        return (f"limits {sorted(limits - set(NUMBERS))}, which check.py "
                f"does not compute (it computes {list(NUMBERS)})")
    if not limits & set(REL_RMS):
        return f"limits neither of {list(REL_RMS)}: the logits go unjudged"
    if "max_logit_gap" not in limits:
        return "has no limit of max_logit_gap: the served tokens go unjudged"
    return None


def judge(compared: dict, limits: dict) -> bool:
    """Every compared number finite and at or under its limit."""
    return all(name in compared and np.isfinite(compared[name]) and
               compared[name] <= limits[name] for name in limits)


def control(s: dict, key, requests: Sequence[Request],
            ref: Optional[List[np.ndarray]] = None) -> Dict[str, float]:
    """The same numbers for the control: the reference with float8
    operands (``row_logits(..., quant="fp8")``) put in the program's
    place, its logits and the tokens it puts first at the same rows of the
    same sequences.
    ``ref``: the reference's logits of ``requests``, where already made."""
    if ref is None:
        ref = reference_logits(s, key, requests)
    low = reference_logits(s, key, requests, quant="fp8")
    return numbers(ref, low, [g.argmax(axis=-1) for g in low])
