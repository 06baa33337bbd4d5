"""Static transit tables for the reservation-ledger clock kernel.

``build_static_floors(links)`` computes, per link, a lower bound on the
delay beyond *now* before any not-yet-committed traffic can emerge from
the link's feeder cone — valid at *every* future query, so the clock
kernel (:func:`fabric._clock_terms`) can accept a small-margin probe with
one integer compare instead of walking the feeder DAG.

The bound is the shortest path, in minimum-transit edge weights, from any
*entry* link to each link's input over the feeder graph.  A link is an
entry — floor 0 — wherever traffic can appear at its input at an
arbitrary tick:

* it heads a publicly-routed path (``_inj_fed``: an injector can act at
  any event tick),
* it is classic/fair or fed by a classic/fair link (event-driven queue
  advances the ledger cannot see),
* it is *parkable* — not sole-fed, so a chained walk may schedule an
  arrival (and push a reservation) at any tick, or
* it is a reservation-push target: its (sole) feeder can be entered via
  ``enqueue`` — the feeder heads a route or is itself fed by a classic
  link — whose admission pushes the successor's reservation directly.

Reservations and injections *at the link itself* remain dynamic terms of
the clock query; the static floor only summarizes the cone upstream of
the link's input, which is exactly the part the recursion walks.

The relaxation runs vectorized over flat link-id-indexed int64 arrays
(numpy Bellman-Ford to the fixpoint, which is also sound for cyclic
censuses).  Set ``REPRO_LEDGER_JAX=1`` to run the same relaxation as a
jitted JAX loop (consistent with ``repro.kernels``; numerically
identical, useful only for very large topologies).
"""

from __future__ import annotations

import os
from collections import deque
from typing import Iterable, List

import numpy as np

_FAR = 1 << 62


def _is_entry(link) -> bool:
    """Can traffic appear at ``link``'s input at an arbitrary tick?"""
    if link._inj_fed or not link.fast or not link.led:
        return True
    sf = link._sole_feed
    if sf is None or sf is False:
        return True                 # parkable: ambiguous feeder order
    # sole-fed: reservation pushes reach this link only via enqueue() on
    # the sole feeder (route heads and classic handoffs)
    if sf._inj_fed or not sf.fast:
        return True
    return any(not u.fast for u in sf._feeders)


def _edges(links: List):
    """Feeder-graph edge arrays (src link-id, dst link-id, transit), plus
    the set of links with a feeder outside this fabric (no static claim
    can be made about such a cone — their floor pins to 0)."""
    lid = {id(l): i for i, l in enumerate(links)}
    src, dst, w = [], [], []
    foreign_fed = set()
    for i, l in enumerate(links):
        for f in l._feeders:
            j = lid.get(id(f))
            if j is None:
                foreign_fed.add(i)
                continue
            src.append(j)
            dst.append(i)
            w.append(f._xfer_lb if f.fast else 0)
    return (np.asarray(src, dtype=np.int64),
            np.asarray(dst, dtype=np.int64),
            np.asarray(w, dtype=np.int64), foreign_fed)


def _relax_numpy(entry: np.ndarray, src: np.ndarray, dst: np.ndarray,
                 w: np.ndarray) -> np.ndarray:
    floor = np.where(entry, np.int64(0), np.int64(_FAR))
    if src.size == 0:
        return floor
    for _ in range(len(entry)):
        cand = np.full_like(floor, _FAR)
        np.minimum.at(cand, dst, floor[src] + w)
        nxt = np.minimum(floor, cand)
        # entry links stay pinned at 0 (they already are the minimum)
        if np.array_equal(nxt, floor):
            break
        floor = nxt
    return floor


def _relax_jax(entry: np.ndarray, src: np.ndarray, dst: np.ndarray,
               w: np.ndarray) -> np.ndarray:
    import jax
    import jax.numpy as jnp

    @jax.jit
    def run(entry_, src_, dst_, w_):
        floor0 = jnp.where(entry_, jnp.int64(0), jnp.int64(_FAR))

        def body(state):
            floor, _ = state
            cand = jnp.full_like(floor, _FAR).at[dst_].min(floor[src_] + w_)
            nxt = jnp.minimum(floor, cand)
            return nxt, jnp.any(nxt != floor)

        def cond(state):
            return state[1]

        floor, _ = jax.lax.while_loop(cond, body, (floor0, jnp.bool_(True)))
        return floor

    with jax.enable_x64(True):
        return np.asarray(run(jnp.asarray(entry), jnp.asarray(src),
                              jnp.asarray(dst), jnp.asarray(w)))


def build_static_floors(links: List) -> List[int]:
    """Per-link static feeder-cone transit floor (plain ints, same order
    as ``links``).  ``_FAR`` means the cone is provably empty (no feeders
    and no entry) — traffic can only ever reach the link via its dynamic
    terms."""
    n = len(links)
    if n == 0:
        return []
    entry = np.fromiter((_is_entry(l) for l in links), dtype=bool, count=n)
    src, dst, w, foreign_fed = _edges(links)
    if foreign_fed:
        entry[list(foreign_fed)] = True
    relax = _relax_jax if os.environ.get("REPRO_LEDGER_JAX") == "1" \
        else _relax_numpy
    floor = relax(entry, src, dst, w)
    # the per-link result is the cone floor at the link's *input*: min
    # over feeder edges of (feeder floor + feeder transit), independent of
    # the link's own entry status (its own resv/inj terms stay dynamic)
    slb = np.full(n, _FAR, dtype=np.int64)
    if src.size:
        np.minimum.at(slb, dst, floor[src] + w)
    out = []
    for i, l in enumerate(links):
        if i in foreign_fed:
            out.append(0)           # cone not fully visible: no claim
        elif l._feeders:
            out.append(int(min(slb[i], _FAR)))
        else:
            out.append(_FAR)        # empty cone: census-complete vacuity
    return out


def _eff(f) -> int:
    """A feeder's contribution to its successors' cone floors: 0 once
    traffic can enter at it at an arbitrary tick, else its own stored
    cone floor."""
    if _is_entry(f):
        return 0
    lb = f._static_lb
    return lb if lb < _FAR else _FAR


def refresh_static_floors(changed: Iterable) -> None:
    """Incrementally refresh ``_static_lb`` after a census epoch.

    ``changed`` is the set of links whose feeder census mutated since the
    last commit (new feeder appended, sole-feed corridor broken, or head
    marked injection-fed).  Registering routes only ever *adds* ways for
    traffic to reach a link, so the true cone floor is monotonically
    non-increasing across commits — a decrease-only worklist over the
    reverse feeder edges (``Link._deps``) reaches the exact fixpoint
    without re-relaxing the whole fabric.

    Two wrinkles keep it exact rather than merely sound:

    * a mutated link's floor *contribution* can drop to zero without its
      own stored floor changing (entry status is not part of ``slb``), so
      every mutated link force-propagates to its deps once; and
    * a link's entry status also reads its *sole feeder*'s direct state
      (``_inj_fed``, non-fast feeders), so deps sole-fed by a mutated
      link are force-propagated too.  One level suffices: past that, the
      effect is an ordinary floor decrease.

    Where a contribution *increases* (a previously feeder-less interior
    segment head gaining its first feeder), stale downstream floors are
    left as under-estimates — a smaller lower bound is still a lower
    bound, and floors only steer chain-vs-park probe decisions, never
    timing, so soundness and bit-exactness both survive.
    """
    work = deque(changed)
    mutated = {id(l) for l in work}
    forced = set(mutated)
    pending = set(mutated)
    while work:
        l = work.popleft()
        lid = id(l)
        pending.discard(lid)
        feeders = l._feeders
        inf = _FAR
        for f in feeders:
            v = _eff(f) + (f._xfer_lb if f.fast else 0)
            if v < inf:
                inf = v
        if inf > _FAR:
            inf = _FAR
        dec = inf < l._static_lb
        if dec:
            l._static_lb = inf
        if dec or lid in forced:
            forced.discard(lid)
            is_mut = lid in mutated
            for d in l._deps:
                if is_mut and d._sole_feed is l:
                    forced.add(id(d))
                if id(d) not in pending:
                    pending.add(id(d))
                    work.append(d)
