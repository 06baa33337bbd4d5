"""Per-link reservation ledgers (ISSUE 4 tentpole): parity and regressions.

The ledger (``NocConfig.fabric_ledger`` / ``Fabric(ledger=...)``) lets the
fast path chain flights through every interior hop whose channel clock it
beats.  The contract is the same as every other fast path: *identical*
simulated timing — ``time_ns`` and per-rank completion times bit-exact with
the ledger on or off, across scale-up wirings and collectives — certified
by the per-link FIFO monitor (``order_violations == 0``); only the
heap-event count may differ.
"""

import pytest

from repro.core import collectives as C
from repro.core.backends import simulate
from repro.core.cluster import Cluster, NocConfig
from repro.core.engine import Engine
from repro.core.infragraph.blueprints import torus2d_fabric
from repro.core.network.fabric import CONTROL, DATA, Fabric
from repro.core.system import simulate_collective

SMALL = dict(mesh_x=2, mesh_y=2, cus_per_router=2, mem_channels=4,
             io_ports=4)


def run_ledger_pair(prog_fn, nranks, *, topology="switch", mode="coalesce",
                    **sim_kw):
    out = {}
    for led in ("on", "off"):
        cluster = Cluster(nranks, noc=NocConfig(fabric_mode=mode,
                                                fabric_ledger=led, **SMALL),
                          topology=topology)
        r = simulate_collective(prog_fn(), cluster=cluster, **sim_kw)
        out[led] = (r, cluster)
    return out


# ---------------------------------------------------------------------------
# parity: ledger on == ledger off, across wirings x collectives x nworkgroups
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("topology", ["switch", "ring"])
@pytest.mark.parametrize("gen,args,kw", [
    (C.ring_all_reduce, (4, 8192, 1, "put"), {}),
    (C.ring_all_reduce, (4, 8192, 2, "put"), {}),
    (C.ring_all_gather, (4, 8192, 1, "get"), {}),
    (C.ring_all_gather, (4, 4096, 2, "get"), {}),
    (C.direct_reduce_scatter, (4, 8192, 1, "get"), {}),
    (C.direct_reduce_scatter, (4, 4096, 2, "get"), {}),
    (C.halving_doubling_all_reduce, (4, 8192, 2), {}),
])
def test_ledger_parity_cluster_wirings(topology, gen, args, kw):
    res = run_ledger_pair(lambda: gen(*args), args[0], topology=topology,
                          **kw)
    r_on, c_on = res["on"]
    r_off, c_off = res["off"]
    assert r_on.time_ns == r_off.time_ns
    assert r_on.per_rank_done_ns == r_off.per_rank_done_ns
    assert c_on.fabric.order_violations == 0
    assert c_off.fabric.order_violations == 0


def test_ledger_parity_all_to_all_switch():
    res = run_ledger_pair(lambda: C.direct_all_to_all(4, 8192, 2, "put"), 4,
                          unroll=8)
    assert res["on"][0].time_ns == res["off"][0].time_ns
    assert res["on"][1].fabric.order_violations == 0


def test_ledger_tie_break_bit_exact_all_to_all_ring():
    """all_to_all over the ring wiring lands symmetric flights on shared
    transit links at the *same integer-picosecond tick*.  Same-tick service
    order used to be heap insertion order — tie-resolution noise no fast
    path preserved.  With the deterministic route tie-break key
    (``fabric.Route``), every mode resolves ties identically: this is now a
    hard bit-exact guarantee across classic/exact/coalesce × ledger."""
    vals = set()
    for mode in ("classic", "exact", "coalesce"):
        for led in ("on", "off"):
            cluster = Cluster(4, noc=NocConfig(fabric_mode=mode,
                                               fabric_ledger=led, **SMALL),
                              topology="ring")
            r = simulate_collective(C.direct_all_to_all(4, 8192, 2, "put"),
                                    cluster=cluster, unroll=8)
            assert cluster.fabric.order_violations == 0
            vals.add((r.time_ns, tuple(r.per_rank_done_ns)))
    assert len(vals) == 1, f"tie-break must make all modes agree: {vals}"


@pytest.mark.parametrize("gen,args", [
    (C.ring_all_reduce, (4, 8192, 1, "put")),
    (C.ring_all_gather, (4, 8192, 2, "get")),
    (C.halving_doubling_all_reduce, (4, 4096, 2)),
])
def test_ledger_parity_torus_wiring(gen, args):
    """Torus scale-up built from InfraGraph edges (to_cluster) must be
    ledger-parity too — the ledger census is wired at warm_routes time for
    graph-built topologies as well."""
    times = {}
    for led in ("on", "off"):
        noc = NocConfig(fabric_ledger=led, **SMALL)
        r = simulate(gen(*args), torus2d_fabric(2, 2), fidelity="fine",
                     noc=noc)
        times[led] = (r.time_ns, tuple(r.per_rank_done_ns))
    assert times["on"] == times["off"]


def test_ledger_parity_exact_mode():
    res = run_ledger_pair(lambda: C.ring_all_reduce(4, 16384, 1, "put"), 4,
                          mode="exact")
    assert res["on"][0].time_ns == res["off"][0].time_ns
    assert res["on"][1].fabric.order_violations == 0


def test_ledger_reduces_events_on_tracked_shape():
    """The point of the ledger: strictly fewer heap events on the tracked
    workload shape (small-scale replica of the benchmark)."""
    res = run_ledger_pair(lambda: C.ring_all_reduce(4, 32768, 1, "put"), 4)
    assert res["on"][0].events < res["off"][0].events
    assert res["on"][0].time_ns == res["off"][0].time_ns


# ---------------------------------------------------------------------------
# regression: add_link must reset the feeder/ledger census (ISSUE 4 s.1)
# ---------------------------------------------------------------------------

def test_add_link_resets_feeder_census():
    eng = Engine()
    fab = Fabric(eng)
    a, b, c = fab.add_node("a"), fab.add_node("b"), fab.add_node("c")
    fab.add_link(a, b, 1.0, 10.0)
    l_bc = fab.add_link(b, c, 1.0, 10.0)
    route = fab.route(a, c)
    # census formed: b->c is sole-fed by a->b, a->b is a marked route head
    assert l_bc._sole_feed is route[0]
    assert l_bc._feeders == [route[0]]
    assert route[0]._inj_fed
    # topology mutation: a second way into b makes the old conclusion stale
    d = fab.add_node("d")
    l_db = fab.add_link(d, b, 1.0, 10.0)
    assert l_bc._sole_feed is None, \
        "census must reset when the route space is invalidated"
    assert l_bc._feeders == [] and not route[0]._inj_fed
    # re-registered routes rebuild it — now genuinely multi-fed
    r1 = fab.route(a, c)
    r2 = fab.route(d, c)
    assert r1[-1] is r2[-1]
    assert r1[-1]._sole_feed is False
    assert set(r1[-1]._feeders) == {r1[0], l_db}


def test_add_link_after_traffic_stays_certified():
    """Wire, route, run traffic; then mutate and run more — the monitor
    must stay clean because the census was rebuilt, not inherited."""
    eng = Engine()
    fab = Fabric(eng)
    nodes = [fab.add_node(f"n{i}") for i in range(4)]
    for u, v in zip(nodes, nodes[1:]):
        fab.add_bidi(u, v, 1.0, 20.0)
    got = []
    for _ in range(8):
        fab.send(fab.route(nodes[0], nodes[3]), 128, DATA,
                 lambda f: got.append(eng.now_ps))
    eng.run()
    # mutate: shortcut link changes the shortest path and the feeder sets
    fab.add_link(nodes[0], nodes[2], 1.0, 5.0)
    for _ in range(8):
        fab.send(fab.route(nodes[0], nodes[3]), 128, DATA,
                 lambda f: got.append(eng.now_ps))
    eng.run()
    assert len(got) == 16 and got == sorted(got)
    assert fab.order_violations == 0


# ---------------------------------------------------------------------------
# regression: empty-route deliveries (ISSUE 4 s.2)
# ---------------------------------------------------------------------------

def test_send_at_empty_route_stamps_eta():
    """send_at(route=[], eager=False) used to deliver with eta_ps == -1."""
    eng = Engine()
    fab = Fabric(eng)
    fab.add_node("a")
    seen = []
    fab.send_at([], 64, CONTROL, lambda f: seen.append((f.eta_ps, eng.now_ps)),
                at_ps=1234)
    eng.run()
    assert seen == [(1234, 1234)]


def test_send_at_empty_route_eager_runs_inline():
    eng = Engine()
    fab = Fabric(eng)
    fab.add_node("a")
    seen = []
    fab.send_at([], 64, CONTROL, lambda f: seen.append(f.eta_ps),
                at_ps=777, eager=True)
    assert seen == [777], "eager empty-route delivery must not need an event"
    assert eng.pending == 0


def test_send_empty_route_honors_eager():
    eng = Engine()
    fab = Fabric(eng)
    fab.add_node("a")
    seen = []
    fab.send([], 64, CONTROL, lambda f: seen.append(f.eta_ps), eager=True)
    assert seen == [0], "send() used to ignore eager for empty routes"
    assert eng.pending == 0
    # non-eager still goes through the event queue for causality
    fab.send([], 64, CONTROL, lambda f: seen.append(f.eta_ps))
    assert seen == [0] and eng.pending == 1
    eng.run()
    assert seen == [0, 0]


# ---------------------------------------------------------------------------
# channel-clock unit behavior
# ---------------------------------------------------------------------------

def test_chan_clock_threshold_is_monotone_in_need():
    """clock >= n2 must imply clock >= n1 for n1 <= n2 (the threshold query
    is a lower-bound proof, so it is monotone by construction)."""
    from repro.core.mscclpp import lower_program

    cluster = Cluster(2, noc=NocConfig(**SMALL))
    fab = cluster.fabric
    eng = cluster.engine
    for k in lower_program(C.ring_all_reduce(2, 4096, 1, "put")):
        cluster.dispatch(k)
    cluster.seal()
    # step the engine and probe links as traffic flows
    for _ in range(40):
        eng.run(max_events=50)
        if not eng.pending:
            break
        for link in fab.links[:: max(1, len(fab.links) // 7)]:
            if not link.led:
                continue
            base = eng.now_ps
            for delta in (2_000, 20_000, 200_000):
                if fab.clock_ge_ps(link, base + delta):
                    assert fab.clock_ge_ps(link, base + delta // 2), \
                        "threshold query must be monotone in need"
    assert fab.order_violations == 0


def test_jax_relaxation_matches_numpy():
    """``REPRO_LEDGER_JAX=1`` runs the static-floor fixpoint as a jitted
    JAX loop; it must give the NumPy floors exactly, cycles included."""
    import numpy as np
    from repro.core.network import ledger_tables as lt
    rng = np.random.default_rng(0)
    n, m = 40, 120
    entry = rng.random(n) < 0.2
    src, dst = rng.integers(0, n, m), rng.integers(0, n, m)
    w = rng.integers(1, 1000, m)
    np.testing.assert_array_equal(lt._relax_jax(entry, src, dst, w),
                                  lt._relax_numpy(entry, src, dst, w))
