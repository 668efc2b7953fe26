"""Sweep-service subsystem (``repro/sweep``, ISSUE 5).

Conformance: every verdict the service streams must be exactly what a
direct ``resimulate_batch`` — and therefore a from-scratch ``simulate`` —
reports, for any block split, shard count/mode, arrival order or cache
state.  Scheduler edge cases (cancellation mid-sweep, priority-lane
ordering and non-starvation, cross-request coalescing, cache eviction)
are driven deterministically through manual-mode ``SweepService.step()``
— no sleeps, no real multi-host.  Process-pool sharding runs under the
``service`` marker (tier-1 keeps the threaded fallback).
"""
import pickle
import threading
import time

import numpy as np
import pytest

from repro.core import (program_fingerprint, resimulate_batch, simulate)
from repro.core import dse as dse_mod
from repro.core.dse import _batch_arrays, solve_block_status
from repro.core.incremental import compile_graph
from repro.designs.paper import fig4_ex5
from repro.designs.typea import producer_consumer, skynet_like
from repro.sweep import (BULK, CANCELLED, INTERACTIVE, GraphCache,
                         SweepService, grid_search, pareto_front,
                         random_search, successive_halving)


def _manual_service(**kw):
    kw.setdefault("autostart", False)
    return SweepService(**kw)


def _assert_outcome_equal(out, ref, note=""):
    assert (out.ok == ref.ok).all(), note
    assert (out.status == ref.status).all(), note
    assert (out.cycles == ref.cycles).all(), note
    for k in range(len(ref.ok)):
        if ref.results[k] is not None:
            assert out.results[k].outputs == ref.results[k].outputs, (note, k)
            assert out.results[k].deadlock == ref.results[k].deadlock, \
                (note, k)


# ------------------------------------------------------------- conformance
def test_service_matches_resimulate_batch_mixed_statuses():
    """fig4_ex5 mixes reuse, constraint flips and fallback re-sims; the
    served sweep must agree row-for-row under a tiny block size."""
    base = simulate(fig4_ex5())
    D = np.array([(2, 100), (100, 2), (2, 2), (1, 1), (64, 64), (2, 100)])
    ref = resimulate_batch(base, D)
    with _manual_service(block=2, shards=2) as svc:
        out = svc.sweep(fig4_ex5(), D)
    _assert_outcome_equal(out, ref, "fig4_ex5")
    assert not out.ok[1] and "constraint" in out.reasons[1]


def test_service_reports_deadlock_rows():
    """Configs that starve a committed blocking write must deadlock with
    the fallback reproducing the full report (as resimulate_batch does)."""
    builder = lambda: producer_consumer(n=32, depth=4)
    base = simulate(builder())
    D = np.array([[8], [1], [2]])
    ref = resimulate_batch(base, D)
    with _manual_service(block=1) as svc:
        out = svc.sweep(builder(), D)
    _assert_outcome_equal(out, ref, "producer_consumer")


def test_service_block_split_and_arrival_order_invariant():
    builder = lambda: skynet_like(items=48, depth=6)
    base = simulate(builder())
    rng = np.random.default_rng(7)
    D = rng.integers(1, 13, size=(24, len(base.depths)))
    ref = resimulate_batch(base, D)
    for block, shards in ((1, 1), (5, 3), (64, 1)):
        with _manual_service(block=block, shards=shards) as svc:
            out = svc.sweep(builder(), D)
            _assert_outcome_equal(out, ref, f"block={block}")
            # warm cache + reversed arrival order: still bit-identical
            out2 = svc.sweep(builder(), D[::-1])
            assert (out2.cycles == ref.cycles[::-1]).all()
            assert (out2.status == ref.status[::-1]).all()


def test_shard_modes_bit_identical():
    builder = lambda: skynet_like(items=48, depth=6)
    rng = np.random.default_rng(3)
    D = rng.integers(2, 13, size=(32, len(builder().fifos)))
    outs = []
    for shards in (1, 4):
        with _manual_service(block=16, shards=shards,
                             mode="thread") as svc:
            outs.append(svc.sweep(builder(), D))
    assert (outs[0].cycles == outs[1].cycles).all()
    assert (outs[0].status == outs[1].status).all()


def test_service_jax_backend_lane_bit_identical():
    """backend="jax" shard lane: the sparse Pallas solver serves sweeps
    with verdicts bit-identical to the numpy lane (deadlock rows, too)."""
    pytest.importorskip("jax")
    builder = lambda: skynet_like(items=32, depth=5)
    base = simulate(builder())
    rng = np.random.default_rng(11)
    D = rng.integers(1, 10, size=(24, len(base.depths)))
    ref = resimulate_batch(base, D)
    with _manual_service(block=8, shards=2, backend="jax") as svc:
        out = svc.sweep(builder(), D)
    _assert_outcome_equal(out, ref, "jax lane")
    assert (out.violated == ref.violated).all()


@pytest.mark.service
def test_process_shard_mode_bit_identical():
    """mode="process": workers hold their own unpickled CompiledGraph."""
    builder = lambda: skynet_like(items=48, depth=6)
    rng = np.random.default_rng(3)
    D = rng.integers(2, 13, size=(32, len(builder().fifos)))
    with _manual_service(block=16, shards=1) as svc:
        ref = svc.sweep(builder(), D)
    with _manual_service(block=16, shards=2, mode="process") as svc:
        out = svc.sweep(builder(), D)
    assert (out.cycles == ref.cycles).all()
    assert (out.status == ref.status).all()


def test_streaming_is_per_config():
    """stream() yields one ConfigResult per row (indices complete), usable
    before the assembled outcome."""
    builder = lambda: producer_consumer(n=32, depth=2)
    D = np.array([[d] for d in (1, 2, 4, 8, 16)])
    with SweepService(block=2) as svc:
        seen = {}
        for cfg in svc.stream(builder(), D):
            seen[cfg.index] = cfg
        assert sorted(seen) == list(range(len(D)))
        for k, cfg in seen.items():
            full = simulate(builder(), depths=(int(D[k, 0]),))
            assert cfg.cycles == full.cycles


# ---------------------------------------------------------------- scheduler
def test_cancellation_mid_sweep():
    """Cancel after one block: delivered rows stay exact, the stream
    terminates, undelivered rows surface as CANCELLED."""
    builder = lambda: skynet_like(items=48, depth=6)
    base = simulate(builder())
    rng = np.random.default_rng(0)
    D = rng.integers(4, 13, size=(30, len(base.depths)))
    ref = resimulate_batch(base, D)
    with _manual_service(block=10) as svc:
        h = svc.submit(builder(), D, priority=BULK)
        assert svc.step()                    # block 1: rows 0..9
        h.cancel()
        svc.step()                           # reaps + finalizes
        out = h.result()
    assert (out.cycles[:10] == ref.cycles[:10]).all()
    assert (out.status[:10] == ref.status[:10]).all()
    assert (out.status[10:] == CANCELLED).all()
    assert (out.cycles[10:] == -1).all()
    assert h.done and h.cancelled
    st = svc.scheduler.stats()
    assert st["cancelled_rows"] == 20 and st["rows"] == 10


def test_priority_lane_preempts_bulk():
    """An interactive query submitted behind a long bulk sweep is served
    in the very next block."""
    bulk_b = lambda: skynet_like(items=48, depth=6)
    inter_b = lambda: producer_consumer(n=32, depth=2)
    Db = np.full((40, len(bulk_b().fifos)), 8, dtype=np.int64)
    Db += np.arange(40)[:, None] % 5         # distinct rows
    with _manual_service(block=8) as svc:
        hb = svc.submit(bulk_b(), Db, priority=BULK)
        svc.step()                           # bulk gets one block first
        hi = svc.submit(inter_b(), np.array([[2], [4]]))
        assert hi._req.priority == INTERACTIVE      # auto-assigned
        svc.step()                           # must serve interactive next
        assert hi._req.delivered == 2 and hi.done is False
        assert hb._req.delivered == 8        # bulk has NOT advanced
        while svc.step():
            pass
        assert hi.result().ok.all()
        assert hb.result().cycles.min() >= 0


def test_bulk_not_starved_by_interactive_flood():
    """After starvation_limit consecutive interactive blocks, one bulk
    block is forced through."""
    inter_b = lambda: producer_consumer(n=32, depth=2)
    bulk_b = lambda: skynet_like(items=48, depth=6)
    Db = np.full((32, len(bulk_b().fifos)), 8, dtype=np.int64)
    with _manual_service(block=4, starvation_limit=2) as svc:
        hb = svc.submit(bulk_b(), Db, priority=BULK)
        his = [svc.submit(inter_b(), np.array([[d], [d + 1]]))
               for d in range(1, 7)]
        for _ in range(3):
            svc.step()
        st = svc.scheduler.stats()
        assert st["blocks_interactive"] == 2 and st["blocks_bulk"] == 1
        assert hb._req.delivered > 0
        while svc.step():
            pass
        assert all(h.result().cycles.min() >= 0 for h in his)


def test_starvation_debt_resets_when_bulk_lane_empty():
    """Interactive blocks served while NO bulk waits must not bank
    starvation debt that lets a later bulk sweep preempt the lane."""
    inter_b = lambda: producer_consumer(n=32, depth=2)
    bulk_b = lambda: skynet_like(items=48, depth=6)
    with _manual_service(block=4, starvation_limit=1) as svc:
        for d in (1, 2, 3):              # 3 interactive blocks, bulk empty
            svc.submit(inter_b(), np.array([[d]]))
            svc.step()
        Db = np.full((16, len(bulk_b().fifos)), 8, dtype=np.int64)
        svc.submit(bulk_b(), Db, priority=BULK)
        hi = svc.submit(inter_b(), np.array([[4]]))
        svc.step()                       # interactive still goes first
        assert hi._req.delivered == 1
        while svc.step():
            pass


def test_coalescing_and_block_dedup_across_requests():
    """Two tenants sweeping the same design share blocks, and identical
    rows across them are solved once."""
    builder = lambda: producer_consumer(n=32, depth=2)
    D1 = np.array([[1], [2], [4]])
    D2 = np.array([[2], [4], [8]])           # overlaps D1 on {2, 4}
    with _manual_service(block=16) as svc:
        h1 = svc.submit(builder(), D1, priority=BULK)
        h2 = svc.submit(builder(), D2, priority=BULK)
        assert svc.step() and not svc.step()     # ONE coalesced block
        st = svc.scheduler.stats()
        assert st["blocks"] == 1
        assert st["rows"] == 6 and st["rows_unique"] == 4
        o1, o2 = h1.result(), h2.result()
    for out, D in ((o1, D1), (o2, D2)):
        for k in range(len(D)):
            full = simulate(builder(), depths=(int(D[k, 0]),))
            assert out.cycles[k] == full.cycles


def test_block_results_carry_row_tuples_and_share_shells(monkeypatch):
    """One block of four requests: duplicate rows within and across them,
    one request cancelled and one whose deadline passes while the block
    solves.  Every delivered ``depths`` is its row as plain ints, the
    results of duplicate rows are one shell, and ``result_shells`` counts
    the block's REUSED unique rows."""
    base = simulate(fig4_ex5())
    Da = np.array([(2, 100), (100, 2), (2, 2), (2, 100), (2, 2)])
    Db = np.array([(2, 2), (8, 8)])
    Dc = np.array([(2, 100), (16, 16)])        # cancelled during the solve
    Dd = np.array([(2, 2), (1, 1)])            # expires during the solve
    with _manual_service(block=16) as svc:
        ha, hb, hc = (svc.submit(fig4_ex5(), D, priority=BULK)
                      for D in (Da, Db, Dc))
        hd = svc.submit(fig4_ex5(), Dd, priority=BULK, deadline_s=60.0)
        sched = svc.scheduler
        solve = sched._solve_unique

        def solve_while_clients_leave(*a, **kw):
            hc.cancel()
            hd._req.t_deadline = time.perf_counter() - 1.0
            return solve(*a, **kw)

        monkeypatch.setattr(sched, "_solve_unique",
                            solve_while_clients_leave)
        assert sched.step() and not sched.step()
        got = {h: list(h.stream(timeout=10)) for h in (ha, hb, hc, hd)}
        st = sched.stats()
    assert st["blocks"] == 1 and st["rows"] == 11
    assert [len(got[h]) for h in (ha, hb, hc, hd)] == [5, 2, 0, 2]
    for h, D in ((ha, Da), (hb, Db), (hd, Dd)):
        for cfg in got[h]:
            assert cfg.depths == tuple(D[cfg.index].tolist())
            assert all(type(d) is int for d in cfg.depths)
            assert cfg.result is None or cfg.result.depths == cfg.depths
    assert all(cfg.status == TIMED_OUT for cfg in got[hd])
    ref = resimulate_batch(base, np.concatenate([Da, Db]))
    for cfg in got[ha] + got[hb]:
        k = cfg.index + (len(Da) if cfg.request_id == hb.request_id else 0)
        assert (cfg.status, cfg.cycles) == (ref.status[k], ref.cycles[k])
    res = {cfg.depths: [] for cfg in got[ha] + got[hb]}
    for cfg in got[ha] + got[hb]:
        res[cfg.depths].append(cfg.result)
    assert all(r is rs[0] for rs in res.values() for r in rs)
    for row in ((2, 100), (2, 2)):
        assert type(res[row][0]).__name__ == "_ReusedShell"
    # unique rows (2,100) (100,2) (2,2) (8,8) (16,16) (1,1): two REUSED;
    # only the live requests' failed rows (100,2) and (8,8) fall back
    assert st["result_shells"] == 2 and st["fallbacks"] == 2


def test_cancel_mid_queue_finalizes_promptly():
    """A cancelled request buried behind a long bulk queue must get its
    terminal sentinel at the next scheduling point, not after the queue
    ahead of it drains."""
    builder = lambda: skynet_like(items=48, depth=6)
    Da = np.full((40, len(builder().fifos)), 8, dtype=np.int64)
    Da += np.arange(40)[:, None] % 5
    with _manual_service(block=4) as svc:
        ha = svc.submit(builder(), Da, priority=BULK)
        hb = svc.submit(builder(), np.full((2, Da.shape[1]), 9), priority=BULK)
        hb.cancel()
        svc.step()                       # one block of A; B reaped here
        assert hb._req.finalized
        out = hb.result()                # returns immediately, no drain of A
        assert (out.status == CANCELLED).all()
        assert not ha.done


def test_empty_depth_matrix_completes_immediately():
    builder = lambda: producer_consumer(n=32, depth=2)
    with _manual_service(block=8) as svc:
        h = svc.submit(builder(), np.zeros((0, 1), dtype=np.int64))
        out = h.result()
        assert len(out.ok) == 0 and h.done
        assert not svc.step()            # nothing ever reached the lanes


def test_scheduler_fault_fails_requests_loudly():
    """A faulting block must not wedge clients: queued requests get their
    sentinel and result()/stream() raise instead of hanging forever."""
    builder = lambda: producer_consumer(n=32, depth=2)
    with SweepService(block=4) as svc:
        def boom(entry, Du, t_deadline=None):
            raise RuntimeError("injected solver fault")

        svc.scheduler._solve_unique = boom
        h = svc.submit(builder(), np.array([[2], [4]]))
        with pytest.raises(RuntimeError, match="injected solver fault"):
            h.result(timeout=10.0)
        # a later result() must re-raise, not fabricate a CANCELLED outcome
        with pytest.raises(RuntimeError, match="injected solver fault"):
            h.result(timeout=10.0)


def test_close_aborts_pending_requests():
    builder = lambda: producer_consumer(n=32, depth=2)
    svc = _manual_service(block=4)
    h = svc.submit(builder(), np.array([[2], [4]]))
    svc.close()                          # never stepped
    with pytest.raises(RuntimeError, match="service closed"):
        h.result()
    # and a closed service refuses new work instead of enqueuing it into
    # a loop that will never run
    with pytest.raises(RuntimeError, match="closed"):
        svc.submit(builder(), np.array([[2]]))


def test_cancelled_rows_skip_fallback_work(monkeypatch):
    """Rows owned only by a cancelled request must not pay for fallback
    re-simulations nobody will receive (cancel landing mid-solve)."""
    builder = lambda: producer_consumer(n=32, depth=4)
    sim_calls = []
    real_sim = dse_mod.simulate

    def counting_sim(program, **kw):
        sim_calls.append(kw.get("depths"))
        return real_sim(program, **kw)

    monkeypatch.setattr(dse_mod, "simulate", counting_sim)
    with _manual_service(block=8) as svc:
        # depth 1 deadlocks (leftover items) -> would need a fallback sim
        h = svc.submit(builder(), np.array([[1], [8]]), priority=BULK)
        blk = svc.scheduler._assemble()
        h.cancel()                       # lands while the block is in flight
        svc.scheduler._deliver(blk)
        assert sim_calls == []           # no engine work for a dead stream
        assert h._req.finalized


# -------------------------------------------------------------------- cache
def test_cache_hit_miss_eviction_stats():
    calls = []

    def counting_sim(program, **kw):
        calls.append(program.name)
        return simulate(program, **kw)

    cache = GraphCache(capacity=1)
    e1 = cache.get_or_build(producer_consumer(n=32, depth=2),
                            simulate_fn=counting_sim)
    # warm repeat: same content fingerprint, no new simulation
    e1b = cache.get_or_build(producer_consumer(n=32, depth=2),
                             simulate_fn=counting_sim)
    assert e1 is e1b and len(calls) == 1
    # different design evicts (capacity 1) ...
    cache.get_or_build(skynet_like(items=24, depth=4),
                       simulate_fn=counting_sim)
    st = cache.stats()
    assert st["evictions"] == 1 and st["size"] == 1
    # ... so the first design must rebuild
    cache.get_or_build(producer_consumer(n=32, depth=2),
                       simulate_fn=counting_sim)
    assert len(calls) == 3
    st = cache.stats()
    assert st["hits"] == 1 and st["misses"] == 3
    assert st["hit_rate"] == pytest.approx(0.25)


def test_cache_content_addressing():
    """Same builder + same args ⇒ same key; changing an argument that a
    module closure captures changes the key."""
    k1 = program_fingerprint(producer_consumer(n=32, depth=2))
    k2 = program_fingerprint(producer_consumer(n=32, depth=2))
    k3 = program_fingerprint(producer_consumer(n=48, depth=2))
    assert k1 == k2 and k1 != k3


def _closure_design(captured):
    from repro.core.program import Emit, Program, Read, Write

    prog = Program("closure_design", declared_type="A")
    d = prog.fifo("d", 2)

    @prog.module("p")
    def p():
        for i in range(4):
            yield Write(d, i)

    @prog.module("c")
    def c():
        tot = 0
        for _ in range(4):
            tot += (yield Read(d))
        yield Emit("sum", tot + (captured is not None))

    return prog


def test_fingerprint_closure_edge_cases():
    """Captured values must hash by CONTENT: deeply nested data beyond
    the recursion bound still distinguishes designs (never a false cache
    hit), and default-repr objects hash stably (never a guaranteed
    miss)."""
    def nest(v, levels=12):
        for _ in range(levels):
            v = [v]
        return v

    deep1 = program_fingerprint(_closure_design(nest(1)))
    deep2 = program_fingerprint(_closure_design(nest(2)))
    assert deep1 != deep2                # differs only below the bound

    class Cfg:                           # default object.__repr__
        def __init__(self, x):
            self.x = x

    a1 = program_fingerprint(_closure_design(Cfg(1)))
    a2 = program_fingerprint(_closure_design(Cfg(1)))
    b = program_fingerprint(_closure_design(Cfg(2)))
    assert a1 == a2                      # stable across instances
    assert a1 != b                       # but content-sensitive


def test_fingerprint_kwonly_defaults_and_globals():
    """Design identity that lives in __kwdefaults__ or module globals
    (not consts/closures) must still change the key."""
    from repro.core.program import Emit, Program

    def build(count):
        prog = Program("kwonly", declared_type="A")

        def gen(*, n=count):
            yield Emit("n", n)

        prog.add_module("m", gen)
        return prog

    assert program_fingerprint(build(3)) == program_fingerprint(build(3))
    assert program_fingerprint(build(3)) != program_fingerprint(build(7))

    glob = {"Emit": __import__("repro.core.program",
                               fromlist=["Emit"]).Emit, "N": 3}
    src = "def gen():\n    yield Emit('n', N)\n"

    def build_global(n):
        from repro.core.program import Program
        g = dict(glob, N=n)
        exec(src, g)
        prog = Program("globdesign", declared_type="A")
        prog.add_module("m", g["gen"])
        return prog

    assert (program_fingerprint(build_global(3))
            == program_fingerprint(build_global(3)))
    assert (program_fingerprint(build_global(3))
            != program_fingerprint(build_global(7)))

    # a global read only inside a nested lambda still counts
    src_nested = "def gen():\n    f = lambda: N\n    yield Emit('n', f())\n"

    def build_nested(n):
        from repro.core.program import Program
        g = dict(glob, N=n)
        exec(src_nested, g)
        prog = Program("nestedglob", declared_type="A")
        prog.add_module("m", g["gen"])
        return prog

    assert (program_fingerprint(build_nested(3))
            != program_fingerprint(build_nested(7)))

    # container TYPE is content: (4, 8) and [4, 8] must not collide
    assert (program_fingerprint(_closure_design((4, 8)))
            != program_fingerprint(_closure_design([4, 8])))


def test_successive_halving_memoizes_survivors():
    """Each round submits only never-seen configs: total rows solved by
    the service is strictly less than population x rounds."""
    builder = lambda: producer_consumer(n=24, depth=2)
    with _manual_service(block=32) as svc:
        out = successive_halving(svc, builder(), n0=8, rounds=3, eta=2,
                                 lo=1, hi=8, seed=3)
        rows = svc.scheduler.stats()["rows"]
    assert rows == len(out.depths) < 8 * 3


def test_cache_accepts_existing_base_result():
    base = simulate(producer_consumer(n=32, depth=2))
    cache = GraphCache()
    entry = cache.get_or_build(base)
    assert entry.result is base
    assert entry.graph is compile_graph(base.graph)


# ------------------------------------------------------------ picklability
def test_compiled_graph_and_batch_arrays_pickle():
    """Worker-process sharding ships CompiledGraph (and its lazily rebuilt
    _BatchArrays view) over pickle; solves must survive the round trip."""
    base = simulate(skynet_like(items=48, depth=6))
    graph = compile_graph(base.graph)
    ba = _batch_arrays(graph)
    ba2 = pickle.loads(pickle.dumps(ba))
    assert (ba2.perm == ba.perm).all() and ba2.bound == ba.bound
    rng = np.random.default_rng(5)
    D = rng.integers(2, 13, size=(8, len(base.depths)))
    s_ref, c_ref, v_ref, _ = solve_block_status(graph, D)
    g2 = pickle.loads(pickle.dumps(graph))
    s2, c2, v2, _ = solve_block_status(g2, D)
    assert (s2 == s_ref).all() and (c2 == c_ref).all() \
        and (v2 == v_ref).all()


# ------------------------------------------------------------------ search
def test_pareto_front_dominance():
    D = np.array([[1, 1], [2, 2], [3, 3], [4, 4], [2, 1]])
    C = np.array([100, 50, 50, 40, 60])
    front = pareto_front(D, C)
    assert front == [((1, 1), 2, 100), ((2, 1), 3, 60), ((2, 2), 4, 50),
                     ((4, 4), 8, 40)]
    # infeasible rows never enter
    feas = np.array([True, True, True, False, True])
    assert all(a != 8 for _d, a, _c in pareto_front(D, C, feas))


def test_grid_search_modes_and_exactness():
    builder = lambda: producer_consumer(n=32, depth=2)
    with _manual_service(block=8) as svc:
        uni = grid_search(svc, builder(), [1, 2, 4, 8])
        assert len(uni.depths) == 4 and uni.feasible.all()
        for row, cyc in zip(uni.depths, uni.cycles):
            assert simulate(builder(),
                            depths=tuple(int(x) for x in row)).cycles == cyc
        axes = grid_search(svc, builder(), [1, 4], mode="axes")
        assert len(axes.depths) == 1 + len(builder().fifos) * 2
        prod = grid_search(svc, builder(), [1, 2], mode="product")
        assert len(prod.depths) == 2
        with pytest.raises(ValueError):
            grid_search(svc, skynet_like(items=24, depth=4),
                        list(range(9)), mode="product", limit=10)


def test_random_search_finds_brute_force_best():
    builder = lambda: producer_consumer(n=24, depth=2)
    with _manual_service(block=16) as svc:
        out = random_search(svc, builder(), n=24, lo=1, hi=8, seed=2)
    base = simulate(builder())
    ref = resimulate_batch(base, out.depths)
    feas = ref.cycles >= 0
    assert out.best[1] == int(ref.cycles[feas].min())
    assert len(out.pareto) >= 1


def test_successive_halving_reduces_area():
    builder = lambda: skynet_like(items=24, depth=4)
    with _manual_service(block=32) as svc:
        out = successive_halving(svc, builder(), n0=8, rounds=3, eta=2,
                                 lo=1, hi=12, seed=4)
    assert out.rounds == 3 and out.feasible.any()
    # the frontier's cheapest point must undercut the cheapest round-0
    # feasible candidate (halving explored toward lower area)
    n0_area = out.depths[:8][out.feasible[:8]].sum(axis=1)
    assert out.pareto[0][1] <= int(n0_area.min())
    # every frontier point is exact
    for dv, _area, cyc in out.pareto:
        assert simulate(builder(), depths=dv).cycles == cyc


# ------------------------------------------- search-driver bugfixes (ISSUE 9)
def test_feasible_mask_excludes_service_terminal_statuses():
    """Regression: rows whose status is a service-level terminal verdict
    (FAULTED / TIMED_OUT / REJECTED / CANCELLED) must be infeasible even
    when the cycles field carries a stale non-negative value — the old
    mask excluded them only via the incidental ``cycles >= 0`` check."""
    from repro.core.dse import (BatchOutcome, CYCLE, FAULTED, REJECTED,
                                REUSED, TIMED_OUT)
    from repro.sweep.search import _feasible_mask

    status = np.array([REUSED, FAULTED, TIMED_OUT, REJECTED, CANCELLED,
                       CYCLE], dtype=np.int8)
    cycles = np.array([10, 11, 12, 13, 14, 15], dtype=np.int64)  # all stale>=0
    K = len(status)
    out = BatchOutcome(ok=status == REUSED, cycles=cycles, status=status,
                       violated=np.zeros(K, dtype=np.int64),
                       reasons=[""] * K, results=[None] * K, elapsed_s=0.0)
    feas = _feasible_mask(out)
    # REUSED is feasible; CYCLE was refined by an exact fallback (cycles
    # >= 0, no deadlock result) so it stays feasible; every service
    # terminal status is excluded regardless of its cycles field
    assert feas.tolist() == [True, False, False, False, False, True]


def test_search_driver_excludes_faulted_rows_under_injected_faults():
    """A persistently faulting shard terminates its rows FAULTED; the
    search driver must keep them out of the frontier and still finish."""
    builder = lambda: producer_consumer(n=32, depth=4)
    # chunk0's launch and its single retry both fault -> FAULTED rows
    inj = FaultInjector(seed=3).arm("shard.fault", at=[0, 2])
    with _manual_service(block=8, shards=2, min_shard_rows=1, injector=inj,
                         retry=RetryPolicy(max_attempts=2,
                                           backoff_s=0.0)) as svc:
        out = grid_search(svc, builder(), [1, 2, 3, 4, 5, 6, 7, 8])
    faulted = np.asarray(out.cycles) == -1
    assert faulted.any(), "injector never fired"
    assert not out.feasible[faulted].any()
    assert out.feasible[~faulted].all()
    front_depths = {dv for dv, _a, _c in out.pareto}
    for k in np.flatnonzero(faulted):
        assert tuple(int(x) for x in out.depths[k]) not in front_depths
    assert out.best is not None


def test_successive_halving_empty_population_is_well_formed():
    """Regression: ``n0 == 0`` used to crash in ``np.concatenate`` on an
    empty list; it must return an empty, well-formed SearchOutcome."""
    builder = lambda: producer_consumer(n=16, depth=2)
    with _manual_service(block=8) as svc:
        out = successive_halving(svc, builder(), n0=0, rounds=3, eta=2)
    assert out.depths.shape == (0, len(builder().fifos))
    assert len(out.cycles) == 0 and len(out.feasible) == 0
    assert out.pareto == [] and out.best is None
    assert out.rounds == 0
    assert out.summary().startswith("0 evaluated")


def test_successive_halving_all_infeasible_round_accounting():
    """Regression: an all-infeasible round-0 population breaks out of the
    loop — ``rounds`` must report the rounds actually run, not the
    requested budget."""
    def exchange(K=6):
        # write-K-then-read-K exchange: live at depth >= K (the base),
        # a true deadlock at every depth < K (all sampled candidates)
        from repro.core.program import Program, Read, Write
        prog = Program("sh_dead", declared_type="B")
        ab = prog.fifo("ab", K)
        ba = prog.fifo("ba", K)

        @prog.module("x")
        def x():
            for i in range(K):
                yield Write(ab, i)
            for _ in range(K):
                yield Read(ba)

        @prog.module("y")
        def y():
            for i in range(K):
                yield Write(ba, i)
            for _ in range(K):
                yield Read(ab)

        return prog

    with _manual_service(block=16) as svc:
        out = successive_halving(svc, exchange(), n0=4, rounds=5,
                                 eta=2, lo=1, hi=5, seed=1)
    assert not out.feasible.any() and out.best is None
    assert out.rounds == 1                       # broke after round 0
    assert len(out.depths) == len(out.cycles) == len(out.feasible)


def test_graph_blob_never_mutates_shared_graph_two_threads(monkeypatch):
    """Regression: ``CacheEntry.graph_blob`` used to null the shared
    ``graph.batch`` around pickling without holding the entry lock; a
    concurrent thread-shard solver could observe ``batch is None``
    mid-solve.  The blob must now be built from a copy."""
    import repro.sweep.cache as cache_mod

    base = simulate(producer_consumer(n=32, depth=2))
    cache = GraphCache()
    entry = cache.get_or_build(base)
    batch_view = entry.batch          # lazy: built on first solver access
    assert batch_view is not None and entry.graph.batch is batch_view

    real_dumps = pickle.dumps
    dumped_graph_batch = []

    def slow_dumps(obj, *a, **kw):
        # capture what a concurrent reader of the SHARED graph would see
        # exactly while the dump is in flight, and widen the race window
        dumped_graph_batch.append(entry.graph.batch)
        time.sleep(0.002)
        return real_dumps(obj, *a, **kw)

    monkeypatch.setattr(cache_mod.pickle, "dumps", slow_dumps)
    observed_none = threading.Event()
    stop = threading.Event()

    def shard_solver():
        while not stop.is_set():
            if entry.graph.batch is None:
                observed_none.set()
                return

    t = threading.Thread(target=shard_solver)
    t.start()
    try:
        for _ in range(20):
            entry._graph_blob = None             # force a fresh pickle
            blob = entry.graph_blob()
    finally:
        stop.set()
        t.join()
    assert not observed_none.is_set()
    assert all(b is batch_view for b in dumped_graph_batch)
    assert entry.graph.batch is batch_view
    g2 = pickle.loads(blob)
    assert g2.batch is None                      # blob still ships stripped
    assert g2.n == entry.graph.n


# ------------------------------------------------------- dse-level dedup
def test_resimulate_batch_dedups_solver_work(monkeypatch):
    """Satellite: identical depth rows are solved once — solver work (and
    fallback re-simulation) scales with UNIQUE configs."""
    builder = lambda: producer_consumer(n=32, depth=4)
    base = simulate(builder())
    rows_seen = []
    real_solve = dse_mod._solve_block_numpy

    def counting_solve(ba, Db):
        rows_seen.append(len(Db))
        return real_solve(ba, Db)

    monkeypatch.setattr(dse_mod, "_solve_block_numpy", counting_solve)
    D = np.array([[1], [8], [1], [8], [1], [8], [1], [8]])
    out = resimulate_batch(base, D)
    assert out.n_unique == 2 and sum(rows_seen) == 2
    # duplicates share one result object and identical verdicts
    assert out.results[0] is out.results[2] is out.results[4]
    assert out.cycles[1] == out.cycles[3] == out.cycles[5]
    ref = resimulate_batch(base, D, dedup=False)
    assert sum(rows_seen) == 2 + len(D)          # dedup=False solves all
    assert (ref.cycles == out.cycles).all()
    assert (ref.status == out.status).all()


def test_resimulate_batch_dedups_fallbacks(monkeypatch):
    """A duplicated violating config pays for ONE full re-simulation."""
    base = simulate(fig4_ex5())
    sim_calls = []
    real_sim = dse_mod.simulate

    def counting_sim(program, **kw):
        sim_calls.append(kw.get("depths"))
        return real_sim(program, **kw)

    monkeypatch.setattr(dse_mod, "simulate", counting_sim)
    D = np.array([(100, 2)] * 6 + [(2, 100)])
    out = resimulate_batch(base, D)
    assert not out.ok[0] and out.ok[6]
    assert len(sim_calls) == 1                   # one fallback for 6 rows
    full = simulate(fig4_ex5(), depths=(100, 2))
    assert (out.cycles[:6] == full.cycles).all()


# ---------------------------------------------------------- fault tolerance
# ISSUE 6: every recovery path driven deterministically through the seeded
# FaultInjector in manual mode — no real crashes, no sleeps (the real-pool
# drills live under the `faults` marker below).  The invariants under test:
# no client stream ever hangs, every row ends in a definite status, and
# rows that ARE delivered stay bit-identical to the generator engine.
from repro.sweep import (DEFAULT_TENANT, DesignQuarantine,  # noqa: E402
                         FAULTED, FaultInjector, REJECTED, RetryPolicy,
                         SweepTimeoutError, TIMED_OUT)


def test_fault_injector_is_deterministic_per_site():
    """Same seed + same plan => same firing pattern, independent of how
    often OTHER sites are drawn in between."""
    a = FaultInjector(seed=7).arm("shard.fault", rate=0.3)
    b = FaultInjector(seed=7).arm("shard.fault", rate=0.3)
    fired_a = [a.draw("shard.fault") for _ in range(40)]
    fired_b = []
    for _ in range(40):
        b.draw("shard.hang")             # interleaved draws at other sites
        fired_b.append(b.draw("shard.fault"))
        b.draw("pool.broken")
    assert fired_a == fired_b and any(fired_a)
    # keyed arms scope to one design: other keys never fire
    c = FaultInjector(seed=7).arm("shard.fault", rate=1.0, key="poisoned")
    assert not c.draw("shard.fault", key="clean")
    assert c.draw("shard.fault", key="poisoned")


def test_retry_policy_backoff_caps():
    p = RetryPolicy(max_attempts=4, backoff_s=0.01, backoff_mult=4.0,
                    max_backoff_s=0.05)
    assert p.backoff(0) == pytest.approx(0.01)
    assert p.backoff(1) == pytest.approx(0.04)
    assert p.backoff(2) == pytest.approx(0.05)   # capped


def test_quarantine_trips_and_cooldown_resets():
    q = DesignQuarantine(threshold=2)
    assert not q.strike("k", "first")
    assert q.strike("k", "second")               # trips on the 2nd strike
    assert q.is_quarantined("k") and "second" in q.reason("k")
    assert not q.is_quarantined("other")
    q.reset("k")
    assert not q.is_quarantined("k")
    qc = DesignQuarantine(threshold=1, cooldown_s=0.0)
    qc.strike("k", "boom")
    assert not qc.is_quarantined("k")            # cooldown already elapsed


def test_transient_shard_fault_is_retried_bit_identical():
    """One injected shard fault, absorbed by the retry policy: verdicts
    identical to the fault-free run, zero rows lost."""
    builder = lambda: producer_consumer(n=32, depth=4)
    base = simulate(builder())
    D = np.array([[1], [2], [4], [8]])
    ref = resimulate_batch(base, D)
    inj = FaultInjector(seed=3).arm("shard.fault", at=[0])
    with _manual_service(block=8, injector=inj,
                         retry=RetryPolicy(max_attempts=3,
                                           backoff_s=0.0)) as svc:
        out = svc.sweep(builder(), D)
    _assert_outcome_equal(out, ref, "transient fault")
    st = svc.scheduler.stats()
    assert st["retries"] >= 1 and st["faulted_rows"] == 0
    assert inj.stats()["fired"]["shard.fault"] == 1


def test_retry_exhaustion_faults_only_that_shard():
    """A persistently faulting shard fails ITS rows (FAULTED, definite,
    with a reason) while the surviving shard's rows deliver exactly."""
    builder = lambda: producer_consumer(n=32, depth=4)
    base = simulate(builder())
    D = np.array([[1], [2], [3], [4], [5], [6], [7], [8]])  # sorted unique
    ref = resimulate_batch(base, D)
    # launch draws: chunk0 -> #0, chunk1 -> #1; chunk0's retry -> #2
    inj = FaultInjector(seed=3).arm("shard.fault", at=[0, 2])
    with _manual_service(block=8, shards=2, min_shard_rows=1, injector=inj,
                         retry=RetryPolicy(max_attempts=2,
                                           backoff_s=0.0)) as svc:
        out = svc.sweep(builder(), D)
    assert (out.status[:4] == FAULTED).all()
    assert (out.cycles[:4] == -1).all()
    for k in range(4):
        assert "attempts" in out.reasons[k], out.reasons[k]
    assert (out.status[4:] == ref.status[4:]).all()
    assert (out.cycles[4:] == ref.cycles[4:]).all()
    st = svc.scheduler.stats()
    assert st["faulted_rows"] == 4 and st["retries"] == 1
    assert svc.quarantine.stats()["strikes"] == 1


def test_shard_corruption_detected_and_retried():
    """A shard returning malformed arrays must never deliver wrong
    verdicts: host-side validation treats it as a retryable fault."""
    builder = lambda: producer_consumer(n=32, depth=4)
    base = simulate(builder())
    D = np.array([[1], [2], [4], [8]])
    ref = resimulate_batch(base, D)
    inj = FaultInjector(seed=11).arm("shard.corrupt", at=[0])
    with _manual_service(block=8, injector=inj,
                         retry=RetryPolicy(max_attempts=3,
                                           backoff_s=0.0)) as svc:
        out = svc.sweep(builder(), D)
    _assert_outcome_equal(out, ref, "corruption retried")
    assert svc.scheduler.stats()["retries"] >= 1


def test_hung_shard_times_out_under_deadline():
    """A hung worker cannot hang the client: the deadline bounds the wait
    and every undelivered row terminates TIMED_OUT."""
    builder = lambda: producer_consumer(n=32, depth=4)
    D = np.array([[1], [2], [3], [4], [5], [6], [7], [8]])
    inj = FaultInjector(seed=5, hang_s=5.0).arm("shard.hang", at=[0])
    with _manual_service(block=8, shards=2, min_shard_rows=1,
                         injector=inj,
                         retry=RetryPolicy(max_attempts=2,
                                           backoff_s=0.0)) as svc:
        h = svc.submit(builder(), D, deadline_s=0.2)
        while svc.step():
            pass
        out = h.result()
    assert (out.status == TIMED_OUT).all()
    assert all("deadline" in r or "timed out" in r for r in out.reasons)
    assert svc.scheduler.stats()["timed_out_rows"] >= len(D)


def test_deadline_expired_before_scheduling_fails_fast():
    builder = lambda: producer_consumer(n=32, depth=4)
    with _manual_service(block=4) as svc:
        h = svc.submit(builder(), np.array([[2], [4]]), deadline_s=0.0)
        _time_spin()
        while svc.step():
            pass
        out = h.result()
    assert (out.status == TIMED_OUT).all()
    assert "before this config was scheduled" in out.reasons[0]


def _time_spin():
    import time
    t0 = time.perf_counter()
    while time.perf_counter() <= t0:
        pass


def test_injected_pool_breakage_respawns_and_delivers():
    """An injected broken pool triggers one bounded respawn; the block
    still delivers bit-identically."""
    builder = lambda: skynet_like(items=48, depth=6)
    base = simulate(builder())
    rng = np.random.default_rng(7)
    D = rng.integers(1, 13, size=(8, len(base.depths)))
    ref = resimulate_batch(base, D)
    inj = FaultInjector(seed=9).arm("pool.broken", at=[0])
    with _manual_service(block=8, shards=2, min_shard_rows=1,
                         injector=inj) as svc:
        out = svc.sweep(builder(), D)
    assert (out.status == ref.status).all()
    assert (out.cycles == ref.cycles).all()
    assert svc.scheduler.stats()["pool_respawns"] == 1


def test_pool_respawn_budget_exhaustion_fails_definite():
    """When the pool keeps breaking past the respawn budget, rows fail
    FAULTED with a reason — never a hang, never a crash."""
    builder = lambda: producer_consumer(n=32, depth=4)
    D = np.array([[1], [2], [3], [4]])
    inj = FaultInjector(seed=2).arm("pool.broken", rate=1.0)
    with _manual_service(block=4, shards=2, min_shard_rows=1,
                         max_pool_respawns=0, injector=inj) as svc:
        out = svc.sweep(builder(), D)
    assert (out.status == FAULTED).all()
    assert all("respawn budget" in r for r in out.reasons)


def test_quarantine_fails_queued_rows_and_rejects_resubmits():
    """Striking past the threshold trips the design's circuit breaker:
    queued same-design rows fail fast and new submits are refused at the
    front door, while a clean design keeps being served; reset restores."""
    builder = lambda: producer_consumer(n=32, depth=4)
    base = simulate(builder())
    clean_builder = lambda: producer_consumer(n=16, depth=2)
    clean_base = simulate(clean_builder())
    ref_clean = resimulate_batch(clean_base, np.array([[2]]))
    inj = FaultInjector(seed=4).arm("shard.fault", at=[0])
    with _manual_service(block=1, injector=inj, quarantine_after=1,
                         retry=RetryPolicy(max_attempts=1,
                                           backoff_s=0.0)) as svc:
        hA = svc.submit(base, np.array([[2]]))
        hB = svc.submit(base, np.array([[4]]))
        while svc.step():
            pass
        outA, outB = hA.result(), hB.result()
        assert (outA.status == FAULTED).all()
        assert (outB.status == FAULTED).all()
        assert "quarantined" in outB.reasons[0]
        # front door refuses the poisoned design...
        hC = svc.submit(base, np.array([[8]]))
        assert hC.rejected
        outC = hC.result()
        assert (outC.status == REJECTED).all()
        assert "quarantined" in outC.reasons[0]
        # ...while a clean design is served normally
        outClean = svc.sweep(clean_base, np.array([[2]]))
        _assert_outcome_equal(outClean, ref_clean, "clean design")
        # reset gives the design a fresh budget (injector plan is spent)
        svc.quarantine.reset()
        outD = svc.sweep(base, np.array([[2]]))
        ref = resimulate_batch(base, np.array([[2]]))
        _assert_outcome_equal(outD, ref, "after reset")
    assert svc.quarantine.stats()["trips"] == 1


def test_admission_quota_rejects_then_releases():
    """Per-tenant quota: excess rows are shed with a definite REJECTED
    verdict; finishing a sweep releases its reservation."""
    builder = lambda: producer_consumer(n=32, depth=4)
    base = simulate(builder())
    D3 = np.array([[1], [2], [4]])
    ref = resimulate_batch(base, D3)
    with _manual_service(block=8,
                         max_inflight_rows_per_tenant=4) as svc:
        h1 = svc.submit(base, D3, tenant="alice")
        h2 = svc.submit(base, D3, tenant="alice")      # 3+3 > 4: shed
        h3 = svc.submit(base, D3, tenant="bob")        # other tenant: fine
        assert not h1.rejected and h2.rejected and not h3.rejected
        out2 = h2.result()                             # immediate, no hang
        assert (out2.status == REJECTED).all()
        assert "quota" in out2.reasons[0]
        assert svc.admission.inflight("alice") == 3
        while svc.step():
            pass
        _assert_outcome_equal(h1.result(), ref, "admitted tenant")
        _assert_outcome_equal(h3.result(), ref, "other tenant")
        # completion released the reservation: same tenant admits again
        assert svc.admission.inflight("alice") == 0
        h4 = svc.submit(base, D3, tenant="alice")
        assert not h4.rejected
        while svc.step():
            pass
        _assert_outcome_equal(h4.result(), ref, "after release")
        st = svc.admission.stats()
        assert st["rejected_requests"] == 1 and st["rejected_rows"] == 3


def test_queue_depth_load_shedding():
    builder = lambda: producer_consumer(n=32, depth=4)
    base = simulate(builder())
    with _manual_service(block=8, max_queued_rows=4) as svc:
        h1 = svc.submit(base, np.array([[1], [2], [4]]), tenant="a")
        h2 = svc.submit(base, np.array([[1], [2], [4]]), tenant="b")
        assert not h1.rejected and h2.rejected
        assert "load shed" in h2.result().reasons[0]
        while svc.step():
            pass
        assert h1.result().ok.any()


def test_cancellation_releases_admission_reservation():
    builder = lambda: producer_consumer(n=32, depth=4)
    base = simulate(builder())
    with _manual_service(block=8,
                         max_inflight_rows_per_tenant=4) as svc:
        h1 = svc.submit(base, np.array([[1], [2], [4]]), tenant="a")
        h1.cancel()
        while svc.step():
            pass
        h1.result()
        assert svc.admission.inflight("a") == 0


def test_close_drains_inflight_and_fails_queued():
    """close(drain=True): a sweep with rows already in completed blocks
    finishes its remaining rows; one that never reached a block fails
    loudly.  Either way no stream hangs."""
    builder = lambda: producer_consumer(n=32, depth=4)
    base = simulate(builder())
    D = np.array([[1], [2], [4], [8]])
    ref = resimulate_batch(base, D)
    with _manual_service(block=2) as svc:
        h1 = svc.submit(base, D)
        assert svc.step()                    # 2 of 4 rows delivered
        h2 = svc.submit(base, np.array([[16]]))
        svc.close(drain=True)
        _assert_outcome_equal(h1.result(), ref, "drained to completion")
        with pytest.raises(RuntimeError, match="service closed"):
            h2.result()


def test_stream_timeout_is_descriptive_and_resumable():
    """stream(timeout=) raises SweepTimeoutError (request id + progress),
    not a bare queue.Empty; the handle keeps working afterwards."""
    builder = lambda: producer_consumer(n=32, depth=4)
    base = simulate(builder())
    D = np.array([[1], [2], [4]])
    ref = resimulate_batch(base, D)
    with _manual_service(block=8) as svc:
        h = svc.submit(base, D)
        with pytest.raises(SweepTimeoutError) as ei:
            next(iter(h.stream(timeout=0.01)))
        assert ei.value.request_id == h.request_id
        assert ei.value.delivered == 0 and ei.value.total == 3
        assert "0/3" in str(ei.value) and "resume" in str(ei.value)
        while svc.step():                    # handle is still live
            pass
        _assert_outcome_equal(h.result(), ref, "resumed after timeout")


def test_acceptance_faulty_run_definite_and_clean_tenant_exact():
    """ISSUE 6 acceptance: under a seeded injector faulting one bulk
    tenant's design, no stream hangs, every row of every request ends in
    a definite status, and the clean tenant's rows are bit-identical."""
    bulk_builder = lambda: skynet_like(items=48, depth=6)
    bulk_base = simulate(bulk_builder())
    live_builder = lambda: producer_consumer(n=32, depth=4)
    live_base = simulate(live_builder())
    rng = np.random.default_rng(13)
    Db = rng.integers(1, 13, size=(20, len(bulk_base.depths)))
    Dl = np.array([[1], [2], [4], [8]])
    ref_b = resimulate_batch(bulk_base, Db)
    ref_l = resimulate_batch(live_base, Dl)
    inj = FaultInjector(seed=5)
    with _manual_service(block=4, quarantine_after=100, injector=inj,
                         retry=RetryPolicy(max_attempts=2,
                                           backoff_s=0.0)) as svc:
        bulk_key = svc.warm(bulk_base).key
        # draw #0 is the interactive tenant's single block; the bulk
        # blocks draw from #1 on.  Plan: bulk block #2 faults on both its
        # attempts (draws 2 and 3) and exhausts the 2-attempt budget.
        inj.arm("shard.fault", at=[2, 3], key=bulk_key)
        hb = svc.submit(bulk_base, Db, tenant="bulk", priority=BULK)
        hl = svc.submit(live_base, Dl, tenant="live")
        while svc.step():
            pass
        out_b, out_l = hb.result(), hl.result()
    # the clean tenant is untouched by the other tenant's faults
    _assert_outcome_equal(out_l, ref_l, "clean tenant")
    # the faulted tenant: every row definite; delivered rows exact
    assert inj.stats()["fired"]["shard.fault"] >= 1
    assert (out_b.status != CANCELLED).all()
    faulted = out_b.status == FAULTED
    assert faulted.any(), "seeded plan should exhaust at least one retry"
    assert (out_b.status[~faulted] == ref_b.status[~faulted]).all()
    assert (out_b.cycles[~faulted] == ref_b.cycles[~faulted]).all()
    assert (out_b.cycles[faulted] == -1).all()


# ------------------------------------------------------- real-pool drills
@pytest.mark.faults
def test_process_pool_blob_reship_and_bit_identity():
    """mode="process": freshly spawned workers pull each design's graph
    through the need-blob round trip once, then stay warm — results
    bit-identical to the library path."""
    builder = lambda: skynet_like(items=48, depth=6)
    base = simulate(builder())
    rng = np.random.default_rng(3)
    D = rng.integers(1, 13, size=(16, len(base.depths)))
    ref = resimulate_batch(base, D)
    with _manual_service(block=16, shards=2, mode="process",
                         min_shard_rows=1) as svc:
        svc.warm(base)
        out = svc.sweep(base, D)
    assert (out.status == ref.status).all()
    assert (out.cycles == ref.cycles).all()
    assert svc.scheduler.stats()["blob_reships"] >= 1


@pytest.mark.faults
def test_process_pool_killed_worker_respawns_and_recovers():
    """A worker hard-exiting breaks the real ProcessPoolExecutor; the
    scheduler respawns it (warm, via the pool initializer) and the sweep
    still delivers bit-identically."""
    import os as _os
    builder = lambda: skynet_like(items=48, depth=6)
    base = simulate(builder())
    rng = np.random.default_rng(3)
    D = rng.integers(1, 13, size=(16, len(base.depths)))
    ref = resimulate_batch(base, D)
    with _manual_service(block=16, shards=2, mode="process",
                         min_shard_rows=1, shard_timeout_s=30.0) as svc:
        svc.warm(base)
        # prime the blob registry so the respawned pool starts warm
        h0 = svc.submit(base, D[:2])
        while svc.step():
            pass
        h0.result()
        svc.scheduler._pool.submit(_os._exit, 11)   # murder a worker
        out = svc.sweep(base, D)
    assert (out.status == ref.status).all()
    assert (out.cycles == ref.cycles).all()
    assert svc.scheduler.stats()["pool_respawns"] >= 1
