"""Batched incremental re-simulation (core/dse.py).

Exactness contract: ``resimulate_batch(result, D)[k]`` must agree
config-for-config with ``resimulate(result, D[k])`` — same reuse verdict —
and, for every config, with a from-scratch ``simulate()`` under those
depths (cycle counts and outputs), whether the config was reused or fell
back (deadlock / WAR cycle / constraint flip).
"""
import numpy as np
import pytest

from batch_oracle import assert_batch_exact
from repro.core import resimulate, resimulate_batch, simulate
from repro.core.program import Emit, Program, Read, Write
from repro.designs.paper import fig4_ex4a, fig4_ex5
from repro.designs.typea import producer_consumer, skynet_like


# ------------------------------------------------------------------- Type A
def test_batch_matches_loop_and_full_typea():
    """Deep blocking-only pipeline; depths from starving (1) to slack."""
    builder = lambda: skynet_like(items=48, depth=6)
    base = simulate(builder())
    rng = np.random.default_rng(7)
    D = rng.integers(1, 13, size=(24, len(base.depths)))
    out = resimulate_batch(base, D)
    assert_batch_exact(out, builder, D, base)
    assert out.n_reused > 0          # slack configs must actually reuse


def test_batch_single_and_shapes():
    base = simulate(producer_consumer(n=32, depth=2))
    out = resimulate_batch(base, [8])            # 1-D = one config
    full = simulate(producer_consumer(n=32, depth=8))
    assert out.cycles[0] == full.cycles and out.ok.shape == (1,)
    with pytest.raises(ValueError):
        resimulate_batch(base, np.ones((3, 5), dtype=int))


# ------------------------------------------------------------------- Type C
def test_batch_typec_constraint_flips():
    """fig4_ex5: (2,100) reuses, (100,2) flips constraints mid-batch —
    the batch must mix reuse and fallback correctly (paper Table 6)."""
    base = simulate(fig4_ex5())
    D = np.array([(2, 100), (100, 2), (2, 2), (1, 1), (64, 64)])
    out = resimulate_batch(base, D)
    assert_batch_exact(out, fig4_ex5, D, simulate(fig4_ex5()))
    assert bool(out.ok[0]) and not bool(out.ok[1])
    assert "constraint" in out.reasons[1]
    # the two ends genuinely diverge functionally
    assert out.results[0].outputs != out.results[1].outputs


def test_batch_typec_nb_drop_design():
    """fig4_ex4a (silent-drop WriteNB): depth changes alter the dropped
    set, so most shrinks must be caught by the constraint re-check."""
    base = simulate(fig4_ex4a(n=96))
    D = np.array([[1], [2], [3], [8], [96]])
    out = resimulate_batch(base, D)
    assert_batch_exact(out, lambda: fig4_ex4a(n=96), D, simulate(fig4_ex4a(n=96)))


def test_batch_detects_new_deadlock():
    """A config that starves a committed blocking write must be masked
    structurally and fall back to a full (deadlocking) simulation."""
    def leftover():
        prog = Program("leftover", declared_type="A")
        d = prog.fifo("d", 8)

        @prog.module("p")
        def p():
            for i in range(8):
                yield Write(d, i)

        @prog.module("c")
        def c():
            tot = 0
            for _ in range(4):
                tot += (yield Read(d))
            yield Emit("sum", tot)

        return prog

    base = simulate(leftover())
    assert not base.deadlock
    D = np.array([[8], [4], [3], [1]])
    out = resimulate_batch(base, D)
    assert_batch_exact(out, leftover, D, simulate(leftover()))
    assert bool(out.ok[0]) and bool(out.ok[1])
    assert not bool(out.ok[2]) and not bool(out.ok[3])
    assert "deadlock" in out.reasons[2]
    assert out.results[2].deadlock        # fallback reproduces the deadlock


def test_batch_detects_war_cycle():
    """Shrinking BOTH channels of a burst ping-pong inverts the recorded
    event order (a genuine WAR cycle across two FIFOs): the batch must
    flag it, fall back, and reproduce the resulting deadlock."""
    def burst_pingpong(n=8, depth=8):
        prog = Program("burst_pingpong", declared_type="A")
        cmd = prog.fifo("cmd", depth)
        resp = prog.fifo("resp", depth)

        @prog.module("ctrl")
        def ctrl():
            for i in range(n):
                yield Write(cmd, i)
            tot = 0
            for _ in range(n):
                tot += (yield Read(resp))
            yield Emit("sum", tot)

        @prog.module("proc")
        def proc():
            for _ in range(n):
                v = yield Read(cmd)
                yield Write(resp, 2 * v)

        return prog

    base = simulate(burst_pingpong())
    D = np.array([(1, 1), (2, 2), (1, 8), (8, 1), (4, 4), (8, 8)])
    out = resimulate_batch(base, D)
    assert_batch_exact(out, burst_pingpong, D, simulate(burst_pingpong()))
    assert "cycle" in out.reasons[0] and "cycle" in out.reasons[1]
    assert out.results[0].deadlock            # fallback finds the deadlock
    assert out.ok[2:].all()                   # one roomy channel suffices


def test_batch_no_fallback_mode():
    base = simulate(producer_consumer(n=32, depth=4))
    out = resimulate_batch(base, np.array([[1], [16]]), fallback=False)
    for k in range(2):
        if not out.ok[k]:
            assert out.results[k] is None and out.cycles[k] == -1


# -------------------------------------------------------------- backends
def test_batch_reference_backend_agrees():
    """The production Gauss-Seidel solver against the Jacobi oracle."""
    base = simulate(skynet_like(items=32, depth=5))
    rng = np.random.default_rng(3)
    D = rng.integers(1, 10, size=(16, len(base.depths)))
    out = resimulate_batch(base, D)
    ref = resimulate_batch(base, D, backend="reference")
    assert (out.ok == ref.ok).all()
    assert (out.cycles == ref.cycles).all()
    assert (out.status == ref.status).all()


def test_batch_jax_backend_agrees():
    """Sparse chain-structured jax backend (Pallas interpret mode)."""
    pytest.importorskip("jax")
    base = simulate(producer_consumer(n=24, depth=3))
    D = np.array([[1], [2], [4], [8]])
    out = resimulate_batch(base, D, backend="numpy")
    jx = resimulate_batch(base, D, backend="jax")
    assert (out.ok == jx.ok).all()
    assert (out.cycles == jx.cycles).all()


# ------------------------------------------------------------- throughput
def test_batch_speedup_256_configs():
    """Acceptance: >= 256 skynet_like depth configs, batched >= 10x faster
    than the resimulate() loop, every config's cycle count exact against a
    from-scratch simulate()."""
    import time

    builder = lambda: skynet_like(items=128, depth=8)
    base = simulate(builder())
    rng = np.random.default_rng(0)
    K = 256
    D = rng.integers(4, 17, size=(K, len(base.depths)))
    # warm the shared compiled-graph cache for both paths
    resimulate(base, tuple(int(d) for d in D[0]))
    resimulate_batch(base, D[:2])

    # best-of-3 on both sides: single-shot wall timings are noisy enough
    # on shared CI boxes to trip the ratio assertion spuriously
    t_loop = float("inf")
    t_batch = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        looped = [resimulate(base, tuple(int(d) for d in row),
                             fallback=False) for row in D]
        t_loop = min(t_loop, time.perf_counter() - t0)
        t0 = time.perf_counter()
        out_nf = resimulate_batch(base, D, fallback=False)
        t_batch = min(t_batch, time.perf_counter() - t0)
    out = resimulate_batch(base, D)        # untimed: exercises fallback too

    # config-for-config agreement with the looped path
    for k, inc in enumerate(looped):
        assert inc.ok == bool(out.ok[k]) == bool(out_nf.ok[k]), \
            (k, out.reasons[k], inc.reason)
        if inc.ok:
            assert inc.result.cycles == out.cycles[k] == out_nf.cycles[k], k
    # cycle counts exact against from-scratch simulation for EVERY config:
    # reused ones from the shared fixpoint, violated ones via fallback
    for k in range(K):
        full = simulate(builder(), depths=tuple(int(d) for d in D[k]))
        assert out.cycles[k] == full.cycles, (k, "reused" if out.ok[k]
                                              else out.reasons[k])
    speedup = t_loop / t_batch
    assert speedup >= 10.0, (
        f"batched DSE only {speedup:.1f}x over looped resimulate "
        f"({t_loop*1e3:.0f} ms vs {t_batch*1e3:.0f} ms for {K} configs)")


def test_batch_jax_sparse_deadlock_and_war_cycle():
    """The sparse jax lane must classify starved writes (DEADLOCK) and
    inverted event orders (WAR CYCLE) bit-identically to numpy — the
    failure verdicts, not just the happy path."""
    pytest.importorskip("jax")

    def leftover():
        prog = Program("leftover", declared_type="A")
        d = prog.fifo("d", 8)

        @prog.module("p")
        def p():
            for i in range(8):
                yield Write(d, i)

        @prog.module("c")
        def c():
            tot = 0
            for _ in range(4):
                tot += (yield Read(d))
            yield Emit("sum", tot)

        return prog

    def burst_pingpong(n=8, depth=8):
        prog = Program("burst_pingpong", declared_type="A")
        cmd = prog.fifo("cmd", depth)
        resp = prog.fifo("resp", depth)

        @prog.module("ctrl")
        def ctrl():
            for i in range(n):
                yield Write(cmd, i)
            tot = 0
            for _ in range(n):
                tot += (yield Read(resp))
            yield Emit("sum", tot)

        @prog.module("proc")
        def proc():
            for _ in range(n):
                v = yield Read(cmd)
                yield Write(resp, 2 * v)

        return prog

    cases = [(leftover, np.array([[8], [4], [3], [1]])),
             (burst_pingpong, np.array([(1, 1), (2, 2), (1, 8), (8, 1),
                                        (4, 4), (8, 8)]))]
    for builder, D in cases:
        base = simulate(builder())
        o_np = resimulate_batch(base, D, backend="numpy", fallback=False)
        o_jx = resimulate_batch(base, D, backend="jax", fallback=False)
        assert (o_np.status == o_jx.status).all(), builder.__name__
        assert (o_np.cycles == o_jx.cycles).all(), builder.__name__
        assert (o_np.violated == o_jx.violated).all(), builder.__name__
    # the failure modes really were exercised
    assert (resimulate_batch(simulate(leftover()), np.array([[1]]),
                             backend="jax", fallback=False).status == 1).all()


def test_jax_backends_refuse_int32_overflow():
    """Regression: the jax lane must refuse (not silently wrap) a graph
    whose path-length bound exceeds int32 headroom; ``"jax_dense"`` is
    an unknown backend."""
    pytest.importorskip("jax")
    from repro.core.dse import _batch_arrays
    from repro.core.incremental import compile_graph

    base = simulate(producer_consumer(n=24, depth=3))
    g = compile_graph(base.graph)
    ba = _batch_arrays(g)
    old = ba.bound
    try:
        ba.bound = 1 << 28              # numpy's int64 switchover point
        with pytest.raises(ValueError, match="numpy"):
            resimulate_batch(base, np.array([[4]]), backend="jax",
                             fallback=False)
    finally:
        ba.bound = old
    with pytest.raises(ValueError, match="unknown backend 'jax_dense'"):
        resimulate_batch(base, np.array([[4]]), backend="jax_dense",
                         fallback=False)


def _reused_shells():
    """fig4_ex5's base run (4 outputs, 2,025 constraints) and three REUSED
    rows' results."""
    base = simulate(fig4_ex5())
    out = resimulate_batch(base, np.array([(2, 100), (2, 3), (2, 50)]))
    assert out.ok.all()
    return base, out.results


def _set_stats(stats):
    stats.queries = -123


@pytest.mark.parametrize("name,mutate", [
    ("outputs", lambda v: v.__setitem__("sentinel", -1)),
    ("stats", _set_stats),
    ("constraints", lambda v: v.append("sentinel")),
])
def test_reused_shells_do_not_alias_mutable_state(name, mutate):
    """Regression: REUSED result shells shared the base run's mutable
    ``outputs``, ``stats`` and ``constraints`` — mutating one sweep result
    corrupted its siblings and the cached base run.  Each shell copies a
    field on first access: equal to the base run's, an object of its own,
    and a mutation reaches neither a sibling read before it, nor one read
    after it, nor the base."""
    import copy
    base, (r0, r1, r2) = _reused_shells()
    snapshot = {"outputs": dict, "stats": copy.copy, "constraints": list}
    pristine = snapshot[name](getattr(base, name))
    seen = [id(getattr(base, name))]
    for r in (r1, r0):                     # r1 copies before the mutation
        assert getattr(r, name) == pristine
        assert getattr(r, name) is getattr(r, name)      # copied once
        seen.append(id(getattr(r, name)))
    mutate(getattr(r0, name))
    assert getattr(r0, name) != pristine
    for r in (r1, r2, base):               # r2 copies after it
        assert getattr(r, name) == pristine, r.engine
    seen.append(id(getattr(r2, name)))
    assert len(set(seen)) == 4             # base and three shells
    # a shell's own value may be set outright, as any result's
    setattr(r2, name, None)
    assert getattr(r2, name) is None and getattr(base, name) == pristine


def test_reused_shells_pickle_replace_and_compare_like_eager_results():
    """A shell is a :class:`SimResult`: ``==`` an eagerly built result
    with the same fields (either way round), survives
    ``dataclasses.replace`` and a pickle round trip, and carries its
    row's depths as plain ints."""
    import dataclasses
    import pickle
    base, (r0, r1, _r2) = _reused_shells()
    assert r0.depths == (2, 100)
    assert all(type(d) is int for d in r0.depths + (r0.cycles,))
    eager = dataclasses.replace(base, cycles=r0.cycles, depths=r0.depths,
                                engine=r0.engine)
    assert type(eager) is not type(r0)
    assert r0 == eager and eager == r0 and r0 != r1
    assert dataclasses.replace(r0) == eager
    moved = dataclasses.replace(r0, cycles=r0.cycles + 1)
    assert moved != eager and moved.outputs == base.outputs
    r0.outputs["sentinel"] = -1
    assert r0 != eager and eager != r0
    # pickle round trip: the design's generator bodies are local
    # functions, so the shell is rebuilt over a base without its graph
    # (and with its constraints a plain list, not the trace's lazy view)
    from repro.core.dse import _ReusedShell
    flat = dataclasses.replace(base, graph=None,
                               constraints=list(base.constraints))
    shell = _ReusedShell.of(flat, r1.cycles, r1.depths, r1.engine)
    back = pickle.loads(pickle.dumps(shell))
    assert type(back) is _ReusedShell
    assert back == shell == dataclasses.replace(eager, graph=None,
                                                cycles=r1.cycles,
                                                depths=r1.depths)
    shell.constraints.append("sentinel")
    assert "sentinel" not in back.constraints


# ------------------------------------------------------------ cycle exit
def _bench_reference():
    """``bench/reference.py``: the benchmark's plain reference simulator,
    which imports nothing of ``repro``."""
    import importlib.util
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "bench", "reference.py")
    spec = importlib.util.spec_from_file_location("bench_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _optical_flow_rows(height, width, seed, k=12):
    """optical_flow's compiled graph and ``k`` depth rows uniform in
    [1, 4 * width]^14: the scale at which about a third deadlock."""
    from repro.core.incremental import compile_graph
    from repro.designs.rosetta import optical_flow
    base = simulate(optical_flow(height, width), trace="auto")
    D = np.random.default_rng(seed).integers(1, 4 * width + 1,
                                             (k, len(base.depths)))
    return base, compile_graph(base.graph), D


@pytest.mark.parametrize("height,width,seed", [(2, 64, 0), (3, 128, 1),
                                               (4, 256, 2)])
def test_optical_flow_lanes_agree_with_the_bench_reference(height, width,
                                                           seed):
    """The jax, numpy and Jacobi lanes give the statuses and cycles of a
    from-scratch run of the benchmark's reference, CYCLE rows included."""
    from repro.core.dse import CYCLE, REUSED, VIOLATED, solve_block_status
    from repro.designs.rosetta import optical_flow
    ref = _bench_reference()
    base, g, D = _optical_flow_rows(height, width, seed)
    bodies = [m.fn for m in optical_flow(height, width).modules]
    rb = ref.simulate(list(base.depths), bodies)
    want_s, want_c = [], []
    for row in D:
        row = [int(d) for d in row]
        run = ref.simulate(row, bodies)
        if not run.deadlock and run.outcomes == rb.outcomes:
            want_s.append(REUSED)
            want_c.append(run.cycles)
        else:
            want_s.append(CYCLE if ref.retime(rb, row) is None
                          else VIOLATED)
            want_c.append(-1)
    want_s, want_c = np.array(want_s), np.array(want_c)
    assert 0 < (want_s == CYCLE).sum() < len(D)
    for backend in ("jax", "numpy", "reference"):
        st, cyc, _, _ = solve_block_status(g, D, backend=backend, block=16)
        np.testing.assert_array_equal(st, want_s, err_msg=backend)
        np.testing.assert_array_equal(cyc, want_c, err_msg=backend)


def test_a_block_below_the_full_height_leaves_cycles_to_the_host():
    """A block that pads to fewer rows than the caller's block runs the
    fixpoint without the cycle check: it stops where the check would
    first run and leaves the rows still pending to the host's exact
    pass, which gives the numpy lane's statuses and cycles."""
    import functools

    import jax

    from repro.core.dse import (CYCLE, _batch_arrays, _sparse_arrays,
                                solve_block_status)
    from repro.kernels.maxplus import sparse as sp
    _, g, D = _optical_flow_rows(2, 64, 3, k=8)
    want = solve_block_status(g, D, backend="numpy", block=64)
    got = solve_block_status(g, D, backend="jax", block=64)
    for i in range(3):
        np.testing.assert_array_equal(got[i], want[i])
    cyclic = want[0] == CYCLE
    assert cyclic.any()
    arr = _sparse_arrays(g, _batch_arrays(g))
    assert sp.solve_chains(arr, D, block=64).unsettled[cyclic].all()
    args, static = sp._fixpoint_args(arr, D)
    text = {check: str(jax.make_jaxpr(functools.partial(
        sp._fixpoint, **static, interpret=True, check=check))(*args))
        for check in (True, False)}
    assert "cycle_fill" in text[True] and "cycle_fill" not in text[False]


@pytest.mark.parametrize("backend", ["jax", "numpy", "reference"])
def test_cycle_exit_rounds_do_not_grow_with_the_frame(backend):
    """A block holding deadlocking rows finishes in rounds set by the
    deadlock's cycle, not by the design's length: the old exit (times past
    the acyclic bound) took about 43 rounds a frame row of width 1,024."""
    from repro.core.dse import CYCLE, solve_block_status
    rounds = []
    for height in (2, 4, 8):
        _, g, D = _optical_flow_rows(height, 256, 0, k=16)
        st, _, _, r = solve_block_status(g, D, backend=backend, block=16)
        assert (st == CYCLE).sum() >= 3
        rounds.append(r)
    assert max(rounds) <= 1.25 * min(rounds) + 8, rounds


def test_pointer_cycle_rows_flags_only_cyclic_rows():
    """Forced at every round of a Jacobi solve, the pointer check flags
    exactly the rows whose from-scratch run deadlocks, and no row of a
    design whose depth rows never deadlock."""
    from repro.core.dse import _batch_arrays, _regen_war_stacked
    from repro.core.graph import pointer_cycle_rows
    from repro.core.incremental import NEGI, compile_graph
    from repro.designs.paper import multicore
    from repro.designs.rosetta import optical_flow
    ref = _bench_reference()
    base, g, D = _optical_flow_rows(2, 64, 3, k=16)
    bodies = [m.fn for m in optical_flow(2, 64).modules]
    cases = [(g, D, [ref.simulate([int(d) for d in row], bodies).deadlock
                     for row in D])]
    mc = simulate(multicore(cores=4, prog_len=32, stride=4), trace="auto")
    gm = compile_graph(mc.graph)
    Dm = np.random.default_rng(4).integers(1, 17, (16, len(mc.depths)))
    cases.append((gm, Dm, [False] * len(Dm)))
    for g, D, want in cases:
        ba = _batch_arrays(g)
        K, n = len(D), len(ba.perm)
        c = np.broadcast_to(np.where(ba.c_inf == NEGI, -(1 << 40),
                                     ba.c_inf), (K, n)).copy()
        dd, ds, dv = _regen_war_stacked(ba, D)
        flagged = np.zeros(K, bool)
        for _ in range(40):
            t = np.empty_like(c)
            for (lo, hi) in ba.slices:
                t[:, lo:hi] = ba.cw[lo:hi] + np.maximum.accumulate(
                    c[:, lo:hi] - ba.cw[lo:hi], axis=1)
            flagged |= pointer_cycle_rows(c, ba.cw, ba.slices, ba.raw_dst,
                                          ba.raw_src, dd, ds, dv, steps=64)
            c[:, ba.raw_dst] = np.maximum(c[:, ba.raw_dst],
                                          t[:, ba.raw_src] + ba.raw_w)
            cand = np.where(dv, np.take_along_axis(t, ds, axis=1) + 1,
                            c[:, dd])
            c[:, dd] = np.maximum(c[:, dd], cand)
        np.testing.assert_array_equal(flagged, want)
    assert 0 < sum(cases[0][2]) < len(cases[0][2])


def _lockstep_block():
    """optical_flow at 4 x 64 and 16 depth rows, the last a lockstep row:
    the z path (``frame4_a`` plus ``gradient_z``) holds barely the x/y
    path's lag, so the two paths advance one pixel a round for the rest
    of the frame and the Jacobi lane needs about 500 rounds."""
    from repro.core.incremental import compile_graph
    from repro.designs.rosetta import optical_flow
    base = simulate(optical_flow(4, 64), trace="auto")
    names = [f.name for f in optical_flow(4, 64).fifos]
    lock = np.full(len(names), 256)
    lock[names.index("frame4_a")] = 32
    lock[names.index("gradient_z")] = 97
    D = np.vstack([np.random.default_rng(1).integers(1, 257,
                                                     (15, len(names))),
                   lock])
    return compile_graph(base.graph), D


def test_straggler_rows_leave_the_device_exactly():
    """The jax lane stops at its straggler cap and solves the lockstep
    row on the host: the same statuses, cycles and violations as the
    numpy and Jacobi lanes, in a fraction of their rounds."""
    from repro.core.dse import REUSED, solve_block_status
    g, D = _lockstep_block()
    out = {be: solve_block_status(g, D, backend=be, block=16)
           for be in ("numpy", "reference", "jax")}
    for be in ("reference", "jax"):
        for i in range(3):
            np.testing.assert_array_equal(out[be][i], out["numpy"][i],
                                          err_msg=be)
    assert out["numpy"][0][-1] == REUSED
    assert out["reference"][3] > 400
    assert out["jax"][3] < 64


@pytest.mark.parametrize("height,width,seed", [(2, 64, 5), (4, 64, 6)])
def test_exact_row_solve_matches_the_numpy_lane(height, width, seed):
    """One topological pass a row gives the numpy lane's converged flags
    and, on converged rows, its times bit for bit."""
    from repro.core.dse import (_batch_arrays, _solve_block_numpy,
                                _solve_rows_exact)
    from repro.kernels.maxplus.sparse import NEG
    _, g, D = _optical_flow_rows(height, width, seed, k=16)
    ba = _batch_arrays(g)
    D = D[~(D < ba.fifo_need[None, :]).any(axis=1)]
    t_np, conv_np, _ = _solve_block_numpy(ba, D)
    t_ex, conv_ex = _solve_rows_exact(ba, D, int(NEG))
    np.testing.assert_array_equal(conv_ex, conv_np)
    assert 0 < conv_np.sum() < len(D)
    np.testing.assert_array_equal(t_ex[:, conv_np], t_np[:, conv_np])


def _verdict_case(case):
    """(compiled graph, depth rows, block) of a device-verdict case."""
    from repro.core.incremental import compile_graph
    from repro.designs.paper import fig2_timer, multicore
    if case == "optical_flow":
        _, g, D = _optical_flow_rows(2, 64, 0, k=16)
        return g, D, 16
    if case == "unsettled":       # a short block leaves rows to the host
        _, g, D = _optical_flow_rows(2, 64, 3, k=8)
        return g, D, 64
    build, lo, hi, k = {
        "multicore": (lambda: multicore(cores=2, prog_len=16, stride=4),
                      0, 12, 64),
        "fig2_timer": (lambda: fig2_timer(64), 0, 1, 16),
        "skynet_like": (lambda: skynet_like(items=16, depth=4), 1, 8, 16),
    }[case]
    base = simulate(build(), trace="auto")
    D = np.random.default_rng(7).integers(lo, hi + 1, (k, len(base.depths)))
    return compile_graph(base.graph), D, k


@pytest.mark.parametrize("case", ["multicore", "fig2_timer", "skynet_like",
                                  "optical_flow", "unsettled"])
def test_device_verdicts_match_the_numpy_lane(case):
    """The jax lane decides each row's violations and cycle count on the
    device and copies back only those: its statuses, cycles and
    violations equal the numpy lane's host re-check row for row.  A Type
    C design with can-read and can-write constraints and REUSED, VIOLATED
    and CYCLE rows; can-read constraints only; designs without
    constraints, CYCLE rows included; a block whose undecided rows the
    host solves and re-checks."""
    from repro.core.dse import (CYCLE, DEADLOCK, VIOLATED, _batch_arrays,
                                _sparse_arrays, solve_block_status)
    from repro.kernels.maxplus import sparse as sp
    g, D, block = _verdict_case(case)
    want = solve_block_status(g, D, backend="numpy", block=block)
    counts = {}
    got = solve_block_status(g, D, backend="jax", block=block,
                             counts=counts)
    for i in range(3):
        np.testing.assert_array_equal(got[i], want[i])
    alive = want[0] != DEADLOCK
    assert (counts["device_verdict_rows"] + counts["host_recheck_rows"]
            == alive.sum())
    arr = _sparse_arrays(g, _batch_arrays(g))
    kinds = (len(arr.chk_rd_src) > 0, len(arr.chk_wr_src) > 0)
    v = sp.solve_chains(arr, D[alive], block=block)
    on_device = np.zeros(len(D), bool)
    on_device[alive] = ~v.unsettled
    assert on_device.sum() == counts["device_verdict_rows"]
    if case in ("multicore", "fig2_timer"):
        assert kinds == (True, case == "multicore")
        assert (want[0][on_device] == VIOLATED).any()
        assert (want[0] == CYCLE).any() and (want[0] == 0).any()
    else:
        assert kinds == (False, False)
    if case == "optical_flow":
        assert (want[0][on_device] == CYCLE).any()
    if case == "unsettled":
        assert counts["host_recheck_rows"] > 0
