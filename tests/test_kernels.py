"""Kernel validation: the sparse max-plus Pallas kernel (interpret mode)
vs pure-jnp and numpy oracles.

The kernel is swept over shapes (parametrize) and asserted equal to
``segmented_cummax_ref`` and to the numpy DSE lane.  interpret=True
executes the kernel body in Python on CPU; the BlockSpecs/grids are
identical to the TPU build.
"""
import functools

import numpy as np
import pytest

jax = pytest.importorskip("jax")
pytest.importorskip("jax.experimental.pallas")
import jax.numpy as jnp


# ----------------------------------------------------------- sparse maxplus
from repro.kernels.maxplus.sparse import NEG as SPARSE_NEG
from repro.kernels.maxplus.sparse import (segmented_cummax,
                                          segmented_cummax_ref)


def _random_segments(rng, npad):
    seg = np.zeros(npad, np.int32)
    lo = 0
    while lo < npad:
        ln = int(rng.integers(1, 17))
        seg[lo:min(lo + ln, npad)] = lo
        lo += ln
    return seg


def _segcummax_oracle(x, seg):
    want = x.copy()
    for j in range(1, x.shape[1]):
        if seg[j] <= j - 1:            # previous column in the same segment
            want[:, j] = np.maximum(want[:, j], want[:, j - 1])
    return want


@pytest.mark.parametrize("K,npad", [(8, 128), (32, 256), (64, 128)])
def test_segmented_cummax_matches_oracle(K, npad):
    """Pallas segmented cummax (and its jnp ref) vs a sequential oracle."""
    rng = np.random.default_rng(K + npad)
    seg = _random_segments(rng, npad)
    x = rng.integers(-50, 50, size=(K, npad)).astype(np.int32)
    want = _segcummax_oracle(x, seg)
    got_pl = np.asarray(segmented_cummax(jnp.asarray(x), jnp.asarray(seg),
                                         interpret=True))
    got_ref = np.asarray(segmented_cummax_ref(jnp.asarray(x),
                                              jnp.asarray(seg)))
    assert (got_pl == want).all()
    assert (got_ref == want).all()


def test_segmented_cummax_max_seg_cap():
    """Capping the doubling scan at the longest segment must not change
    the result (segments here are <= 16 columns)."""
    rng = np.random.default_rng(5)
    seg = _random_segments(rng, 256)
    x = rng.integers(-50, 50, size=(16, 256)).astype(np.int32)
    want = _segcummax_oracle(x, seg)
    for max_seg in (16, 17, None):
        got = np.asarray(segmented_cummax(jnp.asarray(x), jnp.asarray(seg),
                                          max_seg=max_seg, interpret=True))
        assert (got == want).all(), max_seg


def test_solve_chains_matches_numpy_seeded_solver():
    """End-to-end sparse solve over exported flat arrays vs the numpy
    Gauss-Seidel production solver, WAR edges active."""
    from repro.core import simulate
    from repro.core.dse import (_batch_arrays, _solve_block_numpy,
                                _sparse_arrays)
    from repro.core.incremental import compile_graph
    from repro.designs.typea import skynet_like
    from repro.kernels.maxplus import sparse as sp

    base = simulate(skynet_like(items=16, depth=4))
    g = compile_graph(base.graph)
    ba = _batch_arrays(g)
    rng = np.random.default_rng(2)
    Db = rng.integers(1, 9, size=(8, len(base.depths))).astype(np.int64)
    t_np, conv_np, _ = _solve_block_numpy(ba, Db)
    v = sp.solve_chains(_sparse_arrays(g, ba), Db, times=True)
    t_jx, conv_jx = v.times, v.converged
    assert (conv_np == conv_jx).all()
    # converged configs: full (n, K) node-time agreement, not just cycles
    cols = np.flatnonzero(conv_np)
    assert (np.asarray(t_np)[:, cols] == t_jx[:, cols]).all()


@pytest.mark.parametrize("npad,width,max_len", [(512, 128, 300),
                                                (768, 256, 40),
                                                (1024, 128, 1024)])
def test_segmented_cummax_node_tiles(npad, width, max_len):
    """Node-axis tiling: chains crossing one or many tile boundaries
    continue through the per-row carry."""
    rng = np.random.default_rng(npad + width)
    seg = np.zeros(npad, np.int32)
    lo = 0
    while lo < npad:
        ln = int(rng.integers(1, max_len + 1))
        seg[lo:min(lo + ln, npad)] = lo
        lo += ln
    x = rng.integers(-50, 50, size=(16, npad)).astype(np.int32)
    want = _segcummax_oracle(x, seg)
    got = np.asarray(segmented_cummax(jnp.asarray(x), jnp.asarray(seg),
                                      max_seg=max_len, width=width))
    assert (got == want).all()


@pytest.mark.parametrize("npad,width,max_len", [(256, 128, 16),
                                                (512, 128, 300)])
def test_segmented_cummax_per_row_segments(npad, width, max_len):
    """A (K, npad) ``seg_start`` gives each row segments of its own (the
    cycle exit's label fill), across node tiles."""
    rng = np.random.default_rng(npad + max_len)
    K = 16
    seg = np.zeros((K, npad), np.int32)
    for k in range(K):
        lo = 0
        while lo < npad:
            ln = int(rng.integers(1, max_len + 1))
            seg[k, lo:min(lo + ln, npad)] = lo
            lo += ln
    x = rng.integers(-50, 50, size=(K, npad)).astype(np.int32)
    want = np.stack([_segcummax_oracle(x[k:k + 1], seg[k])[0]
                     for k in range(K)])
    got = np.asarray(segmented_cummax(jnp.asarray(x), jnp.asarray(seg),
                                      max_seg=max_len, width=width))
    assert (got == want).all()


def test_solve_chains_node_tiles_match_numpy(monkeypatch):
    """The whole fixpoint with the node axis split into many tiles (a
    narrow LANE_TILE stands in for a design wider than one tile)."""
    from repro.core import simulate
    from repro.core.dse import (_batch_arrays, _solve_block_numpy,
                                _sparse_arrays)
    from repro.core.incremental import compile_graph
    from repro.designs.typea import skynet_like
    from repro.kernels.maxplus import sparse as sp

    base = simulate(skynet_like(items=64, depth=4))
    g = compile_graph(base.graph)
    ba = _batch_arrays(g)
    arr = _sparse_arrays(g, ba)
    monkeypatch.setattr(sp, "LANE_TILE", 128)
    assert sp._padded_width(arr.npad) // sp._tile_width(arr.npad) > 2
    assert arr.max_seg > 128                  # chains span several tiles
    rng = np.random.default_rng(3)
    Db = rng.integers(1, 9, size=(8, len(base.depths))).astype(np.int64)
    t_np, conv_np, _ = _solve_block_numpy(ba, Db)
    v = sp.solve_chains(arr, Db, times=True)
    t_jx, conv_jx = v.times, v.converged
    assert (conv_np == conv_jx).all()
    cols = np.flatnonzero(conv_np)
    assert len(cols)
    assert (np.asarray(t_np)[:, cols] == t_jx[:, cols]).all()


def _war_candidates_per_row_gather(t, wseq, fid, nr, roff, rcols, Db):
    """The WAR candidates as a gather with one index per config row: write
    ``wseq`` of FIFO ``fid`` under depth ``S`` waits on read
    ``wseq - S - 1`` of the FIFO's reads ``rcols[roff:roff + nr]``."""
    S = Db[:, fid]
    tgt = wseq[None, :] - S - 1
    valid = (tgt >= 0) & (tgt < nr[None, :])
    src = rcols[roff[None, :] + jnp.clip(tgt, 0, nr[None, :] - 1)]
    cand = jnp.take_along_axis(t, src, axis=1) + 1
    return jnp.where(valid, cand, jnp.int32(SPARSE_NEG))


@pytest.mark.parametrize("n_fifos,nw,nr,depths", [
    (1, 40, 10, "random"),          # more blocking writes than reads
    (1, 10, 40, "random"),          # more reads than writes
    (3, 20, 20, "zero"),
    (3, 20, 25, "deep"),            # depth >= the segment length
    (2, 30, 30, "huge"),            # the 1 << 30 clip of _fixpoint_args
    (24, (1, 60), (0, 60), "mixed"),
], ids=["writes_gt_reads", "reads_gt_writes", "depth0", "deep", "huge",
        "many"])
def test_war_lane_matches_per_row_gather(n_fifos, nw, nr, depths):
    """The WAR lane (static gather, per-row barrel shift, static read)
    gives the per-row gather's candidates exactly, bucket-padding WAR
    rows (wseq = 0) and FIFOs without reads or blocking writes included."""
    from repro.core.graph import export_chain_flat
    from repro.kernels.maxplus import sparse as sp

    rng = np.random.default_rng(n_fifos * 1000 + sum(map(ord, depths)))

    def draw(v):
        return v if isinstance(v, int) else int(rng.integers(*v))

    sizes = [(draw(nw), draw(nr)) for _ in range(n_fifos)]
    n = sum(a + b for a, b in sizes)
    cols = rng.permutation(n)
    w_cols, r_cols, blocking, lo = [], [], [], 0
    for a, b in sizes:
        w_cols.append(cols[lo:lo + a])
        r_cols.append(cols[lo + a:lo + a + b])
        blk = rng.random(a) < 0.8
        blk[-1] = True
        blocking.append(blk)
        lo += a + b
    if depths == "mixed":
        blocking[0][:] = False              # a FIFO with no blocking write
    arr = export_chain_flat(
        [(0, n)], np.zeros(n, np.int64), np.zeros(n, np.int64),
        np.zeros(0, np.int64), np.zeros(0, np.int64), np.zeros(0, np.int64),
        w_cols, r_cols, blocking, bound=1 << 20, neg=SPARSE_NEG)
    assert (arr.war_wseq == 0).any()        # bucket-padding WAR rows

    K = 8
    seg = arr.war_seg
    Db = {"random": lambda: rng.integers(0, 2 * seg, (K, n_fifos)),
          "zero": lambda: np.zeros((K, n_fifos)),
          "deep": lambda: rng.integers(seg, 3 * seg, (K, n_fifos)),
          "huge": lambda: np.full((K, n_fifos), 1 << 30),
          "mixed": lambda: rng.choice([0, 1, 3, seg - 1, seg, 1 << 30],
                                      (K, n_fifos))}[depths]()
    Db = jnp.asarray(Db, jnp.int32)
    t = jnp.asarray(rng.integers(-1000, 1000, (K, arr.npad)), jnp.int32)

    shift, valid = sp._war_operands(
        Db, *map(jnp.asarray, (arr.war_wseq, arr.war_fid, arr.war_nr,
                               arr.war_lane_fid)), seg)
    got = sp._war_candidates(t, jnp.asarray(arr.war_lane_src),
                             jnp.asarray(arr.war_pos), shift, valid,
                             sp.war_steps(seg))
    roff = np.cumsum([0] + [len(r) for r in r_cols])[arr.war_fid]
    roff = np.where(arr.war_wseq > 0, roff, 0)
    want = _war_candidates_per_row_gather(
        t, *map(jnp.asarray, (arr.war_wseq, arr.war_fid, arr.war_nr, roff,
                              np.concatenate(r_cols).astype(np.int32))), Db)
    assert (np.asarray(got) == np.asarray(want)).all()
    live = np.asarray(want) > SPARSE_NEG     # no depth >= seg leaves a read
    assert live.any() == (depths not in ("deep", "huge"))


def test_solve_chains_multicore_matches_numpy():
    """The whole fixpoint on a Type C design whose redirect FIFOs are
    non-blocking (their writes make no WAR rows) beside blocking ones."""
    from repro.core import simulate
    from repro.core.dse import (_batch_arrays, _solve_block_numpy,
                                _sparse_arrays)
    from repro.core.incremental import compile_graph
    from repro.designs.paper import multicore
    from repro.kernels.maxplus import sparse as sp

    base = simulate(multicore(cores=2, prog_len=16, stride=4), trace="auto")
    g = compile_graph(base.graph)
    ba = _batch_arrays(g)
    arr = _sparse_arrays(g, ba)
    assert not all(b.all() for b in ba.fifo_blocking)   # NB FIFOs present
    assert sp.war_steps(arr.war_seg) > 1
    rng = np.random.default_rng(4)
    Db = rng.integers(0, 12, size=(16, len(base.depths))).astype(np.int64)
    t_np, conv_np, _ = _solve_block_numpy(ba, Db)
    v = sp.solve_chains(arr, Db, interpret=True, times=True)
    t_jx, conv_jx = v.times, v.converged
    assert (conv_np == conv_jx).all()
    cols = np.flatnonzero(conv_np)
    assert len(cols)
    assert (np.asarray(t_np)[:, cols] == t_jx[:, cols]).all()


def _gathers(jaxpr, in_loop=False):
    """(in a while loop?, eqn) of every gather in ``jaxpr``, nested
    jaxprs included."""
    for e in jaxpr.eqns:
        if e.primitive.name == "gather":
            yield in_loop, e
        for p in e.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else [p]):
                inner = getattr(sub, "jaxpr", None)
                if inner is not None:
                    yield from _gathers(getattr(inner, "jaxpr", inner),
                                        in_loop or e.primitive.name == "while")


def test_fixpoint_loop_gathers_share_their_indices():
    """Every gather in the fixpoint's loop body reads whole columns (one
    index for all K rows): the WAR half has no per-row gather."""
    from repro.core import simulate
    from repro.core.dse import _batch_arrays, _sparse_arrays
    from repro.core.incremental import compile_graph
    from repro.designs.paper import multicore
    from repro.kernels.maxplus import sparse as sp

    base = simulate(multicore(cores=2, prog_len=16, stride=4), trace="auto")
    g = compile_graph(base.graph)
    arr = _sparse_arrays(g, _batch_arrays(g))
    K = 16
    args, static = sp._fixpoint_args(
        arr, np.ones((K, len(base.depths)), np.int64))
    jaxpr = jax.make_jaxpr(functools.partial(
        sp._fixpoint, **static, interpret=True))(*args)
    loop = [e for in_loop, e in _gathers(jaxpr.jaxpr) if in_loop]
    # the cross pass's RAW source, WAR lane and WAR slot; the cycle check
    # pulls its labels through the same three inside its own loop
    assert len(loop) == 6
    for e in loop:
        assert e.params["slice_sizes"][0] == K, e


def test_recheck_gathers_share_their_indices():
    """The device epilogue that decides each row's verdict reads whole
    columns (one index for all K rows): a can-write constraint's target
    read comes through the check lane's barrel shift, with no per-row
    gather of the times."""
    from repro.core import simulate
    from repro.core.dse import _batch_arrays, _sparse_arrays
    from repro.core.incremental import compile_graph
    from repro.designs.paper import multicore
    from repro.kernels.maxplus import sparse as sp

    base = simulate(multicore(cores=2, prog_len=16, stride=4), trace="auto")
    g = compile_graph(base.graph)
    arr = _sparse_arrays(g, _batch_arrays(g))
    assert len(arr.chk_rd_src) and len(arr.chk_wr_src)
    assert sp.war_steps(arr.chk_seg) > 1
    K = 16
    args, static = sp._fixpoint_args(
        arr, np.ones((K, len(base.depths)), np.int64))
    jaxpr = jax.make_jaxpr(functools.partial(
        sp._fixpoint, **static, interpret=True))(*args)
    epilogue = [e for in_loop, e in _gathers(jaxpr.jaxpr)
                if not in_loop and "recheck" in str(e.source_info.name_stack)]
    # reads: source and target; writes: source, the lane and its slot,
    # and the depth columns of the shifts, the mask and the outright test
    assert len(epilogue) == 8
    for e in epilogue:
        assert e.params["slice_sizes"][0] == K, e
