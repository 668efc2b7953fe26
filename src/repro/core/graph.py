"""The (partial) simulation graph and its finalization pass.

Construction uses an adjacency list with edges stored *alongside* each node
(paper Sec. 7.3.1) so the orchestrator can traverse the incomplete graph
zero-copy while resolving queries.  Finalization — computing every node's
hardware cycle as the longest path from the virtual start — exploits the
invariant that **node creation order is a topological order** (a node's
predecessors always exist before it; see DESIGN.md Sec. 2), so a single
forward pass suffices.

Three longest-path backends:

  * ``longest_path_numpy`` — vectorized CSR forward pass over levels
    (production path on CPU; reference for the others).
  * ``repro.kernels.maxplus.sparse`` — Pallas TPU kernel: segmented cummax
    over the chain-major flat arrays :func:`export_chain_flat` builds (the
    TPU analogue of LightningSimV2's compiled CSR graph).  Used for
    device-resident batched re-sim.
  * ``longest_path_python`` — straight-line oracle used in tests.
"""
from __future__ import annotations

from typing import List, NamedTuple, Sequence, Tuple

import numpy as np

from .events import Node, NodeKind


class SimGraph:
    """Append-only adjacency-list simulation graph."""

    def __init__(self) -> None:
        self.nodes: List[Node] = []

    # -- construction ----------------------------------------------------------
    def add_node(self, module: int, kind: NodeKind, time: int,
                 fifo: int = -1, seq: int = -1) -> Node:
        n = Node(idx=len(self.nodes), module=module, kind=kind, time=time,
                 fifo=fifo, seq=seq)
        self.nodes.append(n)
        return n

    @property
    def n_nodes(self) -> int:
        return len(self.nodes)

    @property
    def n_edges(self) -> int:
        return sum(len(n.preds) for n in self.nodes)

    # -- export -----------------------------------------------------------------
    def to_csr(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        """CSR by *destination*: (indptr, src, weight, base).

        ``base[i]`` is the node's schedule-intrinsic earliest time (its
        recorded time is max(base, preds)); for reconstruction we only need
        edges + base because times were computed eagerly: base is derived as
        the recorded time when the node has no preds, else 0 (edges carry the
        stall structure; intra-module sequencing is itself an edge).
        """
        n = len(self.nodes)
        indptr = np.zeros(n + 1, dtype=np.int64)
        for i, node in enumerate(self.nodes):
            indptr[i + 1] = indptr[i] + len(node.preds)
        m = int(indptr[-1])
        src = np.zeros(m, dtype=np.int64)
        wgt = np.zeros(m, dtype=np.int64)
        base = np.zeros(n, dtype=np.int64)
        k = 0
        for i, node in enumerate(self.nodes):
            if not node.preds:
                base[i] = node.time
            for (s, w) in node.preds:
                src[k] = s
                wgt[k] = w
                k += 1
        return indptr, src, wgt, base

    def times(self) -> np.ndarray:
        return np.array([n.time for n in self.nodes], dtype=np.int64)


# ------------------------------------------------------------------------------
# Longest-path backends
# ------------------------------------------------------------------------------
def longest_path_python(indptr: np.ndarray, src: np.ndarray, wgt: np.ndarray,
                        base: np.ndarray) -> np.ndarray:
    """O(V+E) forward pass in creation (= topological) order."""
    n = len(base)
    t = base.astype(np.int64).copy()
    for i in range(n):
        lo, hi = indptr[i], indptr[i + 1]
        for k in range(lo, hi):
            cand = t[src[k]] + wgt[k]
            if cand > t[i]:
                t[i] = cand
    return t


def level_schedule(indptr: np.ndarray, src: np.ndarray) -> Tuple[np.ndarray, List[np.ndarray]]:
    """Group nodes into levels where level(i) = 1 + max(level(preds)).

    Nodes within a level have no edges among themselves, so each level can be
    relaxed fully in parallel (level-synchronous max-plus) — this is the
    parallel structure the Pallas kernel and the vectorized numpy backend use.

    Node numbering need NOT be topological (the decoupled baseline's traces
    are not); a Kahn pass computes levels for any DAG and raises on cycles.
    """
    n = len(indptr) - 1
    if n == 0:
        return np.zeros(0, dtype=np.int64), []
    indeg = np.diff(indptr).astype(np.int64)
    # out-adjacency (CSR by source) — fully vectorized Kahn below: each wave
    # gathers all frontier out-edges with the offset trick, bumps target
    # levels with maximum.at, and decrements indegrees with bincount.
    dst = np.repeat(np.arange(n, dtype=np.int64), np.diff(indptr))
    order = np.argsort(src, kind="stable")
    out_dst = dst[order]
    out_counts = np.bincount(src, minlength=n)
    out_indptr = np.concatenate([[0], np.cumsum(out_counts)]).astype(np.int64)

    level = np.zeros(n, dtype=np.int64)
    frontier = np.flatnonzero(indeg == 0)
    levels: List[np.ndarray] = []
    done = 0
    while len(frontier):
        levels.append(frontier)
        done += len(frontier)
        starts = out_indptr[frontier]
        counts = (out_indptr[frontier + 1] - starts)
        total = int(counts.sum())
        if total == 0:
            break
        offs = np.repeat(starts - np.concatenate(
            [[0], np.cumsum(counts)[:-1]]), counts)
        idx = np.arange(total, dtype=np.int64) + offs
        targets = out_dst[idx]
        lvl_edge = np.repeat(level[frontier] + 1, counts)
        np.maximum.at(level, targets, lvl_edge)
        dec = np.bincount(targets, minlength=n)
        indeg -= dec
        frontier = np.flatnonzero((indeg == 0) & (dec > 0))
    if done != n:
        raise ValueError("simulation graph contains a cycle")
    return level, levels


def longest_path_numpy(indptr: np.ndarray, src: np.ndarray, wgt: np.ndarray,
                       base: np.ndarray,
                       levels: Sequence[np.ndarray] = None) -> np.ndarray:
    """Vectorized level-synchronous forward pass."""
    n = len(base)
    t = base.astype(np.int64).copy()
    if levels is None:
        _, levels = level_schedule(indptr, src)
    for nodes in levels:
        # gather all incoming edges of this level's nodes at once
        starts = indptr[nodes]
        counts = (indptr[nodes + 1] - starts).astype(np.int64)
        total = int(counts.sum())
        if total == 0:
            continue
        offs = np.repeat(starts - np.concatenate(
            [[0], np.cumsum(counts)[:-1]]), counts)
        edge_idx = np.arange(total, dtype=np.int64) + offs
        owner = np.repeat(np.arange(len(nodes)), counts)
        cand = t[src[edge_idx]] + wgt[edge_idx]
        upd = t[nodes].copy()
        np.maximum.at(upd, owner, cand)
        t[nodes] = upd
    return t


def longest_path_chains(chains, seq_w, base, cross_dst, cross_src, cross_w,
                        max_iters: int = 0):
    """Chain-decomposed longest path (vectorized fixpoint).

    The simulation graph is a set of per-module *chains* (SEQ edges with
    additive weights) plus sparse cross-module edges (RAW/WAR).  Within a
    chain, t[i] = CW[i] + cummax(c[i] - CW[i]) where CW is the cumulative
    SEQ weight and c[i] the best cross/base contribution — a single
    ``np.maximum.accumulate``.  Cross contributions are a vectorized
    segment-max.  Iterating the two to fixpoint needs only as many rounds
    as the longest cross-edge chain (module hops), not the graph diameter —
    the decisive speedup for incremental re-simulation on deep pipelines.

    chains: list of node-id arrays in chain order; seq_w[i]: SEQ weight into
    node i (0 for chain heads); base[i]: source contribution.
    """
    n = len(base)
    NEGI = np.int64(-(1 << 60))
    c = base.astype(np.int64).copy()
    # precompute per-chain cumulative weights
    cws = [np.cumsum(seq_w[ch]) for ch in chains]
    t = np.full(n, NEGI, dtype=np.int64)
    iters = max_iters or (n + 2)
    for _ in range(iters):
        for ch, cw in zip(chains, cws):
            t[ch] = cw + np.maximum.accumulate(c[ch] - cw)
        if len(cross_dst):
            cand = t[cross_src] + cross_w
            c_new = c.copy()
            np.maximum.at(c_new, cross_dst, cand)
        else:
            c_new = c
        if np.array_equal(c_new, c):
            break
        c = c_new
    else:
        raise ValueError("longest_path_chains did not converge (cycle?)")
    return t


CHECK_FLOOR = 2      # no cycle check before round 2 * CHECK_FLOOR + 1


def cycle_check_due(rounds: int, mark: int) -> bool:
    """Whether a batched fixpoint runs its cycle check this round (callers
    ask only in a round in which no row converged).

    ``mark`` is the last round at which a row of the block converged or
    the check ran, and never below :data:`CHECK_FLOOR`: the check runs
    once some row has stayed pending for twice as many rounds, so a block
    whose rows converge together never runs it, and each check (at most
    ``rounds`` label steps) at least doubles the rounds before the next,
    keeping the checks' work within a constant factor of the rounds.
    The device lane calls it with traced scalars."""
    return rounds > 2 * mark


def pointer_cycle_rows(c, cw, chain_slices, cross_dst, cross_src, dyn_dst,
                       dyn_src, dyn_valid, steps: int) -> np.ndarray:
    """(K,) bool: the rows of a batched chain fixpoint whose predecessor
    pointers close a cycle — rows that can never converge.

    ``c`` is the (K, n) chain-major contribution matrix of the rows still
    iterating, ``cw`` the cumulative SEQ weights; the edges are those of
    :func:`longest_path_chains_batched`.  Every node gets one pointer, an
    edge of its row's graph (the longest-path form of the Bellman–Ford
    predecessor lemma):

    * a node whose own contribution attains its time under ``c`` (a
      *root*: ``c - cw`` reaches its chain's running max there, ties
      included) points to the source of its cross in-edge — its one RAW
      edge, or its WAR edge where the row's depth makes one — and to
      nothing if it has none;
    * any other node points to its chain's latest root at or before it,
      through the SEQ edges between them.

    Max-id labels then flood along the pointers for up to ``steps``
    rounds: a node's label is the largest node id on its pointer path
    (chain pointers are followed a whole run at a time, so a step crosses
    one cross edge).  A node that finds its own id in the label it pulls
    lies on a cycle of pointers.

    Exactness.  Every pointer follows edges of the row's own graph, so a
    pointer cycle is a cycle of that graph, whatever the values, ties or
    Jacobi/Gauss–Seidel staleness that chose the pointers; every cycle
    holds a WAR edge (SEQ + RAW alone are the base run's DAG), a WAR edge
    weighs 1 and no edge less than 0, so every cycle is positive and
    the row has no fixpoint: it is CYCLE, the verdict the ``bound`` and
    iteration-cap exits reach, only sooner.  A row without a cycle is never
    flagged.  The converse needs no proof for exactness: a cyclic row the
    check misses stays pending and is caught by a later check or a
    backstop.  In practice the pointers close along the cycle once its
    weight has gone round it about twice (values on it then dominate their
    other in-edges), and the label needs one step per cross edge of the
    cycle to find it, so detection takes a number of rounds set by the
    cycle's cross edges, not by the length of the design.
    """
    K, n = c.shape
    col = np.arange(n, dtype=np.int64)
    rp = np.empty((K, n), np.int64)          # latest root at or before
    for (lo, hi) in chain_slices:
        x = c[:, lo:hi] - cw[lo:hi]
        top = np.maximum.accumulate(x, axis=1)
        rp[:, lo:hi] = np.maximum.accumulate(
            np.where(x == top, col[lo:hi], -1), axis=1)
    par = np.full((K, n), -1, np.int64)      # a root's cross source
    if len(cross_dst):
        par[:, cross_dst] = cross_src
    if len(dyn_dst):
        par[:, dyn_dst] = np.where(dyn_valid, dyn_src, -1)
    par = np.where(rp == col, par, -1)
    has = par >= 0
    src = np.maximum(par, 0)

    def pull(ids):                           # at roots: max id up the path
        return np.where(has, np.take_along_axis(ids, src, axis=1), -1)

    label = pull(np.broadcast_to(col, (K, n)))
    found = np.zeros(K, dtype=bool)
    for _ in range(max(int(steps), 1)):
        fill = np.take_along_axis(label, rp, axis=1)
        found |= (fill == col).any(axis=1)
        if found.all():
            break
        label = pull(np.maximum(fill, col))
    return found


def longest_path_chains_batched(chain_slices, cw, base, cross_dst, cross_src,
                                cross_w, dyn_dst, dyn_src_idx, dyn_valid,
                                bound: int, max_iters: int = 0):
    """Batched chain-decomposed longest path: K configs in one fixpoint.

    The depth-batched analogue of :func:`longest_path_chains` — node columns
    are permuted chain-major (``chain_slices`` index contiguous column
    ranges), so the per-chain pass is one ``np.maximum.accumulate`` over a
    ``(K, len)`` contiguous view per chain, for ALL K configs at once.

    Cross edges split into two groups:

      * static (config-independent, e.g. RAW): ``cross_dst/src/w`` — 1-D
        arrays shared across the batch;
      * dynamic (config-dependent, e.g. regenerated WAR): ``dyn_dst`` (m,)
        destination columns with per-config gather indices ``dyn_src_idx``
        (K, m) and mask ``dyn_valid`` (K, m); weight is 1 (FIFO hold time).

    Destination columns must be UNIQUE within and across the two groups
    (each read node has exactly one RAW in-edge, each write node at most one
    WAR in-edge per config), so the scatter-max is a plain fancy-indexed
    ``np.maximum`` — no ``np.maximum.at`` buffering.

    ``base`` is the (K, n) initial contribution matrix (consumed in place).
    Rows converge independently: converged rows are retired from the working
    set each round, so one pathological config does not tax the others.  A
    config whose regenerated edges close a cycle is retired as soon as
    :func:`pointer_cycle_rows` finds the cycle among its predecessor
    pointers — a check that runs only when :func:`cycle_check_due` says
    some row has stayed pending well past the rows that converged, and is
    exact (it flags only rows that have a cycle).  A cycle gains only its
    own weight per trip round it, so the older exits — times past the
    acyclic ``bound``, or the iteration cap — take rounds that grow with
    the design's length; they stay as backstops.  Returns ``(times,
    converged, rounds)`` — times (K, n); ``converged[k]`` False means
    config k's regenerated edges formed a cycle (times for that row are
    meaningless).
    """
    K, n = base.shape
    times = np.empty_like(base)
    converged = np.zeros(K, dtype=bool)
    if n == 0 or K == 0:
        converged[:] = True
        return times, converged, 0
    iters = max_iters or (n + 2)
    act = np.arange(K)                      # rows still iterating
    c = base                                # (K_act, n) working contributions
    t = np.empty_like(c)
    have_dyn = len(dyn_dst) > 0
    dyn_src_act = dyn_src_idx if have_dyn else None
    dyn_valid_act = dyn_valid if have_dyn else None
    rounds = 0
    mark = CHECK_FLOOR
    while len(act):
        rounds += 1
        # ---- chain pass: t = cw + cummax(c - cw) per contiguous chain ----
        for (lo, hi) in chain_slices:
            seg = c[:, lo:hi] - cw[lo:hi]
            np.maximum.accumulate(seg, axis=1, out=seg)
            seg += cw[lo:hi]
            t[:, lo:hi] = seg
        if rounds > iters:
            break                           # leftover rows: cycle
        # ---- cross pass: unique-dst scatter-max into c ----
        changed = np.zeros(len(act), dtype=bool)
        if len(cross_dst):
            cand = t[:, cross_src] + cross_w
            old = c[:, cross_dst]
            np.maximum(cand, old, out=cand)
            changed |= (cand != old).any(axis=1)
            c[:, cross_dst] = cand
        if have_dyn:
            cand = np.take_along_axis(t, dyn_src_act, axis=1)
            cand += 1
            old = c[:, dyn_dst]
            # masked candidates: invalid (w <= S, NB, or no target) entries
            # must not contribute
            cand = np.where(dyn_valid_act, cand, old)
            np.maximum(cand, old, out=cand)
            changed |= (cand != old).any(axis=1)
            c[:, dyn_dst] = cand
        # ---- retire rows: fixpoint reached, or a cycle: pointers close
        # one, or times blew past the DAG bound ----
        over = (t > bound).any(axis=1)
        if (~changed & ~over).any():
            mark = max(mark, rounds)
        elif len(act) and cycle_check_due(rounds, mark):
            mark = rounds
            over |= pointer_cycle_rows(
                c, cw, chain_slices, cross_dst, cross_src,
                dyn_dst if have_dyn else (),
                dyn_src_act, dyn_valid_act, steps=rounds)
        done = ~changed | over
        if done.any():
            rows = act[done]
            times[rows] = t[done]
            converged[rows] = ~over[done]
            keep = ~done
            act = act[keep]
            c = c[keep]
            t = t[keep]
            if have_dyn:
                dyn_src_act = dyn_src_act[keep]
                dyn_valid_act = dyn_valid_act[keep]
    if len(act):                            # hit the iteration cap: cycles
        times[act] = t
    return times, converged, rounds


_EMPTY = np.zeros(0, np.int32)


class ChainFlatArrays(NamedTuple):
    """Flat chain-major export of the batched solver's graph view.

    The device-side (sparse Pallas) analogue of the argument list of
    :func:`longest_path_chains_batched`: every array is chain-major and
    ``int32`` (the transfer format of ``repro.kernels.maxplus.sparse``),
    padded on the node axis to a ``lanes`` multiple so VPU tiles are
    hardware-aligned.  Columns ``n..npad`` are inert: each is its own
    one-element segment seeded at the -INF sentinel, so the segmented
    cummax never leaks across them and no edge targets them.

    The WAR tables are the *config-independent* half of WAR regeneration:
    one row per blocking write of every FIFO that has at least one read
    (a blocking overflow with no reads is a structural deadlock, masked
    before solving).  The config-dependent half — which read each write
    waits on under depth ``S`` (``tgt = wseq - S - 1``, valid iff
    ``0 <= tgt < nr``) — is computed on-device from these tables plus the
    depth block, without a gather whose indices differ by config:

    * the *WAR lane* gives each such FIFO one contiguous segment of
      ``max(nr, max wseq)`` slots; its first ``nr`` slots name the FIFO's
      read columns in order (``war_lane_src``), the rest are empty (-1);
      ``war_lane_fid`` names every slot's FIFO;
    * write ``wseq`` reads slot ``war_pos = segment start + wseq - 1``
      after every slot of its segment moved right by ``S``: that brings
      in the slot of read ``tgt`` exactly when ``tgt`` is valid (a source
      before the segment start is ``tgt < 0``, an empty slot
      ``tgt >= nr``, both masked);
    * ``war_seg``, the longest segment, bounds every valid shift
      (``S <= wseq - 1 < war_seg``), so a barrel shifter of
      ``(war_seg - 1).bit_length()`` power-of-two steps suffices.  It is
      exact: a valid target's source lies inside its segment, and so does
      every slot on its way there, all of them moving by the same ``S``.

    RAW edges and WAR rows are sorted by destination column, padding
    included, so the device scatters may declare sorted indices.

    The ``chk_`` fields carry the Table-2 constraint re-check
    (``core.dse._check_constraints_stacked``) for the device, which
    decides each row's verdict from its final times.  A can-read
    constraint compares its source with a static write column.  A
    can-write constraint ``seq`` of FIFO ``f`` holds outright where
    ``seq <= S`` and otherwise compares with read ``seq - S - 1`` of the
    FIFO: that read comes through a lane of the constrained FIFOs' reads
    built as the WAR lane is (one segment of ``max(nr, max seq)`` slots a
    FIFO, slot ``chk_wr_pos`` read after the row's shift ``S``), so it
    needs no gather whose indices differ by row either.  A design without
    constraints has empty ``chk_`` arrays.
    """

    n: int                    # real node count (columns 0..n are live)
    npad: int                 # padded node-axis length (lanes multiple)
    cw: np.ndarray            # (npad,) cumulative SEQ weights, 0 in padding
    seg_start: np.ndarray     # (npad,) chain-start column of each column
    c_seed: np.ndarray        # (npad,) seed contribution (NEG sentinel pad)
    raw_dst: np.ndarray       # (E,) static RAW edges, chain-major columns
    raw_src: np.ndarray       # (E,)
    raw_w: np.ndarray         # (E,)
    war_dst: np.ndarray       # (m,) blocking-write columns (unique)
    war_wseq: np.ndarray      # (m,) 1-based write sequence numbers
    war_fid: np.ndarray       # (m,) owning FIFO (column of the depth row)
    war_nr: np.ndarray        # (m,) reads of that FIFO
    war_pos: np.ndarray       # (m,) WAR lane slot the write reads (shifted)
    war_lane_src: np.ndarray  # (L,) WAR lane: read column, -1 where empty
    war_lane_fid: np.ndarray  # (L,) WAR lane: FIFO of the slot's segment
    bound: int                # upper bound on any acyclic path length
    max_seg: int = 1          # longest chain (caps the scan's doubling steps)
    war_seg: int = 0          # longest WAR lane segment (caps the shifts)
    chk_rd_src: np.ndarray = _EMPTY   # (R,) can-read: source column
    chk_rd_tgt: np.ndarray = _EMPTY   # (R,) its seq-th write's column
    chk_rd_live: np.ndarray = _EMPTY  # (R,) 1 where that write exists
    chk_rd_out: np.ndarray = _EMPTY   # (R,) base outcome (0/1)
    chk_wr_src: np.ndarray = _EMPTY   # (W,) can-write: source column
    chk_wr_seq: np.ndarray = _EMPTY   # (W,) 1-based write sequence
    chk_wr_fid: np.ndarray = _EMPTY   # (W,) its FIFO
    chk_wr_nr: np.ndarray = _EMPTY    # (W,) reads of that FIFO
    chk_wr_pos: np.ndarray = _EMPTY   # (W,) check lane slot it reads
    chk_wr_out: np.ndarray = _EMPTY   # (W,) base outcome (0/1)
    chk_lane_src: np.ndarray = _EMPTY  # (L,) read column, -1 where empty
    chk_lane_fid: np.ndarray = _EMPTY  # (L,) FIFO of the slot's segment
    chk_seg: int = 0          # longest check lane segment (caps the shifts)


def _bucket(k: int) -> int:
    """A power of two >= ``k`` (floor 16): table lengths bucketed so
    solves of different designs reuse the device solver's jit cache."""
    m = 16
    while m < k:
        m *= 2
    return m


def _pad(a, fill):
    """``a`` as int32, padded with ``fill`` to its bucket length."""
    a = np.asarray(a)
    if len(a) == 0:
        return a.astype(np.int32)
    out = np.full(_bucket(len(a)), fill, np.int32)
    out[:len(a)] = a
    return out


def _export_checks(c_kind, c_fifo, c_seq, c_src, c_out, fifo_w_cols,
                   fifo_r_cols) -> dict:
    """The ``chk_`` fields of :class:`ChainFlatArrays` (``c_src`` in
    chain-major columns, ``c_seq`` 1-based as the engine records it)."""
    c_kind, c_fifo, c_seq, c_src, c_out = (
        np.asarray(a, np.int64) for a in (c_kind, c_fifo, c_seq, c_src,
                                          c_out))
    rd = np.flatnonzero(c_kind == 0)
    nw = np.asarray([len(w) for w in fifo_w_cols], np.int64)
    live = c_seq[rd] <= nw[c_fifo[rd]]
    tgt = np.zeros(len(rd), np.int64)
    tgt[live] = [fifo_w_cols[f][s - 1]
                 for f, s in zip(c_fifo[rd][live], c_seq[rd][live])]
    wr = np.flatnonzero(c_kind == 1)
    start, lsrc, lfid, lane, seg = {}, [], [], 0, 0
    for f in np.unique(c_fifo[wr]):
        rcols = fifo_r_cols[f]
        seg_len = max(len(rcols), int(c_seq[wr][c_fifo[wr] == f].max()), 1)
        src = np.full(seg_len, -1, np.int64)
        src[:len(rcols)] = rcols
        lsrc.append(src)
        lfid.append(np.full(seg_len, f, np.int64))
        start[int(f)] = lane
        lane += seg_len
        seg = max(seg, seg_len)
    pos = [start[int(f)] + max(int(s) - 1, 0)
           for f, s in zip(c_fifo[wr], c_seq[wr])]
    nr = np.asarray([len(r) for r in fifo_r_cols], np.int64)
    cat = (lambda parts: np.concatenate(parts) if parts
           else np.zeros(0, np.int64))
    # padding reads never hold and expect not to; padding writes
    # (seq = 0) always hold and expect to: neither counts as a flip
    return dict(
        chk_rd_src=_pad(c_src[rd], 0), chk_rd_tgt=_pad(tgt, 0),
        chk_rd_live=_pad(live, 0), chk_rd_out=_pad(c_out[rd], 0),
        chk_wr_src=_pad(c_src[wr], 0), chk_wr_seq=_pad(c_seq[wr], 0),
        chk_wr_fid=_pad(c_fifo[wr], 0), chk_wr_nr=_pad(nr[c_fifo[wr]], 1),
        chk_wr_pos=_pad(pos, 0), chk_wr_out=_pad(c_out[wr], 1),
        chk_lane_src=_pad(cat(lsrc), -1), chk_lane_fid=_pad(cat(lfid), 0),
        chk_seg=seg)


def export_chain_flat(chain_slices, cw, c_seed, raw_dst, raw_src, raw_w,
                      fifo_w_cols, fifo_r_cols, fifo_blocking, bound: int,
                      neg: int, lanes: int = 128,
                      checks=None) -> ChainFlatArrays:
    """Build the :class:`ChainFlatArrays` transfer view of a chain-major
    graph (``neg`` is the int32 -INF sentinel everything is clipped to).
    ``checks``, where given, is the graph's constraints as ``(c_kind,
    c_fifo, c_seq, c_src, c_out)`` with ``c_src`` in chain-major
    columns; without it the ``chk_`` arrays are empty."""
    n = len(cw)
    npad = max(((n + lanes - 1) // lanes) * lanes, lanes)
    seg = np.arange(npad, dtype=np.int32)      # padding: isolated segments
    for (lo, hi) in chain_slices:
        seg[lo:hi] = lo
    cwp = np.zeros(npad, np.int32)
    cwp[:n] = np.minimum(cw, np.iinfo(np.int32).max)
    cs = np.full(npad, neg, np.int32)
    cs[:n] = np.maximum(c_seed, neg)
    wd, ws, wf, wnr, wpos, lsrc, lfid = [], [], [], [], [], [], []
    lane = war_seg = 0
    for fid, wcols in enumerate(fifo_w_cols):
        rcols = fifo_r_cols[fid]
        blk = fifo_blocking[fid]
        if len(wcols) == 0 or len(rcols) == 0 or not blk.any():
            continue
        keep = np.flatnonzero(blk)             # only blocking writes can WAR
        wd.append(wcols[keep])
        ws.append(keep + 1)                    # 1-based write sequence
        wf.append(np.full(len(keep), fid, np.int64))
        wnr.append(np.full(len(keep), len(rcols), np.int64))
        wpos.append(lane + keep)               # slot wseq - 1 of the segment
        seg_len = max(len(rcols), int(keep[-1]) + 1)
        src = np.full(seg_len, -1, np.int64)
        src[:len(rcols)] = rcols
        lsrc.append(src)
        lfid.append(np.full(seg_len, fid, np.int64))
        lane += seg_len
        war_seg = max(war_seg, seg_len)

    def cat(parts):
        return (np.concatenate(parts).astype(np.int32) if parts
                else np.zeros(0, np.int32))

    raw_dst, raw_src, raw_w = (np.asarray(a) for a in (raw_dst, raw_src,
                                                       raw_w))
    order = np.argsort(raw_dst, kind="stable")
    raw_dst, raw_src, raw_w = raw_dst[order], raw_src[order], raw_w[order]
    war_dst_c = cat(wd)
    worder = np.argsort(war_dst_c, kind="stable")
    war = [cat(p)[worder] for p in (ws, wf, wnr, wpos)]

    # padding edges and WAR rows repeat the last destination, keeping the
    # destinations sorted; what they scatter is masked to -INF
    raw_last = int(raw_dst[-1]) if len(raw_dst) else 0
    war_last = int(war_dst_c[worder[-1]]) if len(war_dst_c) else 0
    return ChainFlatArrays(
        n=n, npad=npad, cw=cwp, seg_start=seg, c_seed=cs,
        # padding edges: weight = -INF (a max-identity), src = 0
        raw_dst=_pad(raw_dst, raw_last),
        raw_src=_pad(raw_src, 0),
        raw_w=_pad(np.maximum(raw_w, neg), neg),
        # padding WAR rows: wseq = 0 makes every target negative (masked);
        # pos = 0 keeps their lane read in bounds.  Padding lane slots are
        # empty, and no real write's shifted source reaches them
        war_dst=_pad(war_dst_c[worder], war_last),
        war_wseq=_pad(war[0], 0), war_fid=_pad(war[1], 0),
        war_nr=_pad(war[2], 1), war_pos=_pad(war[3], 0),
        war_lane_src=_pad(cat(lsrc), -1),
        war_lane_fid=_pad(cat(lfid), 0),
        bound=int(bound),
        max_seg=max([hi - lo for (lo, hi) in chain_slices] or [1]),
        war_seg=war_seg,
        **(_export_checks(*checks, fifo_w_cols, fifo_r_cols)
           if checks is not None else {}))

