"""Depth-batched design-space exploration: K re-simulations in one pass.

The paper's Table 6 capability — re-evaluating a finished run under new
FIFO depths in microseconds — turned into a *throughput* engine.  FIFO
sizing spaces are 10^3–10^5 configurations; evaluating them one
``resimulate()`` call at a time serializes Python and numpy call overhead.
``resimulate_batch`` instead treats the K candidate depth vectors as a
leading batch axis over the whole incremental pipeline (the
compile-once/re-solve-many structure of LightningSimV2, arXiv 2404.09471,
lifted to a batch of solves):

  1. regenerate the depth-dependent WAR edges for ALL K configs as stacked
     index/mask arrays (the static SEQ+RAW skeleton is shared via
     :class:`~repro.core.incremental.CompiledGraph` — for trace-compiled
     base runs it was built directly from the op trace at initial-sim
     time, so no Python graph object is ever walked — and per-(FIFO,
     depth) columns are cached: depth values repeat heavily across a
     sweep);
  2. run the chain-decomposed longest-path fixpoint with a leading batch
     axis — one ``np.maximum.accumulate`` per module chain over the whole
     batch instead of K Python loops.  The production solver seeds every
     config with the depth-INDEPENDENT no-WAR fixpoint (computed once at
     compile time) and Gauss-Seidel-sweeps chains in module order with
     dirty tracking, so a config only pays for the part of the pipeline its
     WAR constraints actually move — slack configs converge with zero
     sweeps;
  3. re-check every stored NB/probe constraint for all K configs in one
     vectorized pass;
  4. mask out structurally-infeasible configs (a committed blocking write
     whose target read never occurred ⇒ deadlock), cyclic configs (the
     regenerated event order is invalid) and constraint-violating configs,
     and fall back to a full re-simulation for exactly that subset.

Backends: ``"numpy"`` (default, above), ``"reference"`` (the synchronous
Jacobi :func:`~repro.core.graph.longest_path_chains_batched` — the oracle
the production solver is tested against), ``"jax"`` — the sparse
chain-structured Pallas max-plus solver (``repro.kernels.maxplus.sparse``:
segmented cummax over the chain-major flat arrays, on-device WAR
regeneration, O(K·n + K·edges) memory) for device-resident sweeps of any
graph size — and ``"jax_dense"``, the historical ``jax.vmap`` lowering of
the dense O(n²)-per-config max-plus fixpoint, kept for tiny graphs and as
a second device oracle.
"""
from __future__ import annotations

import copy
import threading
import time as _time
from contextlib import nullcontext
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from ..device import pallas_interpret, span
from .engine import OmniSim, simulate
from .graph import (CHECK_FLOOR, cycle_check_due, export_chain_flat,
                    longest_path_chains, longest_path_chains_batched,
                    pointer_cycle_rows)
from .incremental import NEGI, CompiledGraph, compile_graph
from .program import SimResult

# per-config status codes.  The first four are solver verdicts (what
# ``solve_block_status`` classifies); the last four are *service-level*
# terminal statuses used by the sweep subsystem (``repro/sweep``) so that
# every submitted row ends in a definite state even when it was never
# solved: cancelled by the client, failed by a faulting shard after
# retries, expired past its deadline, or shed by admission control.
REUSED, DEADLOCK, CYCLE, VIOLATED = 0, 1, 2, 3
CANCELLED, FAULTED, TIMED_OUT, REJECTED = 4, 5, 6, 7

# Per-Program re-entrant locks serializing every transient in-place
# mutation (the fallback re-simulation sets FIFO depths and restores
# them) against readers of that state on other threads — notably the
# sweep cache's fingerprint-and-build path.  Per Program, not global:
# unrelated designs must not stall behind one design's engine re-sims.
_LOCK_CREATE = threading.Lock()


def program_mutation_lock(program) -> threading.RLock:
    lock = getattr(program, "_mutation_lock", None)
    if lock is None:
        with _LOCK_CREATE:
            lock = getattr(program, "_mutation_lock", None)
            if lock is None:
                lock = threading.RLock()
                program._mutation_lock = lock
    return lock

_STATUS_REASON = {
    REUSED: "constraints satisfied",
    CYCLE: "regenerated WAR edges create a cycle (event order invalid)",
    CANCELLED: "request cancelled before this config was scheduled",
    FAULTED: "shard solve faulted repeatedly (retries exhausted)",
    TIMED_OUT: "deadline exceeded before this config was solved",
    REJECTED: "rejected by admission control",
}

# statuses the exact engine fallback applies to: solver verdicts that a
# full re-simulation can refine.  Service-level terminal statuses
# (CANCELLED/FAULTED/TIMED_OUT/REJECTED) must never pay for engine work.
FALLBACK_STATUSES = (DEADLOCK, CYCLE, VIOLATED)


@dataclass
class _BatchArrays:
    """Chain-major-permuted view of a CompiledGraph for batched solving."""

    perm: np.ndarray               # new pos -> original node idx
    inv: np.ndarray                # original node idx -> new pos
    slices: List[tuple]            # contiguous (lo, hi) per module chain
    starts: np.ndarray             # chain start offsets (for chain-of-node)
    cw: np.ndarray                 # cumulative SEQ weight, chain-major
    base_p: np.ndarray             # base contribution, chain-major (NEGI=none)
    raw_dst: np.ndarray            # RAW edges, chain-major columns
    raw_src: np.ndarray
    raw_w: np.ndarray
    raw_buckets: dict              # src chain -> [(dst chain, src, dst, w)]
    fifo_w_cols: List[np.ndarray]  # per FIFO: write node columns
    fifo_r_cols: List[np.ndarray]  # per FIFO: read node columns
    fifo_blocking: List[np.ndarray]
    fifo_need: np.ndarray          # min depth to avoid structural deadlock
    fifo_rchain: np.ndarray        # per FIFO: reader module chain (-1 = none)
    fifo_wchain: np.ndarray        # per FIFO: writer module chain (-1 = none)
    c_src_p: np.ndarray            # constraint source nodes, chain-major
    bound: int                     # upper bound on any acyclic path length
    t_inf: np.ndarray = None       # no-WAR (infinite-depth) fixpoint times
    c_inf: np.ndarray = None       # ... and its contribution vector
    war_cache: Dict[tuple, tuple] = field(default_factory=dict)
    sparse: object = None          # lazy ChainFlatArrays (jax sparse lane)


def _chain_of(starts: np.ndarray, col: int) -> int:
    return int(np.searchsorted(starts, col, side="right") - 1)


def _batch_arrays(cache: CompiledGraph) -> _BatchArrays:
    if cache.batch is not None:
        return cache.batch
    n = cache.n
    perm = (np.concatenate(cache.chains) if cache.chains
            else np.zeros(0, np.int64))
    assert len(perm) == n, "every node must belong to exactly one chain"
    inv = np.empty(n, dtype=np.int64)
    inv[perm] = np.arange(n, dtype=np.int64)
    slices, cw_parts, off = [], [], 0
    for ch in cache.chains:
        slices.append((off, off + len(ch)))
        cw_parts.append(np.cumsum(cache.seq_w[ch]))
        off += len(ch)
    cw = np.concatenate(cw_parts) if cw_parts else np.zeros(0, np.int64)
    starts = np.asarray([lo for (lo, _) in slices] or [0], np.int64)
    raw_dst = inv[cache.raw_dst]
    raw_src = inv[cache.raw_src]
    # the unique-destination invariant the batched scatter-max relies on:
    # one RAW in-edge per read node, one WAR in-edge per write node, and
    # read/write node sets are disjoint (engine construction guarantees it)
    assert len(np.unique(raw_dst)) == len(raw_dst), \
        "RAW destinations must be unique for the batched fixpoint"
    # bucket RAW edges by (src chain, dst chain) for the Gauss-Seidel sweep
    raw_buckets: dict = {}
    if len(raw_dst):
        sc = np.searchsorted(starts, raw_src, side="right") - 1
        dc = np.searchsorted(starts, raw_dst, side="right") - 1
        order = np.lexsort((dc, sc))
        s_s, d_s = sc[order], dc[order]
        cut = np.flatnonzero(np.diff(s_s) | np.diff(d_s))
        bounds = np.concatenate([[0], cut + 1, [len(order)]])
        for a, b in zip(bounds[:-1], bounds[1:]):
            idx = order[a:b]
            raw_buckets.setdefault(int(s_s[a]), []).append(
                (int(d_s[a]), raw_src[idx], raw_dst[idx], cache.raw_w[idx]))
    w_cols, r_cols, blocking, need, rchain, wchain = [], [], [], [], [], []
    for (w_nodes, r_nodes, blk) in cache.fifos:
        wc = inv[w_nodes] if len(w_nodes) else w_nodes
        rc = inv[r_nodes] if len(r_nodes) else r_nodes
        w_cols.append(wc)
        r_cols.append(rc)
        blocking.append(blk)
        rchain.append(_chain_of(starts, rc[0]) if len(rc) else -1)
        wchain.append(_chain_of(starts, wc[0]) if len(wc) else -1)
        if blk.any():
            w_seq = np.arange(1, len(w_nodes) + 1, dtype=np.int64)
            need.append(int(w_seq[blk].max()) - len(r_nodes))
        else:
            need.append(-(1 << 30))
    finite_base = cache.base[cache.base != NEGI]
    bound = int((finite_base.max() if len(finite_base) else 0)
                + cache.seq_w.sum() + cache.raw_w.sum()
                + sum(len(w) for (w, _, _) in cache.fifos) + 1)
    ba = _BatchArrays(
        perm=perm, inv=inv, slices=slices, starts=starts, cw=cw,
        base_p=cache.base[perm] if n else cache.base,
        raw_dst=raw_dst, raw_src=raw_src, raw_w=cache.raw_w,
        raw_buckets=raw_buckets,
        fifo_w_cols=w_cols, fifo_r_cols=r_cols, fifo_blocking=blocking,
        fifo_need=np.asarray(need, np.int64),
        fifo_rchain=np.asarray(rchain, np.int64),
        fifo_wchain=np.asarray(wchain, np.int64),
        c_src_p=(inv[cache.c_src] if len(cache.c_src) else cache.c_src),
        bound=bound)
    # depth-independent seed: the no-WAR (infinite-depth) fixpoint is a
    # lower bound of every config's fixpoint (WAR edges only delay), so the
    # per-config solve starts from it and pays only for the WAR impact
    if n:
        t_inf = longest_path_chains(cache.chains, cache.seq_w, cache.base,
                                    cache.raw_dst, cache.raw_src,
                                    cache.raw_w)[perm]
        c_inf = ba.base_p.copy()
        if len(raw_dst):
            c_inf[raw_dst] = np.maximum(c_inf[raw_dst],
                                        t_inf[raw_src] + cache.raw_w)
    else:
        t_inf = np.zeros(0, np.int64)
        c_inf = np.zeros(0, np.int64)
    ba.t_inf = t_inf
    ba.c_inf = c_inf
    cache.batch = ba
    return ba


def _war_cols(ba: _BatchArrays, fid: int, S: int):
    """Cached per-(FIFO, depth) regenerated-WAR columns.

    Returns (src_col, valid_col, cand_inf): for each of the FIFO's writes,
    the chain-major column of its (w-S)-th read, whether the edge exists
    under depth S (blocking, target read committed), and the edge's
    candidate contribution under the no-WAR seed times (NEGI = none).
    """
    key = (fid, S)
    hit = ba.war_cache.get(key)
    if hit is not None:
        return hit
    w_cols = ba.fifo_w_cols[fid]
    r_cols = ba.fifo_r_cols[fid]
    nw, nr = len(w_cols), len(r_cols)
    w_seq = np.arange(1, nw + 1, dtype=np.int64)
    tgt = w_seq - S - 1
    valid = ba.fifo_blocking[fid] & (tgt >= 0) & (tgt < nr)
    src = (r_cols[np.clip(tgt, 0, nr - 1)] if nr
           else np.zeros(nw, np.int64))
    cand = np.where(valid, ba.t_inf[src] + 1, NEGI)
    # a depth whose candidates cannot move the no-WAR fixpoint needs no
    # seed push at all (slack WAR — the common case when depths grow)
    effective = bool((cand > ba.c_inf[w_cols]).any())
    entry = (src, valid, cand, effective)
    ba.war_cache[key] = entry
    return entry


@dataclass
class BatchOutcome:
    """Result of :func:`resimulate_batch` over K depth configurations."""

    ok: np.ndarray                 # (K,) bool: graph reused for this config
    cycles: np.ndarray             # (K,) int64: cycle count (-1 = no result)
    status: np.ndarray             # (K,) int8: REUSED/DEADLOCK/CYCLE/VIOLATED
    violated: np.ndarray           # (K,) int64: # of flipped constraints
    reasons: List[str]
    results: List[Optional[SimResult]]
    elapsed_s: float
    fixpoint_rounds: int = 0
    n_unique: int = 0              # distinct depth rows actually solved

    @property
    def n_reused(self) -> int:
        return int(self.ok.sum())

    @property
    def n_fallback(self) -> int:
        return len(self.ok) - self.n_reused

    def us_per_config(self) -> float:
        return self.elapsed_s / max(len(self.ok), 1) * 1e6


def _regen_war_stacked(ba: _BatchArrays, Db: np.ndarray):
    """Stacked WAR regeneration for the reference (Jacobi) backend.

    Returns (dyn_dst (m,), dyn_src (B, m), dyn_valid (B, m)) covering every
    FIFO that can overflow for at least one config in the block; entry
    (k, j) is the regenerated WAR edge of the j-th write under config k
    (masked False where w <= S_k, the write is non-blocking, or the target
    read does not exist).
    """
    B = len(Db)
    dst_parts, src_parts, valid_parts = [], [], []
    for fid, w_cols in enumerate(ba.fifo_w_cols):
        nw = len(w_cols)
        if nw == 0 or int(Db[:, fid].min()) >= nw:
            continue                       # no config overflows this FIFO
        r_cols = ba.fifo_r_cols[fid]
        nr = len(r_cols)
        w_seq = np.arange(1, nw + 1, dtype=np.int64)
        tgt = w_seq[None, :] - Db[:, fid][:, None] - 1        # (B, nw)
        valid = ba.fifo_blocking[fid][None, :] & (tgt >= 0) & (tgt < nr)
        if nr:
            src = r_cols[np.clip(tgt, 0, nr - 1)]
        else:
            src = np.zeros((B, nw), np.int64)
        dst_parts.append(w_cols)
        src_parts.append(src)
        valid_parts.append(valid)
    if not dst_parts:
        z = np.zeros(0, np.int64)
        return z, np.zeros((B, 0), np.int64), np.zeros((B, 0), bool)
    return (np.concatenate(dst_parts),
            np.concatenate(src_parts, axis=1),
            np.concatenate(valid_parts, axis=1))


def _check_constraints_stacked(cache: CompiledGraph, ba: _BatchArrays,
                               t: np.ndarray, Db: np.ndarray):
    """Vectorized Table-2 re-check of all constraints for a block of configs.

    ``t``: (n, B) node times in chain-major (node-major) layout.  Returns
    the (B,) count of flipped constraint outcomes (0 ⇒ reusable).
    """
    nC = len(cache.c_kind)
    B = len(Db)
    if nC == 0:
        return np.zeros(B, np.int64)
    ok = np.zeros((nC, B), bool)
    st = t[ba.c_src_p]                                        # (nC, B)
    for fid in range(len(cache.fifos)):
        sel = cache.c_fifo == fid
        if not sel.any():
            continue
        w_cols, r_cols = ba.fifo_w_cols[fid], ba.fifo_r_cols[fid]
        nw, nr = len(w_cols), len(r_cols)
        seq = cache.c_seq[sel]
        kind = cache.c_kind[sel]
        stf = st[sel]                                         # (m, B)
        okf = np.zeros((len(seq), B), bool)
        # reads: target = seq-th write (config-independent)
        rd = kind == 0
        if rd.any():
            tgt = np.minimum(seq[rd] - 1, max(nw - 1, 0))
            exists = (seq[rd] - 1) < nw
            t_tgt = (t[w_cols[tgt]] if nw
                     else np.zeros((int(rd.sum()), B), t.dtype))
            okf[rd] = exists[:, None] & (t_tgt < stf[rd])
        # writes: trivially true if seq <= S, else target read (per config)
        wr = kind == 1
        if wr.any():
            seq_w = seq[wr][:, None]                          # (m, 1)
            S = Db[None, :, fid]                              # (1, B)
            triv = seq_w <= S
            tgt_w = seq_w - S - 1                             # (m, B)
            exists_w = tgt_w < nr
            if nr:
                idx = r_cols[np.clip(tgt_w, 0, nr - 1)]
                t_tgt_w = np.take_along_axis(t, idx, axis=0)
            else:
                t_tgt_w = np.zeros(tgt_w.shape, t.dtype)
            okf[wr] = triv | (exists_w & (t_tgt_w < stf[wr]))
        ok[sel] = okf
    return (ok != cache.c_out[:, None]).sum(axis=0).astype(np.int64)


def _solve_block_reference(ba: _BatchArrays, Db: np.ndarray):
    """Jacobi reference solve via :func:`longest_path_chains_batched`
    (one synchronized cross pass per round; the testing oracle)."""
    B = len(Db)
    n = len(ba.perm)
    if ba.bound < (1 << 28):
        dtype, NEG = np.int32, -(1 << 29)
    else:
        dtype, NEG = np.int64, int(NEGI)
    base = np.where(ba.base_p == NEGI, NEG, ba.base_p).astype(dtype)
    base = np.broadcast_to(base, (B, n)).copy()
    dyn_dst, dyn_src, dyn_valid = _regen_war_stacked(ba, Db)
    times_p, conv, rounds = longest_path_chains_batched(
        ba.slices, ba.cw.astype(dtype), base,
        ba.raw_dst, ba.raw_src, ba.raw_w.astype(dtype),
        dyn_dst, dyn_src, dyn_valid, bound=ba.bound)
    return np.ascontiguousarray(times_p.T), conv, rounds


def _solve_block_numpy(ba: _BatchArrays, Db: np.ndarray):
    """Batched seeded Gauss-Seidel fixpoint for one block of configs.

    Node-major ``(n, K)`` layout (cross-edge gathers/scatters hit
    contiguous K-wide rows; the per-chain cummax streams contiguous
    slabs).  Every config starts AT the no-WAR fixpoint, its regenerated
    WAR candidates (per-(FIFO, depth) cached columns) are applied once,
    and then chains are swept in module order with per-(chain, config)
    dirty tracking — so a sweep recomputes only the chains some config's
    WAR constraints actually moved, and slack configs converge with zero
    sweeps.  int32 when the path-length bound allows (halves the traffic).

    Returns (times (n, K) in solve dtype, converged (K,), sweeps).
    Non-converged configs (a WAR cycle) report False and undefined times:
    retired when :func:`~repro.core.graph.pointer_cycle_rows` finds the
    cycle among their predecessor pointers (run when
    :func:`~repro.core.graph.cycle_check_due` says so), or by the
    backstops — times past the acyclic bound, the sweep cap.
    """
    K = len(Db)
    n = len(ba.perm)
    if ba.bound < (1 << 28):
        dtype, NEG = np.int32, -(1 << 29)
    else:
        dtype, NEG = np.int64, int(NEGI)
    conv_out = np.ones(K, dtype=bool)
    if n == 0 or K == 0:
        return np.zeros((n, K), dtype), conv_out, 0
    cw = ba.cw.astype(dtype)
    t_seed = np.maximum(ba.t_inf, NEG).astype(dtype)
    c_seed = np.maximum(ba.c_inf, NEG).astype(dtype)
    c = np.empty((n, K), dtype=dtype)
    c[:] = c_seed[:, None]
    t = np.empty((n, K), dtype=dtype)
    t[:] = t_seed[:, None]
    nch = len(ba.slices)
    dirty = np.zeros((nch, K), dtype=bool)
    # ---- seed pass: apply each config's WAR candidates over t_inf ----
    war_entries = []        # [rchain, wchain, dcols, src_mat, val_mat, inv]
    for fid, w_cols in enumerate(ba.fifo_w_cols):
        nw = len(w_cols)
        if nw == 0 or int(Db[:, fid].min()) >= nw:
            continue                       # no config overflows this FIFO
        if len(ba.fifo_r_cols[fid]) == 0:
            continue       # blocking overflow ⇒ already masked as deadlock
        uniq, invq = np.unique(Db[:, fid], return_inverse=True)
        cols = [_war_cols(ba, fid, int(S)) for S in uniq]
        src_mat = np.stack([cc[0] for cc in cols], axis=1)    # (nw, u)
        val_mat = np.stack([cc[1] for cc in cols], axis=1)
        if any(cc[3] for cc in cols):      # some depth's WAR binds at seed
            cand_mat = np.maximum(np.stack([cc[2] for cc in cols], axis=1),
                                  NEG).astype(dtype)
            cand = cand_mat[:, invq]                          # (nw, K)
            old = c[w_cols]
            np.maximum(cand, old, out=cand)
            chm = cand != old
            if chm.any():
                c[w_cols] = cand
                dirty[int(ba.fifo_wchain[fid])] |= chm.any(axis=0)
        war_entries.append([int(ba.fifo_rchain[fid]),
                            int(ba.fifo_wchain[fid]), w_cols,
                            src_mat, val_mat, invq])
    war_by_reader: dict = {}
    for e in war_entries:
        war_by_reader.setdefault(e[0], []).append(e)

    times_out = None
    act = np.arange(K)
    sweeps = 0
    max_sweeps = n + 2
    mark = CHECK_FLOOR
    while True:
        # ---- retire configs with no pending chains, or cyclic ones ----
        pend = dirty.any(axis=0)
        if sweeps >= 8 or not pend.any():
            over = (t > ba.bound).any(axis=0)
        else:
            over = np.zeros(len(act), dtype=bool)
        if (~pend & ~over).any():
            mark = max(mark, sweeps)
        elif cycle_check_due(sweeps, mark):
            mark = sweeps
            over |= _pointer_cycles(ba, c, cw, war_entries, sweeps)
        done = ~pend | over
        if done.any():
            if done.all() and len(act) == K:
                # fast path: the whole block settles at once — hand the
                # working matrix back without the (n, K) copy
                conv_out[act] = ~over
                return t, conv_out, sweeps
            if times_out is None:
                times_out = np.empty((n, K), dtype=dtype)
            rows = act[done]
            times_out[:, rows] = t[:, done]
            conv_out[rows] = ~over[done]
            if done.all():
                break
            keep = ~done
            act = act[keep]
            c = np.ascontiguousarray(c[:, keep])
            t = np.ascontiguousarray(t[:, keep])
            dirty = np.ascontiguousarray(dirty[:, keep])
            for e in war_entries:
                e[5] = e[5][keep]
        if sweeps >= max_sweeps:
            if times_out is None:
                times_out = np.empty((n, K), dtype=dtype)
            times_out[:, act] = t                  # cap hit: cyclic leftovers
            conv_out[act] = False
            break
        sweeps += 1
        # ---- one Gauss-Seidel sweep over dirty chains, module order ----
        for ci in range(nch):
            if not dirty[ci].any():
                continue
            dirty[ci] = False
            lo, hi = ba.slices[ci]
            seg = c[lo:hi] - cw[lo:hi, None]
            np.maximum.accumulate(seg, axis=0, out=seg)
            seg += cw[lo:hi, None]
            if np.array_equal(seg, t[lo:hi]):
                continue                   # no new times ⇒ pushes stand
            t[lo:hi] = seg
            for (dc, scols, dcols, w) in ba.raw_buckets.get(ci, ()):
                cand = t[scols] + w[:, None].astype(dtype)
                old = c[dcols]
                np.maximum(cand, old, out=cand)
                chm = cand != old
                if chm.any():
                    c[dcols] = cand
                    dirty[dc] |= chm.any(axis=0)
            for e in war_by_reader.get(ci, ()):
                wc, dcols, src_mat, val_mat, invq = \
                    e[1], e[2], e[3], e[4], e[5]
                src_idx = src_mat[:, invq]                    # (nw, K_act)
                cand = np.take_along_axis(t, src_idx, axis=0)
                cand += 1
                old = c[dcols]
                cand = np.where(val_mat[:, invq], cand, old)
                np.maximum(cand, old, out=cand)
                chm = cand != old
                if chm.any():
                    c[dcols] = cand
                    dirty[wc] |= chm.any(axis=0)
    return times_out, conv_out, sweeps


def _pointer_cycles(ba: _BatchArrays, c: np.ndarray, cw: np.ndarray,
                    war_entries, steps: int) -> np.ndarray:
    """:func:`~repro.core.graph.pointer_cycle_rows` over the node-major
    working contributions of :func:`_solve_block_numpy` (its WAR edges
    as its per-FIFO entries hold them, for the configs still active)."""
    dst = [e[2] for e in war_entries]
    src = [e[3][:, e[5]].T for e in war_entries]
    val = [e[4][:, e[5]].T for e in war_entries]
    K = c.shape[1]
    return pointer_cycle_rows(
        np.ascontiguousarray(c.T), cw, ba.slices, ba.raw_dst, ba.raw_src,
        np.concatenate(dst) if dst else np.zeros(0, np.int64),
        np.concatenate(src, axis=1) if src else np.zeros((K, 0), np.int64),
        np.concatenate(val, axis=1) if val else np.zeros((K, 0), bool),
        steps)


def solve_block_status(cache: CompiledGraph, depth_block,
                       backend: str = "numpy", block: int = 128,
                       jax_interpret: Optional[bool] = None,
                       counts: Optional[dict] = None):
    """Engine-free solve phase of :func:`resimulate_batch`.

    Classifies a block of depth vectors against ``cache`` alone — no
    ``OmniSim`` engine, no Python generators, no fallback re-simulation —
    which makes it the unit of work the sweep service (``repro/sweep``)
    ships to shard workers: a :class:`~repro.core.incremental.CompiledGraph`
    pickles cleanly (numpy arrays + the lazily rebuilt ``_BatchArrays``
    view), so a worker process holding only the compiled graph can solve
    any depth block of that design.

    Returns ``(status, cycles, violated, fixpoint_rounds)`` — per config:
    REUSED with its exact cycle count, or DEADLOCK / CYCLE / VIOLATED with
    ``cycles = -1`` (the caller decides whether to pay for the exact
    fallback re-simulation, which *does* need the engine).

    The ``"jax"`` lane decides each row's constraint re-check and cycle
    count on the device and copies back only those; the host re-checks
    the rows it solved exactly itself (the device left them undecided).
    The other lanes re-check every row on the host.  ``counts``, where
    given, receives ``device_verdict_rows`` and ``host_recheck_rows``,
    the rows each side decided.

    ``jax_interpret`` (jax backends only) defaults to the platform's
    Pallas mode (:func:`repro.device.pallas_interpret`): compiled on a
    TPU, interpreted on the CPU.
    """
    ba = _batch_arrays(cache)
    D = np.asarray(depth_block, dtype=np.int64)
    if D.ndim == 1:
        D = D[None, :]
    K = len(D)
    status = np.zeros(K, dtype=np.int8)
    cycles = np.full(K, -1, dtype=np.int64)
    violated = np.zeros(K, dtype=np.int64)
    # ① structural infeasibility: committed blocking write whose target
    # read never occurred can never commit — deadlock under these depths
    dead = (D < ba.fifo_need[None, :]).any(axis=1)
    status[dead] = DEADLOCK
    alive = np.flatnonzero(~dead)
    total_rounds = 0
    # per solved block: (rows of ``alive``, converged, violated, cycles,
    # positions the host re-checks, their node times (n, len(positions)))
    blocks = []

    if len(alive):
        if backend in ("jax", "jax_dense"):
            jax_interpret = pallas_interpret(jax_interpret)
        if backend == "jax_dense":
            sl = np.arange(len(alive))
            t_nm, conv = _solve_dense_jax(cache, ba, D[alive],
                                          interpret=jax_interpret,
                                          block=block)
            blocks.append(_host_verdicts(sl, t_nm, conv))
        elif backend in ("numpy", "reference", "jax"):
            for lo in range(0, len(alive), max(block, 1)):
                sl = np.arange(lo, min(lo + max(block, 1), len(alive)))
                if backend == "jax":    # sparse chain-structured Pallas lane
                    *verdicts, rounds = _solve_sparse_jax(
                        cache, ba, D[alive[sl]], interpret=jax_interpret,
                        block=block)
                    blocks.append((sl, *verdicts))
                else:
                    solve = (_solve_block_numpy if backend == "numpy"
                             else _solve_block_reference)
                    t_nm, conv, rounds = solve(ba, D[alive[sl]])
                    blocks.append(_host_verdicts(sl, t_nm, conv))
                total_rounds = max(total_rounds, rounds)
        else:
            raise ValueError(f"unknown backend {backend!r}")
    host_rows = 0
    if blocks:
        with span("solve.recheck", rounds=total_rounds) as sp:
            for sl, conv, viol, cyc, host, t_nm in blocks:
                rows = alive[sl]
                if len(host):
                    # ③ constraint re-check, all configs at once
                    viol[host] = _check_constraints_stacked(
                        cache, ba, t_nm, D[rows[host]])
                    cyc[host] = (t_nm.max(axis=0) if t_nm.shape[0]
                                 else 0)
                    host_rows += len(host)
                status[rows[~conv]] = CYCLE                   # ② event order
                violated[rows[conv]] = viol[conv]
                status[rows[conv & (viol > 0)]] = VIOLATED
                good = conv & (viol == 0)
                cycles[rows[good]] = cyc[good]
            sp.set_metadata(rows=host_rows,
                            violated_rows=int((violated > 0).sum()))
    if counts is not None:
        counts["host_recheck_rows"] = host_rows
        counts["device_verdict_rows"] = len(alive) - host_rows
    return status, cycles, violated, total_rounds


def _host_verdicts(sl: np.ndarray, t_nm: np.ndarray, conv: np.ndarray):
    """A host lane's block in :func:`solve_block_status`'s form: every
    row's verdict is the host re-check's."""
    k = len(sl)
    return (sl, conv, np.zeros(k, np.int64), np.zeros(k, np.int64),
            np.arange(k), t_nm)


def status_reason(cache: CompiledGraph, status_k: int, violated_k: int,
                  depths_row: np.ndarray,
                  fifo_names: Optional[List[str]] = None) -> str:
    """Human-readable verdict for one config of :func:`solve_block_status`
    (exactly the strings :func:`resimulate_batch` reports)."""
    if status_k in _STATUS_REASON:
        return _STATUS_REASON[status_k]
    if status_k == DEADLOCK:
        ba = _batch_arrays(cache)
        fid = int(np.flatnonzero(depths_row < ba.fifo_need)[0])
        name = fifo_names[fid] if fifo_names else f"fifo{fid}"
        return (f"a committed write on '{name}' can never commit "
                f"with depth {int(depths_row[fid])} (would deadlock)")
    return (f"{int(violated_k)} constraint(s) violated — "
            f"control/data flow diverges")


class _ReusedShell(SimResult):
    """A REUSED row's result.  Its ``constraints`` are a copy of the base
    run's (``base_constraints``), made on first access: a shell of its
    own to mutate, as any result is, without a copy a row for every
    shell that is never read (2,632 constraints, 21 kB, a row of
    ``multicore``, held for as long as the caller holds the result)."""

    @property
    def constraints(self) -> list:
        own = self.__dict__.get("_constraints")
        if own is None:
            own = self.__dict__["_constraints"] = list(self.base_constraints)
        return own

    @constraints.setter
    def constraints(self, value) -> None:
        self.__dict__["_constraints"] = value


def materialize_block(result: SimResult, Du: np.ndarray,
                      status_u: np.ndarray, cycles_u: np.ndarray,
                      violated_u: np.ndarray, fallback_mask: np.ndarray,
                      engine_label: str = "omnisim-batch", lock=None,
                      hybrid_cache=None):
    """Post-solve verdict assembly shared by :func:`resimulate_batch` and
    the sweep scheduler (``repro/sweep/scheduler.py``).

    For each unique depth row: the human-readable reason string, a
    lightweight REUSED :class:`SimResult` shell carrying the solved cycle
    count, or — where ``fallback_mask`` allows — the exact fallback full
    re-simulation (``cycles_u`` is updated in place with its result).
    ``lock`` serializes the fallback (it temporarily mutates Program FIFO
    depths); the sweep scheduler passes the design's entry lock, direct
    library calls need none.  ``hybrid_cache`` threads a shared
    :class:`~repro.core.trace.HybridCache` into the fallback simulations,
    so a dynamic design's repeat fallbacks (same depths, any tenant)
    replay the verified whole-run entry instead of re-interpreting.
    Returns ``(results_u, reasons_u)``.
    """
    engine: OmniSim = result.graph
    cache = compile_graph(engine)
    fifo_names = [f.name for f in engine.fifos]
    U = len(Du)
    results_u: List[Optional[SimResult]] = [None] * U
    reasons_u: List[str] = [""] * U
    for u in range(U):
        reasons_u[u] = status_reason(cache, int(status_u[u]),
                                     int(violated_u[u]), Du[u], fifo_names)
        if status_u[u] == REUSED:
            # per-shell copies: SimStats is a mutable dataclass and
            # constraints a mutable list — sharing them would let a caller
            # mutating one sweep result corrupt its siblings AND the cached
            # base run (the graph stays shared by design: it IS the cache)
            shell = _ReusedShell(
                program=result.program, outputs=dict(result.outputs),
                cycles=int(cycles_u[u]), engine=engine_label,
                stats=copy.copy(result.stats), graph=engine,
                constraints=None, depths=tuple(int(d) for d in Du[u]))
            shell.base_constraints = result.constraints
            results_u[u] = shell
        elif fallback_mask[u] and status_u[u] in FALLBACK_STATUSES:
            with (lock if lock is not None else nullcontext()), \
                    program_mutation_lock(engine.program):
                saved = engine.program.depths()
                try:
                    full = simulate(engine.program,
                                    depths=tuple(int(d) for d in Du[u]),
                                    hybrid_cache=hybrid_cache)
                finally:
                    engine.program.with_depths(saved)
            results_u[u] = full
            cycles_u[u] = full.cycles
    return results_u, reasons_u


def resimulate_batch(result: SimResult, depth_matrix,
                     fallback: bool = True, backend: str = "numpy",
                     block: int = 128,
                     jax_interpret: Optional[bool] = None,
                     dedup: bool = True) -> BatchOutcome:
    """Incrementally re-simulate ``result`` under K depth vectors at once.

    ``depth_matrix``: (K, n_fifos) array-like of candidate depths.  Returns
    a :class:`BatchOutcome` whose k-th entry is exactly what
    ``resimulate(result, depth_matrix[k])`` would report — reusable configs
    get their cycle count from the shared batched fixpoint; deadlocked,
    cyclic or constraint-violating configs fall back to a full
    re-simulation (``fallback=True``) of just that config.

    ``dedup`` (default True) collapses identical depth rows before solving:
    only the unique rows pay for the fixpoint, the constraint re-check AND
    any fallback re-simulation — duplicate rows share one result object.
    Sweep drivers routinely re-propose configurations (grids revisit corner
    points, halving rounds re-evaluate survivors), so this keeps solver
    work proportional to the number of *distinct* configs
    (``BatchOutcome.n_unique``).

    ``backend="jax"`` lowers the fixpoint onto the sparse chain-structured
    Pallas max-plus kernel (``repro.kernels.maxplus.sparse``) — O(K·n +
    K·edges) memory, device-resident sweeps; ``backend="jax_dense"`` keeps
    the legacy dense O(n^2)-per-config vmap lowering for tiny graphs;
    ``backend="reference"`` runs the synchronous Jacobi oracle.  ``block``
    bounds the per-slab working set for every backend.
    """
    t0 = _time.perf_counter()
    engine: OmniSim = result.graph
    assert isinstance(engine, OmniSim), "batched re-sim needs an OmniSim result"
    D = np.asarray(depth_matrix, dtype=np.int64)
    if D.ndim == 1:
        D = D[None, :]
    K, F = D.shape
    if F != len(engine.fifos):
        raise ValueError(f"depth_matrix has {F} columns for "
                         f"{len(engine.fifos)} FIFOs")
    cache = compile_graph(engine)

    if dedup and K > 1:
        Du, inverse = np.unique(D, axis=0, return_inverse=True)
        inverse = inverse.reshape(-1)
    else:
        Du, inverse = D, np.arange(K)
    U = len(Du)
    status_u, cycles_u, violated_u, total_rounds = solve_block_status(
        cache, Du, backend=backend, block=block, jax_interpret=jax_interpret)

    # ④ fall back to full re-simulation for exactly the failed subset —
    # once per unique config; duplicate rows share the result object
    results_u, reasons_u = materialize_block(
        result, Du, status_u, cycles_u, violated_u,
        np.full(U, bool(fallback)))

    status = status_u[inverse]
    return BatchOutcome(ok=status == REUSED, cycles=cycles_u[inverse],
                        status=status, violated=violated_u[inverse],
                        reasons=[reasons_u[i] for i in inverse],
                        results=[results_u[i] for i in inverse],
                        elapsed_s=_time.perf_counter() - t0,
                        fixpoint_rounds=total_rounds, n_unique=U)


# ---------------------------------------------------------------------------
# jax backends: sparse chain-structured kernel + legacy dense vmap
# ---------------------------------------------------------------------------
# Working-set ceiling for the dense lowering: K * npad^2 int32 entries per
# slab.  A module constant so regression tests can shrink it and exercise
# the chunking/error paths without gigabyte batches.
_DENSE_CAP = 1 << 27


def _int32_saturation_guard(ba: _BatchArrays, backend: str) -> None:
    """Refuse int32 device transfer when finite times could exceed int32.

    ``ba.bound`` bounds every finite (acyclic) node time and the numpy
    path switches to int64 at ``2^28``; the jax lanes are int32-only, so
    past that point a silently wrapped time could flip a constraint
    comparison.  Raise instead of wrapping.
    """
    if ba.bound >= (1 << 28):
        raise ValueError(
            f"backend={backend!r} solves in int32 but the graph's "
            f"path-length bound {ba.bound} >= 2^28 risks overflow; "
            f"use backend='numpy' (int64) for this design")


def _sparse_arrays(cache: CompiledGraph, ba: _BatchArrays):
    """Lazily built (and cached on ``ba``) chain-flat device transfer
    arrays for the sparse jax lane, the constraint re-check's operands
    included."""
    if ba.sparse is None:
        from ..kernels.maxplus.sparse import NEG
        ba.sparse = export_chain_flat(
            ba.slices, ba.cw, ba.c_inf, ba.raw_dst, ba.raw_src, ba.raw_w,
            ba.fifo_w_cols, ba.fifo_r_cols, ba.fifo_blocking,
            bound=ba.bound, neg=int(NEG),
            checks=(cache.c_kind, cache.c_fifo, cache.c_seq, ba.c_src_p,
                    cache.c_out))
    return ba.sparse


def _solve_sparse_jax(cache: CompiledGraph, ba: _BatchArrays,
                      Db: np.ndarray, interpret: Optional[bool] = None,
                      block: Optional[int] = None):
    """Sparse chain-structured Pallas solve for one block of configs.

    Seeds every config at the no-WAR fixpoint contribution (``c_inf``, a
    lower bound of every least fixpoint) and iterates the Jacobi
    chain-pass/cross-pass to the same unique least fixpoint the numpy
    Gauss-Seidel reaches — times, and hence statuses/cycles/violations,
    are bit-identical for converged rows.  O(K·n + K·edges) memory.
    The device decides each converged row's violations and cycle count.
    Rows it leaves undecided (at its straggler cap, or where a block
    shorter than ``block`` stops in place of a cycle check) are solved
    exactly on the host by :func:`_solve_rows_exact`.

    Returns ``(converged, violated, cycles, host, times, rounds)``:
    ``host`` the positions of the rows solved on the host, whose
    ``violated`` and ``cycles`` the caller re-checks from ``times`` (n,
    len(host)).
    """
    from ..kernels.maxplus import sparse as sp

    _int32_saturation_guard(ba, "jax")
    arr = _sparse_arrays(cache, ba)
    v = sp.solve_chains(arr, Db, interpret=interpret, block=block)
    conv = np.array(v.converged)
    host = np.flatnonzero(v.unsettled)
    times = np.zeros((len(ba.perm), 0), np.int64)
    if len(host):
        with span("solve.exact", rows=len(host)):
            times, conv[host] = _solve_rows_exact(ba, Db[host], int(sp.NEG))
    return (conv, v.violated.astype(np.int64), v.cycles.astype(np.int64),
            host, times, v.rounds)


def _solve_rows_exact(ba: _BatchArrays, Db: np.ndarray, neg: int):
    """Each depth row's least fixpoint by one topological pass over its
    own graph: ``(times (n, k) int64 chain-major, converged (k,))``.

    Each chain's pointer advances over the nodes whose one cross in-edge
    source (RAW, or WAR under the row's depth) is valued already, and
    values each as ``max`` of its seed contribution (``c_inf``, floored
    at ``neg`` as the device lane seeds it), its chain predecessor's time
    plus the SEQ weight and the source's time plus the edge weight; the
    chains are swept in turn until none advances.  On an acyclic graph
    that is the least fixpoint the chain iteration converges to, bit for
    bit.  A row whose graph holds a cycle leaves chains that never reach
    their end: it is not converged (CYCLE).  The pass costs O(n) a row
    whatever the rounds the iteration would take, so it finishes the rows
    the device lane leaves at its straggler cap.  The iterating lanes
    cannot: on a lockstep row of ``optical_flow(4, 1024)`` the
    Gauss-Seidel lane (:func:`_solve_block_numpy`) takes 2,044 sweeps and
    11.9 s on a CPU host, the Jacobi lane 4,093 rounds, where this pass
    takes 0.09 s.
    """
    n, k = len(ba.perm), len(Db)
    times = np.zeros((n, k), np.int64)
    conv = np.zeros(k, bool)
    los = [lo for (lo, hi) in ba.slices]
    his = [hi for (lo, hi) in ba.slices]
    owner = np.zeros(n, np.int64)
    for i, (lo, hi) in enumerate(ba.slices):
        owner[lo:hi] = i
    seq_w = np.diff(ba.cw, prepend=0).tolist()
    c0 = np.maximum(ba.c_inf, neg).tolist()
    raw_src = np.full(n, -1, np.int64)
    raw_src[ba.raw_dst] = ba.raw_src
    raw_w = np.zeros(n, np.int64)
    raw_w[ba.raw_dst] = ba.raw_w
    dd, ds, dv = _regen_war_stacked(ba, Db)
    for r in range(k):
        src, w = raw_src.copy(), raw_w.copy()
        src[dd[dv[r]]] = ds[r, dv[r]]
        w[dd[dv[r]]] = 1
        src_chain = owner[np.maximum(src, 0)].tolist()
        src, w = src.tolist(), w.tolist()
        t = [0] * n
        ptr = list(los)
        moved = True
        while moved:
            moved = False
            for i, hi in enumerate(his):
                j, lo = ptr[i], los[i]
                while j < hi:
                    s = src[j]
                    # a source is valued once its chain's pointer passed it
                    if s >= 0 and s >= (j if src_chain[j] == i
                                        else ptr[src_chain[j]]):
                        break
                    v = c0[j]
                    if j > lo:
                        v = max(v, t[j - 1] + seq_w[j])
                    if s >= 0:
                        v = max(v, t[s] + w[j])
                    t[j] = v
                    j += 1
                if j > ptr[i]:
                    ptr[i] = j
                    moved = True
        conv[r] = ptr == his
        times[:, r] = t
    return times, conv


def _solve_dense_jax(cache: CompiledGraph, ba: _BatchArrays, Db: np.ndarray,
                     interpret: bool, block: int = 128):
    """Batched node times via ``jax.vmap`` over the dense Pallas max-plus
    kernel (``repro.kernels.maxplus``) — the legacy O(n^2)-per-config
    lowering, kept as ``backend="jax_dense"`` for tiny graphs.

    Builds dense ``(slab, npad, npad)`` max-plus adjacencies (shared
    SEQ+RAW skeleton broadcast, per-config WAR entries scattered in) and
    vmaps the jitted fixpoint, chunking the batch so one slab never
    exceeds ``_DENSE_CAP`` int32 entries (a *single* config past the cap
    is a hard error).  Convergence is certified by one extra sweep:
    non-converged rows (WAR cycles) report False.
    """
    import jax
    import jax.numpy as jnp

    from ..kernels.maxplus.kernel import BLK, NEG as NEG32, maxplus_sweep
    from ..kernels.maxplus.ops import longest_path

    n = cache.n
    npad = ((n + BLK - 1) // BLK) * BLK if n else BLK
    K = len(Db)
    if npad * npad > _DENSE_CAP:
        raise ValueError(
            f"dense jax backend needs npad^2 <= {_DENSE_CAP} per config "
            f"(got {npad}^2); use backend='numpy' (or the sparse "
            f"backend='jax') for large graphs")
    _int32_saturation_guard(ba, "jax_dense")
    slab = max(1, min(max(block, 1), _DENSE_CAP // (npad * npad)))
    # clip int64 weights against the kernel's -INF before the int32 cast —
    # a bare .astype would wrap NEGI into a huge positive phantom edge
    # (the hazard ops.finalize_times documents for a/base)
    b = np.full((npad,), int(NEG32), dtype=np.int32)
    b[:n] = np.maximum(cache.base, int(NEG32)).astype(np.int32)
    A = np.full((npad, npad), int(NEG32), dtype=np.int32)
    for ch in cache.chains:                      # SEQ skeleton
        if len(ch) > 1:
            A[ch[1:], ch[:-1]] = np.maximum(
                cache.seq_w[ch[1:]], int(NEG32)).astype(np.int32)
    A[cache.raw_dst, cache.raw_src] = np.maximum(
        cache.raw_w, int(NEG32)).astype(np.int32)
    bK = jnp.asarray(b)
    solve = jax.vmap(lambda a: longest_path(a, bK, use_pallas=True,
                                            interpret=interpret))
    sweep = jax.vmap(lambda a, t: maxplus_sweep(a, t, bK,
                                                interpret=interpret))
    times_parts, conv_parts = [], []
    for lo in range(0, K, slab):
        Ds = Db[lo:lo + slab]
        AK = np.broadcast_to(A, (len(Ds), npad, npad)).copy()
        for fid, (w_nodes, r_nodes, blk) in enumerate(cache.fifos):
            nw, nr = len(w_nodes), len(r_nodes)
            if nw == 0 or int(Ds[:, fid].min()) >= nw:
                continue
            w_seq = np.arange(1, nw + 1, dtype=np.int64)
            tgt = w_seq[None, :] - Ds[:, fid][:, None] - 1
            valid = blk[None, :] & (tgt >= 0) & (tgt < nr)
            kk, jj = np.nonzero(valid)
            AK[kk, w_nodes[jj], r_nodes[tgt[kk, jj]]] = 1
        aK = jnp.asarray(AK)
        tK = solve(aK)
        # certify fixpoint: one more sweep must be a no-op (cycles diverge)
        conv_parts.append(np.asarray((sweep(aK, tK) == tK).all(axis=1)))
        times_parts.append(np.asarray(tK)[:, :n].astype(np.int64))
    times = np.concatenate(times_parts) if times_parts else \
        np.zeros((0, n), np.int64)
    conv = np.concatenate(conv_parts) if conv_parts else np.zeros(0, bool)
    times_nm = (np.ascontiguousarray(times[:, ba.perm].T) if n
                else times.T)
    return times_nm, conv
