"""Sparse chain-structured batched max-plus solver (Pallas TPU kernel).

The dense kernel in ``kernel.py`` materializes the whole max-plus
adjacency — O(n^2) per depth config — which caps the ``backend="jax"``
DSE lane at tiny graphs.  This module is its sparse replacement: it runs
the chain-decomposed fixpoint of ``core.dse._solve_block_numpy`` /
``core.graph.longest_path_chains_batched`` directly over the chain-major
flat arrays (:class:`repro.core.graph.ChainFlatArrays`), so a block of K
depth configs costs O(K·n + K·edges) memory and sweeps of 10^5–10^6
configs stay device-resident.

Per fixpoint round (K configs at once):

  1. **chain pass** — ``t = cw + segcummax(c - cw)``: one *segmented*
     cummax over the (K, npad) contribution matrix, segment boundaries at
     chain starts.  This is the Pallas kernel: a Hillis–Steele doubling
     scan (log2 of the tile width or of the longest chain, whichever is
     smaller, shifted-max steps, each a full-tile VPU op) over (rows,
     width) VMEM tiles, gridded over config rows and node tiles; a
     per-row carry continues a chain across node tiles, so the VMEM
     footprint stays fixed however wide the design.  ``max`` is
     idempotent, so overlapping windows need no flag bookkeeping — a
     column takes its shifted partner iff the partner is at/after its
     own chain start.
  2. **cross pass** — static RAW edges (``c[dst] = max(c[dst],
     t[src]+w)``) and depth-dependent WAR edges scattered back into the
     contribution matrix.  Destinations are unique by construction (one
     RAW in-edge per read node, one WAR in-edge per write node), so the
     scatter-max is exact; XLA's native gather/scatter handles the
     irregular indexing between kernel sweeps.

WAR targets are computed **on-device** from the flat FIFO tables and the
depth block: write ``wseq`` of FIFO ``f`` under depth ``S = Db[k, f]``
waits on read ``wseq - S - 1`` (weight 1), masked out where the target
does not exist.  For one FIFO and one config, that is read ``wseq - 1``
moved back by ``S``: the FIFO's read times moved right by ``S`` line each
target up with its write, so the WAR half never gathers with indices
that differ by config.  Each round it gathers the read times into the
static *WAR lane* (one segment per FIFO, see
:class:`~repro.core.graph.ChainFlatArrays`) with one column vector shared
by every row, like the RAW half; moves each row's segments right by
their own ``S`` with a barrel shifter (``(war_seg - 1).bit_length()``
steps, each a select between the lane and its power-of-two shift); and
reads every write's slot at a static column.  The shift is exact: a
valid target's source, and every slot on its way there, lie inside one
segment and move by the same amount; a source before the segment or in
one of its empty slots is a masked target.  Only the shift amounts and
the validity mask depend on the depth block, computed once per solve.

Rows diverge independently: a config whose regenerated WAR edges form a
cycle is frozen (reported non-converged = CYCLE upstream) without taxing
the other rows.  The cycle exit is the device form of
:func:`repro.core.graph.pointer_cycle_rows`, run under a ``lax.cond`` in
the ``cycle_check`` scope once some row has stayed pending for twice the
rounds since a row of the block last converged (a block whose rows
converge together never runs it):

* every node gets a predecessor pointer, an edge of its row's graph: a
  *root* — a node whose own contribution attains its time, ``c == t``,
  ties included — points to the source of its one cross in-edge (RAW, or
  WAR where the row's depth makes one; none otherwise), any other node
  to its chain's latest root at or before it.  One segmented cummax of
  the root columns gives each node that root;
* max-id labels flood along the pointers, one cross edge per step: the
  same segmented-cummax kernel, fed that root column as *per-row*
  segment starts, hands each node its root's label, and the cross pass's
  own gathers (RAW source columns, the WAR lane and its barrel shift)
  pull a root's label from its source.  A node that meets its own id
  lies on a pointer cycle.  Steps stop when every pending row has met
  one, or after as many steps as rounds so far.

It is exact.  A pointer cycle is a cycle of the row's graph whatever the
values, ties or the Jacobi round's simultaneous updates that chose the
pointers: each pointer is an edge (or a run of SEQ edges) of that graph.
Every cycle holds a WAR edge, a WAR edge weighs 1 and no edge less than
0, so the cycle is positive and the row has no fixpoint: it is
CYCLE, the verdict the older exits reach, only sooner.  A cyclic row the
check misses stays pending; its times passing the acyclic ``bound`` and
the ``n + 2`` round cap remain as backstops.  A cycle gains only its own
weight per trip round it, so those backstops take rounds that grow with
the design's length; the pointers close once the cycle's weight has gone
round about twice, so the check finds it in rounds set by the cycle's
cross edges.  ``_fixpoint`` returns each row's freeze round (0 if the
exit did not freeze it).

A row that converges, but slowly, is not held either: once half the
block's rows have settled, the loop stops at :func:`straggler_cap`
(running a cycle check by then only if its label steps end before the
cap) and reports the rows still pending as undecided (``unsettled``);
the caller solves them exactly on the host.  Such rows are lockstep
pairs of processes coupled by a FIFO just deep enough, which advance one
item a round.  The check is compiled only into the program for the
caller's full block height (``solve_chains(block=...)``): a shorter
block stops where the check would first run, while fewer than half its
rows have settled, and leaves the rows still pending to the same exact
pass.

The program ends with each row's verdict, in the ``recheck`` scope
(:func:`_recheck`): the number of Table-2 constraint outcomes its final
times flip and its cycle count, the latest of its real node times.  The
host copies back these (K,) vectors, never the (K, npad) time matrix.
The constraints' operands are static columns as the RAW half's are, and
a lane of the constrained FIFOs' reads moved by the WAR half's barrel
shift (see :class:`~repro.core.graph.ChainFlatArrays`); a design without
constraints compiles only the row max.

Everything is int32 on device — callers must clip against :data:`NEG`
and refuse graphs whose path-length bound nears int32 range (see
``core.dse``'s saturation guard); this mirrors the wrap-around hazard
``ops.finalize_times`` documents for the dense path.

Shape bucketing: batch, edge and WAR-table lengths are padded up to
powers of two (padding rows replicate row 0; padding edges carry the
-INF weight, a max-identity) so repeated solves across designs and slab
tails hit the jit cache instead of recompiling per shape.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.graph import CHECK_FLOOR, ChainFlatArrays, cycle_check_due
from ...device import pallas_interpret, span

# int32 -INF sentinel — matches the numpy solver's int32 mode, and leaves
# headroom: with bound < 2^28 (enforced upstream) no max-plus candidate
# t + w can underflow/overflow int32 arithmetic.
NEG = -(1 << 29)
LANES = 128        # node-axis padding unit (TPU lane width)
ROWS = 8           # minimum configs per kernel row tile (sublane width)
LANE_TILE = 2048   # node-axis tile width once the padded axis is wider
_TILE_BYTES = 1 << 20   # per-buffer VMEM budget for one (rows, width) tile


def _pow2(x: int, floor: int) -> int:
    p = floor
    while p < x:
        p *= 2
    return p


def _tile_width(npad: int) -> int:
    """Node-axis tile: the whole axis up to ``LANE_TILE`` columns, else
    ``LANE_TILE`` — so a tile's VMEM footprint never grows with the
    design (the axis is padded to a tile multiple, see
    :func:`_padded_width`)."""
    return npad if npad <= LANE_TILE else LANE_TILE


def _padded_width(npad: int) -> int:
    w = _tile_width(npad)
    return -(-npad // w) * w


def _rows_for(K: int, width: int) -> int:
    """Row-tile height: as tall as the VMEM budget allows (fewer grid
    steps — interpret mode executes them sequentially), never taller than
    the (power-of-two) batch.  Both are powers of two, so rows | K."""
    cap = ROWS
    while cap * 2 * width * 4 <= _TILE_BYTES and cap < 512:
        cap *= 2
    return min(K, cap)


# ---------------------------------------------------------------------------
# segmented cummax: the chain pass
# ---------------------------------------------------------------------------
def _shift_right(x, s: int):
    """``x`` (rows, n) moved ``s`` columns right along axis 1, NEG in."""
    return jnp.concatenate(
        [jnp.full((x.shape[0], s), NEG, x.dtype), x[:, :-s]], axis=1)


def _doubling_scan(x, seg, col, limit):
    """Hillis–Steele segmented max-scan body shared by the Pallas kernel
    and the jnp reference: log2(limit) shifted-max steps; a column accepts
    its ``s``-shifted partner iff the partner sits at/after the column's
    own segment start (idempotent max ⇒ overlap is harmless).  ``limit``
    (a power of two >= the longest segment) caps the step count — chains
    are usually far shorter than the padded node axis."""
    s = 1
    while s < limit:
        shifted = _shift_right(x, s)
        take = (col - s) >= seg
        x = jnp.where(take, jnp.maximum(x, shifted), x)
        s *= 2
    return x


def _segcummax_kernel(limit, x_ref, seg_ref, o_ref, carry_ref):
    """One (rows, width) tile.  Node tiles of a row block run in order
    (the grid's last axis), and ``carry_ref`` holds the previous tile's
    last column: a segment that began before this tile continues that
    column's running max, so segments may be any length."""
    width = x_ref.shape[1]
    seg = seg_ref[...] - pl.program_id(1) * width   # tile-relative starts
    col = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    y = _doubling_scan(x_ref[...], jnp.maximum(seg, 0), col, limit)
    # seg < 0 never holds in the first tile, so the carry is only read
    # after a previous tile of the same row block wrote it
    y = jnp.where(seg < 0, jnp.maximum(y, carry_ref[...]), y)
    o_ref[...] = y
    carry_ref[...] = jnp.max(jnp.where(col == width - 1, y, NEG),
                             axis=1, keepdims=True)


def _scan_limit(npad: int, max_seg) -> int:
    return npad if max_seg is None else min(_pow2(max(max_seg, 1), 16), npad)


def segmented_cummax_ref(x: jnp.ndarray, seg_start: jnp.ndarray,
                         max_seg=None):
    """jnp reference: inclusive per-segment running max along axis 1."""
    n = x.shape[1]
    col = jnp.arange(n, dtype=jnp.int32)[None, :]
    return _doubling_scan(x, seg_start[None, :].astype(jnp.int32), col,
                          _scan_limit(n, max_seg))


def segmented_cummax(x: jnp.ndarray, seg_start: jnp.ndarray, *,
                     max_seg=None, interpret: Optional[bool] = None,
                     width: Optional[int] = None, name: str = "segcummax"):
    """Segmented cummax over (K, npad); ``seg_start[j]`` is column j's
    segment start — one (npad,) vector shared by every row, or a (K, npad)
    matrix giving each row segments of its own — and ``max_seg`` an
    optional bound on segment length (caps the scan's doubling steps).  K
    must be a ROWS multiple and npad a multiple of the node tile ``width``
    (default :func:`_tile_width`), itself a LANES multiple — callers
    bucket-pad, see :func:`_fixpoint_args`.  ``interpret=None`` derives
    the mode from the platform (:func:`repro.device.pallas_interpret`).
    ``name`` names the kernel in a device trace.
    """
    if interpret is None:
        interpret = pallas_interpret()
    K, npad = x.shape
    width = _tile_width(npad) if width is None else width
    rows = _rows_for(K, width)
    assert K % rows == 0 and npad % width == 0 and width % LANES == 0, \
        (K, npad, width)
    if seg_start.ndim == 2:                 # per-row segments
        seg_spec = pl.BlockSpec((rows, width), lambda i, j: (i, j))
    else:
        seg_spec = pl.BlockSpec((1, width), lambda i, j: (0, j))
        seg_start = seg_start.reshape(1, npad)
    return pl.pallas_call(
        functools.partial(_segcummax_kernel,
                          _scan_limit(width, max_seg)),
        grid=(K // rows, npad // width),
        in_specs=[
            pl.BlockSpec((rows, width), lambda i, j: (i, j)),
            seg_spec,
        ],
        out_specs=pl.BlockSpec((rows, width), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((K, npad), x.dtype),
        scratch_shapes=[pltpu.VMEM((rows, 1), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=name,
    )(x, seg_start.astype(jnp.int32))


# ---------------------------------------------------------------------------
# the batched fixpoint
# ---------------------------------------------------------------------------
def war_steps(war_seg: int) -> int:
    """Barrel-shift steps for WAR lane segments of at most ``war_seg``
    slots: a shift ``S`` that leaves a valid target is below ``war_seg``."""
    return max(int(war_seg) - 1, 0).bit_length()


def _lane_read(t, lane_src, pos, shift, steps: int):
    """(K, m) times at lane slots ``pos`` once each row's segments moved
    right by their ``shift``: a static column gather into the lane (NEG in
    empty slots); a barrel shift, where at step ``b`` a slot takes its
    partner ``2**b`` to the left iff bit ``b`` of its ``shift`` is set
    (exact, as the module docstring says); a static read of the slots."""
    x = jnp.where(lane_src[None, :] >= 0, t[:, lane_src], jnp.int32(NEG))
    for b in range(steps):
        x = jnp.where(((shift >> b) & 1) == 1, _shift_right(x, 1 << b), x)
    return x[:, pos]


def _war_candidates(t, war_lane_src, war_pos, war_shift, war_valid,
                    steps: int):
    """(K, m) WAR candidates ``t[read wseq - S - 1] + 1``, NEG where that
    read does not exist, through the WAR lane (:func:`_lane_read`)."""
    return jnp.where(war_valid,
                     _lane_read(t, war_lane_src, war_pos, war_shift,
                                steps) + 1,
                     jnp.int32(NEG))


def _war_operands(Db, war_wseq, war_fid, war_nr, war_lane_fid, war_seg):
    """Per-solve WAR operands: each lane slot's shift ``S`` (clamped to
    ``war_seg - 1``: any larger shift leaves only masked targets) and the
    (K, m) mask of writes whose target read exists."""
    S = Db[:, war_fid]                                        # (K, m)
    tgt = war_wseq[None, :] - S - 1
    war_valid = (tgt >= 0) & (tgt < war_nr[None, :])
    war_shift = jnp.minimum(Db[:, war_lane_fid], war_seg - 1)
    return war_shift, war_valid


def _recheck(t, Db, n, rd_src, rd_tgt, rd_live, rd_out, wr_src, wr_seq,
             wr_fid, wr_nr, wr_pos, wr_out, lane_src, lane_fid, seg: int):
    """Each row's verdict from its final times ``t`` (K, npad): the count
    of Table-2 constraint outcomes that flip, as
    ``core.dse._check_constraints_stacked`` counts them, and the cycle
    count, the latest time over the graph's ``n`` real columns.  Every
    gather reads whole columns, one index for all rows: a can-write
    constraint ``seq`` that does not hold outright (``seq <= S``) reads
    read ``seq - S - 1`` of its FIFO through the check lane, whose shifts
    and mask are the WAR half's (:func:`_war_operands`)."""
    K, npad = t.shape
    col = jax.lax.broadcasted_iota(jnp.int32, (1, npad), 1)
    cycles = jnp.where(col < n, t, jnp.int32(NEG)).max(axis=1)
    violated = jnp.zeros(K, jnp.int32)
    if rd_src.shape[0]:
        ok = (rd_live[None, :] != 0) & (t[:, rd_tgt] < t[:, rd_src])
        violated += (ok != (rd_out[None, :] != 0)).sum(axis=1,
                                                        dtype=jnp.int32)
    if wr_src.shape[0]:
        shift, valid = _war_operands(Db, wr_seq, wr_fid, wr_nr, lane_fid,
                                     seg)
        x = _lane_read(t, lane_src, wr_pos, shift, war_steps(seg))
        ok = ((wr_seq[None, :] <= Db[:, wr_fid])
              | (valid & (x < t[:, wr_src])))
        violated += (ok != (wr_out[None, :] != 0)).sum(axis=1,
                                                        dtype=jnp.int32)
    return violated, cycles


def straggler_cap(half_at):
    """The round at which the fixpoint stops waiting for its last rows:
    twice the round by which half the block's rows had settled, plus 8.
    A row still pending then (a lockstep pair of processes coupled by a
    shallow FIFO takes about one round per item it streams) leaves the
    device undecided, and the caller solves it exactly on the host
    (``core.dse._solve_rows_exact``), so one row never holds a block for
    thousands of rounds.  Rows of one block that settle together never
    meet the cap: ``multicore``'s settle by round 8 (the cap is at least
    10) and ``skynet_like``'s by round 3; of uniformly drawn
    ``optical_flow`` depth rows at 8 x 1,024, 2 of 6,144 are lockstep
    rows that do.
    """
    return 2 * half_at + 8


@functools.partial(jax.jit,
                   static_argnames=("max_seg", "war_seg", "chk_seg", "width",
                                    "interpret", "check", "times"))
def _fixpoint(c_seed, cw, seg_start, raw_dst, raw_src, raw_w,
              war_dst, war_wseq, war_fid, war_nr, war_pos, war_lane_src,
              war_lane_fid, chk_rd_src, chk_rd_tgt, chk_rd_live, chk_rd_out,
              chk_wr_src, chk_wr_seq, chk_wr_fid, chk_wr_nr, chk_wr_pos,
              chk_wr_out, chk_lane_src, chk_lane_fid, Db, bound, iters, n, *,
              max_seg: int, war_seg: int, chk_seg: int, width: int,
              interpret: bool, check: bool = True, times: bool = False):
    K = Db.shape[0]
    npad = c_seed.shape[0]
    c0 = jnp.broadcast_to(c_seed[None, :], (K, npad))
    cw_row = cw[None, :]
    col = jax.lax.broadcasted_iota(jnp.int32, (1, npad), 1)

    # depth-dependent WAR shifts and mask, computed on-device once per
    # solve: write wseq of FIFO fid waits on read (wseq - S - 1)
    have_war = war_dst.shape[0] > 0
    if have_war:
        war_shift, war_valid = _war_operands(Db, war_wseq, war_fid, war_nr,
                                             war_lane_fid, war_seg)

    def scan(x, seg, name="segcummax"):
        return segmented_cummax(x, seg, max_seg=max_seg, interpret=interpret,
                                width=width, name=name)

    # the scopes name the passes' ops in a device trace (op_name metadata)
    def chain_pass(c):
        with jax.named_scope("chain_pass"):
            return scan(c - cw_row, seg_start) + cw_row

    def cycle_rows(c, t, live, steps):
        """Rows of ``live`` whose predecessor pointers close a cycle: the
        device form of :func:`repro.core.graph.pointer_cycle_rows` (the
        module docstring gives the argument).  ``t`` is ``chain(c)``."""
        with jax.named_scope("cycle_check"):
            def pull(ids, root):
                """(K, npad): at each root, ``ids`` of its cross source
                (NEG where it has none)."""
                lab = jnp.full((K, npad), NEG, jnp.int32)
                if raw_dst.shape[0]:
                    lab = lab.at[:, raw_dst].max(
                        jnp.where(raw_w[None, :] > jnp.int32(NEG),
                                  ids[:, raw_src], jnp.int32(NEG)),
                        indices_are_sorted=True)
                if have_war:
                    cand = _war_candidates(ids, war_lane_src, war_pos,
                                           war_shift, war_valid,
                                           war_steps(war_seg))
                    lab = lab.at[:, war_dst].max(
                        jnp.where(cand > jnp.int32(NEG), cand - 1,
                                  jnp.int32(NEG)),
                        indices_are_sorted=True)
                return jnp.where(root, lab, jnp.int32(NEG))

            def step(state):
                # step 0 scans the root columns over the chains: each
                # column's latest root, a column its own contribution
                # attains (a non-root points to it, a root to its cross
                # source); each later step pulls every root's label from
                # its source and scans it over those per-row segments: a
                # column takes its root's label, the largest id on the
                # root's pointer path.  One scan serves both.
                ids, root_at, found, j = state
                first = j == 0
                x = jnp.where(first, jnp.where(c == t, col, -1),
                              pull(ids, root_at == col))
                fill = scan(x, jnp.where(first, seg_start[None, :], root_at),
                            "cycle_fill")
                found = found | (~first & (fill == col).any(axis=1))
                return (jnp.where(first, ids, jnp.maximum(fill, col)),
                        jnp.where(first, fill, root_at), found, j + 1)

            def more(state):
                _, _, found, j = state
                return jnp.logical_and(j <= steps, (live & ~found).any())

            ids0 = jnp.broadcast_to(col, (K, npad))
            state0 = (ids0, ids0, jnp.zeros(K, bool), jnp.int32(0))
            _, _, found, _ = jax.lax.while_loop(more, step, state0)
            return found & live

    def cross_pass(c, t):
        # export_chain_flat sorts both scatters' destinations (padding
        # included); an unsorted scatter compiles to a sort on the TPU
        c2 = c
        if raw_dst.shape[0]:
            with jax.named_scope("cross_pass_raw"):
                # w == NEG marks bucket-padding edges; real weights are
                # >= 0.  An unmasked padding edge would lift a NEG
                # contribution to NEG + t[src] and perturb unreached-node
                # sentinel times.
                cand = jnp.where(raw_w[None, :] > jnp.int32(NEG),
                                 t[:, raw_src] + raw_w[None, :],
                                 jnp.int32(NEG))
                c2 = c2.at[:, raw_dst].max(cand, indices_are_sorted=True)
        if have_war:
            with jax.named_scope("cross_pass_war"):
                cand = _war_candidates(t, war_lane_src, war_pos, war_shift,
                                       war_valid, war_steps(war_seg))
                c2 = c2.at[:, war_dst].max(cand, indices_are_sorted=True)
        return c2

    def body(state):
        (c, _, diverged, was_pending, rounds, frozen_at, mark, half_at,
         halted) = state
        rounds = rounds + 1
        t = chain_pass(c)
        diverged = diverged | (t > bound).any(axis=1)
        c2 = cross_pass(c, t)
        c2 = jnp.where(diverged[:, None], c, c2)   # freeze cyclic rows
        pending = (c2 != c).any(axis=1) & ~diverged
        # as the numpy lanes: a round in which no row settled, and some
        # row has stayed pending for twice the rounds since one last did;
        # once half the rows have settled, only a check whose label steps
        # (at most ``rounds``) end before the straggler cap: past that the
        # host's exact pass decides what is left
        settled = (was_pending & ~pending & ~diverged).any()
        room = (half_at == 0) | (2 * rounds <= straggler_cap(half_at))
        due = (~settled & pending.any() & room
               & cycle_check_due(rounds, mark))
        mark = jnp.where(settled, jnp.maximum(mark, rounds),
                         jnp.where(due, rounds, mark))
        if check:
            cyc = jax.lax.cond(due, cycle_rows,
                               lambda *_: jnp.zeros(K, bool),
                               c, t, pending, rounds)
        else:
            # no check compiled: a block still short of half its rows
            # stops where it would run one; the host decides what is left
            cyc = jnp.zeros(K, bool)
            halted = halted | (due & (half_at == 0))
        frozen_at = jnp.where(cyc, rounds, frozen_at)
        pending = pending & ~cyc
        half_at = jnp.where((half_at == 0) & (2 * (~pending).sum() >= K),
                            rounds, half_at)
        return (c2, t, diverged | cyc, pending, rounds, frozen_at, mark,
                half_at, halted)

    def cond(state):
        pending, rounds, half_at, halted = (state[3], state[4], state[7],
                                            state[8])
        late = (half_at > 0) & (rounds >= straggler_cap(half_at))
        return pending.any() & (rounds < iters) & ~late & ~halted

    state0 = (c0, c0, jnp.zeros(K, bool), jnp.ones(K, bool), jnp.int32(0),
              jnp.zeros(K, jnp.int32), jnp.int32(CHECK_FLOOR), jnp.int32(0),
              jnp.bool_(False))
    _, t, diverged, pending, rounds, frozen_at, _, _, _ = jax.lax.while_loop(
        cond, body, state0)
    # pending rows at the n + 2 cap never reached a fixpoint (cycle), same
    # as longest_path_chains_batched's iteration-cap leftover rows; rows
    # the straggler cap (or a halt) left pending are undecided
    unsettled = pending & (rounds < iters)
    with jax.named_scope("recheck"):
        violated, cycles = _recheck(
            t, Db, n, chk_rd_src, chk_rd_tgt, chk_rd_live, chk_rd_out,
            chk_wr_src, chk_wr_seq, chk_wr_fid, chk_wr_nr, chk_wr_pos,
            chk_wr_out, chk_lane_src, chk_lane_fid, chk_seg)
    out = (~(diverged | pending), violated, cycles, rounds, frozen_at,
           unsettled)
    return out + (t,) if times else out


def _fixpoint_args(arr: ChainFlatArrays, Db: np.ndarray):
    """Host operands and static arguments of :func:`_fixpoint` for one
    block: the batch is bucketed to a power of two (slab tails reuse the
    compiled solver; padding rows replicate row 0 and converge exactly
    when it does) and the node axis to a tile multiple (inert one-column
    segments at the -INF sentinel)."""
    K = len(Db)
    Kp = _pow2(K, max(ROWS, 1))
    Dp = np.minimum(np.asarray(Db, np.int64), 1 << 30).astype(np.int32)
    if Kp != K:
        Dp = np.concatenate([Dp, np.broadcast_to(Dp[:1], (Kp - K,
                                                          Dp.shape[1]))])
    full = _padded_width(arr.npad)
    extra = full - arr.npad
    c_seed, cw, seg = arr.c_seed, arr.cw, arr.seg_start
    if extra:
        c_seed = np.concatenate([c_seed, np.full(extra, NEG, np.int32)])
        cw = np.concatenate([cw, np.zeros(extra, np.int32)])
        seg = np.concatenate([seg, np.arange(arr.npad, full,
                                             dtype=np.int32)])
    args = (c_seed, cw, seg, arr.raw_dst, arr.raw_src, arr.raw_w,
            arr.war_dst, arr.war_wseq, arr.war_fid, arr.war_nr,
            arr.war_pos, arr.war_lane_src, arr.war_lane_fid,
            arr.chk_rd_src, arr.chk_rd_tgt, arr.chk_rd_live, arr.chk_rd_out,
            arr.chk_wr_src, arr.chk_wr_seq, arr.chk_wr_fid, arr.chk_wr_nr,
            arr.chk_wr_pos, arr.chk_wr_out, arr.chk_lane_src,
            arr.chk_lane_fid, Dp, np.int32(arr.bound), np.int32(arr.n + 2),
            np.int32(arr.n))
    return args, dict(max_seg=arr.max_seg, war_seg=arr.war_seg,
                      chk_seg=arr.chk_seg, width=_tile_width(full))


class Verdicts(NamedTuple):
    """Per-row results of :func:`solve_chains`, on the host."""

    converged: np.ndarray       # (K,) bool: a fixpoint, and not unsettled
    violated: np.ndarray        # (K,) int32 flipped constraint outcomes
    cycles: np.ndarray          # (K,) int32 latest node time
    rounds: int                 # the fixpoint loop's rounds
    unsettled: np.ndarray       # (K,) bool: undecided at the straggler cap
    times: Optional[np.ndarray] = None  # (n, K), only where asked for


def solve_chains(arr: ChainFlatArrays, Db: np.ndarray, *,
                 interpret: Optional[bool] = None,
                 block: Optional[int] = None, times: bool = False):
    """Solve K depth configs over one chain-flat graph.

    ``Db``: (K, n_fifos) depth block.  Returns :class:`Verdicts`:
    ``converged[k]`` False where config k's regenerated WAR edges form a
    cycle or where ``unsettled[k]``: the row was still pending at
    :func:`straggler_cap`, undecided, its verdict not final.  A converged
    row's ``violated`` and ``cycles`` are decided on the device from its
    final times (:func:`_recheck`, in the ``recheck`` scope of the same
    program), so only these (K,) vectors come back to the host.
    ``times=True`` also copies back the node times, (n, K) int32 in
    chain-major node order, for a comparison with the numpy lane's.
    ``interpret=None`` derives the Pallas mode from the platform.

    ``block`` is the caller's full block height.  A block that pads to
    fewer rows runs a program without the cycle check, which costs
    compile time at every height: where the check would first run, while
    fewer than half its rows have settled, it stops and leaves the rows
    still pending undecided, for the host's exact pass.  So a sweep
    compiles the check once, for its full blocks.

    Each phase is a host span: ``solve.upload`` (operands to the device,
    dispatch; metadata: the padded batch ``K``, the WAR lane's length
    ``war_lane`` and its shift steps ``war_steps``), ``solve.fixpoint``
    (waiting for the device; metadata: the loop's ``rounds``, the rows
    the cycle exit froze, ``cyclic_rows``, ``freeze_round``, the round
    the last of them froze, 0 for none, and ``unsettled_rows``) and
    ``solve.copy_back`` (the per-row vectors to the host).
    """
    interpret = pallas_interpret(interpret)
    K = len(Db)
    if K == 0 or arr.n == 0:
        return Verdicts(np.ones(K, bool), np.zeros(K, np.int32),
                        np.zeros(K, np.int32), 0, np.zeros(K, bool),
                        np.zeros((arr.n, K), np.int32) if times else None)
    with span("solve.upload") as sp:
        args, static = _fixpoint_args(arr, Db)
        floor = max(ROWS, 1)
        check = block is None or _pow2(K, floor) >= _pow2(block, floor)
        out = _fixpoint(*map(jnp.asarray, args), **static,
                        interpret=interpret, check=check, times=times)
        sp.set_metadata(K=int(out[0].shape[0]),       # padded batch
                        war_lane=len(arr.war_lane_src),
                        war_steps=war_steps(arr.war_seg))
    with span("solve.fixpoint") as sp:
        conv, violated, cycles, rounds, frozen_at, unsettled = \
            jax.block_until_ready(out)[:6]
        rounds, frozen_at = int(rounds), np.asarray(frozen_at)[:K]
        unsettled = np.asarray(unsettled)[:K]
        sp.set_metadata(rounds=rounds,
                        cyclic_rows=int((frozen_at > 0).sum()),
                        freeze_round=int(frozen_at.max()),
                        unsettled_rows=int(unsettled.sum()))
    with span("solve.copy_back",
              bytes=conv.nbytes + violated.nbytes + cycles.nbytes):
        conv, violated, cycles = jax.device_get((conv, violated, cycles))
    t = np.asarray(out[6])[:K, :arr.n].T if times else None
    return Verdicts(conv[:K], violated[:K], cycles[:K], rounds, unsettled,
                    t)
