"""Plain reference simulator: the oracle that decides a run's ``correct``.

It imports nothing of the simulator under test.  It takes a design as its
author wrote it (FIFO depths and module bodies, each a generator function
that yields operations) and runs it by the cycle-cost model of the design
language, one event at a time, in order of commit cycle:

==========  ===========================================================
op          cost
==========  ===========================================================
Read        commits at u = max(t, time(matching write) + 1); next op u+1
Write       commits at u = t if w <= S, else max(t, time(read w-S) + 1)
ReadNB      samples at t; succeeds iff time(r-th write) < t; 1 cycle
WriteNB     samples at t; succeeds iff w <= S or time(read w-S) < t
Empty/Full  sample the FIFO at t like ReadNB / WriteNB; 1 cycle, no
            cycle and no sample when the result is unused
Delay(n)    advances the local clock by n cycles
Emit        records an output; no cycle
==========  ===========================================================

Operations are recognised by their class name and read through the
attributes the design language gives them (``fifo.fid``, ``value``,
``cycles``, ``used``).  Processing events in order of commit cycle makes
every sample exact: when an access at cycle t is processed, every commit
before t is known, and a later commit can never fall before t.

Two entry points:

* :func:`simulate` — a from-scratch run under a depth vector: deadlock
  flag, cycle count, and the run's record (per-module op list with the
  outcome of every sampled access).
* :func:`retime` — the base run's record replayed under new depths with
  every sampled outcome held as recorded; returns the number of samples
  whose outcome the new times would flip (0 when the record still holds)
  or ``None`` when the held record cannot complete (its waits form a
  cycle).
"""
from __future__ import annotations

import heapq
from typing import Dict, List, NamedTuple, Optional, Sequence

READ, WRITE, READNB, WRITENB, EMPTY, FULL, DELAY, EMIT = range(8)
_KIND = {"Read": READ, "Write": WRITE, "ReadNB": READNB, "WriteNB": WRITENB,
         "Empty": EMPTY, "Full": FULL, "Delay": DELAY, "Emit": EMIT}
_SAMPLES = (READNB, WRITENB, EMPTY, FULL)


class Run(NamedTuple):
    deadlock: bool
    cycles: int                 # latest commit cycle (module ends included)
    record: List[list]          # per module: [(kind, fid, arg), ...]
    outcomes: tuple             # per module, its samples' outcomes in order
    reads: List[int]            # committed reads per FIFO
    blocking_writes: List[int]  # highest blocking-write sequence per FIFO


class _Fifo:
    __slots__ = ("depth", "w_t", "w_v", "r_t")

    def __init__(self, depth: int):
        self.depth = depth
        self.w_t: List[int] = []
        self.w_v: list = []
        self.r_t: List[int] = []


def _run(depths: Sequence[int], sources, held: bool):
    """Event loop shared by both entry points.

    ``sources[m]`` yields module m's operations as ``(kind, fid, arg)``;
    with ``held`` False it is a generator that takes the value of each
    access by ``send``.  Returns (finished, cycles, record, samples, fifos);
    ``samples`` lists every sample as (kind, fid, seq, cycle, outcome).
    """
    fifos = [_Fifo(int(d)) for d in depths]
    n = len(sources)
    clock = [1] * n
    pending: List[Optional[tuple]] = [None] * n
    record: List[list] = [[] for _ in range(n)]
    samples: list = []
    heap: list = []
    wait_r: Dict[int, int] = {}
    wait_w: Dict[int, int] = {}
    done = 0
    ends = [0] * n
    tick = 0

    def schedule(m: int) -> None:
        nonlocal tick
        kind, fid, _ = pending[m]
        f = fifos[fid]
        t = clock[m]
        if kind == READ:
            r = len(f.r_t)
            if r >= len(f.w_t):
                wait_r[fid] = m
                return
            t = max(t, f.w_t[r] + 1)
        elif kind == WRITE:
            tgt = len(f.w_t) - f.depth        # 0-based read it waits on
            if tgt >= 0:
                if tgt >= len(f.r_t):
                    wait_w[fid] = m
                    return
                t = max(t, f.r_t[tgt] + 1)
        tick += 1
        heapq.heappush(heap, (t, tick, m))

    def fetch(m: int, value) -> None:
        nonlocal done
        src = sources[m]
        while True:
            try:
                op = src.send(value) if not held else next(src)
            except StopIteration:
                ends[m] = clock[m]
                done += 1
                return
            value = None
            if held:
                kind, fid, arg = op
            else:
                kind = _KIND[type(op).__name__]
                if kind == DELAY:
                    fid, arg = -1, op.cycles
                elif kind == EMIT:
                    continue
                else:
                    fid = op.fifo.fid
                    arg = getattr(op, "value", None)
                    if kind in (EMPTY, FULL) and not op.used:
                        clock[m] += 1
                        record[m].append((DELAY, -1, 1))
                        continue
            if kind == DELAY:
                clock[m] += arg
                if not held:
                    record[m].append((DELAY, -1, arg))
                continue
            pending[m] = (kind, fid, arg)
            schedule(m)
            return

    for m in range(n):
        fetch(m, None)
    while heap:
        t, _, m = heapq.heappop(heap)
        kind, fid, arg = pending[m]
        pending[m] = None
        f = fifos[fid]
        value = None
        if kind == READ:
            value = f.w_v[len(f.r_t)]
            f.r_t.append(t)
            woke = wait_w.pop(fid, None)
        elif kind == WRITE:
            f.w_t.append(t)
            f.w_v.append(arg)
            woke = wait_r.pop(fid, None)
        else:
            if kind in (READNB, EMPTY):
                seq = len(f.r_t) + 1
                ok = seq <= len(f.w_t) and f.w_t[seq - 1] < t
            else:
                seq = len(f.w_t) + 1
                tgt = seq - f.depth
                ok = tgt <= 0 or (tgt <= len(f.r_t) and f.r_t[tgt - 1] < t)
            if held:
                ok = arg
            samples.append((kind, fid, seq, t, ok))
            woke = None
            if kind == READNB:
                value = (ok, f.w_v[seq - 1] if ok and not held else None)
                if ok:
                    f.r_t.append(t)
                    woke = wait_w.pop(fid, None)
            elif kind == WRITENB:
                value = ok
                if ok:
                    f.w_t.append(t)
                    f.w_v.append(None if held else arg)
                    woke = wait_r.pop(fid, None)
            else:
                value = not ok       # Empty / Full report the opposite
            arg = ok
        if not held:
            record[m].append((kind, fid, arg if kind in _SAMPLES else None))
        clock[m] = t + 1
        if woke is not None:
            schedule(woke)
        fetch(m, value)
    return done == n, max(ends, default=0), record, samples, fifos


def simulate(depths: Sequence[int], bodies) -> Run:
    """From-scratch run of the design whose module bodies (generator
    functions, in module order) are ``bodies`` under ``depths``."""
    gens = [fn() for fn in bodies]
    finished, cycles, record, _, fifos = _run(depths, gens, False)
    writes = [0] * len(fifos)
    blocking = [0] * len(fifos)
    for ops in record:
        for kind, fid, ok in ops:
            if kind == WRITE or (kind == WRITENB and ok):
                writes[fid] += 1
                if kind == WRITE:
                    blocking[fid] = writes[fid]
    outcomes = tuple(tuple(arg for kind, _, arg in ops if kind in _SAMPLES)
                     for ops in record)
    return Run(deadlock=not finished, cycles=cycles, record=record,
               outcomes=outcomes,
               reads=[len(f.r_t) for f in fifos], blocking_writes=blocking)


def need(base: Run) -> List[int]:
    """Least depth per FIFO at which every blocking write of the base
    run's record finds the read it waits on: below it the design
    deadlocks whatever the timing."""
    return [max(0, w - r) for w, r in zip(base.blocking_writes, base.reads)]


def retime(base: Run, depths: Sequence[int]) -> Optional[int]:
    """Samples of ``base`` whose outcome flips when its record is timed
    under ``depths``; ``None`` when the held record cannot complete."""
    srcs = [iter(ops) for ops in base.record]
    finished, _, _, samples, fifos = _run(depths, srcs, True)
    if not finished:
        return None
    flips = 0
    for kind, fid, seq, t, held in samples:
        f = fifos[fid]
        if kind in (READNB, EMPTY):
            ok = seq <= len(f.w_t) and f.w_t[seq - 1] < t
        else:
            tgt = seq - f.depth
            ok = tgt <= 0 or (tgt <= len(f.r_t) and f.r_t[tgt - 1] < t)
        flips += ok != held
    return flips
