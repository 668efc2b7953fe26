"""What decides a run's ``correct``: served answers against the reference.

Once the window has closed, a sample of the answered rows, drawn from the
seed and spread over every (lane, status) class that occurred, is
recomputed by the plain reference (``reference.py``) and compared
exactly.  The expected answer of a depth row D, by the design language's
semantics alone:

* DEADLOCK when some FIFO is below its structural need (a blocking write
  whose read never comes);
* REUSED with the from-scratch cycle count when a from-scratch run of D
  finishes and samples every non-blocking access as the base run did;
* otherwise the base run's record no longer holds: CYCLE when that
  record, timed under D, cannot complete, else VIOLATED with the number of
  samples whose outcome flips.

With the exact fallback on, a non-REUSED row also carries the from-scratch
run: its deadlock flag must agree, and its cycle count where it finished.
The status codes are the sweep service's wire format.

The control (``control_answer``) is the reference with one guarantee of
the configuration broken: every FIFO holds one slot less than configured,
the off-by-one a rewrite of the depth-dependent edges could make.
"""
from __future__ import annotations

from typing import List, NamedTuple, Optional

import numpy as np

import reference

REUSED, DEADLOCK, CYCLE, VIOLATED = 0, 1, 2, 3


class Answer(NamedTuple):
    status: int
    cycles: int               # REUSED or fallback: cycles; else -1
    violated: int
    deadlock: Optional[bool]  # fallback rows: the full run's flag


class Row(NamedTuple):
    lane: str                 # "bulk" | "interactive"
    depths: np.ndarray
    fallback: bool
    served: Answer


class Oracle:
    """The reference for one design: its base run and FIFO needs."""

    def __init__(self, build, base_depths):
        self.bodies = [m.fn for m in build().modules]
        self.base = reference.simulate(list(base_depths), self.bodies)
        if self.base.deadlock:
            raise ValueError("the reference finds the base design deadlocks")
        self.need = np.asarray(reference.need(self.base), np.int64)

    def expected(self, D, fallback: bool) -> Answer:
        D = [int(d) for d in D]
        if (np.asarray(D) < self.need).any():
            if not fallback:
                return Answer(DEADLOCK, -1, 0, None)
            return Answer(DEADLOCK, -1, 0,
                          reference.simulate(D, self.bodies).deadlock)
        run = reference.simulate(D, self.bodies)
        if not run.deadlock and run.outcomes == self.base.outcomes:
            return Answer(REUSED, run.cycles, 0,
                          None if not fallback else False)
        flips = reference.retime(self.base, D)
        status = CYCLE if flips is None else VIOLATED
        if not fallback:
            return Answer(status, -1, flips or 0, None)
        return Answer(status, -1 if run.deadlock else run.cycles, flips or 0,
                      run.deadlock)

    def control_answer(self, D, fallback: bool) -> Answer:
        return self.expected(np.maximum(np.asarray(D, np.int64) - 1, 0),
                             fallback)


def agrees(served: Answer, want: Answer) -> bool:
    if served.status != want.status or served.violated != want.violated:
        return False
    if want.deadlock is None:             # no fallback: cycles only if REUSED
        return served.cycles == want.cycles
    if served.deadlock != want.deadlock:
        return False
    return want.deadlock or served.cycles == want.cycles


def sample(rows: List[Row], n: int, rng, floor: int = 4) -> List[Row]:
    """About ``n`` rows drawn from ``rng``: every (lane, status) class gets
    its share of ``n``, and at least ``floor`` rows where it has them."""
    groups = {}
    for r in rows:
        groups.setdefault((r.lane, r.served.status), []).append(r)
    total = max(len(rows), 1)
    out = []
    for key in sorted(groups):
        g = groups[key]
        take = min(len(g), max(floor, int(round(n * len(g) / total))))
        out.extend(g[i] for i in sorted(rng.choice(len(g), take,
                                                   replace=False)))
    return out


def count_wrong(oracle: Oracle, rows: List[Row], control: bool = False):
    """Rows of ``rows`` whose answer disagrees with the reference; with
    ``control`` the control's answers stand in for the served ones."""
    wrong = []
    for r in rows:
        want = oracle.expected(r.depths, r.fallback)
        got = (oracle.control_answer(r.depths, r.fallback) if control
               else r.served)
        if not agrees(got, want):
            wrong.append((r, got, want))
    return wrong
