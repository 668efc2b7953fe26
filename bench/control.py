#!/usr/bin/env python3
"""Readings of the comparison that decides ``correct``, for its limits.

    python3 bench/control.py --workload <cell> ... --seconds <s> \
        --seeds <n> ... [--fault none|plus1|half|swap]

For each cell and seed: one run of the cell (set-up, a window of
``--seconds``, the program's answers checked as in ``run.py``), then the
control's answers for the same sampled rows checked the same way.  The
control is the reference with every FIFO one slot smaller
(``check.Oracle.control_answer``).  With ``--fault`` the timed path is
broken underneath for the whole run (``plant``), and the program's
reading is that of the broken program.  Prints one JSON line per run.
Needs the chip, as ``run.py`` does; the benchmark's own runs never run
the control or a fault.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)


def _plus1(times, real, arr, Db, kw):
    """An answer altered where it is produced: every time one cycle
    late."""
    return times + 1


def _half(times, real, arr, Db, kw):
    """Half of the block left out: only its first half is solved, and the
    other rows get those rows' answers."""
    k = len(Db)
    if k < 2:
        return times
    first, _, _ = real(arr, Db[: k // 2], **kw)
    return np.asarray(first)[:, np.resize(np.arange(k // 2), k)]


def _swap(times, real, arr, Db, kw):
    """Rows' answers delivered in reverse order."""
    return np.asarray(times)[:, ::-1]


FAULTS = {"plus1": _plus1, "half": _half, "swap": _swap}


def plant(name: str, setattr_=setattr) -> None:
    """Break the device lane's solve (``sparse.solve_chains``) with fault
    ``name``; ``setattr_`` lets a test undo it."""
    from repro.kernels.maxplus import sparse

    real, fault = sparse.solve_chains, FAULTS[name]

    def broken(arr, Db, **kw):
        times, conv, rounds = real(arr, Db, **kw)
        return fault(times, real, arr, Db, kw), conv, rounds
    setattr_(sparse, "solve_chains", broken)


def main(argv=None) -> int:
    import run

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True, nargs="+")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, required=True, nargs="+")
    ap.add_argument("--fault", choices=["none", *FAULTS], default="none")
    args = ap.parse_args(argv)
    import cells
    import check

    first = cells.find_cell(args.workload[0])
    try:
        devices = run.require_chip(first.chips)
    except run.NoChip as exc:
        print(f"control: {exc}", file=sys.stderr)
        return 1
    run.use_compile_cache()
    if args.fault != "none":
        plant(args.fault)
    for name in args.workload:
        cell = cells.find_cell(name)
        for seed in args.seeds:
            keep = {}
            out = run.run_cell(cell, seed, args.seconds, False, devices,
                               t0=time.perf_counter(), keep=keep)
            t = time.perf_counter()
            ctl = check.count_wrong(keep["oracle"], keep["sampled"],
                                    control=True)
            print(json.dumps({
                "workload": name, "seed": seed, "fault": args.fault,
                "correct": out["correct"],
                "answered": len(keep["answered"]),
                "sampled": len(keep["sampled"]),
                "program_wrong_answers": out["checks"]["wrong_answers"][
                    "value"],
                "control_wrong_answers": len(ctl),
                "control_s": time.perf_counter() - t,
                "metrics": out["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
