#!/usr/bin/env python3
"""One-chip smoke run of the served sweep's device lane.

    python chip_smoke.py

Runs the sparse max-plus lane (``SweepService(backend="jax")`` →
``BlockScheduler`` → ``solve_block_status`` → the Pallas fixpoint in
``repro.kernels.maxplus.sparse``) once, compiled, on one TPU chip:

1. builds three designs and simulates each: ``skynet_like`` (102,452
   graph nodes), the first live 1000-module ``BENCH_SPEC`` corpus design,
   and the dynamic ``watchdog_pipe``;
2. compiles the fixpoint for each at the service's block size and checks
   that the compiled program holds the Mosaic kernel (``tpu_custom_call``);
3. serves a bulk sweep of grown depths (plus a slice of shrunk ones) per
   large design while interactive requests ride the priority lane: the
   watchdog's depth-0 rows (CYCLE / VIOLATED) and the corpus design's
   failing rows with the exact fallback re-simulation on;
4. checks every served row against the numpy lane (status, cycles,
   violated count), sampled rows against a from-scratch ``simulate``,
   and that no row was FAULTED or TIMED_OUT and no fault was absorbed.

Any failed check raises, so the exit code is non-zero.  Off a TPU the
script exits non-zero before it builds anything.  The times it prints are
smoke numbers, one run each, not benchmark metrics.  The last line of
standard output is the JSON contract line
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
"""
from __future__ import annotations

import json
import os
import sys
import time
from typing import Dict, List, NamedTuple

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))


class Sizes(NamedTuple):
    skynet_items: int       # skynet_like(items=...): 102,452 nodes at 2048
    skynet_depth: int
    corpus_scale: int       # modules of the BENCH_SPEC corpus design
    watchdog_items: int     # watchdog_pipe(items=...)
    bulk_rows: int          # depth rows per large design's bulk request
    shrunk_rows: int        # of which shrunk (the rest are grown)
    block: int              # the service's block size


# The watchdog stays at 256 items: its depth-0 rows are WAR cycles, which
# both lanes iterate to their n + 2 round cap, so the numpy reference's
# cost grows with the square of the design.
FULL = Sizes(skynet_items=2048, skynet_depth=24, corpus_scale=1000,
             watchdog_items=256, bulk_rows=4096, shrunk_rows=128, block=128)


class Design(NamedTuple):
    name: str
    build: object           # () -> Program, a fresh copy each call
    base: object            # SimResult of the design's own depths
    graph: object           # CompiledGraph


class Request(NamedTuple):
    design: Design
    D: np.ndarray
    priority: str
    fallback: bool


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"smoke check failed: {what}")


def say(msg: str) -> None:
    print(f"smoke: {msg}", flush=True)


def build_designs(sz: Sizes) -> Dict[str, Design]:
    from repro.core import simulate
    from repro.core.incremental import compile_graph
    from repro.corpus import BENCH_SPEC, generate
    from repro.designs.dynamic import watchdog_pipe
    from repro.designs.typea import skynet_like

    for seed in range(8):             # the first live corpus seed
        case = generate(seed, scale=sz.corpus_scale, spec=BENCH_SPEC)
        if not simulate(case.builder(), trace="auto").deadlock:
            break
    else:
        raise RuntimeError("no live corpus seed in 0..7")
    builders = {
        "skynet_like": lambda: skynet_like(items=sz.skynet_items,
                                           depth=sz.skynet_depth),
        f"corpus{sz.corpus_scale}_s{seed}": case.builder,
        "watchdog_pipe": lambda: watchdog_pipe(items=sz.watchdog_items),
    }
    designs = {}
    for name, build in builders.items():
        t0 = time.perf_counter()
        base = simulate(build(), trace="auto")
        check(not base.deadlock, f"{name}: base design deadlocks")
        graph = compile_graph(base.graph)
        designs[name] = Design(name, build, base, graph)
        say(f"{name}: {graph.n} nodes, {len(base.depths)} FIFOs, "
            f"{base.cycles} cycles, engine {base.engine}, built in "
            f"{time.perf_counter() - t0:.3f}s")
    return designs


# ------------------------------------------------------------ depth rows
def grown_rows(base: np.ndarray, n: int, rng) -> np.ndarray:
    """Up to four FIFOs per row grown by 1..8 slots."""
    D = np.repeat(base[None, :], n, axis=0)
    k = min(4, base.size)
    for row in D:
        f = rng.choice(base.size, k, replace=False)
        row[f] += rng.integers(1, 9, k)
    return D


def shrunk_rows(design: Design, n: int, rng) -> np.ndarray:
    """Rows with one FIFO shrunk: below its structural need (DEADLOCK)
    where a FIFO has one, else to max(1, need) — which can flip a
    non-blocking access (VIOLATED) or just slow the design down."""
    from repro.core.dse import _batch_arrays

    base = np.asarray(design.base.depths, np.int64)
    need = np.asarray(_batch_arrays(design.graph).fifo_need, np.int64)
    dead = np.flatnonzero(need > 0)[: n // 4]
    fids = np.concatenate([dead, rng.choice(base.size, n - len(dead))])
    D = np.repeat(base[None, :], n, axis=0)
    D[np.arange(len(dead)), dead] = need[dead] - 1
    rest = np.arange(len(dead), n)
    D[rest, fids[rest]] = np.maximum(need[fids[rest]], 1)
    return D


def depth0_rows(design: Design) -> np.ndarray:
    """One row per FIFO at depth 0: WAR cycles (CYCLE) or a completion
    flag that can never be written (VIOLATED).  Their exact fallback
    would simulate a design that never finishes, so they are served
    without it."""
    base = np.asarray(design.base.depths, np.int64)
    D = np.repeat(base[None, :], base.size, axis=0)
    D[np.arange(base.size), np.arange(base.size)] = 0
    return D


def make_requests(designs: Dict[str, Design], sz: Sizes,
                  seed: int = 0) -> List[Request]:
    from repro.core.dse import DEADLOCK, VIOLATED, solve_block_status

    rng = np.random.default_rng(seed)
    sky, corpus, dog = designs.values()
    reqs = []
    for d in (sky, corpus):
        base = np.asarray(d.base.depths, np.int64)
        D = np.concatenate([grown_rows(base, sz.bulk_rows - sz.shrunk_rows,
                                       rng),
                            shrunk_rows(d, sz.shrunk_rows, rng)])
        reqs.append(Request(d, D[rng.permutation(len(D))], "bulk", False))
    # interactive: the watchdog's depth-0 rows next to grown ones ...
    dog_base = np.asarray(dog.base.depths, np.int64)
    D = np.concatenate([depth0_rows(dog), grown_rows(dog_base, 8, rng)])
    reqs.append(Request(dog, D, "interactive", False))
    # ... and failing rows of the corpus sweep asked again with the exact
    # fallback on (the corpus design runs on the hybrid engine)
    D = reqs[1].D
    st = solve_block_status(corpus.graph, D, backend="numpy")[0]
    dead, viol = np.flatnonzero(st == DEADLOCK), np.flatnonzero(st == VIOLATED)
    check(len(dead) and len(viol), "corpus sweep has no DEADLOCK or no "
                                   "VIOLATED row to fall back on")
    reqs.append(Request(corpus, D[np.concatenate([dead[:4], viol[:4]])],
                        "interactive", True))
    return reqs


# ---------------------------------------------------------------- phases
def compile_lane(design: Design, K: int, interpret: bool):
    """Compile the fixpoint for ``design`` at batch ``K``; returns
    (seconds, whether the compiled program holds the Mosaic kernel)."""
    import jax.numpy as jnp

    from repro.core.dse import _batch_arrays, _sparse_arrays
    from repro.kernels.maxplus import sparse as sp

    arr = _sparse_arrays(design.graph, _batch_arrays(design.graph))
    args, static = sp._fixpoint_args(
        arr, np.repeat(np.asarray(design.base.depths, np.int64)[None], K, 0))
    t0 = time.perf_counter()
    compiled = sp._fixpoint.lower(*map(jnp.asarray, args), **static,
                                  interpret=interpret).compile()
    return time.perf_counter() - t0, "tpu_custom_call" in compiled.as_text()


def numpy_reference(reqs: List[Request], block: int):
    """The numpy lane's verdicts for every request (the oracle)."""
    from repro.core.dse import solve_block_status

    out = []
    for r in reqs:
        t0 = time.perf_counter()
        out.append(solve_block_status(r.design.graph, r.D, backend="numpy",
                                      block=block)[:3])
        dt = time.perf_counter() - t0
        say(f"numpy lane {r.design.name} ({r.priority}): {len(r.D)} rows "
            f"in {dt:.3f}s")
    return out


def serve(reqs: List[Request], block: int):
    """Submit every request to one jax-lane service (bulk first, so the
    interactive ones overtake it) and collect the outcomes."""
    from repro.sweep import SweepService

    with SweepService(backend="jax", block=block) as svc:
        for r in reqs:
            svc.warm(r.design.base)
        t0 = time.perf_counter()
        handles = [(svc.submit(r.design.base, r.D, priority=r.priority,
                               fallback=r.fallback), time.perf_counter())
                   for r in reqs]
        outs = [None] * len(reqs)
        # interactive first: each result is read as soon as it can be
        for i in sorted(range(len(reqs)),
                        key=lambda i: reqs[i].priority != "interactive"):
            h, t_sub = handles[i]
            outs[i] = h.result(timeout=600.0)    # raises if rows stall
            say(f"served {reqs[i].design.name} ({reqs[i].priority}, "
                f"{len(reqs[i].D)} rows) done "
                f"{time.perf_counter() - t_sub:.3f}s after submit")
        say(f"service wall time {time.perf_counter() - t0:.3f}s, "
            f"stats {svc.stats()['scheduler']}")
        stats = svc.stats()
    return outs, stats


def check_served(reqs, outs, refs, stats) -> None:
    from repro.core.dse import (CYCLE, DEADLOCK, FAULTED, REUSED, TIMED_OUT,
                                VIOLATED)

    seen = set()
    for r, out, (st, cy, vi) in zip(reqs, outs, refs):
        tag = f"{r.design.name} ({r.priority})"
        check(not np.isin(out.status, (FAULTED, TIMED_OUT)).any(),
              f"{tag}: FAULTED or TIMED_OUT rows")
        check(np.array_equal(out.status, st), f"{tag}: status != numpy")
        check(np.array_equal(out.violated, vi), f"{tag}: violated != numpy")
        live = st == REUSED
        check(np.array_equal(out.cycles[live], cy[live]),
              f"{tag}: REUSED cycles != numpy")
        if r.fallback:
            check(all(res is not None for res in out.results),
                  f"{tag}: a fallback re-simulation was withheld")
        else:
            check((out.cycles[~live] == -1).all(),
                  f"{tag}: fallback ran where it was off")
        seen.update(int(s) for s in st)
        counts = {int(s): int(c) for s, c in zip(*np.unique(
            st, return_counts=True))}
        say(f"{tag}: statuses {counts} — all rows equal the numpy lane")
    for want in (REUSED, DEADLOCK, CYCLE, VIOLATED):
        check(want in seen, f"no row reached status {want}")
    sched, quar = stats["scheduler"], stats["quarantine"]
    check(sched["faulted_rows"] == 0 and sched["timed_out_rows"] == 0,
          f"scheduler reports faulted/timed-out rows: {sched}")
    check(sched["retries"] == 0 and quar["strikes"] == 0,
          f"a fault was absorbed: retries {sched['retries']}, {quar}")
    check(sched["fallbacks"] > 0, "no fallback re-simulation ran")


def check_from_scratch(reqs, outs, per_design: int = 2) -> int:
    """Sampled REUSED rows, and every fallback row, against a
    from-scratch ``simulate`` of a fresh copy of the design."""
    from repro.core import simulate
    from repro.core.dse import DEADLOCK, REUSED

    n = 0
    for r, out in zip(reqs, outs):
        rows = list(np.flatnonzero(out.status == REUSED)[:per_design])
        if r.fallback:
            rows += list(np.flatnonzero(out.status != REUSED))
        for k in rows:
            full = simulate(r.design.build(),
                            depths=tuple(int(d) for d in r.D[k]))
            check(int(full.cycles) == int(out.cycles[k])
                  and bool(full.deadlock) == (out.status[k] == DEADLOCK),
                  f"{r.design.name} row {k}: served cycles "
                  f"{out.cycles[k]} != from-scratch {full.cycles}")
            n += 1
    return n


def warm_rates(reqs, refs, block: int) -> None:
    """Warm configs/s of the jax lane per request, around host-side
    results (``solve_block_status`` returns numpy arrays)."""
    from repro.core.dse import solve_block_status

    for r, ref in zip(reqs, refs):
        t0 = time.perf_counter()
        got = solve_block_status(r.design.graph, r.D, backend="jax",
                                 block=block)
        dt = time.perf_counter() - t0
        check(all(np.array_equal(a, b) for a, b in zip(got[:3], ref)),
              f"{r.design.name}: warm jax lane != numpy")
        say(f"warm jax lane {r.design.name} ({r.priority}): {len(r.D)} "
            f"rows in {dt:.3f}s = {len(r.D) / dt:.1f} configs/s")


def run(sz: Sizes, seed: int = 0) -> None:
    """Every phase at sizes ``sz`` on JAX's default backend (compiled on a
    TPU, interpreted on the CPU)."""
    from repro.device import pallas_interpret
    from repro.kernels.maxplus.sparse import ROWS, _pow2

    interpret = pallas_interpret()
    designs = build_designs(sz)
    reqs = make_requests(designs, sz, seed)
    for d in designs.values():
        K = _pow2(min(sz.block, max(len(r.D) for r in reqs
                                    if r.design is d)), ROWS)
        dt, kernel = compile_lane(d, K, interpret)
        check(interpret or kernel,
              f"{d.name}: compiled fixpoint holds no tpu_custom_call")
        say(f"compile {d.name} K={K}: {dt:.3f}s, Mosaic kernel in the "
            f"compiled HLO: {kernel}")
    refs = numpy_reference(reqs, sz.block)
    outs, stats = serve(reqs, sz.block)
    check_served(reqs, outs, refs, stats)
    say(f"{check_from_scratch(reqs, outs)} sampled rows equal a "
        f"from-scratch simulate")
    warm_rates(reqs, refs, sz.block)


def main() -> int:
    from repro.device import configure_compile_cache

    cache = configure_compile_cache(os.path.join(HERE, ".jax_cache"))
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {devs[0].platform!r}",
              file=sys.stderr)
        return 1
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    say(f"device {device}, compile cache {cache} "
        f"(smoke numbers below, not benchmark metrics)")
    t0 = time.perf_counter()
    run(FULL)
    say(f"total {time.perf_counter() - t0:.3f}s")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
