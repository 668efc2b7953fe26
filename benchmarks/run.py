"""Benchmark harness: one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run                   # full suite
    PYTHONPATH=src python -m benchmarks.run --quick           # smoke
    PYTHONPATH=src python -m benchmarks.run --quick --out P   # route output

Prints human-readable tables followed by ``name,us_per_call,derived`` CSV,
and writes the core-engine perf numbers (incremental/batched
re-simulation, trace-compiled and hybrid segmented initial simulation) to
``BENCH_core.json`` so future PRs have a machine-readable trajectory to
compare against.

JAX's persistent compilation cache goes to ``$JAX_COMPILATION_CACHE_DIR``
if set, else to ``.jax_cache/`` in the checkout.

``--quick`` runs only the key-producing benchmarks at reduced sizes —
every required key is still written (tests/test_bench_schema.py validates
the schema), but the values are not comparable with the full-size
trajectory, so quick output defaults to ``BENCH_core.quick.json`` (or
``--out PATH``) instead of overwriting the committed file.
"""
from __future__ import annotations

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(quick: bool = False, out: str = None) -> None:
    from benchmarks import tables
    tables.QUICK = quick
    from benchmarks.tables import (fig8_perfsim, fig8_speed_scaling,
                                   pipeline_table, table3_funcsim,
                                   table5_vs_decoupled, table6_batch_dse,
                                   table6_incremental, table_corpus_scaling,
                                   table_delta_resim, table_hybrid_replay,
                                   table_query_periodization,
                                   table_sparse_maxplus,
                                   table_sweep_faults, table_sweep_service,
                                   table_trace_replay)
    rows = []
    if not quick:
        rows += table3_funcsim()
        rows += fig8_perfsim()
        rows += fig8_speed_scaling()
        rows += table5_vs_decoupled()
        rows += table6_incremental()
    rows += table6_batch_dse()
    rows += table_sweep_service()
    rows += table_sweep_faults()
    rows += table_trace_replay()
    rows += table_hybrid_replay()
    rows += table_query_periodization()
    rows += table_corpus_scaling()
    rows += table_sparse_maxplus()
    rows += table_delta_resim()
    if not quick:
        rows += pipeline_table()
    print("\n== CSV (name,us_per_call,derived) ==")
    for r in rows:
        print(r)
    if out is None:
        # quick numbers come from reduced sizes and are not comparable with
        # the committed trajectory — keep them out of BENCH_core.json unless
        # the caller routes them explicitly with --out
        name = "BENCH_core.quick.json" if quick else "BENCH_core.json"
        out = os.path.join(REPO, name)
    with open(out, "w") as f:
        json.dump(tables.BENCH_CORE, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"\nwrote {out}")


if __name__ == "__main__":
    argv = sys.argv[1:]
    out_path = None
    if "--out" in argv:
        i = argv.index("--out")
        if i + 1 >= len(argv):
            sys.exit("usage: python -m benchmarks.run [--quick] [--out PATH]")
        out_path = argv[i + 1]
    from repro.device import configure_compile_cache
    configure_compile_cache(os.path.join(REPO, ".jax_cache"))
    main(quick="--quick" in argv, out=out_path)
