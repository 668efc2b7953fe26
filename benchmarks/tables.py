"""Benchmark implementations — one function per paper table/figure.

Each returns a list of CSV rows (name, us_per_call, derived) plus prints a
human-readable table.  The "co-sim" baseline is the cycle-stepped RTL oracle
(core/rtlsim.py) — see DESIGN.md Sec. 7 for why.
"""
from __future__ import annotations

import time
from typing import Dict, List, Tuple

from repro.core import (LightningSim, UnsupportedDesignError, csim,
                        resimulate, resimulate_batch, simulate, simulate_rtl)
from repro.designs import PAPER_DESIGNS, TYPEA_DESIGNS

# machine-readable core-perf numbers, filled by the benchmarks below and
# dumped to BENCH_core.json by benchmarks/run.py so future PRs have a
# trajectory to compare against
BENCH_CORE: Dict[str, float] = {}

# ``benchmarks/run.py --quick`` sets this: reduced design sizes, fewer
# repeats — every BENCH_CORE key is still produced (the schema test in
# tests/test_bench_schema.py relies on that), the values just carry more
# noise.
QUICK = False


def _timeit(fn, repeats: int = 1):
    best = float("inf")
    out = None
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn()
        best = min(best, time.perf_counter() - t0)
    return out, best


# ---------------------------------------------------------------- Table 3
def table3_funcsim() -> List[str]:
    """Functionality simulation across C-sim / co-sim / OmniSim."""
    rows = []
    print("\n== Table 3: Func Sim comparison (C-sim vs co-sim vs OmniSim) ==")
    print(f"{'design':14s} {'C-sim':>34s} {'co-sim':>26s} {'OmniSim':>26s} {'match':>6s}")
    for name, builder in PAPER_DESIGNS.items():
        c = csim(builder())
        r = simulate_rtl(builder())
        o, dt = _timeit(lambda: simulate(builder()))
        cs = c.outputs.get("__crash__") or \
            {k: v for k, v in c.outputs.items() if not k.startswith("__")}
        ro = "DEADLOCK" if r.deadlock else \
            {k: v for k, v in r.outputs.items() if not k.startswith("__")}
        oo = "DEADLOCK detected" if o.deadlock else \
            {k: v for k, v in o.outputs.items() if not k.startswith("__")}
        match = (o.deadlock == r.deadlock) and (o.deadlock or
                                                o.outputs == r.outputs)
        print(f"{name:14s} {str(cs)[:34]:>34s} {str(ro)[:26]:>26s} "
              f"{str(oo)[:26]:>26s} {'YES' if match else 'NO':>6s}")
        rows.append(f"table3/{name},{dt*1e6:.0f},match={match}")
    return rows


# ------------------------------------------------------------- Fig 8(a,b)
def fig8_perfsim() -> List[str]:
    """Cycle accuracy + speed vs the cycle-stepped oracle."""
    rows = []
    print("\n== Fig 8: cycle accuracy and speed vs co-sim (RTL oracle) ==")
    print(f"{'design':14s} {'cosim cyc':>10s} {'omni cyc':>10s} {'err%':>6s} "
          f"{'cosim ms':>9s} {'omni ms':>8s} {'speedup':>8s}")
    geo_acc, geo_spd, n = 0.0, 1.0, 0
    for name, builder in PAPER_DESIGNS.items():
        r, t_rtl = _timeit(lambda: simulate_rtl(builder()))
        o, t_omni = _timeit(lambda: simulate(builder()))
        if r.deadlock:
            print(f"{name:14s} {'DEADLOCK':>10s} {'DEADLOCK':>10s}")
            rows.append(f"fig8/{name},{t_omni*1e6:.0f},deadlock_detected=True")
            continue
        err = abs(o.cycles - r.cycles) / r.cycles * 100
        spd = t_rtl / t_omni
        geo_spd *= spd
        n += 1
        print(f"{name:14s} {r.cycles:10d} {o.cycles:10d} {err:5.2f}% "
              f"{t_rtl*1e3:8.1f} {t_omni*1e3:7.1f} {spd:7.2f}x")
        rows.append(f"fig8/{name},{t_omni*1e6:.0f},"
                    f"cycle_err_pct={err:.4f};speedup_vs_cosim={spd:.2f}")
    if n:
        print(f"{'geomean speedup':>62s} {geo_spd ** (1 / n):7.2f}x")
        rows.append(f"fig8/geomean,0,speedup={geo_spd ** (1/n):.2f}")
    return rows


# ---------------------------------------------------------------- Table 5
def table5_vs_decoupled() -> List[str]:
    """OmniSim vs the decoupled two-phase baseline on the Type A suite."""
    rows = []
    print("\n== Table 5: Type A suite — decoupled baseline vs OmniSim ==")
    print(f"{'design':20s} {'LS total ms':>12s} {'Omni ms':>9s} {'ratio':>7s} "
          f"{'same?':>6s}")
    for name, builder in TYPEA_DESIGNS.items():
        ls, t_ls = _timeit(lambda: LightningSim(builder()).run(), repeats=2)
        om, t_om = _timeit(lambda: simulate(builder()), repeats=2)
        same = ls.outputs == om.outputs and ls.cycles == om.cycles
        print(f"{name:20s} {t_ls*1e3:11.1f} {t_om*1e3:8.1f} "
              f"{t_ls/t_om:6.2f}x {'YES' if same else 'NO':>6s}")
        rows.append(f"table5/{name},{t_om*1e6:.0f},"
                    f"ratio_vs_decoupled={t_ls/t_om:.2f};exact_match={same}")
    # the decoupled baseline cannot run any Type B/C design at all
    unsupported = 0
    for name, builder in PAPER_DESIGNS.items():
        try:
            LightningSim(builder()).run()
        except UnsupportedDesignError:
            unsupported += 1
    print(f"decoupled baseline rejects {unsupported}/{len(PAPER_DESIGNS)} "
          f"Type B/C designs; OmniSim simulates all of them")
    rows.append(f"table5/unsupported_by_baseline,0,count={unsupported}")
    return rows


# ---------------------------------------------------------------- Table 6
def table6_incremental() -> List[str]:
    """fig4_ex5 FIFO-depth changes: incremental vs full re-simulation."""
    rows = []
    print("\n== Table 6: incremental re-simulation (fig4_ex5) ==")
    builder = PAPER_DESIGNS["fig4_ex5"]
    r0, t_full = _timeit(lambda: simulate(builder()))
    print(f"initial run (2,2): cycles={r0.cycles}  {t_full*1e3:.1f} ms")
    rows.append(f"table6/initial,{t_full*1e6:.0f},cycles={r0.cycles}")
    for depths in ((2, 100), (100, 2)):
        r0i = simulate(builder())
        _ = resimulate(r0i, depths)          # warm the cache
        r0i = simulate(builder())
        inc, t_inc = _timeit(lambda: resimulate(r0i, depths))
        ok = "OK" if inc.ok else "violated -> full re-sim"
        spd = t_full / t_inc
        print(f"depths {depths}: {ok}; cycles={inc.result.cycles} "
              f"{t_inc*1e3:.2f} ms ({spd:.0f}x vs full)")
        rows.append(f"table6/depths_{depths[0]}_{depths[1]},{t_inc*1e6:.0f},"
                    f"ok={inc.ok};cycles={inc.result.cycles};speedup={spd:.0f}")
    return rows


# ------------------------------------------------------- Table 6 extension
def table6_batch_dse() -> List[str]:
    """Depth-batched DSE: K configs per resimulate_batch call vs a Python
    loop of resimulate() calls (the core/dse.py throughput engine)."""
    import numpy as np

    from repro.designs.typea import skynet_like
    rows = []
    print("\n== Table 6 (batch): depth-batched DSE on skynet_like ==")
    items = 128 if QUICK else 512
    builder = lambda: skynet_like(items=items, depth=12)
    base, t_full = _timeit(lambda: simulate(builder()))
    rng = np.random.default_rng(0)
    K = 64 if QUICK else 256
    D = rng.integers(4, 17, size=(K, len(base.depths)))
    resimulate(base, tuple(int(d) for d in D[0]))          # warm the cache
    resimulate_batch(base, D[:2])
    t0 = time.perf_counter()
    for row in D:
        resimulate(base, tuple(int(d) for d in row), fallback=False)
    t_loop = time.perf_counter() - t0
    out, t_batch = _timeit(lambda: resimulate_batch(base, D, fallback=False))
    spd = t_loop / t_batch
    us_loop = t_loop / K * 1e6
    us_batch = t_batch / K * 1e6
    print(f"{K} configs: looped {t_loop*1e3:7.1f} ms ({us_loop:6.0f} us/cfg)"
          f"  batched {t_batch*1e3:6.1f} ms ({us_batch:5.0f} us/cfg)"
          f"  speedup {spd:5.1f}x  reused {out.n_reused}/{K}")
    print(f"vs full re-simulation per config: "
          f"{t_full / (t_batch / K):,.0f}x")
    rows.append(f"table6_batch/skynet_like_K{K},{us_batch:.1f},"
                f"speedup_vs_loop={spd:.1f};reused={out.n_reused}")
    BENCH_CORE.update({
        "full_sim_us": t_full * 1e6,
        "looped_resimulate_us_per_config": us_loop,
        "batched_resimulate_us_per_config": us_batch,
        "batch_speedup_vs_loop": spd,
        "batch_K": K,
        "batch_reused": out.n_reused,
    })
    return rows


# ------------------------------------------------ Sec 5.1 trace compilation
def table_trace_replay() -> List[str]:
    """Initial simulation via trace-compiled replay vs the generator path
    (core/trace.py, ISSUE 2 acceptance: >= 5x on skynet_like)."""
    from repro.designs.typea import skynet_like

    rows = []
    print("\n== Sec 5.1: trace-compiled initial simulation vs generator ==")
    print(f"{'design':22s} {'gen ms':>8s} {'trace ms':>9s} {'speedup':>8s} "
          f"{'ops':>8s} {'stored':>7s} {'same?':>6s}")
    cases = {
        "skynet_like": (lambda: skynet_like(items=256, depth=12)) if QUICK
        else (lambda: skynet_like()),                     # items=2048, d=24
        "skynet_like_small": lambda: skynet_like(items=128 if QUICK else 512,
                                                 depth=12),
        "flowgnn_like": lambda: TYPEA_DESIGNS["flowgnn_like"](
            n_nodes=128 if QUICK else 1024, layers=8),
    }
    for name, builder in cases.items():
        # like-for-like: same best-of-2 timing discipline for both paths
        gen, t_gen = _timeit(lambda: simulate(builder(), trace="never"),
                             repeats=2)
        tr, t_tr = _timeit(lambda: simulate(builder(), trace="always"),
                           repeats=2)
        same = (gen.outputs == tr.outputs and gen.cycles == tr.cycles
                and gen.deadlock == tr.deadlock)
        rec = tr.graph._trace            # periodized op streams
        spd = t_gen / t_tr
        print(f"{name:22s} {t_gen*1e3:7.1f} {t_tr*1e3:8.1f} {spd:7.1f}x "
              f"{rec.n_ops:8d} {rec.n_stored:7d} {'YES' if same else 'NO':>6s}")
        rows.append(f"trace_replay/{name},{t_tr*1e6:.0f},"
                    f"speedup_vs_generator={spd:.1f};exact_match={same}")
        if name == "skynet_like":
            BENCH_CORE.update({
                "initial_sim_generator_us": t_gen * 1e6,
                "initial_sim_trace_us": t_tr * 1e6,
                "trace_replay_speedup_initial": spd,
                "trace_ops": rec.n_ops,
                "trace_ops_stored_after_periodization": rec.n_stored,
            })
    return rows


# ------------------------------------------- Sec 5.1 hybrid (NB/probe) replay
def table_hybrid_replay() -> List[str]:
    """Repeated simulation of *dynamic* (Type B/C) designs via the hybrid
    engine's cached replay vs the generator engine (ISSUE 9 acceptance:
    >= 4x on branch and multicore).

    This is the *warm* profile a DSE loop actually pays: the first hybrid
    run simulates cold (segmented replay) and stores the complete solved
    run in a :class:`~repro.core.trace.HybridCache`; every repeat is a
    whole-run verified replay — bulk array install plus O(N) per-entry
    verification against the claimed FIFO tables, no generator resumption
    at all.  The cold path alone tops out near 2x on the forced-query-
    dominated paper designs (branch/multicore ping-pong one forced poll
    per phase, which no steady-state detector can periodize), so the
    cached fast path is what makes them fast.  Writes
    ``hybrid_replay_speedup_<design>`` (warm) and
    ``hybrid_replay_cold_speedup_<design>`` keys into BENCH_core.json.
    """
    from repro.core.trace import HybridCache
    from repro.designs.dynamic import watchdog_pipe

    rows = []
    print("\n== Sec 5.1 hybrid: cached replay on dynamic designs ==")
    print(f"{'design':16s} {'gen ms':>8s} {'cold ms':>8s} {'warm ms':>8s} "
          f"{'speedup':>8s} {'ops':>8s} {'queries':>8s} {'same?':>6s}")
    if QUICK:
        cases = {
            "fig2_timer": lambda: PAPER_DESIGNS["fig2_timer"](n=512),
            "branch": lambda: PAPER_DESIGNS["branch"](prog_len=512),
            "multicore": lambda: PAPER_DESIGNS["multicore"](cores=8,
                                                            prog_len=64),
            "watchdog_pipe": lambda: watchdog_pipe(items=512, stages=4),
        }
    else:
        cases = {
            "fig2_timer": lambda: PAPER_DESIGNS["fig2_timer"](),
            "branch": lambda: PAPER_DESIGNS["branch"](),
            "multicore": lambda: PAPER_DESIGNS["multicore"](),
            "watchdog_pipe": lambda: watchdog_pipe(items=8192, stages=6),
        }
    for name, builder in cases.items():
        gen, t_gen = _timeit(lambda: simulate(builder(), trace="never"),
                             repeats=1 if QUICK else 2)
        cache = HybridCache()
        cold, t_cold = _timeit(
            lambda: simulate(builder(), trace="always", hybrid_cache=cache),
            repeats=1)
        hyb, t_hyb = _timeit(
            lambda: simulate(builder(), trace="always", hybrid_cache=cache),
            repeats=2 if QUICK else 3)
        assert hyb.engine == "omnisim-hybrid", name
        assert cache.full_hits >= 1 and cache.full_rejects == 0, name
        same = (gen.outputs == hyb.outputs and gen.cycles == hyb.cycles
                and gen.deadlock == hyb.deadlock
                and cold.outputs == hyb.outputs)
        info = hyb.graph._hybrid
        spd = t_gen / t_hyb
        print(f"{name:16s} {t_gen*1e3:7.1f} {t_cold*1e3:7.1f} "
              f"{t_hyb*1e3:7.1f} {spd:7.2f}x {info['ops']:8d} "
              f"{info['queries']:8d} {'YES' if same else 'NO':>6s}")
        rows.append(f"hybrid_replay/{name},{t_hyb*1e6:.0f},"
                    f"speedup_vs_generator={spd:.2f};exact_match={same}")
        BENCH_CORE[f"hybrid_replay_speedup_{name}"] = spd
        BENCH_CORE[f"hybrid_replay_cold_speedup_{name}"] = t_gen / t_cold
        if name == "watchdog_pipe":
            BENCH_CORE.update({
                "hybrid_sim_generator_us_watchdog_pipe": t_gen * 1e6,
                "hybrid_sim_hybrid_us_watchdog_pipe": t_hyb * 1e6,
                "hybrid_queries_watchdog_pipe": info["queries"],
                "hybrid_ops_watchdog_pipe": info["ops"],
            })
    return rows


# ---------------------------------------- Sec 5.1 query periodization burst
def table_query_periodization() -> List[str]:
    """Steady-state query periodization on poll-dominated designs
    (ISSUE 4 acceptance: >= 4x on fig2_timer).

    The hybrid engine's poll-loop detector resolves K definitively-false
    outcomes per burst against the committed FIFO tables instead of one
    generator resumption + Table-2 resolution per query.  fig2_timer is the
    uniform-gap poll loop (one burst covers the whole run); fig2_poll_burst
    cycles through non-uniform gaps, so the detector re-arms per constant-
    gap run and the divergence fallback is on the measured path too;
    multisite_poll round-robins over two FIFOs fed at different rates (the
    multi-site ``(site, gap, outcome)`` tuple pattern a single-site streak
    detector cannot see); nb_success_stream is a steady *successful* NB
    stream, periodized against the producer's run-ahead write table.
    Writes ``query_periodization_*`` keys into BENCH_core.json.
    """
    from repro.designs.dynamic import (fig2_poll_burst, multisite_poll,
                                       nb_success_stream)

    rows = []
    print("\n== Sec 5.1 periodization: poll loops vs generator engine ==")
    print(f"{'design':17s} {'gen ms':>8s} {'hybrid ms':>10s} {'speedup':>8s} "
          f"{'queries':>8s} {'bulk':>8s} {'bursts':>7s} {'same?':>6s}")
    if QUICK:
        cases = {
            "fig2_timer": lambda: PAPER_DESIGNS["fig2_timer"](n=512),
            "fig2_poll_burst": lambda: fig2_poll_burst(items=512, stages=2),
            "multisite_poll": lambda: multisite_poll(items=512),
            "nb_success_stream": lambda: nb_success_stream(items=1024),
        }
    else:
        cases = {
            "fig2_timer": lambda: PAPER_DESIGNS["fig2_timer"](),
            "fig2_poll_burst": lambda: fig2_poll_burst(),
            "multisite_poll": lambda: multisite_poll(),
            "nb_success_stream": lambda: nb_success_stream(),
        }
    for name, builder in cases.items():
        gen, t_gen = _timeit(lambda: simulate(builder(), trace="never"),
                             repeats=2 if QUICK else 3)
        hyb, t_hyb = _timeit(lambda: simulate(builder(), trace="always"),
                             repeats=2 if QUICK else 3)
        assert hyb.engine == "omnisim-hybrid", name
        same = (gen.outputs == hyb.outputs and gen.cycles == hyb.cycles
                and gen.stats.queries == hyb.stats.queries
                and gen.stats.queries_forced_false
                == hyb.stats.queries_forced_false)
        info = hyb.graph._hybrid
        spd = t_gen / t_hyb
        print(f"{name:17s} {t_gen*1e3:7.1f} {t_hyb*1e3:9.1f} {spd:7.2f}x "
              f"{info['queries']:8d} {info['bulk_queries']:8d} "
              f"{info['bursts']:7d} {'YES' if same else 'NO':>6s}")
        rows.append(f"query_periodization/{name},{t_hyb*1e6:.0f},"
                    f"speedup_vs_generator={spd:.2f};"
                    f"bulk={info['bulk_queries']};exact_match={same}")
        BENCH_CORE[f"query_periodization_speedup_{name}"] = spd
        if name == "fig2_timer":
            BENCH_CORE.update({
                "query_periodization_sim_generator_us_fig2_timer": t_gen * 1e6,
                "query_periodization_sim_hybrid_us_fig2_timer": t_hyb * 1e6,
                "query_periodization_bulk_queries_fig2_timer":
                    int(info["bulk_queries"]),
            })
        elif name in ("multisite_poll", "nb_success_stream"):
            BENCH_CORE[f"query_periodization_bulk_queries_{name}"] = \
                int(info["bulk_queries"])
    return rows


# ----------------------------------------------- ISSUE 5: served DSE sweeps
def table_sweep_service() -> List[str]:
    """Sweep service vs a naive per-request resimulate() loop on
    skynet_like (ISSUE 5 acceptance: warm-cache served throughput >= 5x
    the loop), plus dedup ratio and cache hit rate."""
    import numpy as np

    from repro.designs.typea import skynet_like
    from repro.sweep import SweepService

    rows = []
    print("\n== ISSUE 5: served DSE sweeps (repro/sweep) ==")
    items = 128 if QUICK else 512
    builder = lambda: skynet_like(items=items, depth=12)
    K = 96 if QUICK else 512
    n_fifo = len(builder().fifos)
    rng = np.random.default_rng(0)
    # requests re-propose configurations (grids revisit corners, halving
    # re-evaluates survivors): sample rows from a small pool so the block
    # dedup has real duplicates to collapse
    pool = rng.integers(4, 17, size=(max(K // 4, 1), n_fifo))
    D = pool[rng.integers(0, len(pool), size=K)]

    # naive per-request loop: one warm resimulate() call per config
    base, _ = _timeit(lambda: simulate(builder()))
    resimulate(base, tuple(int(d) for d in D[0]))          # warm the cache
    t0 = time.perf_counter()
    for row in D:
        resimulate(base, tuple(int(d) for d in row), fallback=False)
    t_loop = time.perf_counter() - t0

    svc = SweepService(block=128, shards=2, mode="thread")
    try:
        t0 = time.perf_counter()
        cold = svc.sweep(builder(), D)         # pays initial sim + hoisting
        t_cold = time.perf_counter() - t0
        t0 = time.perf_counter()
        warm = svc.sweep(builder(), D)         # served from the warm cache
        t_warm = time.perf_counter() - t0
        st = svc.stats()
    finally:
        svc.close()
    assert (cold.cycles == warm.cycles).all()
    cps_cold = K / t_cold
    cps_warm = K / t_warm
    spd = t_loop / t_warm
    print(f"{K} configs ({cold.n_unique} unique): loop {t_loop*1e3:7.1f} ms"
          f"  cold {t_cold*1e3:7.1f} ms ({cps_cold:,.0f} cfg/s)"
          f"  warm {t_warm*1e3:6.1f} ms ({cps_warm:,.0f} cfg/s)"
          f"  speedup {spd:5.1f}x")
    print(f"dedup {st['scheduler']['dedup_ratio']:.2f}x  "
          f"cache hit rate {st['cache']['hit_rate']:.2f}  "
          f"blocks {st['scheduler']['blocks']}")
    rows.append(f"sweep_service/skynet_like_K{K},{t_warm/K*1e6:.1f},"
                f"speedup_vs_loop={spd:.1f};"
                f"dedup={st['scheduler']['dedup_ratio']:.2f}")
    BENCH_CORE.update({
        "sweep_warm_configs_per_sec": cps_warm,
        "sweep_cold_configs_per_sec": cps_cold,
        "sweep_service_speedup_vs_loop": spd,
        "sweep_dedup_ratio": st["scheduler"]["dedup_ratio"],
        "sweep_cache_hit_rate": st["cache"]["hit_rate"],
    })
    return rows


# ------------------------------------------ ISSUE 6: sweeps under faults
def table_sweep_faults() -> List[str]:
    """Fault-tolerant serving overhead (ISSUE 6): warm served throughput
    with a seeded FaultInjector (transient shard faults, retried) vs
    fault-free, and interactive p99 latency while a bulk tenant's design
    faults at a 10% shard rate."""
    import numpy as np

    from repro.designs.typea import producer_consumer, skynet_like
    from repro.sweep import FaultInjector, RetryPolicy, SweepService

    rows = []
    print("\n== ISSUE 6: sweep serving under injected faults ==")
    items = 128 if QUICK else 512
    builder = lambda: skynet_like(items=items, depth=12)
    K = 96 if QUICK else 512
    n_fifo = len(builder().fifos)
    rng = np.random.default_rng(0)
    pool = rng.integers(4, 17, size=(max(K // 4, 1), n_fifo))
    D = pool[rng.integers(0, len(pool), size=K)]

    def warm_run(injector=None, retry=None):
        svc = SweepService(block=128, shards=2, mode="thread",
                           injector=injector, retry=retry)
        try:
            svc.sweep(builder(), D)            # cold: build + warm-up
            t0 = time.perf_counter()
            out = svc.sweep(builder(), D)
            dt = time.perf_counter() - t0
            st = svc.stats()
        finally:
            svc.close()
        return out, dt, st

    clean, t_clean, _ = warm_run()
    # transient faults at a 10% shard rate (plus a guaranteed first-draw
    # fault so the retry path is always on the measured profile), all
    # absorbed by a fast retry policy
    inj = FaultInjector(seed=0).arm("shard.fault", at=[0], rate=0.10)
    faulty, t_fault, st = warm_run(
        injector=inj, retry=RetryPolicy(max_attempts=4, backoff_s=1e-3,
                                        max_backoff_s=5e-3))
    delivered = faulty.status != 5             # FAULTED: retries exhausted
    assert (faulty.cycles[delivered] == clean.cycles[delivered]).all()
    cps_clean = K / t_clean
    cps_fault = K / t_fault
    overhead = t_fault / t_clean
    retries = int(st["scheduler"]["retries"])
    print(f"{K} configs warm: fault-free {t_clean*1e3:6.1f} ms "
          f"({cps_clean:,.0f} cfg/s)  10% faults {t_fault*1e3:6.1f} ms "
          f"({cps_fault:,.0f} cfg/s)  overhead {overhead:.2f}x  "
          f"retries {retries}  faulted rows "
          f"{int(st['scheduler']['faulted_rows'])}")
    rows.append(f"sweep_faults/skynet_like_K{K},{t_fault/K*1e6:.1f},"
                f"recovery_overhead={overhead:.2f};retries={retries}")

    # interactive p99 while a bulk tenant's design faults at 10%: the
    # quarantine threshold is raised so the poisoned design keeps being
    # scheduled (worst case for the co-tenant), and the clean tenant's
    # small requests ride the interactive lane
    n_live = 12 if QUICK else 40
    live_builder = lambda: producer_consumer(n=64, depth=4)
    inj2 = FaultInjector(seed=1)
    svc = SweepService(block=64, shards=2, mode="thread", injector=inj2,
                       quarantine_after=10**6,
                       retry=RetryPolicy(max_attempts=3, backoff_s=1e-3,
                                         max_backoff_s=5e-3))
    try:
        bulk_key = svc.warm(builder()).key
        inj2.arm("shard.fault", rate=0.10, key=bulk_key)
        svc.warm(live_builder())
        Dl = np.array([[1], [2], [4], [8]])
        svc.sweep(live_builder(), Dl)          # warm the interactive path
        hb = svc.submit(builder(), D, tenant="bulk", priority="bulk")
        lat = []
        for _ in range(n_live):
            t0 = time.perf_counter()
            svc.sweep(live_builder(), Dl, tenant="live")
            lat.append(time.perf_counter() - t0)
        hb.result()
    finally:
        svc.close()
    p99_ms = float(np.percentile(np.asarray(lat), 99) * 1e3)
    print(f"interactive p99 with bulk tenant faulting at 10%: "
          f"{p99_ms:.2f} ms over {n_live} requests")
    rows.append(f"sweep_faults/interactive_p99,{p99_ms*1e3:.1f},"
                f"bulk_fault_rate=0.10")
    BENCH_CORE.update({
        "sweep_fault_free_configs_per_sec": cps_clean,
        "sweep_fault_injected_configs_per_sec": cps_fault,
        "sweep_fault_recovery_overhead": overhead,
        "sweep_fault_retries": retries,
        "sweep_fault_p99_interactive_ms": p99_ms,
    })
    return rows


# ------------------------------------- ISSUE 7: corpus scaling benchmark
def table_corpus_scaling() -> List[str]:
    """Per-engine throughput on constrained-random corpus designs at 100 /
    300 / 1000 modules (ISSUE 7): generator vs auto (hybrid) modules/sec,
    warm sweep-service configs/sec on the 300-module design, and the
    sampled RTL-oracle agreement count."""
    import numpy as np

    from repro.corpus import BENCH_SPEC, generate, rtl_crosscheck
    from repro.sweep import SweepService

    rows = []
    print("\n== ISSUE 7: corpus scaling (constrained-random designs) ==")
    print(f"{'scale':>6s} {'mods':>5s} {'cycles':>7s} {'gen ms':>7s} "
          f"{'auto ms':>8s} {'gen mod/s':>10s} {'auto mod/s':>11s}")
    repeats = 1 if QUICK else 3

    def live_case(scale):
        # first live seed keeps the benchmark on the engine (not on the
        # deadlock early-out), deterministically
        for seed in range(8):
            c = generate(seed, scale=scale, spec=BENCH_SPEC)
            if not simulate(c.builder(), trace="never").deadlock:
                return c
        raise AssertionError(f"no live corpus seed at scale {scale}")

    case300 = None
    for scale in (100, 300, 1000):
        c = live_case(scale)
        if scale == 300:
            case300 = c
        mods = c.meta["modules"]
        g, t_gen = _timeit(lambda: simulate(c.builder(), trace="never"),
                           repeats)
        a, t_auto = _timeit(lambda: simulate(c.builder(), trace="auto"),
                            repeats)
        assert a.cycles == g.cycles and a.outputs == g.outputs
        print(f"{scale:6d} {mods:5d} {g.cycles:7d} {t_gen*1e3:6.1f} "
              f"{t_auto*1e3:7.1f} {mods/t_gen:10,.0f} {mods/t_auto:11,.0f}")
        rows.append(f"corpus_scaling/m{scale},{t_auto*1e6:.0f},"
                    f"modules={mods};cycles={g.cycles}")
        BENCH_CORE[f"corpus_modules_per_sec_generator_{scale}"] = mods / t_gen
        BENCH_CORE[f"corpus_modules_per_sec_auto_{scale}"] = mods / t_auto

    # warm sweep-service throughput over depth variants of the 300-module
    # design: offsets only grow depths, so every variant stays live
    g = simulate(case300.builder(), trace="auto")
    base = np.asarray(g.depths, dtype=np.int64)
    K = 16 if QUICK else 64
    rng = np.random.default_rng(7)
    pool = base + rng.integers(0, 5, size=(max(K // 4, 1), base.size))
    D = pool[rng.integers(0, len(pool), size=K)]
    svc = SweepService(block=16, shards=2, mode="thread")
    try:
        svc.sweep(case300.builder(), D)        # cold: build + warm-up
        t0 = time.perf_counter()
        svc.sweep(case300.builder(), D)
        t_warm = time.perf_counter() - t0
    finally:
        svc.close()
    cps = K / t_warm
    print(f"sweep service on {case300.meta['modules']}-module design: "
          f"{K} configs warm in {t_warm*1e3:.1f} ms ({cps:,.0f} cfg/s)")
    rows.append(f"corpus_scaling/sweep300_K{K},{t_warm/K*1e6:.1f},"
                f"configs_per_sec={cps:.0f}")
    BENCH_CORE["corpus_sweep_configs_per_sec_300"] = cps

    # sampled RTL-oracle cross-check: cycle-exact agreement required
    rtl_cases = ([(s, 10) for s in range(6)] + [(s, 32) for s in range(5)]
                 + [(0, 100)])
    agree = 0
    for seed, scale in rtl_cases:
        c = generate(seed, scale=scale, spec=BENCH_SPEC)
        r = rtl_crosscheck(c.builder)
        assert r["agree"], f"{c.name}: engine vs RTL oracle disagree: {r}"
        agree += 1
    print(f"RTL oracle agreement: {agree}/{len(rtl_cases)} corpus designs "
          f"cycle-exact")
    rows.append(f"corpus_scaling/rtl_agree,{0:.0f},count={agree}")
    BENCH_CORE["corpus_rtl_agree_count"] = agree
    return rows


# ----------------------------------------- sparse Pallas max-plus DSE lane
def table_sparse_maxplus() -> List[str]:
    """Sparse chain-structured Pallas max-plus solver (``backend="jax"``,
    compiled on a TPU, interpret mode on the CPU) on a 100-module corpus
    design: device-lane throughput at K = 1e3 / 1e4 / 1e5 depth configs,
    plus the ratio against the numpy Gauss-Seidel fixpoint at the largest
    K.  The dense ``jax_dense`` lowering cannot run this design at all — its
    (K, npad, npad) working set is O(n^2) per config.  ``--quick`` keeps
    every key but solves K/100 configs per point."""
    import numpy as np

    from repro.core.dse import solve_block_status
    from repro.core.incremental import compile_graph
    from repro.corpus import BENCH_SPEC, generate
    from repro.device import pallas_interpret

    rows = []
    print("\n== Sparse max-plus: backend=\"jax\" on a 100-module corpus "
          "design ==")
    # recorded next to the maxplus_sparse_* keys: interpret mode executes
    # the Pallas kernel body through XLA on CPU, so its numbers are not
    # comparable with a compiled-device trajectory
    jax_interpret = pallas_interpret()
    for seed in range(8):           # first live seed, deterministically
        c = generate(seed, scale=100, spec=BENCH_SPEC)
        base_run = simulate(c.builder(), trace="auto")
        if not base_run.deadlock:
            break
    g = compile_graph(base_run.graph)
    base = np.asarray([int(d) for d in base_run.depths], np.int64)
    rng = np.random.default_rng(0)
    shrink = 100 if QUICK else 1
    block = 1024

    def depths(K):
        # offsets only grow depths, so every config stays live
        return base[None, :] + rng.integers(0, 5, size=(K, base.size))

    # warm both solvers (jit compile + chain-flat export on the jax side)
    solve_block_status(g, depths(min(block, 1000 // shrink)),
                       backend="jax", block=block,
                       jax_interpret=jax_interpret)
    Kn = max(1000 // shrink, 1)
    s_np, t_np = _timeit(lambda: solve_block_status(g, depths(Kn),
                                                    backend="numpy",
                                                    block=block))
    us_np = t_np / Kn * 1e6
    print(f"{'K':>8s} {'sparse ms':>10s} {'us/cfg':>7s} "
          f"{'vs numpy':>9s} {'reused':>7s}")
    us_jx = us_np
    for K in (1000, 10_000, 100_000):
        Keff = max(K // shrink, 1)
        D = depths(Keff)
        out, t_jx = _timeit(lambda: solve_block_status(
            g, D, backend="jax", block=block, jax_interpret=jax_interpret))
        us_jx = t_jx / Keff * 1e6
        reused = int((out[0] == 0).sum())
        print(f"{Keff:8d} {t_jx*1e3:10.1f} {us_jx:7.0f} "
              f"{us_np/us_jx:8.2f}x {reused:7d}")
        rows.append(f"sparse_maxplus/{c.name}_K{K},{us_jx:.1f},"
                    f"reused={reused};Keff={Keff}")
        BENCH_CORE[f"maxplus_sparse_us_per_config_{K}"] = us_jx
    # interpret mode runs the TPU kernel through XLA on CPU, so this ratio
    # understates the device lane; it pins the trajectory either way
    BENCH_CORE["maxplus_sparse_vs_numpy_speedup"] = us_np / us_jx
    BENCH_CORE["maxplus_sparse_jax_interpret"] = jax_interpret
    print(f"numpy baseline: {us_np:.0f} us/cfg at K={Kn} "
          f"(ratio at largest K: {us_np/us_jx:.2f}x)")
    return rows


# -------------------------------------------------- Fig 8(b) scaling regime
def fig8_speed_scaling() -> List[str]:
    """Event-driven vs cycle-stepped scaling: speedup grows with idle cycles
    (the co-sim regime the paper targets — RTL simulators pay every cycle)."""
    from repro.designs.typea import high_latency_pipe
    rows = []
    print("\n== Fig 8(b) scaling: speedup vs idle-cycle fraction ==")
    print(f"{'II':>5s} {'cycles':>8s} {'cosim ms':>9s} {'omni ms':>8s} "
          f"{'speedup':>8s}")
    for ii in (8, 32, 64, 128, 256, 512):
        r, t_rtl = _timeit(lambda: simulate_rtl(high_latency_pipe(ii=ii)))
        o, t_om = _timeit(lambda: simulate(high_latency_pipe(ii=ii)))
        assert o.outputs == r.outputs and o.cycles == r.cycles
        print(f"{ii:5d} {o.cycles:8d} {t_rtl*1e3:8.1f} {t_om*1e3:7.1f} "
              f"{t_rtl/t_om:7.2f}x")
        rows.append(f"fig8_scaling/ii{ii},{t_om*1e6:.0f},"
                    f"speedup_vs_cosim={t_rtl/t_om:.2f};cycles={o.cycles}")
    return rows


# ------------------------------------- structural deltas: edit-and-resim
def table_delta_resim() -> List[str]:
    """Edit-and-resimulate (ISSUE 10): serve every corpus edit class on a
    300-module design through ``SweepService.edit_session`` and compare
    against a from-scratch ``simulate`` of the edited design.  Each pair
    gets its own session pinned to its base design (a fresh tenant editing
    that design), so every ``update()`` exercises the real served path:
    fingerprint, classify, patch-or-reject, insert.

    ``delta_resim_speedup_300`` is the acceptance scenario of the issue —
    one module body-edited, ``update()`` time vs cold ``simulate`` time
    (acceptance >= 5); ``delta_reuse_fraction_300`` the worst-case module
    reuse among the patch-served classes (acceptance >= 0.9); and
    ``delta_reject_rate`` the fraction of edit classes the classifier /
    write-stream / verify gates push to a cold rebuild — positive by
    construction because the corpus includes adversarial (value / rename /
    topology) edits.  Every served result, patched or cold, is asserted
    bit-identical to the from-scratch run.  ``--quick`` runs 60-module
    designs under the same keys."""
    from repro.corpus import BLOCKING_SPEC, edit_pairs, result_record
    from repro.corpus.spec import IntRange
    from repro.core.engine import simulate
    from repro.sweep.service import SweepService

    rows = []
    scale = 60 if QUICK else 300
    repeats = 1 if QUICK else 3
    # heavier module bodies than the default corpus spec: the acceptance
    # scenario is an interactive edit of a *substantial* design
    spec = BLOCKING_SPEC.replace(items=IntRange(48, 96))
    print(f"\n== ISSUE 10: structural deltas on {scale}-module corpus "
          "designs ==")
    print(f"{'edit':>10s} {'served':>8s} {'reuse':>6s} {'cold ms':>8s} "
          f"{'update ms':>9s} {'speedup':>8s}")
    pairs = edit_pairs(11, scale=scale, spec=spec)
    simulate(pairs[0].base())            # untimed warmup (imports, numpy)
    body_speedup, reuse_min, rejects = None, 1.0, 0
    for p in pairs:
        base, edited = p.base(), p.edited()
        cold, t_cold = _timeit(lambda: simulate(edited), repeats)
        # fresh session per repeat: each update() is a first edit against
        # a warm base, exactly the interactive loop's steady state
        t_upd, out, served = float("inf"), None, None
        for _ in range(repeats):
            svc = SweepService(autostart=False)
            sess = svc.edit_session(base)
            t0 = time.perf_counter()
            out = sess.update(edited)
            t_upd = min(t_upd, time.perf_counter() - t0)
            served = sess.entry.result
            svc.close()
        assert (out.mode == "patched") == (p.expect == "patched"), \
            (p.kind, out.mode, out.reason)
        assert result_record(served) == result_record(cold), p.kind
        if out.mode == "patched":
            reuse_min = min(reuse_min, out.reuse_fraction)
            if p.kind == "delay":        # the one-module body edit
                body_speedup = t_cold / t_upd
        else:
            rejects += 1
        print(f"{p.kind:>10s} {out.mode:>8s} {out.reuse_fraction:6.2f} "
              f"{t_cold*1e3:7.1f} {t_upd*1e3:8.1f} "
              f"{t_cold/t_upd:7.1f}x")
        rows.append(f"delta_resim/{p.kind}_m{scale},{t_upd*1e6:.0f},"
                    f"served={out.mode};speedup={t_cold/t_upd:.1f}")
    assert body_speedup is not None, "corpus emitted no body-edit pair"
    reject_rate = rejects / len(pairs)
    print(f"body-edit speedup {body_speedup:.1f}x, worst patched reuse "
          f"{reuse_min:.2f}, reject rate {reject_rate:.2f} "
          f"({rejects}/{len(pairs)})")
    BENCH_CORE["delta_resim_speedup_300"] = body_speedup
    BENCH_CORE["delta_reuse_fraction_300"] = reuse_min
    BENCH_CORE["delta_reject_rate"] = reject_rate
    return rows


# ----------------------------------------------------- beyond-paper: perfsim
def pipeline_table() -> List[str]:
    """OmniSim as distributed-schedule simulator (framework integration)."""
    from repro.perfsim.pipeline import PipelineSpec, simulate_pipeline
    rows = []
    print("\n== Beyond-paper: pipeline-schedule prediction (perfsim) ==")
    print(f"{'schedule':>8s} {'stages':>7s} {'mb':>4s} {'step ticks':>11s} "
          f"{'bubble':>7s} {'sim ms':>7s}")
    for schedule in ("gpipe", "1f1b"):
        for mb in (8, 32):
            spec = PipelineSpec(stages=8, microbatches=mb, fwd_ticks=40,
                                bwd_ticks=80, schedule=schedule)
            out, dt = _timeit(lambda: simulate_pipeline(spec))
            print(f"{schedule:>8s} {8:7d} {mb:4d} {out.step_ticks:11d} "
                  f"{out.bubble_fraction:6.1%} {dt*1e3:6.1f}")
            rows.append(f"perfsim/{schedule}_mb{mb},{dt*1e6:.0f},"
                        f"step_ticks={out.step_ticks};"
                        f"bubble={out.bubble_fraction:.3f}")
    return rows
