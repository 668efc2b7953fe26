"""Spans and counters of the sweep service (``repro.device.span``).

A served sweep under ``jax.profiler.trace`` is read back with
``ProfileData.from_file``, one host line per thread: every span the
service, the solve phase and the cache open is there with its
attributes; the scheduler thread's phase spans are flat and grouped by
their ``block`` number; a request's submit, dequeue and done carry its
``rid``.  The scheduler's counters (``solve_calls``, ``fixpoint_rounds``,
``forced_bulk_blocks``, ``device_verdict_rows``, ``host_recheck_rows``)
are checked against the same run.
"""
import glob
import threading

import numpy as np
import pytest

from repro.core import simulate
from repro.designs.typea import skynet_like
from repro.sweep import BULK, GraphCache, SweepService

# span name -> attributes it must carry
SPANS = {
    "sweep.submit": {"rid", "lane", "rows", "cache"},
    "sweep.cache_build": {"key"},
    "sweep.assemble": {"block", "lane", "forced", "rows",
                       "interactive_rows"},
    "sweep.dequeue": {"rid", "lane", "rows", "block", "wait_us"},
    "sweep.dedup": {"block", "rows_unique", "memo_hits"},
    "solve.upload": {"K", "war_lane", "war_steps"},
    "solve.fixpoint": {"rounds", "cyclic_rows", "freeze_round",
                       "unsettled_rows"},
    "solve.copy_back": {"bytes"},
    "solve.recheck": {"rounds", "violated_rows", "rows"},
    "sweep.materialize": {"block", "fallbacks"},
    "sweep.deliver": {"block", "rows"},
    "sweep.request_done": {"rid", "lane", "latency_us"},
}
# the scheduler thread's phases, which never nest
PHASES = ("sweep.assemble", "sweep.dedup", "solve.upload", "solve.fixpoint",
          "solve.copy_back", "solve.recheck", "sweep.materialize",
          "sweep.deliver")


def _build():
    return skynet_like(items=32, depth=5)


def _drain(svc):
    while svc.step():
        pass


def _traced_sweep(log_dir, backend):
    """Serve one bulk request and three interactive ones under the
    profiler, the blocks stepped on a thread of their own; returns
    (per-thread host lines: [[(name, start, end, stats)]], scheduler
    stats).  ``starvation_limit=1`` with interactive rows left over
    forces a bulk block between interactive ones."""
    import jax

    F = len(simulate(_build()).depths)
    rng = np.random.default_rng(5)
    Db = rng.integers(1, 9, size=(40, F))
    Di = [rng.integers(1, 9, size=(12, F)) for _ in range(3)]
    svc = SweepService(block=16, backend=backend, starvation_limit=1,
                       autostart=False)
    with jax.profiler.trace(str(log_dir)):
        hb = svc.submit(_build(), Db, priority=BULK)
        his = [svc.submit(_build(), D, fallback=True) for D in Di]
        th = threading.Thread(target=_drain, args=(svc,),
                              name="sweep-scheduler")
        th.start()
        th.join(timeout=300)
        assert not th.is_alive()
        outs = [h.result(timeout=10) for h in [hb] + his]
    stats = svc.stats()["scheduler"]
    svc.close()
    assert all(len(o.status) for o in outs)
    from jax.profiler import ProfileData

    path = glob.glob(str(log_dir / "plugins" / "profile" / "*" /
                         "*.xplane.pb"))[0]
    lines = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                    dict(e.stats)) for e in ln.events
                   if e.name.startswith(("sweep.", "solve."))]
            if evs:
                lines.append(sorted(evs, key=lambda e: e[1]))
    return lines, stats


@pytest.fixture(scope="module")
def jax_run(tmp_path_factory):
    pytest.importorskip("jax")
    return _traced_sweep(tmp_path_factory.mktemp("trace_jax"), "jax")


def _scheduler_line(lines):
    (line,) = [ln for ln in lines
               if any(e[0] == "sweep.assemble" for e in ln)]
    return line


def _all(lines):
    return [e for ln in lines for e in ln]


def _idle_poll(e):
    """An assemble that found no rows (``solve.recheck``'s ``rows`` may
    be 0 in a block that did work)."""
    return e[0] == "sweep.assemble" and not e[3].get("rows")


@pytest.mark.parametrize("name", sorted(SPANS))
def test_every_span_appears_with_its_attributes(jax_run, name):
    evs = [e for e in _all(jax_run[0]) if e[0] == name]
    assert evs, name
    # an idle poll's assemble carries rows=0 and nothing else
    evs = [e for e in evs if not _idle_poll(e)]
    assert evs and all(SPANS[name] <= set(e[3]) for e in evs), \
        (name, [e[3] for e in evs])


def test_jax_lane_copies_back_verdicts_not_times(jax_run):
    """The device decides every row's verdict: the host copies back
    per-row vectors, transposes no time matrix and re-checks no row."""
    lines, st = jax_run
    evs = _all(lines)
    assert not [e for e in evs if e[0] == "solve.transpose"]
    recheck = [e[3] for e in evs if e[0] == "solve.recheck"]
    assert recheck and all(r["rows"] == 0 for r in recheck)
    assert st["host_recheck_rows"] == 0
    assert st["device_verdict_rows"] == st["rows_unique"] > 0
    for e in evs:
        if e[0] == "solve.copy_back":       # three (K,) vectors
            assert e[3]["bytes"] <= 3 * 4 * 16, e


def test_phase_spans_are_flat_and_share_their_block(jax_run):
    line = _scheduler_line(jax_run[0])
    phases = [e for e in line if e[0] in PHASES and not _idle_poll(e)]
    for a, b in zip(phases, phases[1:]):
        assert a[2] <= b[1], (a, b)              # never nest or overlap
    block, seen = None, []
    for name, _s, _e, st in phases:
        if name == "sweep.assemble":
            block = st["block"]
            seen.append(block)
        elif name.startswith("sweep."):
            assert st["block"] == block, (name, st, block)
    assert seen == list(range(1, len(seen) + 1))
    # a block's solve spans follow its dedup, and each block solved once
    solves = [e for e in phases if e[0] == "solve.fixpoint"]
    assert len(solves) == len(seen)


def test_request_spans_share_its_rid(jax_run):
    evs = _all(jax_run[0])
    by = {}
    for name in ("sweep.submit", "sweep.dequeue", "sweep.request_done"):
        for e in evs:
            if e[0] == name:
                by.setdefault(e[3]["rid"], {})[name] = e
    assert len(by) == 4
    for rid, spans in by.items():
        sub, deq, done = (spans["sweep.submit"], spans["sweep.dequeue"],
                          spans["sweep.request_done"])
        assert sub[3]["lane"] == deq[3]["lane"] == done[3]["lane"]
        assert sub[3]["rows"] == deq[3]["rows"]
        assert sub[1] <= deq[1] <= done[1]
        assert deq[3]["wait_us"] <= done[3]["latency_us"]
    assert sorted(s["sweep.submit"][3]["cache"] for s in by.values()) == \
        ["hit", "hit", "hit", "miss"]


def test_counters_after_a_jax_lane_sweep(jax_run):
    lines, st = jax_run
    assert st["solve_calls"] >= 1
    assert st["fixpoint_rounds"] >= st["solve_calls"]
    assemble = [e for e in _scheduler_line(lines)
                if e[0] == "sweep.assemble" and e[3].get("rows")]
    assert st["solve_calls"] == len(assemble) == st["blocks"]
    forced = [e for e in assemble if e[3]["forced"]]
    assert st["forced_bulk_blocks"] == len(forced) >= 1
    assert all(e[3]["lane"] == BULK for e in forced)


def test_numpy_lane_emits_the_sweep_spans(tmp_path):
    lines, st = _traced_sweep(tmp_path, "numpy")
    names = {e[0] for e in _all(lines)}
    assert {n for n in SPANS if n.startswith("sweep.")} <= names
    assert "solve.recheck" in names and "solve.fixpoint" not in names
    assert st["solve_calls"] == st["blocks"]
    assert st["device_verdict_rows"] == 0
    assert st["host_recheck_rows"] == st["rows_unique"] > 0


def test_cache_resolve_names_how_the_entry_was_found():
    from repro.corpus import edit_pairs
    from repro.delta import fingerprint_design

    cache = GraphCache(capacity=4)
    assert cache.resolve(_build())[1] == "miss"
    assert cache.resolve(_build())[1] == "hit"
    pairs = {q.kind: q for q in edit_pairs(3, scale=28)}
    p = pairs["delay"]
    look = cache.get_or_patch(p.base(), fingerprint_design(p.base()), None)
    fps = fingerprint_design(p.edited())
    assert cache.get_or_patch(p.edited(), fps, look.state).mode == "patched"
    assert cache.resolve(p.edited())[1] == "patched"


def test_cycle_exit_counts_in_spans_and_stats(tmp_path):
    """A jax-lane sweep of Rosetta's optical flow, a third of whose depth
    rows deadlock: each ``solve.fixpoint`` span says how many rows the
    cycle exit froze and in which round the last one froze, and the
    scheduler counts the CYCLE verdicts."""
    import jax
    from jax.profiler import ProfileData

    from repro.core.dse import CYCLE
    from repro.designs.rosetta import optical_flow

    def build():
        return optical_flow(height=2, width=64)
    D = np.random.default_rng(0).integers(1, 257, (32, 14))
    svc = SweepService(block=16, backend="jax")
    with jax.profiler.trace(str(tmp_path)):
        out = svc.submit(build(), D, priority=BULK).result(timeout=300)
    stats = svc.stats()["scheduler"]
    svc.close()
    n_cycle = int((out.status == CYCLE).sum())
    assert n_cycle > 0
    assert stats["cyclic_rows"] == n_cycle
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    fx = [dict(e.stats) for p in ProfileData.from_file(path).planes
          if p.name.startswith("/host:") for ln in p.lines
          for e in ln.events if e.name == "solve.fixpoint"]
    assert len(fx) == stats["solve_calls"]
    frozen = [st for st in fx if st["cyclic_rows"] > 0]
    assert frozen and sum(st["cyclic_rows"] for st in fx) <= n_cycle
    for st in frozen:
        assert 0 < st["freeze_round"] <= st["rounds"]
