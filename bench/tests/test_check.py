"""The comparison that decides ``correct``: the reference agrees with the
program where it is sound, its control fails, and a run whose timed path
is broken underneath comes out not correct.

The program is used here only as a second witness; the reference itself
imports nothing of it.
"""
import numpy as np
import pytest

import cells
import check
import control
import reference
import rows

RS = {"draw": "random_search", "lo": 1, "hi": 16}


def _designs():
    from repro.corpus import BENCH_SPEC, generate
    from repro.designs.dynamic import watchdog_pipe
    from repro.designs.paper import multicore
    yield "multicore", multicore
    for seed in (0, 1):
        yield f"corpus30_s{seed}", generate(seed, scale=30,
                                            spec=BENCH_SPEC).builder
    yield "watchdog_pipe", lambda: watchdog_pipe(items=24)


def _rows(base, n, seed, p=RS):
    return rows.draw_streams(len(base.depths), [(n, p)],
                             np.random.default_rng(seed))[0]


def _served(build, D):
    """The program's answers for rows D (numpy lane, fallback off)."""
    from repro.core import simulate
    from repro.core.dse import solve_block_status
    from repro.core.incremental import compile_graph

    base = simulate(build(), trace="auto")
    st, cy, vi, _ = solve_block_status(compile_graph(base.graph), D,
                                       backend="numpy")
    return base, [check.Answer(int(s), int(c), int(v), None)
                  for s, c, v in zip(st, cy, vi)]


@pytest.mark.parametrize("name,build", list(_designs()),
                         ids=[n for n, _ in _designs()])
def test_reference_agrees_with_the_program(name, build):
    from repro.core import simulate
    from repro.core.dse import _batch_arrays
    from repro.core.incremental import compile_graph

    base = simulate(build(), trace="auto")
    oracle = check.Oracle(build, base.depths)
    assert oracle.base.cycles == base.cycles
    need = np.maximum(np.asarray(_batch_arrays(
        compile_graph(base.graph)).fifo_need), 0)
    assert np.array_equal(oracle.need, need)
    D = _rows(base, 48, 3)
    _, served = _served(build, D)
    wrong = check.count_wrong(oracle, [check.Row("bulk", d, False, a)
                                       for d, a in zip(D, served)])
    assert wrong == []


def test_fallback_answers_agree_with_from_scratch_runs():
    from repro.core import simulate
    from repro.corpus import BENCH_SPEC, generate

    build = generate(0, scale=30, spec=BENCH_SPEC).builder
    base = simulate(build(), trace="auto")
    oracle = check.Oracle(build, base.depths)
    D = _rows(base, 16, 5)
    for d in D:
        full = simulate(build(), depths=tuple(int(x) for x in d))
        want = oracle.expected(d, True)
        assert want.deadlock == bool(full.deadlock)
        if not full.deadlock:
            assert want.cycles == full.cycles


@pytest.mark.parametrize("name,build", list(_designs())[:3],
                         ids=[n for n, _ in _designs()][:3])
def test_control_fails_where_the_program_passes(name, build):
    from repro.core import simulate

    base = simulate(build(), trace="auto")
    oracle = check.Oracle(build, base.depths)
    D = _rows(base, 48, 11)
    _, served = _served(build, D)
    rs = [check.Row("bulk", d, False, a) for d, a in zip(D, served)]
    assert check.count_wrong(oracle, rs) == []
    assert len(check.count_wrong(oracle, rs, control=True)) >= 3


def test_sample_spreads_over_every_class():
    rs = [check.Row("bulk", np.zeros(2), False,
                    check.Answer(s, 1, 0, None))
          for s in [0] * 500 + [1] * 6 + [3] * 2]
    got = check.sample(rs, 32, np.random.default_rng(0))
    by = {}
    for r in got:
        by[r.served.status] = by.get(r.served.status, 0) + 1
    assert by[1] == 4 and by[3] == 2 and 28 <= by[0] <= 34


def test_retime_counts_flips_and_cycles():
    from repro.designs.dynamic import watchdog_pipe
    prog = watchdog_pipe(items=24)
    bodies = [m.fn for m in prog.modules]
    base = reference.simulate(prog.depths(), bodies)
    assert reference.retime(base, prog.depths()) == 0
    zero = [0] * len(prog.fifos)
    assert reference.retime(base, zero) is None


# ---------------------------------------------------------------- faults
def _small_cell(name, check_rows=64):
    """The cell's own design, served in blocks of 16 with fewer rows, so
    that a run fits the CPU."""
    cell = cells.find_cell(name)
    cfg = dict(cell.config, check_rows=check_rows)
    tr = dict(cell.traffic, service=dict(cell.traffic["service"], block=16))
    tr["streams"] = [dict(st, request_rows=64, max_rate=100)
                     if st["loop"] == "closed" else dict(st, rate=2)
                     for st in tr["streams"]]
    return cell._replace(config=cfg, traffic=tr)


def _run(cell, tmp_path, seed=2**31 + 77):
    import time

    import jax

    import run
    return run.run_cell(cell, seed, 2.0, False, jax.devices(),
                        t0=time.perf_counter(),
                        trace_dir=str(tmp_path / "trace"))


CELLS = [w["name"] for w in cells.load_benchmark()["workloads"]]


@pytest.mark.parametrize("fallback", [False, True])
@pytest.mark.parametrize("name", CELLS)
def test_a_sound_small_run_is_correct(name, fallback, tmp_path):
    cell = _small_cell(name)
    if fallback:         # closed streams with the exact fallback on too
        tr = cell.traffic
        cell = cell._replace(traffic=dict(tr, streams=[
            dict(st, fallback=True) for st in tr["streams"]]))
    out = _run(cell, tmp_path)
    assert out["correct"], out
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("fault", sorted(control.FAULTS))
@pytest.mark.parametrize("name", CELLS)
def test_a_broken_timed_path_is_caught(name, fault, monkeypatch, tmp_path):
    control.plant(fault, monkeypatch.setattr)
    out = _run(_small_cell(name), tmp_path)
    assert not out["correct"] and out["checks"]["wrong_answers"]["value"]
