"""The program-span reduction (``spans.py``) and its readers, on made-up
traces, on a trace the service writes here on the CPU, and on a piece of
a chip trace.

``data/trace_multicore_spans_v5e.json`` is an excerpt (``spans.excerpt``)
of a traced run of ``multicore.interactive`` on a TPU v5 lite: three
complete blocks of 1,024 rows on the scheduler thread (the last a forced
bulk block), with every device op and fixpoint execution between them
and the op names of the fixpoint program's HLO.  It pins the span names,
the device scope match and the existing kernel and fixpoint matches.
"""
import json
import os
import threading
from types import SimpleNamespace

import numpy as np
import pytest

import cells
import spans
import tracing

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CHIP = os.path.join(DATA, "trace_multicore_spans_v5e.json")
READERS = ("interactive_queue_ms_p50", "block_host_ms_p50",
           "copy_back_ms_p50", "deliver_ms_p50", "raw_scatter_ms_per_round",
           "war_pass_ms_per_round")
MS = 1e6                 # ns


def _ev(name, s, e, **stats):
    return tracing.Event(name, float(s) * MS, float(e) * MS, stats)


def _made_up():
    """Two blocks on a scheduler thread (times in ms): block 1 forced
    bulk, block 2 interactive; a client thread submits.  The device runs
    one fixpoint execution per block inside its solve.fixpoint span."""
    sched = [
        _ev("sweep.assemble", 10, 11),                  # idle poll
        _ev("sweep.assemble", 20, 22, block=1, lane="bulk", forced=1,
            rows=16, interactive_rows=0),
        _ev("sweep.dequeue", 21, 21, rid=1, lane="bulk", rows=40, block=1,
            wait_us=15000.0),
        _ev("sweep.dedup", 22, 24, block=1, rows_unique=16, memo_hits=0),
        _ev("solve.upload", 24, 26, K=16),
        _ev("solve.fixpoint", 26, 40),
        _ev("solve.copy_back", 40, 45, bytes=100),
        _ev("solve.transpose", 45, 46),
        _ev("solve.recheck", 46, 50, rounds=3, violated_rows=0),
        _ev("sweep.dedup", 50, 51, block=1, rows_unique=16, memo_hits=0,
            memo_inserts=16),
        _ev("sweep.materialize", 51, 56, block=1, fallbacks=0),
        _ev("sweep.deliver", 56, 60, block=1, rows=16),
        _ev("sweep.assemble", 60, 61, block=2, lane="interactive", forced=0,
            rows=16, interactive_rows=8),
        _ev("sweep.dequeue", 60.5, 60.5, rid=2, lane="interactive", rows=8,
            block=2, wait_us=30000.0),
        _ev("sweep.dedup", 61, 62, block=2, rows_unique=16, memo_hits=0),
        _ev("solve.upload", 62, 63, K=16),
        _ev("solve.fixpoint", 63, 80),
        _ev("solve.copy_back", 80, 83, bytes=100),
        _ev("solve.transpose", 83, 84),
        _ev("solve.recheck", 84, 88, rounds=2, violated_rows=0),
        _ev("sweep.materialize", 88, 90, block=2, fallbacks=1),
        _ev("sweep.deliver", 90, 91, block=2, rows=16),
        _ev("sweep.request_done", 90.5, 90.5, rid=2, lane="interactive",
            latency_us=60000.0),
    ]
    client = [_ev("sweep.submit", 30, 30.5, rid=2, lane="interactive",
                  rows=8, cache="hit")]
    k = '%segcummax.3 = s32[16,128] custom_call_target="tpu_custom_call"'
    raw = "%fusion.40 = s32[16,128] fusion()"
    ops = [_ev("%while = loop", 27, 39), _ev(k, 27, 30), _ev(k, 31, 34),
           _ev(raw, 34, 38), _ev("%while = loop", 64, 79.5), _ev(k, 64, 70),
           _ev(raw, 70, 72), _ev(k, 72, 79.5)]
    mods = [_ev("jit__fixpoint(1)", 27, 39.5), _ev("jit__fixpoint(1)", 64,
                                                   79.8)]
    devices = {"/device:TPU:0": {tracing.OPS_LINE: ops,
                                 tracing.MODULES_LINE: mods}}
    names = {"jit__fixpoint(1)": {
        "fusion.40": "jit(_fixpoint)/while/body/cross_pass_raw/scatter-max",
        "segcummax.3": "jit(_fixpoint)/while/body/chain_pass/segcummax"}}
    return spans.Trace(0.0, 100 * MS, devices, [sched, client], names)


def test_blocks_phases_and_solve_assignment():
    red = spans.reduce(_made_up())
    assert [b.number for b in red.blocks] == [1, 2]
    b1, b2 = red.blocks
    assert b1.stats["forced"] == 1 and b2.stats["lane"] == "interactive"
    # solve.* spans join the block of the sweep.dedup before them
    assert b1.phase_s("solve.copy_back") == pytest.approx(5e-3)
    assert b2.phase_s("solve.copy_back") == pytest.approx(3e-3)
    assert b1.phase_s("sweep.dedup") == pytest.approx(3e-3)
    assert b1.wall_s == pytest.approx(40e-3)
    assert b1.covered_s == pytest.approx(40e-3)
    assert spans.block_host_s(b1) == pytest.approx(26e-3)
    assert spans.block_host_s(b2) == pytest.approx(14e-3)
    assert spans.deliver_s(b2) == pytest.approx(3e-3)
    assert [b.number for b in red.forced] == [1]
    assert spans.after_forced(red) == [True]
    assert spans.waited_through_forced(red) == [True]
    assert red.fixpoint_runs == 2 and red.fixpoint_in_span == 2
    assert red.kernel_calls == 4
    assert red.scope_s["cross_pass_raw"] == pytest.approx(6e-3)
    assert red.scope_s["chain_pass"] == pytest.approx(19.5e-3)
    assert red.scope_s["cross_pass_war"] == 0


def test_idle_is_attributed_to_the_span_that_covers_it():
    red = spans.reduce(_made_up())
    # device busy 27..39 and 64..79.5: idle 100 - 12 - 15.5 ms
    assert red.idle_s == pytest.approx(72.5e-3)
    assert red.idle_in["solve.copy_back"] == pytest.approx(8e-3)
    assert red.idle_in["solve.fixpoint"] == pytest.approx(3.5e-3)
    assert red.idle_in["sweep.assemble"] == pytest.approx(4e-3)
    inside = sum(red.idle_in.values())
    # outside every span: 0..10, 11..20 and 91..100 ms
    assert inside == pytest.approx(72.5e-3 - 28e-3)
    # from the first scheduler span (10 ms) to the last (91 ms)
    assert red.idle_spanned_s == pytest.approx(72.5e-3 - 19e-3)
    assert "sweep.submit" not in red.idle_in        # client thread
    assert "idle inside scheduler-thread spans" in spans.table(_made_up())


def test_readers_on_a_made_up_trace(monkeypatch):
    tr = _made_up()
    monkeypatch.setattr(spans, "reduced", lambda path: spans.reduce(tr))
    ctx = {"summary": SimpleNamespace(window_s=0.1), "xplane": "made-up"}
    got = {n: cells.metric_reader(n)(ctx) for n in READERS}
    assert got["interactive_queue_ms_p50"] == pytest.approx(30.0)
    assert got["block_host_ms_p50"] == pytest.approx(20.0)
    assert got["copy_back_ms_p50"] == pytest.approx(4.0)
    assert got["deliver_ms_p50"] == pytest.approx(6.0)
    assert got["raw_scatter_ms_per_round"] == pytest.approx(1.5)
    assert got["war_pass_ms_per_round"] is None      # no WAR op
    # another run's trace (another marked part) gives nothing
    ctx["summary"] = SimpleNamespace(window_s=0.2)
    assert all(cells.metric_reader(n)(ctx) is None for n in READERS)


def test_every_reader_gives_none_without_spans(monkeypatch, tmp_path):
    """A program that opens no spans, as the parent's, and a run whose
    trace is missing: every new reader returns None and raises nothing."""
    k = 'x custom_call_target="tpu_custom_call"'
    devices = {"/device:TPU:0": {
        tracing.OPS_LINE: [_ev(k, 1, 2), _ev("%fusion.40 = a", 2, 3)],
        tracing.MODULES_LINE: [_ev("jit__fixpoint(1)", 1, 3)]}}
    bare = spans.Trace(0.0, 10 * MS, devices, [])
    monkeypatch.setattr(spans, "reduced", lambda path: spans.reduce(bare))
    ctx = {"summary": SimpleNamespace(window_s=1e-2), "xplane": "bare"}
    for n in READERS:
        assert cells.metric_reader(n)(ctx) is None, n
    monkeypatch.setattr(spans, "TRACE_DIR", str(tmp_path))
    for ctx in ({"summary": SimpleNamespace(window_s=1e-2)},
                {"summary": None}):
        for n in READERS:
            assert cells.metric_reader(n)(ctx) is None, n


def _drain(svc):
    while svc.step():
        pass


@pytest.fixture(scope="module")
def cpu_trace(tmp_path_factory):
    """A sweep served on the CPU under the profiler, inside a marked
    part: the loader's per-thread lines and the block records."""
    import jax

    from repro.core import simulate
    from repro.designs.typea import skynet_like
    from repro.sweep import SweepService

    log = tmp_path_factory.mktemp("trace")
    base = simulate(skynet_like(items=32, depth=5))
    D = np.random.default_rng(3).integers(1, 9, (56, len(base.depths)))
    svc = SweepService(block=16, backend="jax", autostart=False)
    svc.sweep(base, D[40:])        # warm: batch view, the K=16 program
    D = D[:40]
    with jax.profiler.trace(str(log)):
        with jax.profiler.TraceAnnotation(tracing.MARK):
            h = [svc.submit(base, D), svc.submit(base, D[:8])]
            th = threading.Thread(target=_drain, args=(svc,))
            th.start()
            th.join(timeout=300)
            assert not th.is_alive()
    svc.close()
    assert all(x.result(timeout=10).cycles.min() >= 0 for x in h)
    return tracing.find_xplane(str(log))


def test_loader_on_a_cpu_trace(cpu_trace):
    tr = spans.load(cpu_trace)
    assert spans.load(cpu_trace) is tr               # memoised by path
    red = spans.reduce(tr)
    assert not tr.devices and red.idle_s == 0.0
    assert [b.number for b in red.blocks] == [2, 3, 4]   # 1 warmed up
    # CPU blocks last milliseconds: what no span covers (the calls
    # between phases) stays well under one
    assert all(b.wall_s - b.covered_s < 5e-3 for b in red.blocks)
    names = {e.name for ln in tr.lines for e in ln}
    assert {"sweep.submit", "sweep.dequeue", "sweep.request_done",
            "solve.fixpoint", "sweep.deliver"} <= names
    assert len(spans.interactive_waits_ms(red)) == 1
    ctx = {"summary": SimpleNamespace(window_s=red.window_s),
           "xplane": cpu_trace}
    assert cells.metric_reader("block_host_ms_p50")(ctx) > 0
    assert cells.metric_reader("raw_scatter_ms_per_round")(ctx) is None


@pytest.fixture(scope="module")
def chip():
    if not os.path.exists(CHIP):
        pytest.skip("no chip excerpt recorded")
    with open(CHIP) as f:
        return spans.from_json(json.load(f))


def test_names_matched_on_a_chip_trace(chip):
    red = spans.reduce(chip)
    assert [b.number for b in red.blocks] == [17, 18, 19]
    assert [b.number for b in red.forced] == [19]
    assert red.blocks[2].stats["lane"] == "bulk"
    names = {e.name for ln in chip.lines for e in ln}
    assert set(spans.PHASES) | {"sweep.dequeue", "sweep.request_done",
                                "sweep.submit"} <= names
    assert red.fixpoint_runs == 3
    assert red.fixpoint_in_span == red.fixpoint_runs   # one clock
    assert red.kernel_calls == 3 * 8                   # 8 rounds a block
    # the fixpoint's device time is the WAR half's take_along_axis gather
    war, raw = red.scope_s["cross_pass_war"], red.scope_s["cross_pass_raw"]
    assert war > 50 * raw > 0 and red.scope_s["chain_pass"] > 0
    assert all(b.covered_s / b.wall_s > 0.95 for b in red.blocks)
    assert sum(red.idle_in.values()) > 0.9 * red.idle_s
    # the harness's own reduction still finds the kernel and the fixpoint
    s = tracing.reduce(chip.devices, [tracing.Event(tracing.MARK, chip.lo,
                                                    chip.hi, {})])
    assert s.fixpoint_runs == 3 and s.kernel_calls == 24
    assert all(k.startswith("%segcummax") and "s32[1024,8192]" in k
               for k in s.kernel_shapes)


def test_readers_on_a_chip_trace(chip, monkeypatch):
    monkeypatch.setattr(spans, "reduced", lambda path: spans.reduce(chip))
    ctx = {"summary": SimpleNamespace(window_s=(chip.hi - chip.lo) * 1e-9),
           "xplane": "chip"}
    got = {n: cells.metric_reader(n)(ctx) for n in READERS}
    assert 200 < got["interactive_queue_ms_p50"] < 400
    assert 150 < got["block_host_ms_p50"] < 300
    assert 5 < got["copy_back_ms_p50"] < 30
    assert 40 < got["deliver_ms_p50"] < 200
    assert 0 < got["raw_scatter_ms_per_round"] < 2
    assert 30 < got["war_pass_ms_per_round"] < 60
