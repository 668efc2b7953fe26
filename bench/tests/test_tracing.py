"""The trace reduction on a piece of a chip trace, and on a made-up one.

``data/trace_skynet_v5e.json`` is an excerpt (``tracing.excerpt``) of a
traced run of the ``skynet_like`` design served in blocks of 128 on a TPU
v5 lite: two complete fixpoint executions on its 104,448-column axis.
It pins the names the reduction matches: the fixpoint program's module
name and the kernel's call target.
"""
import json
import os

import pytest

import cells
import tracing

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def chip():
    with open(os.path.join(DATA, "trace_skynet_v5e.json")) as f:
        return tracing.reduce(*tracing.from_json(json.load(f)))


def test_names_matched_on_a_chip_trace(chip):
    assert chip.fixpoint_runs == 2
    assert chip.kernel_calls == 6               # 3 rounds per block
    assert all("s32[128,104448]" in s for s in chip.kernel_shapes)
    assert 0 < chip.kernel_s < chip.fixpoint_busy_s <= chip.busy_s
    assert chip.busy_s <= chip.window_s
    assert not any(n.startswith("%while") for n, _ in chip.ops)


def test_readers_on_a_chip_trace(chip):
    ctx = {"summary": chip, "peaks": cells.peaks("TPU v5 lite")}
    roof = cells.metric_reader("segcummax_roofline")(ctx)
    assert 1.0 < roof < 100.0
    assert cells.metric_reader("fixpoint_rounds_per_block")(ctx) == 3.0
    cross = cells.metric_reader("cross_pass_share")(ctx)
    assert 90.0 < cross < 100.0
    idle = cells.metric_reader("device_idle_share")(ctx)
    assert 0.0 < idle < 100.0


def _ev(name, s, e):
    return tracing.Event(name, float(s), float(e), {})


def test_busy_idle_and_gaps_on_a_made_up_trace():
    k = 'x custom_call_target="tpu_custom_call"'
    ops = [_ev("%while = loop", 10, 60), _ev("%fusion.1 = a", 10, 30),
           _ev(k, 30, 35), _ev("%fusion.1 = a", 35, 60),
           _ev("%fusion.2 = b", 80, 90)]
    mods = [_ev("jit__fixpoint(1)", 10, 60), _ev("jit_other(2)", 80, 90)]
    devices = {"/device:TPU:0": {tracing.OPS_LINE: ops,
                                 tracing.MODULES_LINE: mods}}
    host = [_ev(tracing.MARK, 0, 100), _ev("np.asarray(jax.Array)", 60, 80),
            _ev("bench.submit.closed", 0, 8)]
    s = tracing.reduce(devices, host)
    assert s.window_s == pytest.approx(100e-9)
    assert s.busy_s == pytest.approx(60e-9)
    assert s.fixpoint_runs == 1 and s.kernel_calls == 1
    assert s.kernel_s == pytest.approx(5e-9)
    assert s.fixpoint_busy_s == pytest.approx(50e-9)
    assert dict(s.ops)["%fusion.1 = a"] == pytest.approx(45e-9)
    assert s.gaps[0] == ("np.asarray(jax.Array)", pytest.approx(20e-9))
    assert ("bench.submit.closed", pytest.approx(10e-9)) in s.gaps
    ctx = {"summary": s._replace(kernel_shapes=[]), "peaks": {}}
    assert cells.metric_reader("segcummax_roofline")(ctx) is None


def test_a_trace_without_the_program_gives_no_kernel_numbers():
    devices = {"/device:TPU:0": {tracing.OPS_LINE: [_ev("%f = a", 1, 2)]}}
    s = tracing.reduce(devices, [_ev(tracing.MARK, 0, 10)])
    ctx = {"summary": s, "peaks": cells.peaks("TPU v5 lite")}
    for name in ("segcummax_roofline", "cross_pass_share",
                 "fixpoint_rounds_per_block"):
        assert cells.metric_reader(name)(ctx) is None
