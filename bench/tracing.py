"""Device trace of a traced run, and its reduction to intervals.

A traced run (``--trace 1``) records a JAX profiler trace of a steady part
of its window, marked on the host by a ``bench.trace`` annotation, and
reduces it here:

* busy: the union of the intervals in which an operation ran on a device
  (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane), inside the
  marked part; idle is the rest of it;
* per operation name: summed device time (the ``breakdown``);
* idle gaps, each named by the host event that overlaps it most (the
  benchmark's own ``bench.*`` annotations and the runtime's host events);
* the executions of the fixpoint program (``XLA Modules`` events whose
  name holds ``FIXPOINT``) that lie wholly inside the marked part, with
  the kernel events (op names holding ``KERNEL``) and the other device
  time inside each.

Op events are named by their HLO text (``%fusion.40 = s32[...] fusion(...)``);
a loop op spans the ops of its body, so it counts towards busy time but not
in the per-op breakdown.

The op and module names matched are pinned by ``tests/test_tracing.py``
on a small recorded trace.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, List, NamedTuple, Tuple

FIXPOINT = "jit__fixpoint"       # jitted fixpoint program (module name)
# the Pallas segmented-cummax kernel is the fixpoint's only Mosaic call; its
# op carries no name of its own yet, so it is matched by its call target
KERNEL = 'custom_call_target="tpu_custom_call"'
CONTAINERS = ("%while", "%conditional", "%call")   # ops holding other ops
NAME_CHARS = 160                 # op names are HLO text; the breakdown cuts them
MARK = "bench.trace"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"

Interval = Tuple[float, float]   # (start_ns, end_ns)


class Event(NamedTuple):
    name: str
    start: float
    end: float
    stats: dict


class Summary(NamedTuple):
    window_s: float                       # length of the marked part
    busy_s: float                         # device busy, mean over devices
    ops: List[Tuple[str, float]]          # (op name, device s), largest first
    gaps: List[Tuple[str, float]]         # (host activity, idle s), longest
    fixpoint_runs: int                    # complete fixpoint executions
    fixpoint_busy_s: float                # device busy inside them
    kernel_calls: int                     # kernel events inside them
    kernel_s: float                       # kernel device time inside them
    kernel_shapes: List[str]              # HLO text of one kernel call per run


def options():
    """Profiler options: device and host events, no Python tracer (which
    would time every Python call of the service)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _events(line) -> List[Event]:
    out = []
    for e in line.events:
        try:
            stats = dict(e.stats)
        except Exception:                # noqa: BLE001 — stats are optional
            stats = {}
        start = float(e.start_ns)
        out.append(Event(e.name, start, start + float(e.duration_ns), stats))
    return out


def _union(iv: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(iv: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def _length(iv: List[Interval]) -> float:
    return sum(e - s for s, e in iv)


def load(path: str):
    """(device planes: {name: {line name: [Event]}}, host: [Event])."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, Dict[str, List[Event]]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            devices[plane.name] = {ln.name: _events(ln) for ln in plane.lines}
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host.extend(_events(ln))
    return devices, host


def _op_name(ev: Event) -> str:
    return ev.name[:NAME_CHARS]


def reduce(devices, host: List[Event], top: int = 10) -> Summary:
    """Reduce a loaded trace to a :class:`Summary` of its marked part."""
    marks = [e for e in host if e.name == MARK]
    if not marks:
        raise ValueError(f"no {MARK!r} host event in the trace")
    lo, hi = marks[0].start, marks[0].end
    if not devices:
        raise ValueError("no TPU device plane in the trace")
    busy = 0.0
    per_op: Dict[str, float] = defaultdict(float)
    gaps: List[Tuple[float, float]] = []
    runs = 0
    fx_busy = k_time = 0.0
    k_calls = 0
    shapes: List[str] = []
    for lines in devices.values():
        ops = lines.get(OPS_LINE, [])
        iv = _union(_clip([(e.start, e.end) for e in ops], lo, hi))
        busy += _length(iv)
        for e in ops:
            d = min(e.end, hi) - max(e.start, lo)
            if d > 0 and not e.name.startswith(CONTAINERS):
                per_op[_op_name(e)] += d
        edges = [lo] + [x for s, e in iv for x in (s, e)] + [hi]
        gaps.extend((edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
        for m in lines.get(MODULES_LINE, []):
            if FIXPOINT not in m.name or m.start < lo or m.end > hi:
                continue
            runs += 1
            inside = [e for e in ops if e.start >= m.start and e.end <= m.end]
            fx_busy += _length(_union([(e.start, e.end) for e in inside]))
            kern = [e for e in inside if KERNEL in e.name]
            k_calls += len(kern)
            k_time += _length(_union([(e.start, e.end) for e in kern]))
            shapes.extend(e.name for e in kern[:1])
    n_dev = len(devices)
    return Summary(
        window_s=(hi - lo) * 1e-9,
        busy_s=busy * 1e-9 / n_dev,
        ops=[(k, v * 1e-9) for k, v in
             sorted(per_op.items(), key=lambda kv: -kv[1])[:top]],
        gaps=_label_gaps(gaps, host, top),
        fixpoint_runs=runs, fixpoint_busy_s=fx_busy * 1e-9,
        kernel_calls=k_calls, kernel_s=k_time * 1e-9, kernel_shapes=shapes)


def _label_gaps(gaps: List[Interval], host: List[Event], top: int):
    """The ``top`` longest idle gaps, each named by the host event (other
    than the mark) that overlaps it most, or ``"no host event"``."""
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    named = []
    cand = [e for e in host if e.name != MARK and e.end > e.start]
    for s, e in longest:
        best, over = "no host event", 0.0
        for h in cand:
            o = min(e, h.end) - max(s, h.start)
            if o > over:
                best, over = h.name, o
        named.append((best, (e - s) * 1e-9))
    return named


def excerpt(devices, host: List[Event], runs: int = 2) -> dict:
    """A small piece of a loaded trace, as plain JSON: the first ``runs``
    complete fixpoint executions inside the marked part with every device
    event between their start and end, the host events overlapping that
    span, and the mark cut down to it.  ``from_json`` reads it back; the
    trace reduction's test runs on such a piece of a chip trace."""
    mark = [e for e in host if e.name == MARK][0]
    plane, lines = next(iter(devices.items()))
    mods = [m for m in lines.get(MODULES_LINE, []) if FIXPOINT in m.name
            and m.start >= mark.start and m.end <= mark.end][:runs]
    lo, hi = mods[0].start - 1e5, mods[-1].end + 1e5

    def keep(evs):
        return [[e.name, e.start, e.end - e.start, {}]
                for e in evs if e.end > lo and e.start < hi]
    return {"devices": {plane: {ln: keep(evs) for ln, evs in lines.items()
                                if ln in (OPS_LINE, MODULES_LINE)}},
            "host": [[MARK, lo, hi - lo, {}]]
            + keep([e for e in host if e.name != MARK])[:200]}


def from_json(d: dict):
    """(devices, host) of an :func:`excerpt`."""
    def ev(rows):
        return [Event(n, s, s + dur, st) for n, s, dur, st in rows]
    return ({p: {ln: ev(r) for ln, r in lines.items()}
             for p, lines in d["devices"].items()}, ev(d["host"]))
