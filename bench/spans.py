"""Program spans of a traced run, and their reduction to blocks.

The sweep service opens host spans in the profiler's trace
(``repro.device.span``), on the same clock as the device planes:

* on the scheduler thread, flat phase spans that carry the block number
  (``sweep.assemble``, ``sweep.dedup``, ``sweep.materialize``,
  ``sweep.deliver``) and the solve phase's ``solve.upload``,
  ``solve.fixpoint``, ``solve.copy_back``, ``solve.transpose`` and
  ``solve.recheck``, which carry none: they belong to the block of the
  ``sweep.dedup`` before them;
* zero-length markers: ``sweep.dequeue`` (a request's first rows placed
  in a block, with its queue wait) and ``sweep.request_done``;
* on the client's threads, ``sweep.submit`` and ``sweep.cache_build``.

On the device, the fixpoint's ops carry the scopes ``chain_pass``,
``cross_pass_raw`` and ``cross_pass_war`` in their ``op_name`` metadata.
The trace's op events hold neither (their names are HLO text without
metadata); the profiler keeps each program's HLO proto in the
``/host:metadata`` plane, which ``ProfileData`` does not expose, so
:func:`op_names` reads it from the file's protobuf wire format and maps
each instruction of the fixpoint program to its ``op_name``.

``tracing.load`` flattens the host threads, so this module has its own
loader, which keeps one list of program spans per host thread.  It
memoises by path, so every reader of a run shares one load.  The
reduction gives per-block records on the scheduler thread, the device's
idle time inside each program span, and the device time of each scope
inside complete fixpoint executions.  A trace without program spans (a
program that opens none) reduces to no blocks, and the readers built on
it give no number.

    python3 bench/spans.py [--trace-dir DIR] [--excerpt OUT.json]

prints the table of a traced run (span counts and times, idle inside
spans, the blocks' split) and can write an excerpt of it, as
``bench/tests/data/trace_multicore_spans_v5e.json`` was written.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import statistics
import sys
from collections import defaultdict
from typing import Dict, List, NamedTuple, Optional

import numpy as np

import tracing
from tracing import Event

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE_DIR = os.path.join(os.path.dirname(HERE), ".bench_trace")
PREFIXES = ("sweep.", "solve.")
# the scheduler thread's phases: flat, never nested
PHASES = ("sweep.assemble", "sweep.dedup", "solve.upload", "solve.fixpoint",
          "solve.copy_back", "solve.transpose", "solve.recheck",
          "sweep.materialize", "sweep.deliver")
SCOPES = ("chain_pass", "cross_pass_raw", "cross_pass_war")
CLOCK_SLACK_NS = 1e6     # a fixpoint execution ends inside its span ± 1 ms


class Trace(NamedTuple):
    lo: float                                   # the marked part, ns
    hi: float
    devices: Dict[str, Dict[str, List[Event]]]  # as tracing.load gives
    lines: List[List[Event]]                    # program spans per thread
    op_names: Dict[str, Dict[str, str]] = {}    # program -> op -> op_name


class Block(NamedTuple):
    number: int
    stats: dict             # the sweep.assemble attributes (empty: unseen)
    start: Optional[float]  # sweep.assemble start, ns
    end: Optional[float]    # sweep.deliver end, ns
    spans: List[Event]      # every phase span of the block, in order

    def phase_s(self, name: str) -> float:
        return 1e-9 * sum(e.end - e.start for e in self.spans
                          if e.name == name)

    @property
    def wall_s(self) -> float:
        return 1e-9 * (self.end - self.start)

    @property
    def covered_s(self) -> float:
        return 1e-9 * tracing._length(tracing._union(
            [(e.start, e.end) for e in self.spans]))


class Reduction(NamedTuple):
    window_s: float
    idle_s: float                        # device idle in the window
    idle_spanned_s: float                # ... from the first scheduler
    #                                      span's start to the last's end
    durations: Dict[str, List[float]]    # span name -> seconds, in window
    idle_in: Dict[str, float]            # scheduler-thread span -> idle s
    blocks: List[Block]                  # complete blocks in the window
    dequeues: List[dict]                 # sweep.dequeue stats (+ "t")
    done: List[dict]                     # sweep.request_done stats
    forced: List[Block]                  # forced blocks, complete or not
    fixpoint_runs: int                   # complete fixpoint executions
    fixpoint_in_span: int                # ... ending in a solve.fixpoint
    fixpoint_unmatched: List[tuple]      # the others: (ms after the
    #                                      window opens, ms before it
    #                                      closes, kernel calls)
    kernel_calls: int                    # kernel events inside them
    scope_s: Dict[str, float]            # scope -> device s inside them


def _program_events(line) -> List[Event]:
    out = []
    for e in line.events:
        if e.name.startswith(PREFIXES) or e.name == tracing.MARK:
            start = float(e.start_ns)
            out.append(Event(e.name, start, start + float(e.duration_ns),
                             dict(e.stats)))
    return out


@functools.lru_cache(maxsize=2)
def load(path: str) -> Trace:
    """The marked part, the device planes and each host thread's program
    spans of the ``.xplane.pb`` at ``path``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, Dict[str, List[Event]]] = {}
    lines: List[List[Event]] = []
    for plane in data.planes:
        if plane.name.startswith(tracing.DEVICE_PREFIX):
            devices[plane.name] = {
                ln.name: tracing._events(ln) for ln in plane.lines
                if ln.name in (tracing.OPS_LINE, tracing.MODULES_LINE)}
        elif plane.name.startswith("/host:"):
            lines.extend(evs for evs in map(_program_events, plane.lines)
                         if evs)
    marks = [e for ln in lines for e in ln if e.name == tracing.MARK]
    if not marks:
        raise ValueError(f"no {tracing.MARK!r} host event in the trace")
    lines = [sorted((e for e in ln if e.name != tracing.MARK),
                    key=lambda e: e.start) for ln in lines]
    with open(path, "rb") as f:
        names = op_names(f.read())
    return Trace(marks[0].start, marks[0].end, devices,
                 [ln for ln in lines if ln], names)


def _fields(buf):
    """(field number, value) of a protobuf message's wire encoding: an int
    for a varint, a memoryview for the rest."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            v, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {kind} at byte {i}")
        yield key >> 3, v


def _varint(buf, i: int):
    out = shift = 0
    while True:
        c = buf[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return out, i


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def op_names(xspace: bytes) -> Dict[str, Dict[str, str]]:
    """For each fixpoint program whose HLO proto the trace holds (the
    ``Hlo Proto`` stat of an event metadata of the ``/host:metadata``
    plane, named like the ``XLA Modules`` events): instruction name ->
    ``op_name`` metadata.  Field numbers: ``XSpace.planes`` 1;
    ``XPlane`` name 2, event_metadata 4, stat_metadata 5 (map entries: key
    1, value 2); ``XEventMetadata`` name 2, stats 5; ``XStatMetadata``
    name 2; ``XStat`` metadata_id 1, bytes_value 6; ``HloProto`` module
    1; ``HloModuleProto`` computations 3; ``HloComputationProto``
    instructions 2; ``HloInstructionProto`` name 1, metadata 7;
    ``OpMetadata`` op_name 2."""
    out: Dict[str, Dict[str, str]] = {}
    for f, plane in _fields(memoryview(xspace)):
        if f != 1:
            continue
        parts = defaultdict(list)
        for pf, pv in _fields(plane):
            parts[pf].append(pv)
        if not parts[2] or _text(parts[2][0]) != "/host:metadata":
            continue
        stat_name = {}
        for entry in parts[5]:
            meta = dict(_fields(dict(_fields(entry)).get(2, b"")))
            stat_name[meta.get(1)] = _text(meta.get(2, b""))
        for entry in parts[4]:
            meta = list(_fields(dict(_fields(entry)).get(2, b"")))
            name = next((_text(v) for k, v in meta if k == 2), "")
            if tracing.FIXPOINT not in name:
                continue
            for k, stat in meta:
                st = dict(_fields(stat)) if k == 5 else {}
                if stat_name.get(st.get(1)) == "Hlo Proto" and 6 in st:
                    out[name] = _hlo_op_names(st[6])
    return out


def _hlo_op_names(hlo_proto) -> Dict[str, str]:
    out = {}
    for f, module in _fields(hlo_proto):
        for mf, comp in (_fields(module) if f == 1 else ()):
            for cf, ins in (_fields(comp) if mf == 3 else ()):
                if cf != 2:
                    continue
                name = op_name = None
                for k, v in _fields(ins):
                    if k == 1:
                        name = _text(v)
                    elif k == 7:
                        op_name = next((_text(x) for j, x in _fields(v)
                                        if j == 2), None)
                if name and op_name:
                    out[name] = op_name
    return out


def for_run(ctx) -> Optional[Reduction]:
    """The reduction of a run's trace: ``ctx["xplane"]`` if the harness
    names it, else the newest trace under the harness's trace directory,
    taken only if its marked part is the one ``ctx["summary"]`` reduced.
    None where there is no trace or it holds no complete block."""
    s = ctx.get("summary")
    if s is None:
        return None
    path = ctx.get("xplane")
    if path is None:
        try:
            path = tracing.find_xplane(TRACE_DIR)
        except FileNotFoundError:
            return None
    red = reduced(path)
    if red.window_s != s.window_s or not red.blocks:
        return None
    return red


@functools.lru_cache(maxsize=2)
def reduced(path: str) -> "Reduction":
    return reduce(load(path))


def _scheduler_line(tr: Trace) -> List[Event]:
    """The host line holding the most numbered ``sweep.assemble`` spans."""
    def n(ln):
        return sum(1 for e in ln if e.name == "sweep.assemble"
                   and "block" in e.stats)
    best = max(tr.lines, key=n, default=[])
    return best if n(best) else []


def blocks_of(line: List[Event]) -> List[Block]:
    """Every block whose spans appear on the scheduler ``line``, complete
    or not; a ``solve.*`` span joins the block of the latest
    ``sweep.dedup``."""
    recs: Dict[int, dict] = {}
    cur = None
    for e in line:
        if e.name not in PHASES:
            continue
        if e.name.startswith("sweep."):
            if "block" not in e.stats:
                continue                   # an idle poll's assemble
            cur = recs.setdefault(int(e.stats["block"]),
                                  dict(stats={}, start=None, end=None,
                                       spans=[]))
            if e.name == "sweep.assemble":
                cur.update(stats=e.stats, start=e.start)
            elif e.name == "sweep.deliver":
                cur["end"] = e.end
        elif cur is None:
            continue                       # a solve before any block
        cur["spans"].append(e)
    return [Block(k, r["stats"], r["start"], r["end"], r["spans"])
            for k, r in sorted(recs.items())]


def _idle_gaps(ops: List[Event], lo: float, hi: float):
    """(starts, ends) of the device's idle gaps inside [lo, hi]."""
    iv = tracing._union(tracing._clip([(e.start, e.end) for e in ops],
                                      lo, hi))
    edges = [lo] + [x for s, e in iv for x in (s, e)] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    return (np.array([g[0] for g in gaps], float),
            np.array([g[1] for g in gaps], float))


def _idle_before(gs, ge, x: float) -> float:
    """Idle time of the gaps (gs, ge) before time ``x``."""
    i = int(np.searchsorted(ge, x, side="right"))
    full = float((ge[:i] - gs[:i]).sum())
    return full + (max(x - gs[i], 0.0) if i < len(gs) else 0.0)


def _instruction(e: Event) -> str:
    """``fusion.40`` of an op event named ``%fusion.40 = s32[...] ...``."""
    return e.name.split(" ", 1)[0].lstrip("%")


def reduce(tr: Trace) -> Reduction:
    lo, hi = tr.lo, tr.hi
    sched = _scheduler_line(tr)
    blocks = blocks_of(sched)
    durations: Dict[str, List[float]] = defaultdict(list)
    dequeues, done = [], []
    # every solve.fixpoint span: one that opened before the marked part
    # may still hold a fixpoint execution inside it
    fx_spans = [(e.start, e.end) for ln in tr.lines for e in ln
                if e.name == "solve.fixpoint"]
    for ln in tr.lines:
        for e in ln:
            if not (lo <= e.start and e.end <= hi):
                continue
            durations[e.name].append(1e-9 * (e.end - e.start))
            if e.name == "sweep.dequeue":
                dequeues.append(dict(e.stats, t=e.start))
            elif e.name == "sweep.request_done":
                done.append(dict(e.stats))
    idle_s = spanned = 0.0
    runs = in_span = k_calls = 0
    unmatched = []
    idle_in: Dict[str, float] = defaultdict(float)
    scope_s: Dict[str, float] = defaultdict(float)
    for lines in tr.devices.values():
        ops = lines.get(tracing.OPS_LINE, [])
        gs, ge = _idle_gaps(ops, lo, hi)
        idle_s += float((ge - gs).sum())
        if sched:
            last = max(e.end for e in sched)
            spanned += (_idle_before(gs, ge, min(last, hi))
                        - _idle_before(gs, ge, max(sched[0].start, lo)))
        for e in sched:
            if e.end > e.start:
                s, t = max(e.start, lo), min(e.end, hi)
                if t > s:
                    idle_in[e.name] += (_idle_before(gs, ge, t)
                                        - _idle_before(gs, ge, s))
        for m in lines.get(tracing.MODULES_LINE, []):
            if tracing.FIXPOINT not in m.name or m.start < lo or m.end > hi:
                continue
            runs += 1
            inside = [e for e in ops if e.start >= m.start
                      and e.end <= m.end
                      and not e.name.startswith(tracing.CONTAINERS)]
            k = sum(1 for e in inside if tracing.KERNEL in e.name)
            k_calls += k
            if any(s - CLOCK_SLACK_NS <= m.end <= t + CLOCK_SLACK_NS
                   for s, t in fx_spans):
                in_span += 1
            else:
                unmatched.append(((m.start - lo) * 1e-6, (hi - m.end) * 1e-6,
                                  k))
            names = tr.op_names.get(m.name, {})
            for sc in SCOPES:
                scope_s[sc] += 1e-9 * tracing._length(tracing._union(
                    [(e.start, e.end) for e in inside
                     if f"/{sc}/" in names.get(_instruction(e), "")]))
    n_dev = max(len(tr.devices), 1)
    return Reduction(
        window_s=(hi - lo) * 1e-9, idle_s=idle_s * 1e-9 / n_dev,
        idle_spanned_s=spanned * 1e-9 / n_dev,
        durations=dict(durations),
        idle_in={k: v * 1e-9 / n_dev for k, v in idle_in.items()},
        blocks=[b for b in blocks if b.start is not None
                and b.end is not None and lo <= b.start and b.end <= hi],
        dequeues=dequeues, done=done,
        forced=[b for b in blocks if b.stats.get("forced")],
        fixpoint_runs=runs, fixpoint_in_span=in_span,
        fixpoint_unmatched=unmatched, kernel_calls=k_calls,
        scope_s=dict(scope_s))


# --------------------------------------------------------------- readings
def median_ms(xs) -> Optional[float]:
    xs = list(xs)
    return 1e3 * statistics.median(xs) if xs else None


def block_host_s(b: Block) -> float:
    """A block's wall time less the time its thread waited on the
    device (``solve.fixpoint``)."""
    return b.wall_s - b.phase_s("solve.fixpoint")


def deliver_s(b: Block) -> float:
    return b.phase_s("sweep.materialize") + b.phase_s("sweep.deliver")


def interactive_waits_ms(red: Reduction) -> List[float]:
    return [d["wait_us"] / 1e3 for d in red.dequeues
            if d.get("lane") == "interactive"]


def after_forced(red: Reduction) -> List[bool]:
    """Per interactive dequeue in the window: was the block before it a
    bulk block forced past waiting interactive rows?"""
    forced = {b.number for b in red.forced}
    return [d["block"] - 1 in forced for d in red.dequeues
            if d.get("lane") == "interactive"]


def waited_through_forced(red: Reduction) -> List[bool]:
    """Per interactive dequeue in the window: did a forced block run while
    the request waited (between its submit and its dequeue)?"""
    return [any((b.start is None or b.start < d["t"])
                and (b.end is None or b.end > d["t"] - d["wait_us"] * 1e3)
                for b in red.forced)
            for d in red.dequeues if d.get("lane") == "interactive"]


def interactive_split(red: Reduction) -> str:
    """Server-side latency of the interactive requests dequeued in the
    window, split by whether a forced block ran while they waited."""
    lat = {d["rid"]: d["latency_us"] / 1e3 for d in red.done}
    deq = [d for d in red.dequeues if d.get("lane") == "interactive"]
    groups = {True: [], False: []}
    for d, forced in zip(deq, waited_through_forced(red)):
        if d["rid"] in lat:
            groups[forced].append((d["wait_us"] / 1e3, lat[d["rid"]]))
    parts = []
    for forced in (False, True):
        g = sorted(groups[forced], key=lambda x: x[1])
        if g:
            wait = statistics.median(w for w, _ in g)
            parts.append(
                f"{'through' if forced else 'without'} a forced block: "
                f"{len(g)}, wait median {wait:.3f} ms, latency median "
                f"{statistics.median(x for _, x in g):.3f} ms, max "
                f"{g[-1][1]:.3f} ms")
    return "interactive latency, " + "; ".join(parts)


def table(tr: Trace) -> str:
    """The run's span table, as text."""
    red = reduce(tr)
    out = [f"window {red.window_s:.6f} s, device idle {red.idle_s:.6f} s "
           f"({100 * red.idle_s / red.window_s:.3f} %)",
           f"{'span':<22}{'count':>7}{'total ms':>12}{'median ms':>12}"
           f"{'idle ms':>11}{'idle %':>9}"]
    for name in sorted(red.durations):
        d = red.durations[name]
        idle = red.idle_in.get(name, 0.0)
        out.append(f"{name:<22}{len(d):>7}{1e3 * sum(d):>12.3f}"
                   f"{median_ms(d):>12.3f}{1e3 * idle:>11.3f}"
                   f"{100 * idle / max(red.idle_s, 1e-12):>9.3f}")
    inside = sum(red.idle_in.values())
    out.append(f"device idle inside scheduler-thread spans: "
               f"{100 * inside / max(red.idle_s, 1e-12):.3f} % of the "
               f"window's, {100 * inside / max(red.idle_spanned_s, 1e-12):.3f}"
               f" % of that from the first span's start to the last's end "
               f"({1e3 * red.idle_spanned_s:.3f} ms; the profiler drops a "
               f"span open when it starts or stops)")
    bl = red.blocks
    if bl:
        wall = sum(b.wall_s for b in bl)
        cov = sum(b.covered_s for b in bl)
        out.append(f"{len(bl)} complete blocks: wall median "
                   f"{median_ms(b.wall_s for b in bl):.3f} ms, no span "
                   f"covers {1e3 * (wall - cov):.3f} ms of "
                   f"{1e3 * wall:.3f} ms ({100 * (1 - cov / wall):.3f} %), "
                   f"least coverage of a block "
                   f"{100 * min(b.covered_s / b.wall_s for b in bl):.3f} %")
        for name in PHASES:
            out.append(f"  {name:<20} median "
                       f"{median_ms(b.phase_s(name) for b in bl):10.3f} ms")
        out.append(f"  block host (wall - fixpoint) median "
                   f"{median_ms(map(block_host_s, bl)):.3f} ms; "
                   f"materialize + deliver median "
                   f"{median_ms(map(deliver_s, bl)):.3f} ms")
        for b in bl:
            out.append(f"  block {b.number}: {dict(b.stats)} wall "
                       f"{1e3 * b.wall_s:.3f} ms")
    out.append(f"fixpoint executions {red.fixpoint_runs}, ending inside "
               f"their solve.fixpoint span (1 ms) {red.fixpoint_in_span}"
               f" (the others, ms from the window's open and to its "
               f"close, kernel calls: {red.fixpoint_unmatched}); "
               f"kernel calls {red.kernel_calls}; scope device s "
               f"{ {k: round(v, 6) for k, v in red.scope_s.items()} }")
    waits = interactive_waits_ms(red)
    if waits:
        out.append(interactive_split(red))
        lat = sorted(d["latency_us"] / 1e3 for d in red.done
                     if d.get("lane") == "interactive")
        af = after_forced(red)
        wf = waited_through_forced(red)
        out.append(f"interactive: {len(waits)} dequeued, queue wait median "
                   f"{statistics.median(waits):.3f} ms max "
                   f"{max(waits):.3f} ms; done {len(lat)}, latency median "
                   f"{statistics.median(lat) if lat else float('nan'):.3f} "
                   f"ms max {max(lat) if lat else float('nan'):.3f} ms; "
                   f"dequeued right after a forced block {sum(af)} of "
                   f"{len(af)}; waited through a forced block {sum(wf)} of "
                   f"{len(wf)}; forced blocks "
                   f"{[b.number for b in red.forced]}")
    return "\n".join(out)


# --------------------------------------------------------------- excerpts
def excerpt(tr: Trace, blocks: int = 3) -> dict:
    """A piece of a trace as plain JSON: ``blocks`` consecutive complete
    blocks of the scheduler thread (the first run holding a forced block,
    if any does), every device op and module between their start and end,
    the ``op_name`` of each of those ops, and every thread's program spans
    there, the mark cut down to it.  :func:`from_json` reads it back."""
    bl = reduce(tr).blocks
    runs = [bl[i:i + blocks] for i in range(max(len(bl) - blocks + 1, 1))]
    pick = next((r for r in runs if any(b.stats.get("forced") for b in r)),
                runs[0])
    lo, hi = pick[0].start - 1e5, pick[-1].end + 1e5

    def keep(evs, stats):
        return [[e.name, e.start, e.end - e.start, stats(e)]
                for e in evs if e.end > lo and e.start < hi]
    plane, lines = next(iter(tr.devices.items()))
    devices = {ln: keep(evs, lambda e: {}) for ln, evs in lines.items()}
    ops = {_instruction(tracing.Event(n, 0, 0, {}))
           for n, *_ in devices.get(tracing.OPS_LINE, [])}
    return {"mark": [lo, hi], "devices": {plane: devices},
            "lines": [r for r in (keep(ln, lambda e: dict(e.stats))
                                  for ln in tr.lines) if r],
            "op_names": {m: {k: v for k, v in names.items() if k in ops}
                         for m, names in tr.op_names.items()}}


def from_json(d: dict) -> Trace:
    def ev(rows):
        return [Event(n, s, s + dur, st) for n, s, dur, st in rows]
    lo, hi = d["mark"]
    return Trace(lo, hi, {p: {ln: ev(r) for ln, r in lines.items()}
                          for p, lines in d["devices"].items()},
                 [ev(r) for r in d["lines"]], d["op_names"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--trace-dir", default=TRACE_DIR)
    ap.add_argument("--excerpt", help="write an excerpt of the trace here")
    ap.add_argument("--blocks", type=int, default=3)
    args = ap.parse_args(argv)
    tr = load(tracing.find_xplane(args.trace_dir))
    print(table(tr))
    if args.excerpt:
        with open(args.excerpt, "w") as f:
            json.dump(excerpt(tr, args.blocks), f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
