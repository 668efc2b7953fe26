#!/usr/bin/env python3
"""Benchmark of the served sweep: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration (a design, in
``configs/``) and a traffic mix (``traffic/``).  The run:

1. refuses anything but a TPU with the cell's chip count, and a device
   kind that ``peaks.json`` does not hold, before it builds anything;
2. set-up: builds the design, simulates it once, runs the reference's
   base run, draws each stream's rows and arrivals from ``--seed``
   (``rows.py``), and warms the sweep service (``SweepService(backend=
   "jax")``) and every fixpoint shape a block can take;
3. drives the service from the client side for ``--seconds`` (see
   ``client.py``); with ``--trace 1`` a profiler trace covers a steady part
   of the window and the per-layer readers of ``metrics/`` reduce it;
4. reads the device's peak memory, closes the service, and checks a
   sample of the answers against the reference (``check.py``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer ones), ``device`` and, traced,
``breakdown``; its last key, ``checks``, holds each number compared with
its limit, which also close standard error.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))
# the TPU runtime's logs stay inside the checkout
if "TPU_LOG_DIR" not in os.environ:
    os.environ["TPU_LOG_DIR"] = os.path.join(ROOT, ".tpu_logs")
    os.makedirs(os.environ["TPU_LOG_DIR"], exist_ok=True)

import numpy as np  # noqa: E402

import cells  # noqa: E402
import check  # noqa: E402
import client  # noqa: E402
import rows  # noqa: E402
import tracing  # noqa: E402

CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
FAILED = (5, 6, 7)            # FAULTED, TIMED_OUT, REJECTED


class NoChip(RuntimeError):
    pass


def say(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def require_chip(chips: int):
    """JAX's devices if they are TPUs, at least ``chips`` of them, of a
    kind in the peaks table; else :class:`NoChip`."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"needs {chips} chips; JAX found {len(devs)}")
    try:
        cells.peaks(devs[0].device_kind)
    except KeyError as exc:
        raise NoChip(str(exc)) from None
    return devs[:chips]


def use_compile_cache() -> None:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, whatever the environment names, so that two checkouts never
    share compiled programs; every program is cached, however quick."""
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def percentile(values, q: float) -> float:
    """Nearest-rank percentile."""
    v = sorted(values)
    return v[max(math.ceil(q / 100.0 * len(v)) - 1, 0)]


def run_cell(cell: cells.Cell, seed: int, seconds: float, trace: bool,
             devices, t0: float = T0, trace_dir: str = TRACE_DIR,
             keep: Optional[dict] = None) -> dict:
    """One run of ``cell``; ``keep``, if given, receives the oracle, the
    answered rows and the sampled ones (for the control's readings)."""
    from repro.core import simulate
    from repro.sweep import SweepService

    traffic = cell.traffic
    svc_kw = dict(traffic["service"])
    build = cells.build_design(cell.config)
    base = simulate(build(), trace="auto")
    oracle = check.Oracle(build, base.depths)
    streams = _streams(traffic["streams"], len(base.depths), seed, seconds)
    svc = SweepService(**svc_kw)
    svc.warm(base)
    client.warm(svc, base, int(svc_kw["block"]))
    tracer = None
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        tracer = _tracer(trace_dir, traffic["trace"])
    setup_s = time.perf_counter() - t0
    say(f"set-up {setup_s:.3f}s: {len(base.depths)} FIFOs, "
        f"{int((oracle.need > 0).sum())} with a structural need; rows "
        f"drawn per stream {[len(st.rows) for st in streams]}")
    try:
        obs = client.drive(svc, base, streams, seconds, trace=tracer)
        peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in devices)
    finally:
        svc.close(drain=False)
    for th in obs["threads"]:
        th.join(timeout=30.0)
    return _report(cell, seed, seconds, trace, devices, obs, oracle,
                   streams, setup_s, peak, trace_dir, keep)


def _streams(specs, F: int, seed: int, seconds: float):
    """Each stream's rows (and, open loop, due times), from the seed.  A
    closed stream gets rows for ``max_rate`` rows/s over the window and
    its ``outstanding`` requests besides."""
    arrive = np.random.default_rng([seed, 1])
    wants, dues = [], []
    for st in specs:
        R = int(st["request_rows"])
        due = None
        if st["loop"] == "closed":
            n = (math.ceil(float(st["max_rate"]) * seconds / R)
                 + int(st["outstanding"])) * R
        else:
            due = rows.arrivals(float(st["rate"]), seconds,
                                int(st["gap_seed"]), arrive,
                                int(st.get("burst", 1)))
            n = len(due) * R
        wants.append((n, st["rows"]))
        dues.append(due)
    drawn = rows.draw_streams(F, wants, np.random.default_rng([seed, 0]))
    return [client.Stream(st, D, due)
            for st, D, due in zip(specs, drawn, dues)]


def _tracer(trace_dir: str, spec: dict):
    """Thread body tracing ``spec["seconds"]`` of the window from
    ``spec["lead_s"]`` after its open."""
    def body(t_open: float, t_close: float) -> None:
        import jax

        start = t_open + float(spec["lead_s"])
        time.sleep(max(start - time.perf_counter(), 0.0))
        jax.profiler.start_trace(trace_dir,
                                 profiler_options=tracing.options())
        try:
            with jax.profiler.TraceAnnotation(tracing.MARK):
                time.sleep(float(spec["seconds"]))
        finally:
            jax.profiler.stop_trace()
    return body


def _report(cell, seed, seconds, trace, devices, obs, oracle, streams,
            setup_s, peak, trace_dir, keep) -> dict:
    t_open, t_close = obs["t_open"], obs["t_close"]
    in_window = [r for r in obs["rows"] if t_open <= r.t <= t_close]
    answered = []
    for r in in_window:
        if r.status in FAILED:
            continue
        st = streams[r.stream]
        R = int(st.spec["request_rows"])
        fb = bool(st.spec["fallback"])
        answered.append(check.Row(
            st.spec["name"], st.rows[r.request * R + r.index], fb,
            check.Answer(int(r.status), int(r.cycles), int(r.violated),
                         r.deadlock if fb else None)))
    failed = sum(1 for r in in_window if r.status in FAILED)
    attempted = len(in_window)
    lat_ms, never = [], 0
    for x in obs["interactive"]:
        out = x.outcome
        if x.done is None or out is None:
            continue
        st = streams[x.stream]
        n = int(st.spec["request_rows"])
        fb = bool(st.spec["fallback"])
        for i in range(n):
            if out.status[i] in FAILED:
                failed += 1
                continue
            res = out.results[i]
            answered.append(check.Row(
                st.spec["name"], st.rows[x.j * n + i], fb,
                check.Answer(int(out.status[i]), int(out.cycles[i]),
                             int(out.violated[i]),
                             None if not fb or res is None
                             else bool(res.deadlock))))
    if obs["n_due"]:
        done = [x for x in obs["interactive"] if x.done is not None
                and x.outcome is not None]
        never = obs["n_due"] - len(done)
        sizes = [int(st.spec["request_rows"]) for st in streams]
        due_rows = sum(len(st.due) * n for st, n in zip(streams, sizes)
                       if st.due is not None)
        failed += due_rows - sum(sizes[x.stream] for x in done)
        attempted += due_rows
        wait_end = t_close + 60.0
        lat_ms = sorted([(x.done - x.due) * 1e3 for x in done]
                        + [(wait_end - t_open) * 1e3] * never)
        late = [x.sent - x.due for x in obs["interactive"]] or [0.0]
        say(f"open loop: {obs['n_due']} due, {len(done)} answered; "
            f"generator lateness p50 {np.median(late) * 1e3:.3f} ms, max "
            f"{max(late) * 1e3:.3f} ms")
    before, after = obs["stats_before"], obs["stats_after"]
    say("scheduler over the window: " + ", ".join(
        f"{k} {after[k] - before[k]}" for k in
        ("blocks", "blocks_interactive", "blocks_bulk", "rows", "rows_unique",
         "memo_hits", "fallbacks", "faulted_rows", "timed_out_rows",
         "retries")))
    if obs["starved"]:
        say("a closed stream's rows ran out inside the window")
    n_bulk = sum(1 for r in in_window if r.status not in FAILED)
    values = {"bulk_configs_per_s": n_bulk / seconds,
              "setup_s": setup_s}
    if lat_ms:
        values["interactive_p95_ms"] = percentile(lat_ms, 95)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    breakdown = None
    if trace:
        summary = tracing.reduce(*tracing.load(tracing.find_xplane(trace_dir)))
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        breakdown = {"device_ops": [[k, v] for k, v in summary.ops],
                     "idle_gaps": [[k, v] for k, v in summary.gaps]}
        ctx = dict(summary=summary, obs=obs, devices=devices,
                   peaks=cells.peaks(devices[0].device_kind))
        metrics = {}
        for m in cell.per_layer:
            v = cells.metric_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] in values}
    # correctness, once the window has closed and the service is gone
    sampled = check.sample(answered, int(cell.config["check_rows"]),
                           np.random.default_rng([seed, 2]))
    if keep is not None:
        keep.update(oracle=oracle, sampled=sampled, answered=answered)
    t = time.perf_counter()
    wrong = check.count_wrong(oracle, sampled)
    classes = {}
    for r in sampled:
        classes[f"{r.lane}:{r.served.status}"] = classes.get(
            f"{r.lane}:{r.served.status}", 0) + 1
    say(f"checked {len(sampled)} of {len(answered)} answered rows against "
        f"the reference in {time.perf_counter() - t:.3f}s; by lane:status "
        f"{classes}")
    for r, got, want in wrong[:5]:
        say(f"wrong: {r.lane} row {r.depths.tolist()} served {got} "
            f"reference {want}")
    checks = {"wrong_answers": {"value": len(wrong), "limit": 0},
              "never_answered": {"value": never, "limit": 0}}
    correct = bool(sampled) and all(c["value"] <= c["limit"]
                                    for c in checks.values())
    if not sampled:
        say("no answered row to check")
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = cells.find_cell(args.workload)
    try:
        devices = require_chip(cell.chips)
    except NoChip as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 1
    use_compile_cache()
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices)
    for name, c in out["checks"].items():
        print(f"bench: check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
