"""The client side of a run: set-up warm-up, then the measured window.

A traffic file lists its ``streams``; each is driven on its own:

* ``"loop": "closed"`` — ``outstanding`` requests of ``request_rows`` rows
  are kept in the service; a new one is submitted as soon as one
  finishes.  Every delivered row is stamped on arrival.
* ``"loop": "open"`` — requests of ``request_rows`` rows are submitted at
  their due times whatever the service is doing, each watched by a thread
  of its own, so that a slow reply never delays a later submission.

Every stream also names its ``priority`` lane and ``fallback``.  Only rows
stamped inside the window count.  At the close the closed streams'
requests are cancelled (their backlog is not waited for) and the open
streams' requests due in the window are waited for, at most ``grace_s``
past the close.
"""
from __future__ import annotations

import contextlib
import queue
import threading
import time
from typing import List, NamedTuple, Optional

import numpy as np


class Delivered(NamedTuple):
    t: float            # host clock at arrival
    stream: int         # index of the stream in the traffic file
    request: int        # index of the request (chunk of the stream's rows)
    index: int          # row in that request
    status: int
    cycles: int
    violated: int
    deadlock: Optional[bool]    # the fallback run's flag, where there is one


class Interactive(NamedTuple):
    stream: int         # index of the stream in the traffic file
    j: int              # index of the request in the arrival order
    due: float          # host clock the request was due at
    sent: float         # host clock its submit began
    submit_s: float     # time inside svc.submit
    done: Optional[float]   # host clock its last row arrived (None: never)
    outcome: object     # BatchOutcome, or None


class Stream(NamedTuple):
    spec: dict                  # the stream's entry in the traffic file
    rows: np.ndarray            # its rows, request after request
    due: Optional[np.ndarray]   # open loop: due times (s after the open)


def warm(svc, base, block: int, floor: int = 8) -> None:
    """Serve one request per power-of-two block height up to ``block``, so
    every fixpoint shape a window can meet is compiled (or loaded from the
    compile cache) before it opens.  The rows give FIFO 0 a depth no
    traffic row has, so none of them is a traffic row."""
    D0 = np.asarray(base.depths, np.int64)
    K, j = floor, 0
    while True:
        D = np.repeat(D0[None], K, 0)
        D[:, 0] += 1000 + j + np.arange(K)
        svc.submit(base, D, fallback=False).result(timeout=1200.0)
        j += K
        if K >= block:
            return
        K *= 2


def _annotate(on: bool, name: str):
    if not on:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


def drive(svc, base, streams: List[Stream], seconds: float, trace=None,
          grace_s: float = 60.0) -> dict:
    """Run the window; ``trace`` (optional) is a callable started on its
    own thread with the window's open and close times.  The caller closes
    the service, then joins the returned ``threads``."""
    tracing = trace is not None
    rows: List[Delivered] = []
    finished: "queue.Queue[int]" = queue.Queue()
    handles, consumers, waiters = [], [], []
    inter: List[Interactive] = []
    stop = threading.Event()
    nxt = [0] * len(streams)
    starved = [False] * len(streams)

    def consume(h, s: int, r: int) -> None:
        try:
            for it in h.stream():
                rows.append(Delivered(
                    time.perf_counter(), s, r, it.index, it.status,
                    it.cycles, it.violated,
                    None if it.result is None else bool(it.result.deadlock)))
        except RuntimeError:
            pass                         # aborted at the close
        finally:
            finished.put(s)

    def submit_closed(s: int) -> None:
        st = streams[s]
        R, r = int(st.spec["request_rows"]), nxt[s]
        chunk = st.rows[r * R:(r + 1) * R]
        if len(chunk) < R:
            starved[s] = True
            return
        nxt[s] += 1
        with _annotate(tracing, "bench.submit.closed"):
            h = svc.submit(base, chunk, priority=st.spec["priority"],
                           fallback=bool(st.spec["fallback"]))
        handles.append(h)
        th = threading.Thread(target=consume, args=(h, s, r), daemon=True)
        th.start()
        consumers.append(th)

    def open_client(s: int, t_open: float) -> None:
        st = streams[s]
        n = int(st.spec["request_rows"])
        for j, d in enumerate(st.due):
            target = t_open + float(d)
            while True:
                left = target - time.perf_counter()
                if left <= 0 or stop.is_set():
                    break
                time.sleep(min(left, 0.05))
            if stop.is_set():
                return
            sent = time.perf_counter()
            with _annotate(tracing, "bench.submit.open"):
                h = svc.submit(base, st.rows[j * n:(j + 1) * n],
                               priority=st.spec["priority"],
                               fallback=bool(st.spec["fallback"]))
            submit_s = time.perf_counter() - sent
            slot = len(inter)
            inter.append(Interactive(s, j, target, sent, submit_s, None,
                                     None))

            def wait(h=h, slot=slot):
                try:
                    out = h.result()
                except RuntimeError:
                    return               # aborted at the close: missing
                inter[slot] = inter[slot]._replace(done=time.perf_counter(),
                                                   outcome=out)
            th = threading.Thread(target=wait, daemon=True)
            th.start()
            waiters.append(th)

    before = svc.stats()["scheduler"]
    t_open = time.perf_counter()
    t_close = t_open + seconds
    opens = []
    for s, st in enumerate(streams):
        if st.spec["loop"] == "open":
            th = threading.Thread(target=open_client, args=(s, t_open),
                                  daemon=True)
            th.start()
            opens.append(th)
    trace_th = None
    if trace is not None:
        trace_th = threading.Thread(target=trace, args=(t_open, t_close),
                                    daemon=True)
        trace_th.start()
    for s, st in enumerate(streams):
        if st.spec["loop"] == "closed":
            for _ in range(int(st.spec["outstanding"])):
                submit_closed(s)
    while True:
        left = t_close - time.perf_counter()
        if left <= 0:
            break
        try:
            s = finished.get(timeout=left)
        except queue.Empty:
            break
        submit_closed(s)
    # the close: no more closed-loop work; wait for what was due in the
    # window
    for h in handles:
        h.cancel()
    for th in opens:
        th.join(timeout=max(grace_s, 1.0))
    deadline = t_close + grace_s
    for th in waiters:
        th.join(timeout=max(deadline - time.perf_counter(), 0.0))
    stop.set()
    after = svc.stats()["scheduler"]
    if trace_th is not None:
        trace_th.join()
    return dict(t_open=t_open, t_close=t_close, rows=list(rows),
                interactive=list(inter),
                n_due=sum(len(st.due) for st in streams
                          if st.due is not None),
                starved=any(starved), stats_before=before,
                stats_after=after, threads=consumers + waiters)
