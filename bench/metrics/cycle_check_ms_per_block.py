"""Device time of the fixpoint's cycle exit (ops whose ``op_name`` lies in
the ``cycle_check`` scope) per fixpoint execution, in ms, over the
complete fixpoint executions of the traced part of the window.

``spans.SCOPES`` does not name the scope, so the attribution is made here
the way ``spans.reduce`` makes it: each op event inside an execution is
mapped through the execution's HLO ``op_name`` table to its scope path.
A program whose fixpoint has no such scope gives no number."""
import spans
import tracing

SCOPE = "/cycle_check/"


def read(ctx):
    s = ctx.get("summary")
    if s is None:
        return None
    path = ctx.get("xplane")
    if path is None:
        try:
            path = tracing.find_xplane(spans.TRACE_DIR)
        except FileNotFoundError:
            return None
    tr = spans.load(path)
    if (tr.hi - tr.lo) * 1e-9 != s.window_s:
        return None
    scoped = {m for m, names in tr.op_names.items()
              if any(SCOPE in v for v in names.values())}
    if not scoped:
        return None
    runs, busy = 0, 0.0
    for lines in tr.devices.values():
        ops = lines.get(tracing.OPS_LINE, [])
        for m in lines.get(tracing.MODULES_LINE, []):
            if (tracing.FIXPOINT not in m.name or m.start < tr.lo
                    or m.end > tr.hi):
                continue
            runs += 1
            names = tr.op_names.get(m.name, {})
            busy += 1e-9 * tracing._length(tracing._union(
                [(e.start, e.end) for e in ops
                 if e.start >= m.start and e.end <= m.end
                 and not e.name.startswith(tracing.CONTAINERS)
                 and SCOPE in names.get(spans._instruction(e), "")]))
    return 1e3 * busy / runs if runs else None
