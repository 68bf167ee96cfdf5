"""restore_idle_s.burst: device seconds idle while a transition restores
the job onto its new mesh.  For each ``fwi.place`` span whose parent is
an ``orch.transition``: the idle time of the new session's devices, as
their mean, from the start of the placement to the end of the same
session's first ``fwi.wait`` (the first block on the new mesh done);
the mean over transitions (profiler trace, spans mapped onto it by the
window).  Moves burst_s.  None without such a transition."""
from bench import trace as tr
from bench.program_spans import in_window


def read(run):
    spans = in_window(run)
    if run.trace is None or not spans:
        return None
    by_id = {s.id: s for s in spans}
    first_wait: dict[int, float] = {}
    for s in sorted(spans, key=lambda s: s.t0):
        if s.name == "fwi.wait":
            first_wait.setdefault(s.attrs["session"], s.t1)
    idle = []
    for s in spans:
        parent = by_id.get(s.parent)
        if (s.name != "fwi.place" or parent is None
                or parent.name != "orch.transition"
                or s.attrs["session"] not in first_wait):
            continue
        end = first_wait[s.attrs["session"]]
        lo, hi = run.to_trace(s.t0), run.to_trace(end)
        devices = s.attrs["devices"]
        busy = sum(tr.total(tr.intersect(run.trace.busy(d), [(lo, hi)]))
                   for d in devices)
        idle.append(((hi - lo) - busy / len(devices)) / 1e9)
    if not idle:
        return None
    return sum(idle) / len(idle)
