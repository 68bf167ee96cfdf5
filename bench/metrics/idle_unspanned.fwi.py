"""idle_unspanned.fwi: share of the device time the job held in the
window in which no operation ran and the host was in none of the
program's spans (``orch.*``, ``fwi.*``): the idle that the program's
spans do not name, on the base of ``idle_share.fwi`` (profiler trace,
spans mapped onto it by the window); moves gpts_per_s.  None without
the spans."""
from bench import trace as tr
from bench.program_spans import in_window


def read(run):
    spans = in_window(run)
    if run.trace is None or not spans:
        return None
    named = tr.union((run.to_trace(s.t0), run.to_trace(s.t1))
                     for s in spans if s.name.startswith(("orch.", "fwi.")))
    held = run.held()
    total = sum(tr.total(v) for v in held.values())
    unspanned = sum(
        tr.total(tr.subtract(tr.subtract(v, run.trace.busy(d)), named))
        for d, v in held.items())
    return 100.0 * unspanned / total
