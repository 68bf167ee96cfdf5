"""halo_exposed_share: share of the device time held by multi-stripe
sessions in which a collective (the halo exchange's
collective-permute) runs on a device while no other operation runs
there (profiler trace).  None without a multi-stripe session."""

from bench import trace as tr


def read(run):
    if run.trace is None:
        return None
    held: dict[int, list] = {}
    for s in run.sessions:
        if s.stripes > 1:
            lo = run.to_trace(s.created)
            hi = run.to_trace(s.ended)
            for d in s.devices:
                held.setdefault(d, []).append((lo, hi))
    if not held:
        return None
    total = exposed = 0.0
    for d, v in held.items():
        v = tr.union(v)
        comm = run.trace.busy(d, tr.COLLECTIVE)
        compute = tr.union(
            (a, b) for a, b, n in run.trace.ops.get(d, ())
            if not tr.COLLECTIVE.search(n))
        exposed += tr.total(tr.intersect(tr.subtract(comm, compute), v))
        total += tr.total(v)
    return 100.0 * exposed / total
