"""reshard_s.burst: mean seconds of the session factory's call with a
restored state at each completed transition: the new mesh, its runner
and the placement of the fields on it (host clock around the call)."""


def read(run):
    done = run.completed_transitions()
    if not done:
        return None
    return sum(t.factory[1] - t.factory[0] for t in done) / len(done)
