"""ckpt_s.burst: mean seconds of the session's checkpoint call (the
wavefields copied to the host) at each completed transition (host
clock around ``Session.checkpoint``)."""


def read(run):
    done = run.completed_transitions()
    if not done:
        return None
    return sum(t.ckpt[1] - t.ckpt[0] for t in done) / len(done)
