"""burst_s: mean over the window's completed transitions of the wall
time from the start of the transition's checkpoint to the end of the
first block on the new mesh (host clock).  None without a transition."""


def read(run):
    done = run.completed_transitions()
    if not done:
        return None
    return sum(t.first_block_end - t.ckpt[0] for t in done) / len(done)
