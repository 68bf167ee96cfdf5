"""dispatch_ms.fwi: mean milliseconds the session takes to enqueue one
scan block (the program's ``fwi.dispatch`` span: the runner's call up to
its return, before ``block_until_ready``) in the cells of the
4096-square grid (host clock); moves gpts_per_s.  None without the
span."""
from bench.program_spans import mean_ms


def read(run):
    return mean_ms(run, "fwi.dispatch")
