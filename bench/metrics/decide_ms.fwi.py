"""decide_ms.fwi: mean milliseconds of the orchestrator's decision
(the program's ``orch.decide`` span: the deadline estimate, the
planner's or the autoscaler's verdict and the resources it gives) in
the cells of the 4096-square grid (host clock); moves gpts_per_s.
None without the span."""
from bench.program_spans import mean_ms


def read(run):
    return mean_ms(run, "orch.decide")
