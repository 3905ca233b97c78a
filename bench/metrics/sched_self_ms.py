"""Scheduler self time: per tick, the tick's host wall time less the
stage sums of the ``StageRecord``s its rung engines returned (rung
selection, bucketing, membership churn, accounting); mean over the
window's ticks, in ms."""


def read(run):
    if not run.ticks:
        return None
    own = [(t.t1 - t.t0) - sum(sum(s.stages.values()) for s in t.steps)
           for t in run.ticks]
    return 1e3 * sum(own) / len(own)
