"""Executor time per tick: the ``inference`` stage of every rung engine's
``StageRecord`` (dirty-slot upload, dispatch, device wait and the one
``device_get``), summed over the tick's engine calls; mean over the
window's ticks, in ms."""


def read(run):
    if not run.ticks:
        return None
    per = [sum(s.stages.get("inference", 0.0) for s in t.steps)
           for t in run.ticks]
    return 1e3 * sum(per) / len(per)
