"""Host post-processing time per tick: the ``post_processing`` stage of
every rung engine's ``StageRecord``, summed over the tick's engine calls;
mean over the window's ticks, in ms."""


def read(run):
    if not run.ticks:
        return None
    per = [sum(s.stages.get("post_processing", 0.0) for s in t.steps)
           for t in run.ticks]
    return 1e3 * sum(per) / len(per)
