"""Padded slots computed per frame served: capacity times the rung-engine
steps dispatched, over the frames those steps served, in the window."""


def read(run):
    steps = [s for t in run.ticks for s in t.steps if s.n_frames]
    frames = sum(s.n_frames for s in steps)
    if not frames:
        return None
    return run.config["capacity"] * len(steps) / frames
