"""Roofline share of the jitted rung step (the program's
``jit_counted_step`` modules): the least time the chips could take for
every step dispatched in the traced slice, each at capacity (the larger
of operations over peak FLOP/s and bytes over peak bytes/s, by the
configuration's model module, ``bench/model_flops.py``), over the steps'
device time in the trace averaged over the chips, in %."""

from bench import model_flops

MODULE = "jit_counted_step"


def read(run):
    if run.trace is None or not run.trace.module_s.get(MODULE):
        return None
    least = sum(model_flops.least_step_seconds(
        run.model, run.config, s.rung, run.peak, run.chips)[0]
        for t in run.traced_ticks for s in t.steps if s.n_frames)
    device = run.trace.module_s[MODULE] / run.trace.devices
    return 100.0 * least / device
