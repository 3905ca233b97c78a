"""Model FLOP/s utilization of the whole served path: frames served in
the window times the model operations per frame of each frame's rung
(the configuration's model module), over the window, over chips times
the chip's bf16 peak, in %.  Taken on the host clock: in a cell whose
traffic pins one rung it is ``frames_per_s`` times a constant."""


def read(run):
    served = run.served()
    if not served:
        return None
    ops = sum(run.model.flops_per_frame(run.config, f.rung) for f in served)
    peak = run.chips * run.peak["bf16_flops_per_s"]
    return 100.0 * ops / run.seconds / peak
