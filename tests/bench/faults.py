"""Ways the timed path can be broken underneath the harness, and the
control the limits must fail.

Each fault returns ``(owner, name, broken)``: setting ``owner.name`` to
``broken`` plants it (``monkeypatch.setattr(*fault())`` in a test, plain
``setattr`` in a child process).
"""
import dataclasses

import numpy as np

CONTROL_SEED = 2 ** 31 + 99
CONTROL_FRAMES = 64           # frames a rung the control is read on


def no_exchange():
    """Every shard's results replaced by the first shard's: what a
    readback that never gathered the other chips would hand the host."""
    import jax

    from repro.batched.executor import PipelinedExecutor

    drain = PipelinedExecutor.drain

    def broken(self):
        d = drain(self)
        block = self.capacity // self.n_shards
        d.host = jax.tree.map(
            lambda x: np.concatenate([x[:block]] * self.n_shards), d.host)
        return d
    return PipelinedExecutor, "drain", broken


def shift_answers():
    """Every served frame's boxes moved by 3 px where the host post
    produces them."""
    from repro.batched.engine import BatchedPerceptionEngine

    post = BatchedPerceptionEngine._post

    def broken(self, host, active_mask):
        return [o if o is None else dataclasses.replace(o, boxes=o.boxes + 3)
                for o in post(self, host, active_mask)]
    return BatchedPerceptionEngine, "_post", broken


def drop_half():
    """The fused step blanks the upper half of its slots."""
    import jax.numpy as jnp

    import repro.batched.engine as engine

    program = engine.fused_batch_program

    def broken(built):
        step = program(built)

        def run(raw):
            keep = jnp.arange(raw.shape[0]) < raw.shape[0] // 2
            return step(raw * keep[:, None, None, None])
        return run
    return engine, "fused_batch_program", broken


def stale_state():
    """The resident batch keeps its first frames: later ticks upload
    nothing and the step serves the old state again."""
    from repro.batched.executor import PipelinedExecutor

    submit = PipelinedExecutor.submit

    def broken(self, slot_frames, payload=None):
        seen = getattr(self, "_stale_seen", False)
        self._stale_seen = True
        return submit(self, {} if seen else slot_frames, payload)
    return PipelinedExecutor, "submit", broken


def control_verdicts(bench, cell: dict) -> tuple:
    """``(control, itself)``: the verdicts of the cell's limits on its
    configuration's reference in bfloat16 against the same reference in
    float32, and on the float32 reference against itself.  Everything
    comes from ``cell`` (``bench.load_cell``): the configuration, its
    reference module, the traffic's mix and rungs, and scene pools at the
    configuration's frame, ``CONTROL_FRAMES`` frames a rung."""
    import jax.numpy as jnp

    from bench.calibrate import control_numbers
    from bench.detection import weights_key

    cfg, traffic = cell["config"], cell["traffic"]
    n = cfg["streams"]
    per = max(1, CONTROL_FRAMES // n)
    pools = bench.fleet_pools(CONTROL_SEED, n, traffic["mix"], per,
                              cfg["frame"])
    used = bench.rungs_used(traffic, cfg["ladder"])
    frames = [bench.Frame(i, j, 0.0, rung=r)
              for r in used for i in range(n) for j in range(per)]
    run = bench.Run(cell=cell, seconds=1.0, t_open=0.0, t_close=1.0,
                    ticks=[], frames=frames, ladder=used, chips=cell["chips"],
                    peak={}, pools=pools)
    Reference = cell["reference"].Reference
    hi = Reference(cfg, weights_key())
    lo = Reference(cfg, weights_key(), jnp.bfloat16)
    control = bench.verdict(control_numbers(run, frames, lo, hi),
                            cell["limits"])
    _fill_outputs(run, hi)
    itself = bench.verdict(bench.check(run, frames, hi), cell["limits"])
    return control, itself


def _fill_outputs(run, ref) -> None:
    """Give every frame the reference's own answer."""
    from repro.perception.pipelines import FrameOutput

    for rung in sorted({f.rung for f in run.frames}):
        fs = [f for f in run.frames if f.rung == rung]
        images = np.stack([run.pools[f.stream][f.scene].image for f in fs])
        for f, det in zip(fs, ref.detect(rung, images)):
            f.out = FrameOutput(boxes=np.asarray(det.boxes), num_objects=0.0,
                                num_proposals=det.n_proposals)
