"""Pipelined device-resident executor — the batched engine's hot path as
a depth-k software pipeline.

The paper's I/O and runtime perspectives (§III, §IV) show host↔device
copies and dispatch gaps are first-order contributors to both mean
latency and variance.  The PR 3 engine serialized them: every tick
rebuilt the full padded batch on host, re-uploaded all ``capacity``
frames, blocked on the fused step, then read results back leaf by leaf —
upload, compute, and Python post-processing in strict sequence, the
device idle through every host phase.  This executor exploits JAX async
dispatch instead: frame *t+1*'s per-slot upload and scene acquisition
overlap frame *t*'s fused device step, which overlaps frame *t−1*'s host
post-processing.

Three jitted programs, each traced exactly once (counted, like the
engine's ``trace_count``):

* ``step`` — the *identical* vmapped ``preprocess_device + infer``
  program the synchronous engine has always run.  Keeping it
  byte-for-byte the same program (assembly is a separate dispatch, so
  XLA cannot fuse selection arithmetic into the conv pipeline) is what
  makes depth-k outputs **bitwise identical** to depth-1 and keeps the
  scenario golden fixtures byte-stable.
* ``assemble`` — builds the next resident batch from the previous one
  plus this tick's dirty frames: ``where(dirty, stack(frames), raw)``.
  Clean slots pass a cached *device* zero buffer, so host→device traffic
  is exactly the dirty frames (``h2d_bytes`` accounts it per submit).
  Deliberately **not** donated: on the CPU/PJRT backend, dispatching a
  computation that donates a buffer with pending producers or consumers
  blocks the host thread until the buffer resolves (measured: the whole
  previous step latency), which would serialize the very pipeline this
  class exists to create.  The copy it pays instead runs asynchronously
  on the device queue, overlapped with host work.
* ``slot_update`` — ``raw.at[slot].set(frame)`` **with** buffer donation
  (``donate_argnums``): the out-of-band carve-out path (join/leave
  zeroing, probes).  These run at churn frequency, not tick frequency,
  where donation's in-place write is worth its synchronization.

**All-dirty upload** (every slot has a frame this tick, the steady state
of a full fleet): the frames are stacked on the host into one contiguous
``(capacity, H, W, 3)`` float32 staging batch and cross in ONE
``jax.device_put`` that lands directly as the resident batch, with the
batch sharding — no per-slot puts and no program launch before the
step.  Which path a tick takes is decided by what ``submit`` observes
(every slot dirty or not), not by a knob; the step receives the same
values, shape and sharding either way, so its outputs are bitwise
identical.  A partial tick keeps the per-slot puts and ``assemble``, so
churned, skipped or half-seated ticks still upload only their dirty
bytes.  Staging safety: ``depth + 1`` staging batches, allocated once
and reused round robin (a fresh array a tick would fault in every host
page it fills), each with a fence: the device values that may still
read it.  On an accelerator the transfer may still be in flight when
``submit`` returns; on the CPU backend ``device_put`` may alias the
(64-byte aligned) batch, so every value computed from the resident
batch while it may alias joins the fence.  A staging batch is rewritten
only once its fence is ready, which in steady state it already is: the
work that read it drained ``depth`` ticks ago.  The stack copies the
frames, so the caller may reuse its frame buffers as soon as ``submit``
returns.  ``warmup`` and ``run_direct`` put a fresh array, leaving the
staging batches to the pipeline.

Results drain oldest-first with ONE ``jax.device_get`` of the whole
output tree — the single-readback contract replacing the per-leaf
``np.asarray`` walks.  ``payload`` riding on each submit is echoed back
on drain so callers can re-associate a result with the (stale) tick that
produced it: at depth k, a drained result is k−1 ticks old.

With an ``obs`` (a ``repro.obs.Observatory``) attached, ``submit`` opens
live ``upload`` (the dirty frames' ``device_put`` calls: one stacked
batch when every slot is dirty) and ``dispatch`` (assembly and step
launch) spans and ``drain`` a ``readback`` span (the device wait and the
one ``device_get``), and the tracer counts ``h2d_bytes``,
``h2d_transfers`` (the ``device_put`` calls a submit issues),
``frames_submitted`` and ``d2h_bytes``.  The engine
attaches it for wall-clock ticks only.

**Fleet sharding** (``mesh=``): with a ``jax.sharding.Mesh`` carrying a
``data`` axis, the resident raw batch, the cached zero batch, the all-dirty
upload, and every program output carry a ``NamedSharding`` splitting the
slot dim across the mesh — shard *k* owns the contiguous slot block
``[k·capacity/K, (k+1)·capacity/K)``.  The programs themselves are
unchanged (GSPMD partitions them from the declared ``out_shardings``),
so trace counts, donation, and the dirty-slot upload contract all hold
per shard exactly as on one device (the all-dirty put hands each shard
its own slot block in the same call); a 1-device mesh is the identical
program and bitwise-identical outputs.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.distributed.sharding import data_shards, slot_batch_spec
from repro.obs.span import NO_SPAN

__all__ = ["Drained", "PipelinedExecutor"]


def _host_batch(shape) -> np.ndarray:
    """A zeroed float32 host array on a 64-byte boundary, where the CPU
    backend's ``device_put`` reads it in place instead of copying it, its
    pages faulted in now rather than in a tick's stack."""
    n = int(np.prod(shape)) * 4
    raw = np.empty(n + 64, np.uint8)
    off = -raw.ctypes.data % 64
    out = raw[off:off + n].view(np.float32).reshape(shape)
    out.fill(0.0)
    return out


def _unfinished(values) -> list:
    """The device arrays among ``values`` still being computed (a donated,
    deleted one was fenced through the value it was donated to)."""
    return [x for x in jax.tree.leaves(values)
            if not x.is_deleted() and not x.is_ready()]


@dataclasses.dataclass
class Drained:
    """One completed pipeline entry, back on host."""

    host: Any                     # full output tree after one device_get
    payload: Any                  # caller's submit payload, echoed
    seq: int                      # submission index (0-based)
    staleness: int                # ticks spent in flight (depth-1 in steady state)
    h2d_bytes: int                # dirty-slot bytes uploaded by its submit
    dispatch_s: float             # host time its submit spent dispatching
    wait_s: float                 # host time drain blocked on the readback
    latency_s: float              # wall clock from submit to drained


@dataclasses.dataclass
class _InFlight:
    dev: Any
    payload: Any
    seq: int
    submitted_at: int             # submit counter value when enqueued
    n_frames: int                 # dirty slots uploaded by its submit
    h2d_bytes: int
    dispatch_s: float
    t_submit: float


class PipelinedExecutor:
    """Depth-k pipeline over a device-resident padded batch.

    ``depth=1`` degenerates to fully synchronous semantics (submit is
    immediately drainable and the caller drains it in the same tick) —
    the scenario replayer's virtual-clock determinism rides on that
    path.  ``depth>=2`` keeps up to ``depth`` steps in flight; ``drain``
    returns the oldest.
    """

    # program inventory: every jitted hot-path callable this executor
    # dispatches, by short name, with its declared buffer donation.  The
    # static certifier (repro.analysis.cert) enumerates PROGRAMS to
    # instrument them and cross-checks DONATED_ARGNUMS against the
    # donated_invars the traced jaxpr actually carries.
    PROGRAMS = ("step", "assemble", "slot_update")
    DONATED_ARGNUMS = {"step": (), "assemble": (), "slot_update": (0,)}

    def __init__(
        self,
        step_fn,
        capacity: int,
        image_shape: tuple[int, int, int],
        depth: int = 1,
        mesh: Optional[Mesh] = None,
    ) -> None:
        if depth < 1:
            raise ValueError(f"depth must be >= 1 (got {depth})")
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1 (got {capacity})")
        self.capacity = capacity
        self.image_shape = tuple(image_shape)
        self.depth = depth
        self.frame_bytes = int(np.prod(self.image_shape)) * 4   # f32
        self.mesh = mesh
        self.n_shards = data_shards(mesh)
        # slot-dim sharding for the resident batch and (as a tree prefix)
        # every program output; P() on a plain/1-device setup
        self._batch_sharding = (
            NamedSharding(mesh, slot_batch_spec(mesh, capacity))
            if mesh is not None else None)
        # per-frame uploads (no slot dim) replicate across the mesh: a
        # plain device_put would commit them to device 0 only, and jit
        # rejects arguments committed to mismatched device sets
        self._replicated = (NamedSharding(mesh, P())
                            if mesh is not None else None)

        # trace counters: a recompile of any program — which static
        # shapes are supposed to rule out — is observable
        self.step_traces = 0
        self.assemble_traces = 0
        self.update_traces = 0

        def counted_step(raw):
            self.step_traces += 1
            return step_fn(raw)

        def counted_assemble(raw, dirty, *frames):
            self.assemble_traces += 1
            return jnp.where(dirty[:, None, None, None], jnp.stack(frames), raw)

        def counted_update(raw, slot, frame):
            self.update_traces += 1
            return raw.at[slot].set(frame)

        # in mesh mode, pin every program's output to the slot-dim
        # sharding (a single sharding is a tree prefix, so the step's
        # whole output tree — every leaf leads with the slot dim — shards
        # identically); without it, assemble's stack of replicated
        # per-frame uploads would leave the resident batch replicated and
        # the fused step unpartitioned
        shard_kw = ({"out_shardings": self._batch_sharding}
                    if self._batch_sharding is not None else {})
        self._step = jax.jit(counted_step, **shard_kw)
        self._assemble = jax.jit(counted_assemble, **shard_kw)
        # donation: carve-outs mutate the resident batch in place
        self._slot_update = jax.jit(counted_update, donate_argnums=(0,),
                                    **shard_kw)
        self._zero_frame = None       # cached device zeros, made lazily
        self._raw = self._zeros_batch()
        # all-dirty staging: host batches the frames are stacked into,
        # reused round robin, one more than the pipeline's depth, so the
        # work that read a batch has drained before it comes round; each
        # keeps the device values that may still read it (its fence)
        self._staging: list[np.ndarray] = []      # made by the first use
        self._stage_fence: list[list] = []
        self._stage_next = 0
        self._raw_stage: Optional[int] = None     # staging _raw may alias
        self._queue: deque[_InFlight] = deque()
        self._seq = 0
        self.obs = None               # live spans and counters when set

    def _zeros_batch(self):
        """A blank resident batch, carrying the mesh sharding when set."""
        z = jnp.zeros((self.capacity, *self.image_shape), jnp.float32)
        if self._batch_sharding is not None:
            z = jax.device_put(z, self._batch_sharding)
        return z

    def shard_of_slot(self, slot: int) -> int:
        """Which mesh shard owns a slot (contiguous block partition)."""
        return slot // (self.capacity // self.n_shards)

    def programs(self) -> dict:
        """The live jitted program per short name in ``PROGRAMS``."""
        return {name: getattr(self, f"_{name}") for name in self.PROGRAMS}

    def instrument(self, wrap) -> dict:
        """Replace every jitted program with ``wrap(name, fn)`` and return
        the wrappers keyed by short name.  The certifier passes recorders
        that trace (``jax.make_jaxpr``) instead of executing, turning a
        full engine sweep into a compile-free static analysis; tests can
        pass counting or fault-injecting wrappers the same way."""
        out = {}
        for name, fn in self.programs().items():
            wrapped = wrap(name, fn)
            setattr(self, f"_{name}", wrapped)
            out[name] = wrapped
        return out

    # ---------------- resident-batch maintenance ----------------
    def _put(self, x):
        """Host→device upload of a per-frame (or scalar) value, on the
        mesh's full device set when sharded."""
        if self._replicated is not None:
            return jax.device_put(x, self._replicated)
        return jax.device_put(x)

    def _put_batch(self, frames, staged: bool = False) -> tuple[Any, int]:
        """ONE host→device transfer of a whole batch: the checked frames,
        one per slot, stacked into one contiguous host array that lands
        with the resident batch's sharding (each mesh shard receives its
        own slot block).  ``staged`` stacks into the next staging batch,
        once every device value that may still read it is ready (an
        in-flight transfer reads it; a CPU ``device_put`` may alias it);
        otherwise into a fresh array.  Returns the device batch and the
        staging index (-1 for a fresh array)."""
        frames = [self._checked(f) for f in frames]
        if not staged:
            return self._device_batch(np.stack(frames)), -1
        if not self._staging:
            shape = (self.capacity, *self.image_shape)
            self._staging = [_host_batch(shape)
                             for _ in range(self.depth + 1)]
            self._stage_fence = [[] for _ in self._staging]
        i = self._stage_next
        self._stage_next = (i + 1) % len(self._staging)
        jax.block_until_ready(_unfinished(self._stage_fence[i]))
        buf = self._staging[i]
        np.stack(frames, out=buf)
        batch = self._device_batch(buf)
        self._stage_fence[i] = [batch]
        return batch, i

    def _device_batch(self, batch: np.ndarray) -> Any:
        if self._batch_sharding is not None:
            return jax.device_put(batch, self._batch_sharding)
        return jax.device_put(batch)

    def _reads_raw(self, value) -> None:
        """Fence a device value dispatched over the resident batch: while
        that batch may alias a staging batch, the staging batch is not
        rewritten until the value is ready."""
        if self._raw_stage is not None:
            fence = self._stage_fence[self._raw_stage]
            fence[:] = _unfinished(fence) + [value]

    def _zero(self):
        if self._zero_frame is None:
            self._zero_frame = self._put(
                np.zeros(self.image_shape, np.float32))
        return self._zero_frame

    def _checked(self, frame) -> np.ndarray:
        """Coerce one host frame, rejecting shape mismatches loudly — a
        consistently wrong-shaped batch would otherwise silently RETRACE
        the jitted programs and run inference at the wrong resolution."""
        f = np.ascontiguousarray(np.asarray(frame, np.float32))
        if f.shape != self.image_shape:
            raise ValueError(
                f"frame shape {f.shape} != executor image shape "
                f"{self.image_shape}")
        return f

    def set_slot(self, slot: int, frame: Optional[np.ndarray]) -> None:
        """Out-of-band per-slot write (``None`` blanks the slot) via the
        donated in-place update.  May block briefly if the resident
        buffer still has an in-flight consumer — carve-outs are churn
        events, not tick events, and correctness is preserved either
        way (PJRT fences donated buffers on their pending events)."""
        if not 0 <= slot < self.capacity:
            raise IndexError(f"slot {slot} out of range [0, {self.capacity})")
        f = self._zero() if frame is None else self._checked(frame)
        # slot index as a device int32 (matching warmup's aval) so the
        # carve-out is also clean under jax.transfer_guard("disallow")
        # donation may keep the staging alias, so the fence follows it
        self._raw = self._slot_update(
            self._raw, self._put(np.int32(slot)),
            self._put(f) if isinstance(f, np.ndarray) else f)
        self._reads_raw(self._raw)

    def reset(self) -> None:
        """Drop all in-flight work and blank the resident batch."""
        self._queue.clear()
        self._raw = self._zeros_batch()
        self._raw_stage = None

    def warmup(self) -> None:
        """Trace + compile every jitted program on throwaway buffers so
        neither the first tick nor the first churn carve-out pays a
        multi-second XLA outlier.  The executor owns the program
        inventory, so a new fast path added here cannot be forgotten by
        callers' warmups.  Resident slot contents are untouched."""
        # sharded like the live resident batch, so the warmed executables
        # are exactly the ones the tick path replays (jit caches on input
        # shardings as well as avals): the step over an assembled batch
        # (partial ticks) and over an all-dirty upload
        zero = np.zeros(self.image_shape, np.float32)
        raw = self._assemble(self._zeros_batch(),
                             self._put(np.zeros(self.capacity, bool)),
                             *[self._zero()] * self.capacity)
        jax.block_until_ready(self._step(raw))
        batch, _ = self._put_batch([zero] * self.capacity)
        jax.block_until_ready(self._step(batch))
        # same avals as set_slot's call (device int32 slot), so the carve
        #-out path warms exactly the executable set_slot will replay
        self._slot_update(self._zeros_batch(), self._put(np.int32(0)),
                          self._zero())             # donates the throwaway

    def run_direct(self, frames=None):
        """One blocking fused step *outside* the pipeline (calibration
        probes): over the resident batch (``frames is None``, read-only)
        or over a throwaway batch of ``frames`` cycled across the slots,
        uploaded in one transfer as an all-dirty tick's is (from a fresh
        host array: the staging batches belong to the pipeline).
        Returns the device outputs, ready."""
        if frames is None:
            dev = self._step(self._raw)
        else:
            batch, _ = self._put_batch(
                [frames[b % len(frames)] for b in range(self.capacity)])
            dev = self._step(batch)
        jax.block_until_ready(dev)
        return dev

    # ---------------- the pipeline ----------------
    @property
    def pending(self) -> int:
        return len(self._queue)

    def ready(self) -> bool:
        """True when the pipeline is full: the caller should drain one
        result before (or after) the next submit to hold steady depth."""
        return len(self._queue) >= self.depth

    def submit(self, slot_frames: Mapping[int, np.ndarray],
               payload: Any = None) -> int:
        """Dispatch one tick: upload the dirty slots, assemble the next
        resident batch, launch the fused step.  With every slot dirty the
        frames cross in one stacked transfer that becomes the resident
        batch itself (no assembly).  Never blocks on device work.
        Returns the submission's sequence number."""
        obs = self.obs
        t0 = time.perf_counter()
        for slot in slot_frames:
            if not 0 <= slot < self.capacity:
                raise IndexError(
                    f"slot {slot} out of range [0, {self.capacity})")
        n_dirty = len(slot_frames)
        all_dirty = n_dirty == self.capacity
        h2d = n_dirty * self.frame_bytes
        with NO_SPAN if obs is None else obs.span(
                "upload", batch_size=n_dirty, axis="io"):
            # explicit device_puts so the H2D copy happens here, on the
            # host thread, and is accounted — only dirty slots transfer
            if all_dirty:
                self._raw, self._raw_stage = self._put_batch(
                    [slot_frames[b] for b in range(self.capacity)],
                    staged=True)
                transfers = 1
            else:
                dirty = np.zeros(self.capacity, bool)
                frames: list[Any] = [self._zero()] * self.capacity
                for slot, frame in slot_frames.items():
                    dirty[slot] = True
                    frames[slot] = self._put(self._checked(frame))
                transfers = n_dirty
        # tvlint: disable=TV006 (the dispatch span times the async enqueue,
        # not execution; the readback span in drain() holds the device wait)
        with NO_SPAN if obs is None else obs.span(
                "dispatch", batch_size=n_dirty, axis="runtime"):
            if n_dirty and not all_dirty:
                # the mask crosses explicitly too: under the sentinel's
                # jax.transfer_guard("disallow") an implicit numpy→device
                # argument is an error, and the tick path must stay
                # guard-clean
                self._raw = self._assemble(
                    self._raw, self._put(dirty), *frames)
                self._reads_raw(self._raw)
                self._raw_stage = None
                transfers += 1
            dev = self._step(self._raw)
            self._reads_raw(dev)
        if obs is not None:
            obs.tracer.add("h2d_bytes", h2d)
            obs.tracer.add("h2d_transfers", transfers)
            obs.tracer.add("frames_submitted", n_dirty)
        seq = self._seq
        self._seq += 1
        self._queue.append(_InFlight(
            dev=dev, payload=payload, seq=seq, submitted_at=self._seq,
            n_frames=n_dirty,
            # tvlint: disable=TV006 (dispatch_s deliberately measures async
            # enqueue cost, not execution; drain() fences before latency_s)
            h2d_bytes=h2d, dispatch_s=time.perf_counter() - t0,
            t_submit=t0))
        return seq

    def drain(self) -> Drained:
        """Block for the OLDEST in-flight step and return it after one
        ``jax.device_get`` of the whole output tree."""
        if not self._queue:
            raise RuntimeError("drain() on an empty pipeline")
        entry = self._queue.popleft()
        obs = self.obs
        with NO_SPAN if obs is None else obs.span(
                "readback", batch_size=entry.n_frames, axis="io"):
            t0 = time.perf_counter()
            host = jax.device_get(entry.dev)
            t1 = time.perf_counter()
        if obs is not None:
            obs.tracer.add("d2h_bytes", sum(
                leaf.nbytes for leaf in jax.tree.leaves(host)))
        return Drained(
            host=host, payload=entry.payload, seq=entry.seq,
            staleness=self._seq - entry.submitted_at,
            h2d_bytes=entry.h2d_bytes, dispatch_s=entry.dispatch_s,
            wait_s=t1 - t0, latency_s=t1 - entry.t_submit)

    def flush(self) -> list[Drained]:
        """Drain everything in flight, oldest first."""
        out = []
        while self._queue:
            out.append(self.drain())
        return out
