"""Instrumented perception pipelines — the paper's profiling harness
(Fig. 3 timeline: read → pre-process → inference → post-process) wired to
the synthetic scenes, with both the paper-faithful *dynamic* post-processing
and the static-shape mitigation.

Pipelines are **registry-driven**: each fidelity variant registers a
factory under a name (``PIPELINES``), the single ``run_pipeline`` runner
drives any of them through the identical stage-timed loop, and the legacy
``run_*`` entry points are thin wrappers.  The anytime subsystem
(``repro.anytime``) addresses rungs by these registry names.

Every run returns a ``TimelineRecorder`` whose records carry the stage
breakdown plus metadata (``num_proposals``, ``num_objects``) so a
caller can compute the paper's correlations directly; ``collect=True``
additionally returns per-frame detections in the original image frame so
quality can be scored against ``Scene.boxes``.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Any, Callable, Dict, Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.timing import StageTimer, TimelineRecorder
from .data import H, W, Scene, SceneConfig, generate_scene
from .detector import OneStageDetector, TwoStageDetector
from .lane import LaneDetector

__all__ = [
    "FrameOutput",
    "SlotOutputs",
    "BuiltPipeline",
    "PIPELINES",
    "register_pipeline",
    "build_pipeline",
    "run_frame",
    "run_pipeline",
    "run_one_stage",
    "run_two_stage",
    "run_lane",
    "run_lane_static",
    "preprocess",
    "preprocess_device",
]


def _default_key() -> jax.Array:
    """Per-run PRNG key, created lazily so importing this module does no
    JAX work (CLI ``--help`` paths stay cheap)."""
    return jax.random.PRNGKey(7)


def preprocess(image: np.ndarray, scale: float = 1.0, pad: bool = True) -> np.ndarray:
    """Resize (λ scaling, paper Fig. 6) + normalize + color juggling —
    the real host work of the paper's pre-processing stage.

    ``pad=True`` (legacy) crops/pads the scaled image back to the model's
    fixed input shape.  ``pad=False`` returns the genuinely smaller scaled
    image — the anytime ladder's λ rungs use it so a lower scale buys a
    proportional inference-FLOP reduction, not just fewer bright pixels.
    """
    img = image
    if scale != 1.0:
        h, w = img.shape[:2]
        nh, nw = max(int(h * scale), 8), max(int(w * scale), 8)
        if not pad:
            # detectors pool in 8-px cells; round the unpadded input down
            # to the cell grid so any λ yields a valid static shape
            nh, nw = max(nh // 8 * 8, 8), max(nw // 8 * 8, 8)
        ys = (np.arange(nh) * (h / nh)).astype(np.int64)
        xs = (np.arange(nw) * (w / nw)).astype(np.int64)
        img = img[ys][:, xs]
        if pad:
            # crop/pad back to the model's fixed input (paper: transpose+crop
            # when the input exceeds the max size — the λ=10 outlier)
            out = np.zeros(image.shape, np.float32)
            ch, cw = min(h, nh), min(w, nw)
            out[:ch, :cw] = img[:ch, :cw]
            img = out
    img = img[..., ::-1]                      # BGR↔RGB convert (paper's cvt)
    img = (img - img.mean()) / (img.std() + 1e-6)
    return img.astype(np.float32)


def preprocess_device(image: jax.Array, scale: float = 1.0, pad: bool = True) -> jax.Array:
    """``preprocess`` as a traceable device computation, stage for stage.

    The batched engine folds pre-processing into the one jitted batch step
    (``vmap`` over this + ``infer``): N streams then pay one fused device
    pass instead of N host-side NumPy passes.  Shapes are static — the λ
    gather indices are computed from the (trace-time) input shape exactly
    as the host version computes them, so the two paths agree numerically.
    """
    img = image
    if scale != 1.0:
        h, w = int(img.shape[0]), int(img.shape[1])
        nh, nw = max(int(h * scale), 8), max(int(w * scale), 8)
        if not pad:
            nh, nw = max(nh // 8 * 8, 8), max(nw // 8 * 8, 8)
        ys = (np.arange(nh) * (h / nh)).astype(np.int64)
        xs = (np.arange(nw) * (w / nw)).astype(np.int64)
        img = img[ys][:, xs]
        if pad:
            out = jnp.zeros(image.shape, jnp.float32)
            ch, cw = min(h, nh), min(w, nw)
            img = out.at[:ch, :cw].set(img[:ch, :cw])
    img = img[..., ::-1]
    img = (img - img.mean()) / (img.std() + 1e-6)
    return img.astype(jnp.float32)


@dataclasses.dataclass(frozen=True)
class FrameOutput:
    """One frame's host-side result: detections mapped back to the
    original (unscaled) image frame plus the paper's variance covariates."""

    boxes: np.ndarray            # (k, 4) detections, original image coords
    num_objects: float
    num_proposals: float


class SlotOutputs(list):
    """A ``post_batch`` result: the per-slot ``FrameOutput`` list (``None``
    for inactive slots) plus ``counters``, name → value totals of the
    pass, for the batched engine to add to an attached tracer."""

    def __init__(self, counters: dict) -> None:
        super().__init__()
        self.counters = counters


@dataclasses.dataclass
class BuiltPipeline:
    """A pipeline variant ready to run: a jitted device stage and a host
    post stage.  The runner owns the timing; this owns the compute.

    **Single-readback contract**: ``post`` and ``post_batch`` receive the
    device outputs *already fetched to host* — the runner (or the batched
    engine's drain) performs exactly ONE ``jax.device_get`` of the whole
    output tree per frame/tick, and the post stages operate on NumPy
    arrays.  (Historically each post re-read leaves one by one with
    ``np.asarray`` and paid double copies like ``np.asarray(boxes)[k]``.)

    ``post_batch`` is the vectorized form of ``post`` for the batched
    multi-camera engine (``repro.batched``): it takes the fetched batch
    outputs plus an active-slot mask and returns a per-slot
    ``FrameOutput`` list (``None`` for inactive slots).  Factories that
    cannot vectorize their post stage leave it ``None``; the engine falls
    back to slicing the batch through ``post`` per slot.  A ``post_batch``
    may return a ``SlotOutputs``, whose ``counters`` the engine adds to an
    attached tracer."""

    name: str
    scale: float
    infer: Callable[[jax.Array], Any]        # device stage (jitted)
    post: Callable[[Any], FrameOutput]       # host post-processing stage
    pad: bool = True                         # False: truly smaller λ input
    post_batch: Optional[Callable[[Any, np.ndarray], list]] = None


PIPELINES: Dict[str, Callable[..., BuiltPipeline]] = {}


def register_pipeline(name: str):
    def deco(factory: Callable[..., BuiltPipeline]):
        PIPELINES[name] = factory
        return factory
    return deco


def build_pipeline(name: str, scale: float = 1.0, key: Optional[jax.Array] = None,
                   pad: bool = True, **det_kw) -> BuiltPipeline:
    if name not in PIPELINES:
        raise KeyError(
            f"unknown pipeline {name!r}; registered: {sorted(PIPELINES)}"
        )
    if key is None:
        key = _default_key()
    return PIPELINES[name](scale=scale, key=key, pad=pad, **det_kw)


def _effective_scales(scale: float, pad: bool) -> tuple[float, float]:
    """The per-axis scale factors preprocess actually applies to the
    canonical (H, W) scene: integer rounding (and the unpadded 8-px grid
    snap) makes them differ from the nominal λ, and from each other."""
    if scale == 1.0:
        return 1.0, 1.0
    nh, nw = max(int(H * scale), 8), max(int(W * scale), 8)
    if not pad:
        nh, nw = max(nh // 8 * 8, 8), max(nw // 8 * 8, 8)
    return nh / H, nw / W


def _unscale(boxes: np.ndarray, scale: float, pad: bool) -> np.ndarray:
    """Detections on a λ-scaled input live in the shrunk frame; map them
    back (per axis, using the effective scales) so quality is comparable
    across rungs.  Broadcasts over any (..., 4) shape, so the batched
    post paths unscale a whole (B, k, 4) readback in one pass; the
    per-element division is identical either way, so masking kept boxes
    before or after unscaling yields the same floats."""
    sy, sx = _effective_scales(scale, pad)
    if sy == sx == 1.0 or not len(boxes):
        return boxes
    return boxes / np.array([sy, sx, sy, sx], boxes.dtype)


@register_pipeline("one_stage")
def _make_one_stage(scale: float = 1.0, key=None, pad: bool = True, **det_kw) -> BuiltPipeline:
    det = OneStageDetector(**det_kw)
    params = det.init(key if key is not None else _default_key())
    infer = jax.jit(lambda img: det.infer(params, img))

    def post(host) -> FrameOutput:
        boxes, _, keep = host                 # NumPy after the one readback
        b = _unscale(boxes[keep], scale, pad)
        return FrameOutput(boxes=b, num_objects=float(keep.sum()),
                           num_proposals=float(det.top_k))

    def post_batch(host, active: np.ndarray) -> list:
        boxes, _, kb = host                   # (B, k) keep mask, NumPy
        bb = _unscale(boxes, scale, pad)
        outs: list[Optional[FrameOutput]] = []
        for b in range(kb.shape[0]):
            if not active[b]:
                outs.append(None)
                continue
            outs.append(FrameOutput(
                boxes=bb[b][kb[b]], num_objects=float(kb[b].sum()),
                num_proposals=float(det.top_k)))
        return outs

    return BuiltPipeline("one_stage", scale, infer, post, pad=pad,
                         post_batch=post_batch)


@register_pipeline("early_exit")
def _make_early_exit(scale: float = 1.0, key=None, pad: bool = True, **det_kw) -> BuiltPipeline:
    """Truncated-backbone one-stage variant: 1 conv + coarse 16-px grid —
    the anytime ladder's cheapest detection rung."""
    det_kw.setdefault("depth", 1)
    det_kw.setdefault("cell", 16)
    built = _make_one_stage(scale=scale, key=key, pad=pad, **det_kw)
    return dataclasses.replace(built, name="early_exit")


@register_pipeline("two_stage")
def _make_two_stage(scale: float = 1.0, key=None, pad: bool = True, **det_kw) -> BuiltPipeline:
    det = TwoStageDetector(**det_kw)
    params = det.init(key if key is not None else _default_key())
    infer = jax.jit(lambda img: det.infer_device(params, img))

    def post(host) -> FrameOutput:
        feat, obj = host                      # NumPy after the one readback
        boxes, n_prop = det.post_host(params, feat, obj)
        # boxes are already NumPy (post_host is host-side): no re-wrap
        return FrameOutput(boxes=_unscale(boxes, scale, pad),
                           num_objects=float(len(boxes)),
                           num_proposals=float(n_prop))

    def post_batch(host, active: np.ndarray) -> SlotOutputs:
        feat, obj = host
        per_slot, sweeps = det.post_host_batch(params, feat, obj,
                                               active=active)
        outs = SlotOutputs(counters={"nms_candidates": 0,
                                     "nms_sweeps": sweeps})
        for slot in per_slot:
            if slot is None:
                outs.append(None)
                continue
            boxes, n_prop = slot
            outs.counters["nms_candidates"] += n_prop
            outs.append(FrameOutput(
                boxes=_unscale(boxes, scale, pad),
                num_objects=float(len(boxes)), num_proposals=float(n_prop)))
        return outs

    return BuiltPipeline("two_stage", scale, infer, post, pad=pad,
                         post_batch=post_batch)


_NO_BOXES = np.zeros((0, 4), np.float32)


@register_pipeline("lane")
def _make_lane(scale: float = 1.0, key=None, pad: bool = True, **det_kw) -> BuiltPipeline:
    det = LaneDetector(**det_kw)
    params = det.init(key if key is not None else _default_key())
    infer = jax.jit(lambda img: det.infer_device(params, img))

    def post(host) -> FrameOutput:
        fits, n_pix = det.cluster_host(host)  # NumPy after the one readback
        return FrameOutput(boxes=_NO_BOXES, num_objects=float(len(fits)),
                           num_proposals=float(n_pix))

    return BuiltPipeline("lane", scale, infer, post, pad=pad)


@register_pipeline("lane_static")
def _make_lane_static(scale: float = 1.0, key=None, pad: bool = True, **det_kw) -> BuiltPipeline:
    """The mitigation: identical lane pipeline with static-shape top-k
    fitting on device — post-processing variance collapses."""
    det = LaneDetector(**det_kw)
    params = det.init(key if key is not None else _default_key())

    def full(img):
        prob = det.infer_device(params, img)
        return det.static_fit_device(prob)

    infer = jax.jit(full)

    def post(host) -> FrameOutput:
        fits, n_pix = host              # fixed-size, NumPy after readback
        return FrameOutput(boxes=_NO_BOXES, num_objects=float(fits.shape[0]),
                           num_proposals=float(n_pix))

    return BuiltPipeline("lane_static", scale, infer, post, pad=pad)


def run_frame(built: BuiltPipeline, scene: Scene):
    """One stage-timed frame through a built pipeline — the Fig. 3 loop
    body every harness (legacy runners, calibration, the anytime loop)
    shares.  Returns ``(StageRecord, FrameOutput)``."""
    timer = StageTimer()
    with timer.stage("read"):
        raw = scene.image.copy()
    with timer.stage("pre_processing"):
        img = preprocess(raw, built.scale, built.pad)
    with timer.stage("inference"):
        dev = built.infer(jnp.asarray(img))
        jax.block_until_ready(dev)
    with timer.stage("post_processing"):
        # ONE readback of the whole output tree, then host-side post —
        # no per-leaf np.asarray walks, no double copies
        out = built.post(jax.device_get(dev))
    timer.note("num_objects", out.num_objects)
    timer.note("num_proposals", out.num_proposals)
    return timer.finish(), out


def _scenes(cfg: SceneConfig, n: int, images: Optional[Iterable[np.ndarray]] = None):
    if images is not None:
        for i, im in enumerate(images):
            sc = generate_scene(cfg, i)
            sc.image = im
            yield sc
    else:
        # start at 1: scene 0 is reserved for the synthetic warmup frame,
        # keeping the recorded scene sequence identical to the historical
        # contract (frames 1..n)
        for i in range(1, n + 1):
            yield generate_scene(cfg, i)


def run_pipeline(
    name: str,
    cfg: SceneConfig,
    n: int = 40,
    scale: float = 1.0,
    images: Optional[Iterable[np.ndarray]] = None,
    key: Optional[jax.Array] = None,
    collect: bool = False,
    built: Optional[BuiltPipeline] = None,
    pad: bool = True,
):
    """Drive any registered pipeline through the stage-timed frame loop.

    The warmup frame (XLA compilation outlier) is a *synthetic* scene and
    is never recorded — caller-supplied ``images`` are all real frames, so
    the recorded count always equals the supplied count.  (Historically the
    first user image was silently consumed as the unrecorded warmup frame:
    n images in, n−1 records out, frame 0 lost.)  With ``collect=True``
    returns ``(recorder, [(scene, FrameOutput), ...])`` so callers can
    score detections against ground truth; otherwise just the recorder
    (the legacy contract).  ``built`` reuses an already-jitted pipeline
    (the anytime runner keeps one per rung).
    """
    if built is None:
        built = build_pipeline(name, scale=scale, key=key, pad=pad)
    # warm up on a synthetic frame, never a caller-supplied one: the XLA
    # compile outlier is discarded without consuming user input.  The
    # warmup frame takes the first user image's SHAPE (jit traces per
    # shape — a canonical-shape warmup would leave oddly-sized caller
    # images to compile inside the recorded loop).
    warm_scene = generate_scene(cfg, 0)
    if images is not None:
        it = iter(images)
        first = next(it, None)
        if first is None:
            return (TimelineRecorder(), []) if collect else TimelineRecorder()
        images = itertools.chain([first], it)
        if first.shape != warm_scene.image.shape:
            warm_scene.image = np.zeros_like(first)
    run_frame(built, warm_scene)                 # warmup, never recorded
    rec = TimelineRecorder()
    outputs: list[tuple[Scene, FrameOutput]] = []
    for scene in _scenes(cfg, n, images):
        record, out = run_frame(built, scene)
        rec.add(record)
        if collect:
            outputs.append((scene, out))
    return (rec, outputs) if collect else rec


# ---------------------------------------------------------------------------
# legacy entry points — thin wrappers over the registry runner
# ---------------------------------------------------------------------------

def run_one_stage(
    cfg: SceneConfig, n: int = 40, scale: float = 1.0,
    images: Optional[Iterable[np.ndarray]] = None,
) -> TimelineRecorder:
    return run_pipeline("one_stage", cfg, n=n, scale=scale, images=images)


def run_two_stage(
    cfg: SceneConfig, n: int = 40, scale: float = 1.0,
    images: Optional[Iterable[np.ndarray]] = None,
) -> TimelineRecorder:
    return run_pipeline("two_stage", cfg, n=n, scale=scale, images=images)


def run_lane(
    cfg: SceneConfig, n: int = 40,
    images: Optional[Iterable[np.ndarray]] = None,
) -> TimelineRecorder:
    return run_pipeline("lane", cfg, n=n, images=images)


def run_lane_static(
    cfg: SceneConfig, n: int = 40,
    images: Optional[Iterable[np.ndarray]] = None,
) -> TimelineRecorder:
    return run_pipeline("lane_static", cfg, n=n, images=images)
