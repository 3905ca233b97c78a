"""Detection pipelines: one-stage (static work) vs two-stage
(proposal-driven host work) on a shared conv backbone — the paper's model
variability axis (Insight 3), implemented so the *mechanism* is explicit:

* one-stage: grid head → fixed-size tensor → **static-shape** top-k + NMS
  entirely on device.  Inference-dominated; post-processing time is
  data-independent (the TPU-native fix).
* two-stage: proposal head → host extracts a *variable-length* proposal
  list → per-proposal second stage + O(n²) host NMS.  Post-processing time
  scales with the proposal count — the paper's LaneNet/Faster-R-CNN
  pathology, faithfully reproduced.  The batched engine's pass
  (``post_host_batch``) runs one greedy NMS over every frame of a tick
  (``batched_nms``), keeping per frame exactly what ``dynamic_nms`` keeps;
  its work is still O(n²) in the proposal count.
* early exit: the one-stage detector truncated after ``depth`` backbone
  convs (remaining stride recovered by average pooling) with a coarser
  objectness grid — the anytime ladder's cheapest rung: less compute,
  coarser localization.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.params import ParamSpec, axes_tree, init_params

__all__ = [
    "backbone_specs",
    "backbone_apply",
    "OneStageDetector",
    "TwoStageDetector",
    "batched_nms",
    "dynamic_nms",
    "static_nms",
]

GRID_H, GRID_W = 12, 40      # 96/8, 320/8


def backbone_specs(channels: int = 16) -> dict:
    c = channels
    return {
        "conv1": ParamSpec((3, 3, 3, c), (None, None, None, None), scale=1.4),
        "conv2": ParamSpec((3, 3, c, c), (None, None, None, None), scale=1.4),
        "conv3": ParamSpec((3, 3, c, c), (None, None, None, None), scale=1.4),
    }


def _conv(x, w, stride):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def backbone_apply(params, image: jax.Array, depth: int = 3) -> jax.Array:
    """(B, 96, 320, 3) → (B, 12, 40, C) feature map.

    ``depth`` backbone convs run (stride 2 each); an early exit (depth < 3)
    recovers the remaining stride by average pooling, so the head always
    sees the canonical (12, 40) grid while skipping most of the FLOPs.
    """
    x = image
    for name in ("conv1", "conv2", "conv3")[:depth]:
        x = _conv(x, params[name], 2)
        x = jax.nn.relu(x)
    rem = 2 ** (3 - depth)
    if rem > 1:
        b, h, w, c = x.shape
        x = x[:, : h // rem * rem, : w // rem * rem]   # crop to the tile grid
        x = x.reshape(b, h // rem, rem, w // rem, rem, c).mean((2, 4))
    return x


def _pool(img: jax.Array, size: int, mode: str = "avg") -> jax.Array:
    """(H, W, 3) → (H//size, W//size) pooled luma (border cropped to the
    tile grid, so any input shape is valid)."""
    luma = img.mean(-1)
    h, w = luma.shape
    luma = luma[: h // size * size, : w // size * size]
    tiles = luma.reshape(h // size, size, w // size, size)
    if mode == "avg":
        return tiles.mean((1, 3))
    return tiles.max((1, 3))


def _pool8(img: jax.Array, mode: str = "avg") -> jax.Array:
    return _pool(img, 8, mode)


# --------------------------------------------------------------------------
# NMS variants
# --------------------------------------------------------------------------

def _iou_matrix(boxes: np.ndarray) -> np.ndarray:
    y0, x0, y1, x1 = boxes[:, 0], boxes[:, 1], boxes[:, 2], boxes[:, 3]
    area = np.maximum(y1 - y0, 0) * np.maximum(x1 - x0, 0)
    iy0 = np.maximum(y0[:, None], y0[None, :])
    ix0 = np.maximum(x0[:, None], x0[None, :])
    iy1 = np.minimum(y1[:, None], y1[None, :])
    ix1 = np.minimum(x1[:, None], x1[None, :])
    inter = np.maximum(iy1 - iy0, 0) * np.maximum(ix1 - ix0, 0)
    union = area[:, None] + area[None, :] - inter
    return inter / np.maximum(union, 1e-9)


def dynamic_nms(boxes: np.ndarray, scores: np.ndarray, iou_thr: float = 0.5) -> np.ndarray:
    """Host-side greedy NMS over a VARIABLE-length candidate list — O(n²)
    in the data-dependent count (the paper's variance source)."""
    order = np.argsort(-scores)
    keep = []
    suppressed = np.zeros(len(boxes), bool)
    iou = _iou_matrix(boxes)
    for i in order:
        if suppressed[i]:
            continue
        keep.append(i)
        suppressed |= iou[i] > iou_thr
        suppressed[i] = True
    return np.asarray(keep, np.int64)


def _x_overlap_pairs(x0: np.ndarray, x1: np.ndarray,
                     frame: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Every same-frame pair ``(p, q)`` whose x extents may overlap: a
    superset of the pairs with ``min(x1) - max(x0) > 0``.

    Sweep and prune: candidates are sorted by (frame, ``x0``), and each
    ``p`` pairs with the later ``q`` of its frame whose ``x0`` is at most
    ``x1[p]``.  One stable sort of the ``x0`` and ``x1`` values together
    gives those counts (an ``x0`` stays before an equal ``x1``); a NaN
    sorts last and keeps its pairs.
    """
    n = len(x0)
    o = np.lexsort((np.concatenate([x0, x1]), np.concatenate([frame, frame])))
    x0_first = o < n
    below = np.cumsum(x0_first) - x0_first     # x0s sorted before each entry
    by_x = o[x0_first]                          # candidates in (frame, x0) order
    x1_first = ~x0_first
    end = np.empty(n, np.intp)
    end[o[x1_first] - n] = below[x1_first]
    n_after = np.maximum(end[by_x] - np.arange(1, n + 1), 0)
    first = np.repeat(np.arange(n), n_after)
    later = np.arange(len(first)) + np.repeat(
        np.arange(1, n + 1) - np.cumsum(n_after) + n_after, n_after)
    return by_x[first], by_x[later]


def batched_nms(boxes: np.ndarray, scores: np.ndarray, counts: np.ndarray,
                iou_thr: float = 0.5) -> tuple[np.ndarray, np.ndarray, int]:
    """``dynamic_nms`` over several frames' candidates at once.

    ``boxes`` (N, 4) and ``scores`` (N,) hold the frames' candidates one
    frame after another, ``counts`` (B,) how many each frame has.  Returns
    ``(keep, kept, sweeps)``: ``keep`` indexes ``boxes`` with every
    frame's kept candidates, frame after frame, ``kept`` (B,) how many
    each frame keeps, and ``sweeps`` the fixed-point passes taken (0
    without candidates).  Frame ``b``'s run of ``keep``, less its first
    index ``s``, is exactly ``dynamic_nms(boxes[s:e], scores[s:e],
    iou_thr)``: the same indices in the same order.

    Each frame is ranked by ``dynamic_nms``'s own ``argsort``, so ties
    break alike.  IoU > ``iou_thr`` >= 0 needs overlapping x extents, so
    only the pairs a sweep over ``x0`` finds get an IoU, computed with
    ``_iou_matrix``'s float expressions, so the values are bit-equal.
    Greedy keeps rank ``j`` iff no kept earlier rank ``i`` of its frame
    has IoU > ``iou_thr`` with it; that is the unique fixed point of
    ``keep = ~any(kept suppressor)``, which the sweeps reach from
    all-kept after at most the longest suppression chain + 1 passes.
    The work is still O(n²) in a frame's candidate count at worst.
    """
    if not iou_thr >= 0:
        raise ValueError(f"iou_thr must be >= 0 (got {iou_thr})")
    counts = np.asarray(counts, np.intp)
    ends = np.cumsum(counts)
    starts = ends - counts
    n = int(ends[-1]) if len(ends) else 0
    if n == 0:
        return np.zeros(0, np.intp), np.zeros(len(counts), np.intp), 0
    frame = np.repeat(np.arange(len(counts)), counts)
    order = np.concatenate(     # rank order, frame by frame
        [np.argsort(-scores[s:e]) for s, e in zip(starts, ends)]) + starts[frame]
    # columns y0, x0, y1, x1 of the boxes in rank order
    cols = np.ascontiguousarray(boxes.take(order, axis=0).T)
    area = (np.maximum(cols[2] - cols[0], 0)
            * np.maximum(cols[3] - cols[1], 0))
    p, q = _x_overlap_pairs(cols[1], cols[3], frame)
    i, j = np.minimum(p, q), np.maximum(p, q)   # earlier rank suppresses
    ci, cj = cols.take(i, axis=1), cols.take(j, axis=1)
    side = np.maximum(np.minimum(ci[2:], cj[2:])
                      - np.maximum(ci[:2], cj[:2]), 0)
    inter = side[0] * side[1]
    union = area.take(i) + area.take(j) - inter
    over = inter / np.maximum(union, 1e-9) > iou_thr
    sup_by, sup = i[over], j[over]
    keep = np.ones(n, bool)
    sweeps = 0
    while True:
        sweeps += 1
        nxt = np.ones(n, bool)
        nxt[sup[keep[sup_by]]] = False
        if not (nxt ^ keep).any():
            break
        keep = nxt
    return order[keep], np.bincount(frame[keep], minlength=len(counts)), sweeps


def static_nms(boxes: jax.Array, scores: jax.Array, k: int, iou_thr: float = 0.5):
    """Fixed-shape device NMS: top-k candidates, fixed-iteration greedy
    suppression via lax.fori_loop — identical result on the top-k set,
    ZERO data-dependent time (the framework's mitigation)."""
    n = boxes.shape[0]
    k = min(k, n)
    top_scores, idx = jax.lax.top_k(scores, k)
    top_boxes = boxes[idx]

    y0, x0, y1, x1 = (top_boxes[:, i] for i in range(4))
    area = jnp.maximum(y1 - y0, 0) * jnp.maximum(x1 - x0, 0)
    iy0 = jnp.maximum(y0[:, None], y0[None, :])
    ix0 = jnp.maximum(x0[:, None], x0[None, :])
    iy1 = jnp.minimum(y1[:, None], y1[None, :])
    ix1 = jnp.minimum(x1[:, None], x1[None, :])
    inter = jnp.maximum(iy1 - iy0, 0) * jnp.maximum(ix1 - ix0, 0)
    iou = inter / jnp.maximum(area[:, None] + area[None, :] - inter, 1e-9)

    def body(i, keep):
        alive = keep[i]
        # suppress everything with IoU > thr to box i (only if i is alive)
        sup = (iou[i] > iou_thr) & (jnp.arange(k) > i)
        return jnp.where(alive, keep & ~sup, keep)

    keep0 = top_scores > -jnp.inf
    keep = jax.lax.fori_loop(0, k, body, keep0)
    return top_boxes, top_scores, keep, idx


# --------------------------------------------------------------------------
# detectors
# --------------------------------------------------------------------------

@dataclasses.dataclass
class OneStageDetector:
    """YOLO-ish: grid head predicting (dy, dx, dh, dw) box refinements per
    cell.  Post-processing is static_nms on the fixed grid — constant time.

    Objectness is the same matched filter the two-stage RPN uses (pooled
    brightness above the scene floor) so detections line up with the
    synthetic ground truth and the anytime ladder can score quality
    against ``Scene.boxes``; the conv head supplies only box refinements.

    ``depth`` < 3 truncates the backbone (early exit) and ``cell`` > 8
    coarsens the objectness grid — cheaper inference, coarser boxes.
    """

    channels: int = 16
    top_k: int = 32
    score_thr: float = 0.5
    depth: int = 3               # backbone convs used (< 3 = early exit)
    cell: int = 8                # objectness grid granularity in pixels
    obj_thr: float = -1.0        # matched-filter floor; <0 = derive from cell

    def __post_init__(self) -> None:
        # cell//8 must divide the feature grid; powers of two always do,
        # e.g. cell=24 (factor 3) would not divide the 40-wide grid
        if self.cell not in (8, 16, 32):
            raise ValueError(f"cell must be 8, 16, or 32 (got {self.cell})")
        if not 1 <= self.depth <= 3:
            raise ValueError(f"depth must be in [1, 3] (got {self.depth})")
        if self.obj_thr < 0:
            # a coarser cell dilutes an object's brightness with background:
            # lower the floor so part-covered cells still fire
            self.obj_thr = 0.55 - 0.13 * math.log2(self.cell / 8)

    def specs(self) -> dict:
        c = self.channels
        return {
            "backbone": backbone_specs(c),
            "head": ParamSpec((c, 4), (None, None), scale=1.0),
        }

    def init(self, key):
        return init_params(self.specs(), key, jnp.float32)

    def infer(self, params, image: jax.Array):
        """Device path: features → grid preds → static top-k+NMS. Returns
        fixed-shape (boxes (k,4), scores (k,), keep (k,))."""
        feat = backbone_apply(params["backbone"], image[None], depth=self.depth)[0]
        preds = jnp.einsum("hwc,co->hwo", feat, params["head"])
        # de-normalize: pipelines standardize the image; recover 0-1 luma
        img = image - image.min()
        img = img / jnp.maximum(img.max(), 1e-6)
        obj2d = jax.nn.sigmoid(12.0 * (_pool(img, self.cell, "avg") - self.obj_thr))
        gh, gw = obj2d.shape
        f = self.cell // 8
        if f > 1:       # coarsen the head to the objectness grid
            preds = preds[: gh * f, : gw * f]
            preds = preds.reshape(gh, f, gw, f, 4).mean((1, 3))
        else:
            preds = preds[:gh, :gw]
        obj = obj2d.reshape(-1)
        gy, gx = jnp.meshgrid(jnp.arange(gh), jnp.arange(gw), indexing="ij")
        cell = float(self.cell)
        cy = (gy.reshape(-1) + 0.5) * cell + preds[..., 0].reshape(-1)
        cx = (gx.reshape(-1) + 0.5) * cell + preds[..., 1].reshape(-1)
        bh = 1.8 * cell * jnp.exp(jnp.clip(preds[..., 2].reshape(-1), -1, 1))
        bw = 2.4 * cell * jnp.exp(jnp.clip(preds[..., 3].reshape(-1), -1, 1))
        boxes = jnp.stack([cy - bh / 2, cx - bw / 2, cy + bh / 2, cx + bw / 2], -1)
        tb, ts, keep, _ = static_nms(boxes, obj, self.top_k)
        keep = keep & (ts > self.score_thr)
        return tb, ts, keep


@dataclasses.dataclass
class TwoStageDetector:
    """Faster-R-CNN-ish: stage 1 proposes variable-count regions (host
    extraction), stage 2 refines each on host — O(n) + O(n²) NMS in the
    proposal count."""

    channels: int = 16
    proposal_thr: float = 0.55
    refine_flops: int = 24           # per-proposal host work (feature dot)
    # host copy of params["refine"], keyed on the device buffer's identity:
    # without it every post_host call pays a device→host readback of the
    # refinement head (a per-frame TV001 hazard tvlint flags in loops)
    _refine_src: object = dataclasses.field(
        default=None, repr=False, compare=False)
    _refine_host: object = dataclasses.field(
        default=None, repr=False, compare=False)

    def _refine(self, params) -> np.ndarray:
        dev = params["refine"]
        if self._refine_src is not dev:
            self._refine_src = dev
            self._refine_host = np.asarray(dev)
        return self._refine_host

    def specs(self) -> dict:
        c = self.channels
        return {
            "backbone": backbone_specs(c),
            "rpn": ParamSpec((c, 1), (None, None), scale=1.0),
            "refine": ParamSpec((c, 5), (None, None), scale=1.0),
        }

    def init(self, key):
        return init_params(self.specs(), key, jnp.float32)

    def infer_device(self, params, image: jax.Array):
        """Stage 1 on device: objectness map + features (fixed shape).

        Objectness is a matched filter for object-like blobs — pooled
        brightness above the scene floor (objects are bright filled
        rectangles; lanes are thin and dilute under 8×8 pooling; rain fog
        pulls cells toward gray and below threshold).  The conv features
        feed the stage-2 refinement.
        """
        # de-normalize: pipelines standardize the image; recover 0-1 luma
        img = image - image.min()
        img = img / jnp.maximum(img.max(), 1e-6)
        obj = jax.nn.sigmoid(12.0 * (_pool8(img, "avg") - 0.55))
        feat = backbone_apply(params["backbone"], image[None])[0]
        return feat, obj

    def post_host(self, params, feat: np.ndarray, obj: np.ndarray):
        """Host post-processing whose cost scales with the proposal count
        (the paper's Fig. 5/11 mechanism). Returns (boxes, n_proposals)."""
        ys, xs = np.nonzero(obj > self.proposal_thr)       # variable length!
        n = len(ys)
        refine = self._refine(params)
        boxes = np.zeros((n, 4), np.float32)
        scores = np.zeros((n,), np.float32)
        for i in range(n):                                  # per-proposal work
            f = feat[ys[i], xs[i]]
            # RoI refinement: a few feature-space iterations per proposal
            for _ in range(8):
                f = np.tanh(f + 0.1 * (f @ refine[:, :1]) * refine[:, 0])
            out = f @ refine                                # (5,)
            cy = (ys[i] + 0.5) * 8.0 + out[1]
            cx = (xs[i] + 0.5) * 8.0 + out[2]
            # box prior matched to the scene generator's object statistics
            # (the refinement head supplies residuals around it)
            bh = 16.0 * np.exp(np.clip(out[3], -1, 1))
            bw = 20.0 * np.exp(np.clip(out[4], -1, 1))
            boxes[i] = (cy - bh / 2, cx - bw / 2, cy + bh / 2, cx + bw / 2)
            scores[i] = 1.0 / (1.0 + np.exp(-out[0]))
        if n:
            keep = dynamic_nms(boxes, scores)
            boxes = boxes[keep]
        return boxes, n

    def post_host_batch(
        self,
        params,
        feat: np.ndarray,
        obj: np.ndarray,
        active: np.ndarray | None = None,
    ):
        """``post_host`` over a (B, ...) batch in one vectorized pass.

        Proposals from every active slot are gathered into a single
        (N, C) matrix, the per-proposal RoI refinement runs as N-row
        matrix ops instead of a Python loop, and one ``batched_nms`` runs
        the greedy NMS of every active slot at once; its work is still
        O(n²) in each slot's proposal count.  Kept boxes are exactly
        what ``dynamic_nms`` keeps per slot on the same refined boxes.
        The refinement's N-row matrix products may round differently
        from the serial path's per-proposal ones (BLAS sums in another
        order), so boxes match ``post_host`` per slot to float32 rounding.

        Returns ``(per_slot, sweeps)``: a list of length B holding
        ``(boxes, n_proposals)`` per active slot and ``None`` for inactive
        ones, and the NMS's fixed-point passes.
        """
        B = obj.shape[0]
        if active is None:
            active = np.ones(B, bool)
        masked = np.where(active[:, None, None], obj, -np.inf)
        bidx, ys, xs = np.nonzero(masked > self.proposal_thr)
        refine = self._refine(params)
        f = feat[bidx, ys, xs]                          # (N, C)
        for _ in range(8):
            f = np.tanh(f + 0.1 * (f @ refine[:, :1]) * refine[:, 0])
        out = f @ refine                                # (N, 5)
        cy = (ys + 0.5) * 8.0 + out[:, 1]
        cx = (xs + 0.5) * 8.0 + out[:, 2]
        bh = 16.0 * np.exp(np.clip(out[:, 3], -1, 1))
        bw = 20.0 * np.exp(np.clip(out[:, 4], -1, 1))
        boxes = np.stack(
            [cy - bh / 2, cx - bw / 2, cy + bh / 2, cx + bw / 2], -1
        ).astype(np.float32)
        scores = (1.0 / (1.0 + np.exp(-out[:, 0]))).astype(np.float32)
        counts = np.bincount(bidx, minlength=B)     # bidx ascends
        keep, kept, sweeps = batched_nms(boxes, scores, counts)
        boxes = boxes.take(keep, axis=0)
        ends = np.cumsum(kept)
        return [(boxes[e - k:e], int(n)) if on else None
                for on, n, k, e in zip(active, counts, kept, ends)], sweeps
