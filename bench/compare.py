"""The numbers that decide ``correct``: served frames against the reference.

Each served frame is paired with the reference's detection of the same
scene on the same rung.  Greedy NMS keeps or drops a box at an IoU
threshold, so two sound runs whose boxes differ by rounding can keep
different boxes.  The box numbers therefore measure each served box
against the reference's whole candidate list (every box its NMS chose
from), and excuse a reference box that the served run dropped where a
box the served run kept overlaps it by nearly the threshold.

- ``prop_off_pct``: two-stage frames whose proposal count differs, in %.
- ``kept_off_pct``: frames whose kept-box count differs, in %.
- ``box_dev_mean_px`` / ``box_dev_max_px``: mean and largest gap, on the
  worst coordinate, between each served box and the nearest reference
  candidate, and between each reference kept box (not excused) and the
  nearest served box.  A box with nothing on the other side of its frame
  counts ``missing_px``, the frame's width, which ``compare`` is handed.
"""
from __future__ import annotations

import numpy as np

from .detection import Detection, iou_one

# A dropped reference box is excused only where no served box lies within
# this many pixels of it on every coordinate (so it has no counterpart)
# and a kept served box overlaps it by at least ``EXCUSE_IOU``: within 0.1
# of the 0.5 NMS threshold, about what rounding of a few tenths of a pixel
# moves an IoU between the small boxes of these frames.
PAIR_PX = 2.0
EXCUSE_IOU = 0.4


def _pct(num: float, den: float):
    return 100.0 * num / den if den else None


def _nearest(boxes: np.ndarray, others: np.ndarray,
             missing_px: float) -> np.ndarray:
    """Per box, the worst-coordinate gap to the nearest of ``others``, or
    ``missing_px`` where there are none."""
    if not len(others):
        return np.full(len(boxes), missing_px)
    return np.abs(boxes[:, None] - others[None]).max(-1).min(1)


def compare(frames: list, missing_px: float) -> dict:
    """``frames``: ``(kind, served_boxes, served_n_proposals, Detection)``
    tuples; ``missing_px``: the frame's width in pixels.  Returns every
    number named in the module docstring (None where nothing was there to
    count)."""
    prop = [0, 0]
    kept_off = 0
    gaps: list[np.ndarray] = []
    for kind, boxes, n_prop, ref in frames:
        ref: Detection
        boxes = np.asarray(boxes, np.float32).reshape(-1, 4)
        if kind == "two_stage":
            prop[0] += n_prop != ref.n_proposals
            prop[1] += 1
        kept_off += len(boxes) != len(ref.boxes)
        gaps.append(_nearest(boxes, ref.candidates, missing_px))
        back = _nearest(ref.boxes, boxes, missing_px)
        excused = np.array([
            g > PAIR_PX and len(boxes) > 0
            and iou_one(boxes, box).max() >= EXCUSE_IOU
            for g, box in zip(back, ref.boxes)], bool)
        gaps.append(back[~excused] if len(back) else back)
    gap = np.concatenate(gaps) if gaps else np.zeros(0)
    return {
        "prop_off_pct": _pct(prop[0], prop[1]),
        "kept_off_pct": _pct(kept_off, len(frames)),
        "box_dev_mean_px": float(gap.mean()) if gap.size else None,
        "box_dev_max_px": float(gap.max()) if gap.size else None,
    }
