"""The benchmark's copy of the program's detection-quality score
(``repro.anytime.ladder.frame_quality``): 0.5 * recall + 0.5 * mean matched
IoU of a frame's detections against its ground-truth boxes."""
from __future__ import annotations

from typing import Optional

import numpy as np


def iou_matrix(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Pairwise IoU of (n, 4) and (m, 4) boxes given as (y0, x0, y1, x1)."""
    if not len(a) or not len(b):
        return np.zeros((len(a), len(b)))
    area_a = np.maximum(a[:, 2] - a[:, 0], 0) * np.maximum(a[:, 3] - a[:, 1], 0)
    area_b = np.maximum(b[:, 2] - b[:, 0], 0) * np.maximum(b[:, 3] - b[:, 1], 0)
    iy0 = np.maximum(a[:, 0][:, None], b[:, 0][None])
    ix0 = np.maximum(a[:, 1][:, None], b[:, 1][None])
    iy1 = np.minimum(a[:, 2][:, None], b[:, 2][None])
    ix1 = np.minimum(a[:, 3][:, None], b[:, 3][None])
    inter = np.maximum(iy1 - iy0, 0) * np.maximum(ix1 - ix0, 0)
    return inter / np.maximum(area_a[:, None] + area_b[None] - inter, 1e-9)


def frame_quality(gt: np.ndarray, boxes: np.ndarray,
                  iou_thr: float = 0.1) -> Optional[float]:
    """Quality in [0, 1] of ``boxes`` against ground truth ``gt``; None when
    the frame has no ground-truth object."""
    if not len(gt):
        return None
    best = iou_matrix(gt, boxes)
    best = best.max(axis=1) if best.size else np.zeros(len(gt))
    matched = best >= iou_thr
    recall = float(matched.mean())
    miou = float(best[matched].mean()) if matched.any() else 0.0
    return 0.5 * recall + 0.5 * miou
