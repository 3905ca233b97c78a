"""What every plain reference under ``bench/references/`` shares: the
record of one frame's detections, IoU, greedy NMS, the key the weights
are drawn from, and the number of frames a reference computes at once.

Like the references, it imports nothing of the program.
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np

BLOCK = 64                    # frames per jitted reference call


@dataclasses.dataclass(frozen=True)
class Detection:
    boxes: np.ndarray         # (k, 4) kept boxes, original frame coordinates
    candidates: np.ndarray    # (n, 4) every box NMS chose from, same frame
    n_proposals: float        # what the served FrameOutput reports


# The program's rung steps close over their weights, which XLA compiles in
# as constants: weights that changed with the seed would make every run
# compile every step.  So the weights come from one fixed key, the one the
# program's pipelines default to (the detector as it ships), and the seed
# drives every input.
WEIGHTS_WORD = 7


def weights_key() -> jax.Array:
    """The PRNG key both the program and the reference build weights from."""
    return jax.random.PRNGKey(WEIGHTS_WORD)


def iou_one(boxes: np.ndarray, box: np.ndarray) -> np.ndarray:
    """IoU of each of (n, 4) ``boxes`` with one ``box`` (y0, x0, y1, x1)."""
    inter = (np.clip(np.minimum(boxes[:, 2], box[2])
                     - np.maximum(boxes[:, 0], box[0]), 0, None)
             * np.clip(np.minimum(boxes[:, 3], box[3])
                       - np.maximum(boxes[:, 1], box[1]), 0, None))
    area = (np.maximum(boxes[:, 2] - boxes[:, 0], 0)
            * np.maximum(boxes[:, 3] - boxes[:, 1], 0))
    own = max(box[2] - box[0], 0) * max(box[3] - box[1], 0)
    return inter / np.maximum(area + own - inter, 1e-9)


def greedy_nms(boxes: np.ndarray, order: np.ndarray, iou_thr: float) -> np.ndarray:
    """Visit ``boxes`` in ``order``; keep a box unless a kept one overlaps it
    by IoU above ``iou_thr``.  Returns the kept indices in visiting order."""
    keep: list[int] = []
    for i in order:
        if not keep or not np.any(iou_one(boxes[keep], boxes[i]) > iou_thr):
            keep.append(int(i))
    return np.asarray(keep, np.int64)
