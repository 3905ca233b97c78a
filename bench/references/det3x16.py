"""Plain reference of the ``det3x16`` detector, written from its equations.

It imports nothing of the program and takes nothing the program made: the
weights are drawn again from the benchmark's key with the same scheme
(normal, std = scale / sqrt(fan-in), one ``fold_in`` of the CRC-32 of each
parameter's name), and every size comes from the configuration file.

Each rung is computed densely over its whole grid in ``jax.numpy`` at
``Precision.HIGHEST`` (float32) or, for the control, wholly in bfloat16.
Proposal selection, top-k and greedy NMS run in NumPy on the host.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from bench.detection import BLOCK, Detection, greedy_nms

HIGHEST = jax.lax.Precision.HIGHEST


def _fold(key, name: str):
    return jax.random.fold_in(key, zlib.crc32(name.encode()) % (2 ** 31))


def _leaf(key, shape: tuple, scale: float):
    fan_in = math.prod(shape[:-1]) if len(shape) > 1 else shape[0]
    return jax.random.normal(key, shape, jnp.float32) * (scale / math.sqrt(fan_in))


def weights(key, rung: dict) -> dict:
    """The rung's parameters.  Every detector declares three backbone convs
    (an early exit uses the first ``backbone_depth``)."""
    c, k = rung["channels"], rung["kernel"]
    kb = _fold(key, "backbone")
    w = {f"conv{i + 1}": _leaf(_fold(kb, f"conv{i + 1}"),
                               (k, k, 3 if i == 0 else c, c), 1.4)
         for i in range(3)}
    if rung["kind"] == "two_stage":
        w["refine"] = _leaf(_fold(key, "refine"), (c, rung["refine_dim"]), 1.0)
    else:
        w["head"] = _leaf(_fold(key, "head"), (c, rung["head_dim"]), 1.0)
    return w


def scaled_shape(frame, scale: float) -> tuple[int, int]:
    """Input rows and columns after the rung's nearest-neighbour resize,
    snapped down to the 8-pixel grid."""
    h, w = frame[0], frame[1]
    if scale == 1.0:
        return h, w
    nh, nw = max(int(h * scale), 8), max(int(w * scale), 8)
    return max(nh // 8 * 8, 8), max(nw // 8 * 8, 8)


def _pool(x, size: int):
    """(B, h, w) -> (B, h // size, w // size) block means, border cropped."""
    b, h, w = x.shape
    x = x[:, :h // size * size, :w // size * size]
    return x.reshape(b, h // size, size, w // size, size).mean((2, 4))


def _dense(w: dict, raw, rung: dict, frame, dtype):
    """Every grid cell's box and score for a (B, H, W, 3) block of frames."""
    h, wd = frame[0], frame[1]
    nh, nw = scaled_shape(frame, rung["scale"])
    x = raw.astype(dtype)
    if (nh, nw) != (h, wd):
        ys = (np.arange(nh) * (h / nh)).astype(np.int64)
        xs = (np.arange(nw) * (wd / nw)).astype(np.int64)
        x = x[:, ys][:, :, xs]
    x = x[..., ::-1]
    x = (x - x.mean((1, 2, 3), keepdims=True)) / (x.std((1, 2, 3), keepdims=True) + 1e-6)
    unit = x - x.min((1, 2, 3), keepdims=True)
    unit = unit / jnp.maximum(unit.max((1, 2, 3), keepdims=True), 1e-6)
    cell = rung["cell"]
    score = jax.nn.sigmoid(rung["obj_gain"] * (_pool(unit.mean(-1), cell)
                                               - rung["obj_floor"]))
    f = x
    for i in range(rung["backbone_depth"]):
        f = jax.nn.relu(jax.lax.conv_general_dilated(
            f, w[f"conv{i + 1}"].astype(dtype), (rung["stride"],) * 2, "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST))
    rem = 8 // rung["stride"] ** rung["backbone_depth"]
    if rem > 1:                    # an early exit pools to the stride-8 grid
        b, fh, fw, c = f.shape
        f = f[:, :fh // rem * rem, :fw // rem * rem]
        f = f.reshape(b, fh // rem, rem, fw // rem, rem, c).mean((2, 4))
    gh, gw = score.shape[1:]
    gy, gx = (jnp.asarray(a, dtype) for a in
              np.meshgrid(np.arange(gh), np.arange(gw), indexing="ij"))
    if rung["kind"] == "two_stage":
        r = w["refine"].astype(dtype)
        for _ in range(rung["refine_iters"]):
            f = jnp.tanh(f + 0.1 * jnp.matmul(f, r[:, :1], precision=HIGHEST)
                         * r[:, 0])
        p = jnp.matmul(f, r, precision=HIGHEST)           # (B, gh, gw, 5)
        cy = (gy + 0.5) * 8.0 + p[..., 1]
        cx = (gx + 0.5) * 8.0 + p[..., 2]
        bh = 16.0 * jnp.exp(jnp.clip(p[..., 3], -1, 1))
        bw = 20.0 * jnp.exp(jnp.clip(p[..., 4], -1, 1))
        box_score = jax.nn.sigmoid(p[..., 0])
    else:
        p = jnp.einsum("bhwc,co->bhwo", f, w["head"].astype(dtype),
                       precision=HIGHEST)
        k = cell // 8
        b = p.shape[0]
        p = p[:, :gh * k, :gw * k].reshape(b, gh, k, gw, k, 4).mean((2, 4))
        cy = (gy + 0.5) * cell + p[..., 0]
        cx = (gx + 0.5) * cell + p[..., 1]
        bh = 1.8 * cell * jnp.exp(jnp.clip(p[..., 2], -1, 1))
        bw = 2.4 * cell * jnp.exp(jnp.clip(p[..., 3], -1, 1))
        box_score = score
    boxes = jnp.stack([cy - bh / 2, cx - bw / 2, cy + bh / 2, cx + bw / 2], -1)
    return boxes, box_score, score


class Reference:
    """The reference detector for every rung of a configuration, at one
    ``dtype`` (float32 at highest precision, or bfloat16 for the control)."""

    def __init__(self, config: dict, key, dtype=jnp.float32) -> None:
        self.config = config
        self.frame = tuple(config["frame"])
        self.weights = {name: weights(key, rung)
                        for name, rung in config["rungs"].items()}
        self._fns = {
            name: jax.jit(lambda w, raw, _r=rung: _dense(
                w, raw, _r, self.frame, dtype))
            for name, rung in config["rungs"].items()}

    def detect(self, rung_name: str, images: np.ndarray) -> list[Detection]:
        """Detections for an (N, H, W, 3) stack of frames on one rung."""
        rung = self.config["rungs"][rung_name]
        out = []
        for s in range(0, len(images), BLOCK):
            block = np.zeros((BLOCK, *self.frame), np.float32)
            n = min(BLOCK, len(images) - s)
            block[:n] = images[s:s + n]
            boxes, box_score, score = (
                np.asarray(a, np.float32) for a in jax.device_get(
                    self._fns[rung_name](self.weights[rung_name], block)))
            out.extend(self._select(rung, boxes[i], box_score[i], score[i])
                       for i in range(n))
        return out

    def _select(self, rung: dict, boxes, box_score, score) -> Detection:
        nh, nw = scaled_shape(self.frame, rung["scale"])
        unscale = np.array([nh, nw, nh, nw], np.float32) / np.array(
            [self.frame[0], self.frame[1]] * 2, np.float32)
        boxes = boxes.reshape(-1, 4)
        if rung["kind"] == "two_stage":
            idx = np.flatnonzero(score.reshape(-1) > rung["proposal_thr"])
            cand, s = boxes[idx], box_score.reshape(-1)[idx]
            keep = greedy_nms(cand, np.argsort(-s), rung["nms_iou"])
            n_prop = float(len(idx))
        else:
            s = box_score.reshape(-1)
            k = min(rung["top_k"], len(s))
            top = np.argsort(-s, kind="stable")[:k]
            cand, s = boxes[top], s[top]
            keep = greedy_nms(cand, np.arange(k), rung["nms_iou"])
            keep = keep[s[keep] > rung["score_thr"]]
            n_prop = float(rung["top_k"])
        cand = cand / unscale
        return Detection(boxes=cand[keep].reshape(-1, 4),
                         candidates=cand.reshape(-1, 4), n_proposals=n_prop)
