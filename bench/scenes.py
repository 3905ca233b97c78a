"""The benchmark's own scene generator and its per-stream scene pools.

A copy of the program's synthetic KITTI-like generator
(``repro.perception.data.generate_scene``): the same three scenarios, object
and lane drawing, and rain rendering.  Two changes:

- a pool's object and lane counts are not drawn per scene but taken from
  fixed Poisson quantiles, shuffled by the seed, so every seed gives a
  pool the same set of sizes (and so the same host work) in another order;
- frames take the configuration's ``frame`` (rows, columns, channels).
  The program draws at ``H`` x ``W``; at another frame, object sizes,
  placement bands, lanes and rain are scaled by the frame's rows and
  columns over those, and at ``H`` x ``W`` every draw is the program's.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

H, W = 96, 320                # the generator's base scale

# scenario -> (mean objects, mean lanes): city busiest, road sparsest
SCENARIOS = {
    "city": (12.0, 2.5),
    "residential": (6.0, 3.0),
    "road": (2.5, 4.0),
}


@dataclasses.dataclass(frozen=True)
class BenchScene:
    image: np.ndarray            # the frame, float32 in [0, 1]
    boxes: np.ndarray            # (n, 4) ground-truth boxes (y0, x0, y1, x1)
    scenario: str
    rain: float


def poisson_quantiles(mean: float, n: int) -> np.ndarray:
    """The Poisson(mean) quantiles at (i + 0.5) / n for i < n: a fixed
    multiset of n counts with the distribution's shape."""
    qs = (np.arange(n) + 0.5) / n
    out = np.empty(n, np.int64)
    k, pmf = 0, math.exp(-mean)
    cdf = pmf
    for i, q in enumerate(qs):              # qs ascend, so k only grows
        while cdf < q:
            k += 1
            pmf *= mean / k
            cdf += pmf
        out[i] = k
    return out


def _draw_objects(rng, n: int, img: np.ndarray) -> np.ndarray:
    fh, fw = img.shape[:2]
    sy, sx = fh / H, fw / W
    edge = max(1, round(2 * sy))
    boxes = []
    for _ in range(n):
        h = round(rng.integers(8, 28) * sy)
        w = round(rng.integers(8, 36) * sx)
        y0 = rng.integers(fh // 3, fh - h)
        x0 = rng.integers(0, fw - w)
        shade = 0.55 + 0.4 * rng.random()
        img[y0:y0 + h, x0:x0 + w] = shade
        img[y0:y0 + edge, x0:x0 + w] = 1.0    # high-contrast edge
        boxes.append((y0, x0, y0 + h, x0 + w))
    return np.asarray(boxes, np.float32).reshape(-1, 4)


def _draw_lanes(rng, n: int, img: np.ndarray) -> None:
    fh, fw = img.shape[:2]
    sy, sx = fh / H, fw / W
    width = max(1, round(2 * sx))
    for i in range(n):
        x_base = (i + 1) * fw / (n + 1) + rng.normal(0, 6 * sx)
        curve = rng.normal(0, 0.15)
        for y in range(fh // 2, fh):
            x = int(x_base + curve * ((y - fh // 2) / sy) ** 1.2 * sx)
            if 0 <= x <= fw - width:
                img[y, x:x + width, :] = 0.95


def _render_rain(rng, img: np.ndarray, mm_per_hour: float) -> None:
    """Streaks, contrast loss and fog, stronger with the rain rate."""
    if mm_per_hour <= 0:
        return
    fh, fw = img.shape[:2]
    sy, sx = fh / H, fw / W
    length, width = round(8 * sy), max(1, round(sx))
    strength = min(mm_per_hour / 200.0, 1.0)
    img *= 1.0 - 0.5 * strength
    img += 0.45 * 0.5 * strength
    for _ in range(int(250 * strength * sy * sx)):
        x = rng.integers(0, fw)
        y = rng.integers(0, fh - length)
        streak = img[y:y + length, x:x + width]
        streak[:] = 0.5 * streak + 0.27
    img += rng.normal(0.0, 0.05 * strength, img.shape).astype(np.float32)
    np.clip(img, 0.0, 1.0, out=img)


def render(rng, scenario: str, rain: float, n_obj: int, n_lane: int,
           frame) -> BenchScene:
    img = np.full(tuple(frame), 0.25, np.float32)
    img += rng.normal(0, 0.02, img.shape).astype(np.float32)  # sensor noise
    _draw_lanes(rng, n_lane, img)
    boxes = _draw_objects(rng, n_obj, img)
    _render_rain(rng, img, rain)
    np.clip(img, 0.0, 1.0, out=img)
    return BenchScene(image=img, boxes=boxes, scenario=scenario, rain=rain)


def stream_pool(seed: int, stream: int, scenario: str, rain: float,
                size: int, frame) -> list[BenchScene]:
    """``size`` scenes of one camera stream.  The counts are the scenario's
    Poisson quantiles, permuted by (seed, stream); everything else is
    drawn from a generator seeded by (seed, stream, index)."""
    mu_obj, mu_lane = SCENARIOS[scenario]
    order = np.random.default_rng([seed, stream]).permutation(size)
    n_obj = poisson_quantiles(mu_obj, size)[order]
    n_lane = poisson_quantiles(mu_lane, size)[order]
    return [render(np.random.default_rng([seed, stream, i]), scenario, rain,
                   int(n_obj[i]), int(n_lane[i]), frame)
            for i in range(size)]


def fleet_pools(seed: int, n_streams: int, mix: list, size: int,
                frame) -> list:
    """One pool per stream of ``frame``-shaped scenes; stream ``i`` takes
    ``mix[i % len(mix)]``, a ``[scenario, rain mm/h]`` pair."""
    return [stream_pool(seed, i, *mix[i % len(mix)], size, frame)
            for i in range(n_streams)]
