"""Operations and bytes of a rung's jitted step, from the configuration's
widths alone, and the chip peaks they are held against.

Operations count the model's multiply-adds as 2 each: every backbone conv
(2 * Ho * Wo * Cin * Cout * k^2 at stride s, SAME padding) and the
one-stage head.  Bytes are the least any step must move: the resized input
frames read once, the outputs written once, and the weights the step uses.
"""
from __future__ import annotations

import json
import math
from pathlib import Path

from .reference import scaled_shape

PEAKS = Path(__file__).resolve().parent / "peaks.json"
F32 = 4


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; an unknown kind is an error."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}: known {sorted(table)}")
    return table[device_kind]


def _grids(config: dict, rung: dict):
    """(conv output shapes [(Ho, Wo, Cin, Cout)], feature grid, score grid)."""
    h, w = scaled_shape(config["frame"], rung["scale"])
    s, c = rung["stride"], rung["channels"]
    convs, cin = [], config["frame"][2]
    for _ in range(rung["backbone_depth"]):
        h, w = math.ceil(h / s), math.ceil(w / s)
        convs.append((h, w, cin, c))
        cin = c
    rem = 8 // s ** rung["backbone_depth"]
    sh, sw = scaled_shape(config["frame"], rung["scale"])
    return convs, (h // rem, w // rem), (sh // rung["cell"], sw // rung["cell"])


def conv_flops(config: dict, rung_name: str) -> int:
    rung = config["rungs"][rung_name]
    k = rung["kernel"]
    return sum(2 * ho * wo * ci * co * k * k
               for ho, wo, ci, co in _grids(config, rung)[0])


def flops_per_frame(config: dict, rung_name: str) -> int:
    """Model operations per frame: the convs, plus the one-stage head."""
    rung = config["rungs"][rung_name]
    total = conv_flops(config, rung_name)
    if rung["kind"] != "two_stage":
        fh, fw = _grids(config, rung)[1]
        total += 2 * fh * fw * rung["channels"] * rung["head_dim"]
    return total


def bytes_per_step(config: dict, rung_name: str) -> int:
    """Least bytes one step at capacity moves: inputs, outputs, weights."""
    rung = config["rungs"][rung_name]
    convs, (fh, fw), (gh, gw) = _grids(config, rung)
    h, w = scaled_shape(config["frame"], rung["scale"])
    k, c = rung["kernel"], rung["channels"]
    per_frame = h * w * config["frame"][2] * F32
    weights = sum(k * k * ci * co for _, _, ci, co in convs) * F32
    if rung["kind"] == "two_stage":
        per_frame += (fh * fw * c + gh * gw) * F32      # features, objectness
    else:
        top = min(rung["top_k"], gh * gw)
        per_frame += top * (4 + 1) * F32 + top          # boxes, scores, keep
        weights += c * rung["head_dim"] * F32
    return config["capacity"] * per_frame + weights


def least_step_seconds(config: dict, rung_name: str, peak: dict,
                       chips: int) -> tuple[float, str]:
    """The least time one step at capacity can take on ``chips`` chips, and
    which bound sets it (``compute`` or ``memory``)."""
    t_ops = (config["capacity"] * flops_per_frame(config, rung_name)
             / (chips * peak["bf16_flops_per_s"]))
    t_mem = bytes_per_step(config, rung_name) / (chips * peak["hbm_bytes_per_s"])
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
