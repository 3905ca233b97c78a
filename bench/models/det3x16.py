"""The ``det3x16`` detector as the benchmark builds and counts it: the
program's own 3-conv detector over its built-in rungs.

A configuration names this file under ``"model"``.  ``build_scheduler``
returns the program's scheduler; ``flops_per_frame`` and
``bytes_per_step`` count a rung's step from the configuration's widths
alone.

Operations count the model's multiply-adds as 2 each: every backbone conv
(2 * Ho * Wo * Cin * Cout * k^2 at stride s, SAME padding) and the
one-stage head.  Bytes are the least any step must move: the resized input
frames read once, the outputs written once, and the weights the step uses.
"""
from __future__ import annotations

import math

from bench.references.det3x16 import scaled_shape

F32 = 4


def build_scheduler(config: dict, key, mesh):
    """The program's ``RungBucketScheduler`` over its built-in rungs, in
    the order the configuration's ``ladder`` lists them, at its
    ``capacity`` and pipeline ``depth``."""
    from repro.batched.scheduler import RungBucketScheduler
    from repro.scenarios.replay import replay_ladder

    ladder = replay_ladder(config["ladder"])
    if [r.name for r in ladder] != config["ladder"]:
        raise ValueError(f"the program orders the ladder "
                         f"{[r.name for r in ladder]}, the configuration "
                         f"{config['ladder']}")
    return RungBucketScheduler(ladder, capacity=config["capacity"], key=key,
                               depth=config["depth"], mesh=mesh)


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
