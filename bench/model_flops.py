"""The chip peaks, and the least time a rung's step can take at them.

The operations and bytes of a step come from the configuration's model
module (``"model"`` in its file: ``flops_per_frame`` and
``bytes_per_step``); this file holds only what every model shares.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; an unknown kind is an error."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}: known {sorted(table)}")
    return table[device_kind]


def least_step_seconds(model, config: dict, rung_name: str, peak: dict,
                       chips: int) -> tuple[float, str]:
    """The least time one step at capacity can take on ``chips`` chips, by
    ``model``'s counts, and which bound sets it (``compute`` or
    ``memory``)."""
    t_ops = (config["capacity"] * model.flops_per_frame(config, rung_name)
             / (chips * peak["bf16_flops_per_s"]))
    t_mem = (model.bytes_per_step(config, rung_name)
             / (chips * peak["hbm_bytes_per_s"]))
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
