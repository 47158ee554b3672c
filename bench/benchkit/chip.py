"""The device a run is on, and its peaks."""
from __future__ import annotations

import json
from pathlib import Path
from typing import Any, Dict, Tuple

PEAKS = Path(__file__).resolve().parents[1] / "peaks.json"


def peaks_for(kind: str) -> Dict[str, float]:
    """The chip's published peaks; a kind not in the table is an error."""
    with open(PEAKS) as f:
        table = json.load(f)
    if kind not in table:
        raise SystemExit(f"no peaks for device kind {kind!r} in {PEAKS.name}: "
                         f"known {sorted(table)}")
    return table[kind]


def require(chips: int) -> Tuple[Dict[str, Any], Dict[str, float]]:
    """The devices, if they are at least ``chips`` TPUs; otherwise exit."""
    import jax

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise SystemExit(f"bench: no TPU: JAX's first device is on platform {dev.platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips and JAX sees {len(devs)}")
    return ({"platform": dev.platform, "kind": dev.device_kind, "count": len(devs)},
            peaks_for(dev.device_kind))
