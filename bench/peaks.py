"""Peak rates of one chip, keyed by JAX's ``device_kind`` (``peaks.json``)."""
from __future__ import annotations

import json
import os
from typing import Dict

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str, path: str = PEAKS_FILE) -> Dict[str, float]:
    """``{"flops": FLOP/s bf16, "bytes": HBM bytes/s}`` of one chip of this
    kind. A kind that is not in the table is an error, never a default."""
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peak rates for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    row = table[device_kind]
    return {"flops": float(row["bf16_flops_per_s"]),
            "bytes": float(row["hbm_bytes_per_s"])}
