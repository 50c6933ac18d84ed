"""Published peaks per device kind, and the bytes a kernel must move.

A device kind missing from the table is an error, never a default.
"""

from __future__ import annotations

import math

#: keyed by ``jax.Device.device_kind``
PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, 'TPU v5e' (per chip)",
    },
}


def peak(device_kind: str, key: str) -> float:
    try:
        return PEAKS[device_kind][key]
    except KeyError:
        raise KeyError(f"no published {key} for device kind "
                       f"{device_kind!r}: add it to PEAKS with its "
                       f"source") from None


def masked_select_bytes(rows: int, devices: int) -> int:
    """Least HBM bytes one ``masked_select`` call must move, from its
    logical shapes: the (rows, devices) validity matrix at one bit per
    entry, the int32 key row, and the two int32 outputs per row.  The
    kernel publishes no integer-op peak and does one compare and one
    select per entry, so its roofline is this byte count over the HBM
    bandwidth; one bit per entry keeps any layout at or under 100%."""
    return math.ceil(rows * devices / 8) + 4 * devices + 8 * rows
