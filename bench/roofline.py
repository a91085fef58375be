"""The chip's published peaks and the bytes each kernel call must move.

A kernel's roofline share is the least time the chip could take for the
call, from these bytes (or operations) and the peaks, over the kernel's
measured device time.
"""

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The peaks of one device kind. A kind that is not in the table is an
    error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in {PEAKS_FILE.name}; known: {sorted(table)}")
    return table[device_kind]


def matvec_bytes(n: int, p: int, batch: int, itemsize: int = 4) -> int:
    """HBM bytes of one screening matvec call (``_matvec_kernel``): X read
    once (the n*p term of ``bytes_per_screen``), the B centres read and the
    (B, p) correlations written."""
    return itemsize * (n * p + batch * n + batch * p)
