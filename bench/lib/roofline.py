"""Published chip peaks and the bytes each measured kernel must move.

A kernel's roofline share is the least time the chip could take for the
call, the larger of operations over the compute peak and bytes over the
HBM peak, divided by the kernel's device time from the trace. The two
kernels measured here do integer VPU work (XOR, popcount, compares) for
which no peak is published, so both are bounded by bytes alone: their
shares are of the HBM roofline.

The byte counts are the least each call must move, from the shapes of its
interface, whatever the kernel loops over inside: every input read once,
every output written once, 4 bytes per int32 or uint32 element.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Dict

PEAKS_FILE = Path(__file__).resolve().parents[1] / "peaks.json"
WORD = 4


def peaks(device_kind: str, table_file: Path = PEAKS_FILE) -> Dict:
    """The peak row of a device kind (``jax.Device.device_kind``). A kind
    that is not in the table is an error, never a default."""
    table = json.loads(Path(table_file).read_text())
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}: add a row with its source to "
                       f"{Path(table_file).name}")
    return table[device_kind]


def hamming_scan_bytes(batch: int, rows: int, words: int) -> int:
    """Packed Hamming scan, (batch, words) x (rows, words) -> (batch, rows)
    int32: both code tables read, the counts written."""
    return WORD * (batch * words + rows * words + batch * rows)


def bucket_gather_bytes(batch: int, runs: int, probes: int) -> int:
    """Segmented gather over ``runs`` probe-ordered bucket runs: the
    (batch, runs + 1) ``cum`` and (batch, runs) ``starts`` read, the
    (batch, probes) CSR positions written."""
    return WORD * (batch * (runs + 1) + batch * runs + batch * probes)


def share_pct(nbytes: float, seconds: float, bytes_per_s: float) -> float:
    """Per cent of the HBM roofline: (bytes / peak bandwidth) / time."""
    if seconds <= 0:
        raise ValueError(f"kernel time must be positive, got {seconds}")
    return 100.0 * nbytes / bytes_per_s / seconds
