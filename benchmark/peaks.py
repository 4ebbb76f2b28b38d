"""Published peaks by device_kind, and the match kernel's bytes and operations.

Peaks: Google Cloud documentation, "TPU v5e" (one chip): 819 GB/s of HBM,
197 TFLOP/s bf16, 393 TOP/s int8. A device that is not in the table is an
error, never a default.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": dict(
        hbm_bytes_per_s=819e9, bf16_flops_per_s=197e12, int8_ops_per_s=393e12,
        source="Google Cloud documentation, TPU v5e",
    ),
}

#: Integer operations per book slot visited by one op of the match step
#: (price compare, priority compare, mask, select, lot arithmetic, shift of
#: the slot on insert or removal): counted by hand from the step's equations
#: and rounded up. The bound it feeds never binds (see kernel_cost).
OPS_PER_SLOT = 12


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; add a row "
            f"to benchmark/peaks.py with its source"
        ) from None


def kernel_cost(rows: int, t: int, cap: int, max_fills: int,
                itemsize: int = 4) -> tuple[int, int]:
    """(bytes, operations) the match kernel needs for one [rows, t] grid at
    cap class `cap`: what the algorithm must move and do, from its shapes.

    Bytes: each row's book enters and leaves once (5 fields x 2 sides x cap
    slots, plus 2 counts and 1 sequence number); each of the rows*t ops reads
    its 8-word op record and writes 5 fill-record fields of max_fills entries
    and an 8-word scalar record. Operations: every op visits both sides' cap
    slots, OPS_PER_SLOT integer operations each."""
    book = 2 * (10 * rows * cap + 3 * rows) * itemsize
    per_op = rows * t * (8 + 5 * max_fills + 8) * itemsize
    ops = rows * t * 2 * cap * OPS_PER_SLOT
    return book + per_op, ops


def kernel_min_seconds(device_kind: str, rows: int, t: int, cap: int,
                       max_fills: int, itemsize: int = 4) -> tuple[float, str]:
    """The least time the chip could take for the grid, and which peak sets
    it. The kernel computes in int32 on the vector unit, for which no peak is
    published: the int8 figure stands in, which can only understate the time,
    and the bytes bound is the one that binds at every geometry in use."""
    p = peaks(device_kind)
    nbytes, ops = kernel_cost(rows, t, cap, max_fills, itemsize)
    by_bytes = nbytes / p["hbm_bytes_per_s"]
    by_ops = ops / p["int8_ops_per_s"]
    return (by_bytes, "bytes") if by_bytes >= by_ops else (by_ops, "ops")
