"""Peaks of the chip and the bytes a window must move.

The byte counts are lower bounds of the semantics, not of today's
implementation: an implementation that touches only the ring slots a
window actually uses, and each process's state once, reads 100%. Today's
kernels sweep every slot of every ring (``capacity_sweep_bytes``), which is
why their shares read low.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"

#: bytes of one message's availability stamp and touch stamp
STAMPS = 8
#: per ring: head and size, each read and written (int32)
RING_INDEX = 16
#: per ring: the head slot's availability stamp, read to decide a pop
HEAD_STAMP = 4
#: per process: clock, step count and six message counters (4 bytes
#: each) and the done flag (1 byte)
PROCESS_SCALARS = 8 * 4 + 1


def peaks(kind: str) -> dict:
    """The peak rates of ``kind``; an unknown device is an error."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r}; known: "
                       f"{sorted(table)}")
    return table[kind]


def message_bytes(L: int, itemsize: int = 4) -> int:
    """One message: its two stamps and its payload words."""
    return STAMPS + L * itemsize


def duct_window_bytes(n: int, R: int, L: int, drained: float,
                      pushed: float) -> float:
    """One ``duct_window`` pass: every ring's head and size and head stamp,
    each receiver's clock and activity, each message drained read once and
    each staged push written once."""
    return (R * (RING_INDEX + HEAD_STAMP) + n * 5
            + (drained + pushed) * message_bytes(L))


def duct_commit_bytes(R: int, L: int, pushed: float) -> float:
    """One superstep commit: every ring's head, base size and push count
    read, and each push of the superstep read from the push buffer and
    written into its ring."""
    return R * 12 + 2 * pushed * message_bytes(L)


def window_bytes(n: int, R: int, L: int, simels: int, n_colors: int,
                 drained: float, pushed: float) -> float:
    """One whole window: each process's scalars, colouring state (a colour
    and ``n_colors`` float32 probabilities per simel) and halo read and
    written, plus the ring traffic of ``duct_window_bytes``."""
    per_process = 2 * (PROCESS_SCALARS + simels * 4 * (1 + n_colors)
                       + 4 * L * 4)
    return n * per_process + duct_window_bytes(n, R, L, drained, pushed)


def capacity_sweep_bytes(R: int, C: int, L: int) -> float:
    """What a pass that reads and writes every slot of every ring moves."""
    return 2.0 * R * C * message_bytes(L)


def share(bytes_moved: float, seconds: float, bytes_per_s: float):
    """Percent of the bandwidth roofline; None where nothing was timed."""
    if not seconds or seconds <= 0:
        return None
    return 100.0 * bytes_moved / bytes_per_s / seconds
