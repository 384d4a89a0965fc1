"""The work a 1:N search needs, and the chip's peaks.

``flops`` and ``bytes_moved`` count what one exact top-k search of ``q``
queries over a scope of ``n`` rows of width ``d`` must do at the least,
whatever implements it: ``2 q n d`` multiply-adds for the scores, the
scope's rows read once in their storage dtype (with an f32 scale per
row for int8), the f32 queries read and the (score, index) results
written.  ``least_s`` is the time the chip needs for that work at its
published peaks; the roofline share of a measured time is
``least_s / measured``.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"
ROW_BYTES = {"fp32": 4, "bf16": 2, "int8": 1}
SCALE_BYTES = {"fp32": 0, "bf16": 0, "int8": 4}
RESULT_BYTES = 8                      # f32 score + i32 index per slot


def flops(q: int, n: int, d: int) -> float:
    return 2.0 * q * n * d


def bytes_moved(q: int, n: int, d: int, dtype: str, k: int = 1) -> float:
    rows = n * (d * ROW_BYTES[dtype] + SCALE_BYTES[dtype])
    return float(rows + q * d * 4 + q * k * RESULT_BYTES)


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a kind missing from the table is an
    error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}; add its published numbers")
    return table[device_kind]


def least_s(calls, peak: dict) -> tuple:
    """Least time for a sequence of searches ``calls`` = [(q, n, d, dtype,
    k), ...] run back to back: the larger of all their operations over
    the bf16 MXU peak and all their bytes over HBM bandwidth.  Returns
    ``(seconds, bound)`` with ``bound`` "compute" or "memory"."""
    f = sum(flops(q, n, d) for q, n, d, _, _ in calls)
    b = sum(bytes_moved(q, n, d, dt, k) for q, n, d, dt, k in calls)
    tc = f / peak["bf16_flops_per_s"]
    tm = b / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
