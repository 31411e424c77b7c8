"""Bytes the candidates stage must move for one wave — shapes only.

Whatever implements the stage, one wave has to read, once, the columns of
the scan window that the active filters and scores consume, read the pod
batch, and write the ``(batch, k)`` candidate indices and priorities.
With no pod selector, affinity or constraint in play (the ``uniform``
pods of both first deployments) the label planes, zone, region and name
are not needed and are not counted: counting the whole 241-byte row would
overstate the share.
"""

from __future__ import annotations

# NodeResourcesFit + LeastAllocated + BalancedAllocation + TaintToleration
BASE_COLUMNS = (
    "cpu_alloc", "mem_alloc", "cpu_req", "mem_req", "pods_req",
    "pods_alloc", "meta", "taint_id",
)
# what one uniform pod brings: cpu, memory, its name id and a flag word
POD_BYTES = 16


def window_rows(rows: int, score_pct: int, chunk: int) -> int:
    """Rows one wave scans: ``score_pct`` percent of the table, rounded
    up to whole chunks (the whole table at 100)."""
    if score_pct >= 100:
        return rows
    want = -(-rows * score_pct // 100)
    return min(rows, -(-want // chunk) * chunk)


def row_bytes(column_shapes: dict, columns=BASE_COLUMNS) -> int:
    """``column_shapes``: name -> (itemsize, elements per row)."""
    return sum(column_shapes[c][0] * column_shapes[c][1] for c in columns)


def wave_bytes(*, scan_rows: int, bytes_per_row: int, batch: int, k: int,
               pod_bytes: int) -> int:
    return scan_rows * bytes_per_row + batch * pod_bytes + batch * k * 8


def hbm_share_pct(bytes_moved: int, seconds: float, hbm_bytes_per_s: float) -> float:
    """Least time the chip's HBM could take for ``bytes_moved`` over the
    time the kernel took, in percent (the bound is ``hbm``)."""
    return 100.0 * (bytes_moved / hbm_bytes_per_s) / seconds
