"""Operations and bytes a kernel call must move, from the cell's shapes.

These are the counts any implementation of the stage must do, whatever its
tiling, so a kernel's roofline share cannot be raised by changing them.
"""

from __future__ import annotations

ID_BYTES = 4


def ivf_scan(rows_per_chip: int, d_pad: int, batch: int) -> dict:
    """Stage-1 scan of one flat batch step on one chip: every int8 code row
    and its int32 row id streamed once; one int8 multiply-add per code and
    query (two operations)."""
    return {"bytes": rows_per_chip * (d_pad + ID_BYTES),
            "int8_ops": 2 * batch * rows_per_chip * d_pad}


def least_time_s(cost: dict, peaks: dict) -> tuple[float, str]:
    """(seconds, binding term): the larger of bytes over HBM bandwidth and
    int8 operations over the int8 peak."""
    t_mem = cost["bytes"] / peaks["hbm_bytes_per_s"]
    t_ops = cost["int8_ops"] / peaks["int8_ops_per_s"]
    return (t_mem, "hbm_bytes") if t_mem >= t_ops else (t_ops, "int8_ops")
