"""Operations and bytes of each kernel, from its shapes alone.

The least time a kernel can take on a chip is the larger of its operations
over the chip's peak operation rate and its bytes over the chip's memory
bandwidth; its roofline share is that least time over its measured device
time (``bench/metrics/*_roofline_pct.py``).
"""
from __future__ import annotations

import math

from bench.peaks import peaks


def predicate_filter_cost(n: int, fields: int, channels: int,
                          tile: int = 256) -> dict:
    """``predicate_filter``: an (N, F) int32 record block against (C, F)
    lower, upper and not-equal bounds, writing an (N, C) int8 bitmap. Each
    (record, channel, field) takes two range compares, one not-equal
    compare, its guard and three logical ops: 7 integer ops. N is padded to
    the kernel's 256-row tile."""
    n_pad = math.ceil(n / tile) * tile
    ops = 7 * n_pad * channels * fields
    bytes_ = 4 * n_pad * fields + 3 * 4 * channels * fields + n_pad * channels
    return {"ops": ops, "bytes": bytes_}


def least_time_s(cost: dict, device_kind: str) -> tuple:
    """(seconds, bound): the roofline's least time and which side binds.
    Integer compares have no published vector-unit peak, so the operations
    are held against the int8 peak, the chip's highest integer rate."""
    pk = peaks(device_kind)
    t_ops = cost["ops"] / pk["int8_ops"]
    t_bytes = cost["bytes"] / pk["hbm_bytes_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_ops else (t_ops, "ops")
