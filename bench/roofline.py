"""Bytes the counting passes need, and their share of the chip's roofline.

One executed fused counting pass (``repro.kernels.fused``) reads every key
and value once and writes it once: ``2 * n * (key bytes + value bytes)``,
the per-pass term of the paper's ``(2 * p_exec + 1) * n * b`` bound (the
``+1`` prologue sweep is the histogram kernel's, not this pass's).  The pass
moves bytes and does no arithmetic worth counting, so its floor is
bandwidth-bound: bytes over the chip's HBM bandwidth.
"""
from __future__ import annotations

from typing import Optional


def counting_pass_bytes(n: int, key_bytes: int, value_bytes: int,
                        passes: int) -> int:
    return passes * 2 * n * (key_bytes + value_bytes)


def share(bytes_needed: float, seconds: float, bytes_per_s: float) -> Optional[float]:
    """Per cent of the roofline: least time over the time taken."""
    if seconds <= 0 or bytes_needed <= 0:
        return None
    return 100.0 * (bytes_needed / bytes_per_s) / seconds


def fused_pass_share(run) -> Optional[float]:
    """Roofline share of the counting passes of the traced window: each
    fused-pass kernel that ran in it is one executed pass over ``n``
    records."""
    from bench import reduce

    trace = run.trace
    if trace is None:
        return None
    lo, hi = reduce.window(trace)
    kernels = [op for op in reduce.kernel_ops(trace, "counting pass",
                                              run.event_map)
               if lo <= op[1] < hi]
    cell = run.cell
    needed = counting_pass_bytes(cell.n, cell.key_bytes, cell.value_bytes,
                                 len(kernels))
    return share(needed, reduce.covered(kernels, lo, hi) / 1e9,
                 run.peak["hbm_bytes_per_s"])
