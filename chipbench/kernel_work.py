"""Work of a kernel call that any implementation must do, from its shapes.

The Dawid-Skene E-step over T tasks with V vote slots, N of them holding a
real vote, and C classes (``kernels/ds_estep.py``, batched over tables: T
and N count the tasks and votes of every table in the call) must read
each slot's row index (4 bytes), the C float32 log-confusions of the row
each real vote names, and write the log-posterior and the posterior (two
float32 per task and class). An empty slot names the all-zero null row,
which no implementation needs to fetch. It adds one row per vote (N C
adds) and normalizes each task's C classes: the max, the subtraction, the
exponential, the sum and the division, about 5 T C operations. A row
gather and a one-hot contraction are judged on the same count.
"""
from __future__ import annotations


def ds_estep_bytes(T: int, V: int, C: int, N: float) -> float:
    return float(4 * T * V + 4 * N * C + 8 * T * C)


def ds_estep_flops(T: int, C: int, N: float) -> float:
    return float(N * C + 5 * T * C)


def roofline_s(bytes_: float, flops: float, peak: dict) -> float:
    """The least time the chip needs for the work: the larger of its bytes
    at the HBM's rate and its operations at the peak rate."""
    return max(bytes_ / peak["hbm_bytes_per_s"], flops / peak["bf16_flops"])
