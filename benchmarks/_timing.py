"""Shared marginal-device-time estimation for chip benchmarks.

Every dispatch pays a fixed cost (program launch + the host's wait for
the result) that swamps an op taking microseconds. Benchmarks that need
per-op device time therefore (a) chain `steps` iterations inside ONE
jitted dispatch with a data dependence and (b) run at `steps` and
`3*steps` and difference the totals so the fixed floor cancels. This
module owns step (b); the chaining closures stay in each bench (their
data-feedback shapes differ).

Used by benchmarks/vtrace_bench.py and benchmarks/pallas_attn_bench.py.
"""

from __future__ import annotations


def marginal_from_totals(
    lo_total_ms: float, hi_total_ms: float, steps: int
) -> tuple[float, bool]:
    """Per-iteration ms from totals at `steps` and `3*steps` chains.

    Returns (ms, floor_contaminated): the two-point marginal when the
    totals are ordered sanely, else the amortized hi total — a positive
    UPPER BOUND that still contains the per-dispatch floor, flagged so
    callers can mark the row instead of publishing it as a clean
    marginal.
    """
    if hi_total_ms > lo_total_ms:
        return (hi_total_ms - lo_total_ms) / (2 * steps), False
    return hi_total_ms / (3 * steps), True
