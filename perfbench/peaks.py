"""Published peaks per chip, keyed by a substring of `device_kind`.

Copied from bench.py's tables (the original is listed in PERF.md for a
later PR to delete). Sources: Google Cloud documentation, "TPU v5e"
(197 TFLOP/s bf16, 819 GB/s HBM, 16 GB), and the matching system
architecture pages for the other generations. A kind that is not in the
table is an error: a utilization against an assumed peak is a number
about no chip in particular.
"""

PEAK_BF16_TFLOPS = {
    "v2": 45.0,
    "v3": 123.0,
    "v4": 275.0,
    "v5 lite": 197.0,
    "v5e": 197.0,
    "v5p": 459.0,
    "v6 lite": 918.0,
    "v6e": 918.0,
}

PEAK_HBM_GBPS = {
    "v2": 700.0,
    "v3": 900.0,
    "v4": 1228.0,
    "v5 lite": 819.0,
    "v5e": 819.0,
    "v5p": 2765.0,
    "v6 lite": 1640.0,
    "v6e": 1640.0,
}


def peak_for(kind: str, table) -> float:
    lowered = kind.lower()
    for name, peak in table.items():
        if name in lowered:
            return peak
    raise ValueError(
        f"device_kind {kind!r} is not in perfbench/peaks.py "
        f"({sorted(table)}); add its published figure with a source"
    )
