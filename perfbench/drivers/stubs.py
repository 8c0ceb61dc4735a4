"""Traffic drivers that are named but not built yet. A cell that asks
for one fails loudly; PERF.md's open questions say what each waits for."""


def run(cell, seed, seconds, trace, devices, meter):
    raise NotImplementedError(
        f"traffic driver {cell.traffic['driver']!r} (cell {cell.name}) "
        "is not built yet: see perfbench/README.md"
    )
