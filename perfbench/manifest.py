"""Everything the harness knows about a cell comes from data files.

`BENCHMARK.json` names cells, configurations and metrics; each name is
the stem of a file of its own under `perfbench/`:

    configs/<config>.json        sizes as run, and the plain reference's name
    traffic/<traffic>.json       which driver runs it, with its parameters
    layer_metrics/<metric>.json  reducer, selector and scale of one metric

A later PR adds a cell, a configuration or a per-layer metric by adding
such files and the matching `BENCHMARK.json` entries; nothing here is
edited for it.
"""

import json
import os
from typing import Any, Dict, List, NamedTuple, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

# Traffic driver -> the module whose run() measures it.
DRIVERS = {
    "learner": "perfbench.drivers.learner",
    "learner_dp": "perfbench.drivers.learner",
    "poly": "perfbench.drivers.poly",
    "mono": "perfbench.drivers.stubs",
    "anakin": "perfbench.drivers.stubs",
}


class ManifestError(ValueError):
    """A file the manifest names is missing or does not say what it must."""


class Cell(NamedTuple):
    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]
    traffic_name: str
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]  # BENCHMARK.json entry + its file


def _read_json(path: str) -> Dict[str, Any]:
    try:
        with open(path) as f:
            value = json.load(f)
    except FileNotFoundError:
        raise ManifestError(f"{path}: no such file") from None
    except json.JSONDecodeError as e:
        raise ManifestError(f"{path}: not JSON ({e})") from None
    if not isinstance(value, dict):
        raise ManifestError(f"{path}: not a JSON object")
    return value


def load_benchmark(root: str = ROOT) -> Dict[str, Any]:
    return _read_json(os.path.join(root, "BENCHMARK.json"))


def _applies(metric: Dict[str, Any], cell_name: str) -> bool:
    listed = metric.get("workloads")
    return listed is None or cell_name in listed


def load_cell(
    workload: str, root: str = ROOT, bench_dir: Optional[str] = None
) -> Cell:
    """The cell `workload` with every file it names, read and checked.
    `bench_dir` is where configs/, traffic/ and layer_metrics/ live
    (default: this package)."""
    bench_dir = bench_dir or HERE
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise ManifestError(
            f"workload {workload!r} is not in BENCHMARK.json "
            f"(has {sorted(cells)})"
        )
    entry = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    if entry["config"] not in configs:
        raise ManifestError(
            f"{workload}: configuration {entry['config']!r} is not "
            "listed under configs"
        )
    config = _read_json(os.path.join(root, configs[entry["config"]]["file"]))
    traffic = _read_json(
        os.path.join(bench_dir, "traffic", entry["traffic"] + ".json")
    )
    if traffic.get("driver") not in DRIVERS:
        raise ManifestError(
            f"traffic {entry['traffic']!r}: driver "
            f"{traffic.get('driver')!r} is not one of {sorted(DRIVERS)}"
        )
    per_layer = []
    for metric in bench["per_layer"]:
        if not _applies(metric, workload):
            continue
        spec = _read_json(os.path.join(
            bench_dir, "layer_metrics", metric["name"] + ".json"
        ))
        for key in ("unit", "layer", "moves", "source"):
            if spec.get(key) != metric[key]:
                raise ManifestError(
                    f"layer_metrics/{metric['name']}.json: {key} "
                    f"{spec.get(key)!r} differs from BENCHMARK.json's "
                    f"{metric[key]!r}"
                )
        per_layer.append(dict(metric, **spec))
    return Cell(
        name=workload,
        chips=int(entry["chips"]),
        config_name=entry["config"],
        config=config,
        traffic_name=entry["traffic"],
        traffic=traffic,
        end_to_end=[
            m for m in bench["end_to_end"] if _applies(m, workload)
        ],
        per_layer=per_layer,
    )
