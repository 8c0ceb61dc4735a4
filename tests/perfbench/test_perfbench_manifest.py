"""BENCHMARK.json against the contract, and the loader finding what a
later PR adds as new files without an edit."""

import json
import os
import re
import shutil

import pytest

from perfbench import manifest, readers

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
BENCH = manifest.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_top_level_keys():
    assert set(BENCH) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    # 2 + 14 x 24 runs of run_seconds + 60, 24 x 180 to compile, 1200 spare
    budget = (2 + 14 * 24) * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200
    assert budget <= 43200
    assert os.path.getsize(
        os.path.join(manifest.ROOT, "BENCHMARK.json")
    ) <= 64 * 1024


def test_command_and_paths():
    assert 1 <= len(BENCH["command"]) <= 32
    assert 1 <= len(BENCH["paths"]) <= 16
    for path in BENCH["paths"]:
        assert os.path.isdir(os.path.join(manifest.ROOT, path))
        assert not path.startswith("/") and ".." not in path


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_config_entry(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(config["name"])
    assert any(config["file"].startswith(p + "/") for p in BENCH["paths"])
    assert 1 <= len(config["source"]) <= 200
    assert 1 <= len(config["why"]) <= 200
    with open(os.path.join(manifest.ROOT, config["file"])) as f:
        body = json.load(f)
    assert body["name"] == config["name"]
    assert set(config["reduced"]) <= set(body)
    assert any(w["config"] == config["name"] for w in BENCH["workloads"])
    # The plain reference the file names is beside the benchmark.
    assert os.path.isfile(os.path.join(
        manifest.HERE, "reference", body["reference"] + ".py"
    ))


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_workload_entry(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    for key in ("name", "config", "traffic"):
        assert NAME.match(cell[key])
    assert cell["chips"] in (1, 4)
    assert 1 <= len(cell["why"]) <= 200 and "\n" not in cell["why"]


def test_four_chip_cells_within_a_quarter():
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)


def test_names_are_unique():
    for group in (METRICS, BENCH["workloads"], BENCH["configs"]):
        names = [entry["name"] for entry in group]
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric):
    end_to_end = metric in BENCH["end_to_end"]
    keys = {"name", "unit", "better", "source"}
    keys |= {"bound"} if end_to_end else {"layer", "moves"}
    assert set(metric) - {"workloads"} == keys
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    for listed in metric.get("workloads", []):
        assert listed in CELLS
    if end_to_end:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        assert 1 <= len(metric["layer"]) <= 200
        moved = next(
            m for m in BENCH["end_to_end"] if m["name"] == metric["moves"]
        )
        # The moved metric is reported in every cell this one is in.
        for cell in metric.get("workloads", CELLS):
            assert cell in moved.get("workloads", CELLS)


@pytest.mark.parametrize("cell_name", CELLS)
def test_cell_loads_with_all_its_files(cell_name):
    cell = manifest.load_cell(cell_name)
    names = [m["name"] for m in cell.end_to_end]
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer, "every cell reports a per-layer metric"
    assert cell.traffic["driver"] in manifest.DRIVERS
    for spec in cell.per_layer:
        assert spec["reader"] in readers.READERS


def test_every_layer_metric_file_is_listed():
    listed = {m["name"] for m in BENCH["per_layer"]}
    on_disk = {
        os.path.splitext(f)[0]
        for f in os.listdir(os.path.join(manifest.HERE, "layer_metrics"))
    }
    assert on_disk == listed


@pytest.fixture
def grown(tmp_path):
    """A checkout to which a later PR added a configuration, a traffic
    mix, a per-layer metric and a cell: new files and new entries."""
    bench_dir = tmp_path / "perfbench"
    for sub in ("configs", "traffic", "layer_metrics"):
        shutil.copytree(os.path.join(manifest.HERE, sub), bench_dir / sub)
    config = json.loads((bench_dir / "configs/impala_deep_lstm.json").read_text())
    config.update(name="impala_deep_x2_lstm", trunk_channels=[32, 64, 64])
    (bench_dir / "configs/impala_deep_x2_lstm.json").write_text(
        json.dumps(config)
    )
    traffic = json.loads((bench_dir / "traffic/poly32.json").read_text())
    traffic["num_actors"] = 64
    (bench_dir / "traffic/poly64.json").write_text(json.dumps(traffic))
    metric = {
        "name": "ring_waits_per_frame", "unit": "1/frame",
        "better": "lower", "source": "program_counter",
        "layer": "env servers, wire", "moves": "env_frames_per_s",
        "reader": "ratio",
        "args": {"num": [["counters", "pool.ring_doorbell_waits"]],
                 "den": [["counters", "pool.env_steps"]]},
    }
    (bench_dir / "layer_metrics/ring_waits_per_frame.json").write_text(
        json.dumps(metric)
    )
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({
        "name": "impala_deep_x2_lstm", "source": "x", "reduced": [],
        "file": "perfbench/configs/impala_deep_x2_lstm.json", "why": "x",
    })
    bench["workloads"].append({
        "name": "deep_x2_lstm.poly64", "config": "impala_deep_x2_lstm",
        "traffic": "poly64", "chips": 1, "why": "x",
    })
    for m in bench["end_to_end"]:
        if m["name"] == "env_frames_per_s":
            m["workloads"].append("deep_x2_lstm.poly64")
    bench["per_layer"].append({
        k: metric[k]
        for k in ("name", "unit", "better", "source", "layer", "moves")
    } | {"workloads": ["deep_x2_lstm.poly64"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(tmp_path), str(bench_dir)


def test_added_files_are_found_without_an_edit(grown):
    root, bench_dir = grown
    cell = manifest.load_cell("deep_x2_lstm.poly64", root, bench_dir)
    assert cell.config["trunk_channels"] == [32, 64, 64]
    assert cell.traffic["num_actors"] == 64 and cell.traffic["driver"] == "poly"
    assert [m["name"] for m in cell.per_layer] == ["ring_waits_per_frame"]
    facts = {"counters": {"pool.ring_doorbell_waits": 6, "pool.env_steps": 3}}
    assert readers.read_metric(cell.per_layer[0], facts) == 2.0
    # and the cells that were there load as before
    old = manifest.load_cell("deep_lstm.poly", root, bench_dir)
    assert "ring_waits_per_frame" not in [m["name"] for m in old.per_layer]


@pytest.mark.parametrize("breakage,match", [
    ("unknown_cell", "is not in BENCHMARK.json"),
    ("missing_traffic", "no such file"),
    ("bad_driver", "is not one of"),
    ("metric_disagrees", "differs from BENCHMARK.json"),
])
def test_loader_says_what_is_wrong(grown, breakage, match):
    root, bench_dir = grown
    name = "deep_x2_lstm.poly64"
    if breakage == "unknown_cell":
        name = "nope"
    elif breakage == "missing_traffic":
        os.unlink(os.path.join(bench_dir, "traffic", "poly64.json"))
    elif breakage == "bad_driver":
        with open(os.path.join(bench_dir, "traffic", "poly64.json"), "w") as f:
            json.dump({"driver": "carrier_pigeon"}, f)
    else:
        path = os.path.join(bench_dir, "layer_metrics", "ring_waits_per_frame.json")
        spec = json.load(open(path))
        spec["unit"] = "furlongs"
        json.dump(spec, open(path, "w"))
    with pytest.raises(manifest.ManifestError, match=match):
        manifest.load_cell(name, root, bench_dir)
