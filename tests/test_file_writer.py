"""FileWriter: dynamic CSV schema, resume-append, metadata
(reference capability: core/file_writer.py — SURVEY.md §5.5)."""

import csv
import json

from torchbeast_tpu.utils import FileWriter, Timings


def read_rows(path):
    with open(path) as f:
        return list(csv.DictReader(f))


def test_basic_logging_and_files(tmp_path):
    fw = FileWriter(xpid="xp", xp_args={"a": 1}, rootdir=str(tmp_path))
    fw.log({"loss": 1.0, "step": 100})
    fw.log({"loss": 0.5, "step": 200})
    fw.close()

    base = tmp_path / "xp"
    rows = read_rows(base / "logs.csv")
    assert len(rows) == 2
    assert rows[0]["loss"] == "1.0"
    assert rows[1]["step"] == "200"
    assert (base / "fields.csv").exists()
    meta = json.loads((base / "meta.json").read_text())
    assert meta["args"] == {"a": 1}
    assert meta["successful"] is True
    assert (tmp_path / "latest").exists()


def test_dynamic_schema_widens(tmp_path):
    fw = FileWriter(xpid="xp", rootdir=str(tmp_path))
    fw.log({"loss": 1.0})
    fw.log({"loss": 0.9, "mean_episode_return": 5.0})
    fw.close()
    rows = read_rows(tmp_path / "xp" / "logs.csv")
    assert rows[0].get("mean_episode_return") in (None, "")
    assert rows[1]["mean_episode_return"] == "5.0"
    # fields.csv records one row per schema version.
    with open(tmp_path / "xp" / "fields.csv") as f:
        versions = list(csv.reader(f))
    assert len(versions) == 2
    assert "mean_episode_return" in versions[1]


def test_resume_continues_tick(tmp_path):
    fw = FileWriter(xpid="xp", rootdir=str(tmp_path))
    fw.log({"loss": 1.0})
    fw.log({"loss": 0.9})
    fw.close()

    fw2 = FileWriter(xpid="xp", rootdir=str(tmp_path))
    fw2.log({"loss": 0.8})
    fw2.close()
    rows = read_rows(tmp_path / "xp" / "logs.csv")
    assert [r["_tick"] for r in rows] == ["0", "1", "2"]


def test_unsuccessful_close(tmp_path):
    fw = FileWriter(xpid="xp", rootdir=str(tmp_path))
    fw.close(successful=False)
    meta = json.loads((tmp_path / "xp" / "meta.json").read_text())
    assert meta["successful"] is False


def test_close_releases_log_handlers(tmp_path):
    """Regression: close() must detach AND close the out.log
    FileHandler — the logger outlives the writer in logging's global
    registry, so long test sessions / multi-writer runs used to
    accumulate one open fd per FileWriter."""
    import logging

    fw = FileWriter(xpid="leak", rootdir=str(tmp_path))
    logger = logging.getLogger("filewriter.leak")
    assert len(logger.handlers) == 1
    handler = logger.handlers[0]
    fw.close()
    assert logger.handlers == []
    assert handler.stream is None or handler.stream.closed

    # Sequential same-xpid writers never stack handlers (the old
    # `if not handlers` guard would have seen the stale one and logged
    # through a closed stream).
    for _ in range(3):
        fw = FileWriter(xpid="leak", rootdir=str(tmp_path))
        assert len(logger.handlers) == 1
        fw.log({"loss": 1.0}, verbose=True)
        fw.close()
    assert logger.handlers == []


def test_telemetry_path_in_paths(tmp_path):
    """The drivers point their JsonLinesExporter at
    paths['telemetry']; it must live under the xpid dir."""
    fw = FileWriter(xpid="xp", rootdir=str(tmp_path))
    assert fw.paths["telemetry"] == str(tmp_path / "xp" / "telemetry.jsonl")
    fw.close()


def test_timings_mean_and_summary():
    import time

    t = Timings()
    for _ in range(3):
        with t.section("a"):
            time.sleep(0.01)
        with t.section("b"):
            time.sleep(0.02)
    means = t.means()
    assert 0.005 < means["a"] < 0.05
    assert means["b"] > means["a"]
    summary = t.summary("prefix: ")
    assert "a:" in summary and "b:" in summary and "%" in summary
    assert set(t.stds()) == {"a", "b"}


def test_schema_widening_preserves_long_history(tmp_path):
    """Late-appearing keys patch the header without losing rows (streamed
    + atomic; regression for the in-memory whole-file rewrite)."""
    fw = FileWriter(xpid="wide", rootdir=str(tmp_path))
    for i in range(500):
        fw.log({"a": i})
    fw.log({"a": 500, "late_key": 1.5})  # widens after many rows
    fw.log({"a": 501, "late_key": 2.5})

    with open(tmp_path / "wide" / "logs.csv") as f:
        rows = list(csv.DictReader(f))
    assert len(rows) == 502
    assert rows[0]["a"] == "0" and rows[0]["late_key"] in ("", None)
    assert rows[-1]["late_key"] == "2.5"

    with open(tmp_path / "wide" / "fields.csv") as f:
        versions = list(csv.reader(f))
    assert versions[-1][-1] == "late_key"
    assert len(versions) == 2  # initial schema + one widening
