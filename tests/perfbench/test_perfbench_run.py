"""The command: it fails without a chip, and what it shares."""

import json
import os
import subprocess
import sys

import pytest

from perfbench import common, manifest
from perfbench import run as run_module
from perfbench.drivers import stubs

BENCH = manifest.load_benchmark()


@pytest.mark.parametrize(
    "workload", ["deep_lstm.learner", "deep_lstm.poly"]
)
def test_run_fails_on_the_cpu_and_prints_no_result(workload):
    """JAX_PLATFORMS=cpu: no fall-back, no metric line, exit code 1."""
    proc = subprocess.run(
        BENCH["command"] + ["--workload", workload, "--seed", "3000000011",
                            "--seconds", "1", "--trace", "0"],
        cwd=manifest.ROOT, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode == 1
    assert "no TPU" in proc.stderr
    for line in proc.stdout.splitlines():
        assert "metrics" not in line


def test_unknown_workload_fails():
    proc = subprocess.run(
        BENCH["command"] + ["--workload", "nope", "--seed", "1",
                            "--seconds", "1", "--trace", "0"],
        cwd=manifest.ROOT, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode == 1 and proc.stdout == ""


def test_claim_devices_wants_enough_chips(monkeypatch):
    import jax

    class Fake:
        platform = "tpu"

    monkeypatch.setattr(jax, "devices", lambda: [Fake()])
    assert len(run_module.claim_devices(1)) == 1
    with pytest.raises(RuntimeError, match="asks for 4 chips"):
        run_module.claim_devices(4)


@pytest.mark.parametrize("driver", ["mono", "anakin"])
def test_stub_drivers_fail_loudly(driver):
    assert manifest.DRIVERS[driver] == "perfbench.drivers.stubs"
    cell = manifest.load_cell("deep_lstm.learner")
    cell = cell._replace(traffic=dict(cell.traffic, driver=driver))
    with pytest.raises(NotImplementedError, match=driver):
        stubs.run(cell, 0, 1.0, False, [], None)


@pytest.mark.parametrize("driver", sorted(manifest.DRIVERS))
def test_every_named_driver_has_a_module(driver):
    import importlib

    module = importlib.import_module(manifest.DRIVERS[driver])
    assert callable(module.run)


def test_seconds_since_process_start_counts_the_interpreter():
    code = (
        "import time, sys; t = time.monotonic(); time.sleep(0.3);"
        f"sys.path.insert(0, {manifest.ROOT!r});"
        "from perfbench import common;"
        "print(common.seconds_since_process_start())"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=60,
    )
    assert 0.3 <= float(out.stdout) < 30


def test_work_dir_is_ignored_by_git():
    with open(os.path.join(manifest.ROOT, ".gitignore")) as f:
        ignored = f.read().split()
    assert os.path.basename(common.WORK_DIR) + "/" in ignored
    assert ".jax_cache/" in ignored and "chiprun_out/" in ignored


def test_layer_metrics_leave_out_what_cannot_be_read():
    cell = manifest.load_cell("deep_lstm.learner")
    facts = {"values": {"flops_per_step": 1e12, "steps_per_s": 17.0,
                        "peak_flops": 197e12, "chips": 1}, "trace": None}
    got = run_module.layer_metrics(cell, facts)
    assert set(got) == {"mfu_pct.learn"}
    assert got["mfu_pct.learn"]["unit"] == "%"
    json.dumps(got)


@pytest.mark.parametrize("stats,want", [
    # The v5e's runtime: a program's temporaries are a reservation.
    ({"peak_bytes_in_use": 225346048, "peak_bytes_reserved": 3048898560},
     3274244608),
    # A runtime that reports no reservation.
    ({"peak_bytes_in_use": 6500000000}, 6500000000),
])
def test_memory_peak_counts_reserved_temporaries(stats, want):
    assert common.memory_peak_bytes(stats) == want
