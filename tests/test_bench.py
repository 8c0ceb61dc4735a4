"""bench.py's contract: one process; no accelerator means a non-zero
exit and no metric line; BENCH_FORCE_CPU=1 is the labelled CPU
rehearsal; a device that is not in the peaks tables is an error, and a
cost analysis that fails is not turned into a hole in the line."""

import json
import os
import subprocess
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import bench  # noqa: E402


def test_base_result_schema():
    r = bench._base_result(platform="cpu", note="x")
    assert set(r) == {
        "metric", "value", "unit", "vs_baseline", "platform", "note",
    }
    assert r["unit"] == "frames/sec/chip"
    assert r["value"] is None and r["vs_baseline"] is None
    assert json.dumps(r).startswith('{"metric"')


def test_no_chip_and_no_force_cpu_exits_nonzero_without_metric_line():
    """The process that finds only the CPU measures nothing: no
    `{"metric"` line on stdout, a reason on stderr, exit code != 0."""
    env = {
        k: v for k, v in os.environ.items() if k != "BENCH_FORCE_CPU"
    }
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=env, capture_output=True, text=True, timeout=120, cwd=REPO,
    )
    assert out.returncode != 0
    assert '{"metric"' not in out.stdout
    assert "no accelerator" in out.stderr


def test_forced_cpu_measures_on_the_cpu_and_says_so(monkeypatch, capsys):
    """BENCH_FORCE_CPU=1 reaches the measurement on the CPU device, and
    what run_bench prints for a CPU device is labelled as such."""
    seen = []
    monkeypatch.setenv("BENCH_FORCE_CPU", "1")
    monkeypatch.setattr(bench, "run_bench", seen.append)
    assert bench.main() == 0
    assert [d.platform for d in seen] == ["cpu"]
    assert os.environ["JAX_PLATFORMS"] == "cpu"
    assert "running on cpu" in capsys.readouterr().err


def test_measurement_line_passes_through_main(monkeypatch, capsys):
    """main() adds nothing to and drops nothing from what the
    measurement prints: its last stdout line is the metric line."""
    line = json.dumps(bench._base_result(
        value=1.0, platform="cpu", step_ms=5.0
    ))
    monkeypatch.setenv("BENCH_FORCE_CPU", "1")
    monkeypatch.setattr(bench, "run_bench", lambda device: print(line))
    assert bench.main() == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last)["step_ms"] == 5.0


@pytest.mark.parametrize(
    "kind,bf16_tflops,hbm_gbps",
    [("TPU v5 lite", 197.0, 819.0), ("TPU v4", 275.0, 1228.0)],
)
def test_known_device_kinds_have_peaks(kind, bf16_tflops, hbm_gbps):
    assert bench._peak_for(kind, bench.PEAK_BF16_TFLOPS) == bf16_tflops
    assert bench._peak_for(kind, bench.PEAK_HBM_GBPS) == hbm_gbps


@pytest.mark.parametrize("kind", ["cpu", "TPU v9 imaginary", ""])
def test_unknown_device_kind_is_an_error(kind):
    """No max(...) over the table, no null MFU: a utilization needs the
    peak of the chip it ran on."""
    with pytest.raises(ValueError, match="peaks tables"):
        bench._peak_for(kind, bench.PEAK_BF16_TFLOPS)
    with pytest.raises(ValueError, match="peaks tables"):
        bench._peak_for(kind, bench.PEAK_HBM_GBPS)


def test_cost_analysis_failure_is_not_swallowed():
    def broken_lower(*args):
        raise RuntimeError("no cost analysis on this backend")

    with pytest.raises(RuntimeError, match="no cost analysis"):
        bench._cost_analysis(types.SimpleNamespace(lower=broken_lower))


def test_cost_analysis_reads_flops_and_bytes():
    import jax
    import jax.numpy as jnp

    x = jnp.ones((64, 64), jnp.float32)
    flops, nbytes = bench._cost_analysis(jax.jit(lambda a: a @ a), x)
    assert flops >= 2 * 64**3 * 0.9
    assert nbytes > 0
