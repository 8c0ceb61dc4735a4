"""The poly driver end to end at a tiny size on the CPU. Slow: it
builds the native runtime and runs polybeast with spawned servers."""

import os
import subprocess
import sys
import textwrap

import pytest

from perfbench import manifest

SCRIPT = textwrap.dedent("""
    import json, sys
    sys.path.insert(0, {root!r})

    def main():
        import jax
        from perfbench import common, manifest
        from perfbench.drivers import poly

        common.device_report = lambda devices: {{
            "platform": "cpu", "kind": "none", "count": 1,
            "memory_peak_bytes": 1,
        }}
        cell = manifest.load_cell("deep_lstm.poly")
        traffic = dict(cell.traffic, num_actors=4, num_servers=2)
        traffic["window"] = dict(
            traffic["window"], min_updates=2, steady_seconds=2,
            steady_share=0.9, max_wait_s=30,
        )
        cell = cell._replace(
            config=dict(cell.config, unroll_length=4, batch_size=4),
            traffic=traffic,
        )
        result = poly.run(
            cell, 5, 3.0, False, jax.devices()[:1], common.CompileMeter()
        )
        result.pop("facts")
        print(json.dumps(result))

    if __name__ == "__main__":
        main()
""")


@pytest.mark.slow
def test_poly_cell_runs_and_leaves_nothing_behind(tmp_path):
    script = tmp_path / "poly_tiny.py"
    script.write_text(SCRIPT.format(root=manifest.ROOT))
    proc = subprocess.run(
        [sys.executable, str(script)], capture_output=True, text=True,
        timeout=900, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        cwd=manifest.ROOT,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    import json

    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"], result["notes"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["end_to_end"]["env_frames_per_s"] > 0
    assert result["notes"]["shm_unlinked_at_exit"] == []
    assert len(result["series"]["env_steps_per_s"]) >= 2
    from perfbench.drivers import poly

    assert poly.tagged_processes() == []
