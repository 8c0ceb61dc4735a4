"""The plain reference against the program, and the learner drivers
end to end at a tiny size on the CPU (control flow, not speed)."""

import json

import numpy as np
import pytest

from perfbench import common, manifest
from perfbench.drivers import learner as learner_driver


def _tiny(workload, **config):
    cell = manifest.load_cell(workload)
    return cell._replace(
        config=dict(cell.config, unroll_length=3, batch_size=4, **config),
        traffic=dict(cell.traffic, reference_rows=2, warmup_steps=2),
    )


@pytest.mark.parametrize(
    "workload", ["deep_lstm.learner", "deep_x4_lstm.learner"]
)
def test_reference_agrees_with_the_program(workload):
    """Same seeded weights, same rows: on the CPU both compute in f32,
    so they agree to f32 rounding — far inside the chip's tolerance."""
    import jax

    cell = _tiny(workload)
    *_, check = learner_driver.build(cell, 2**31 + 5, jax.devices()[:1])
    report = check(first_step_loss=None)
    assert report["ok"]
    assert report["rel_diff"] < 1e-5
    assert np.isfinite(report["reference_loss"])


def test_reference_sees_a_wrong_program():
    """A program that dropped the done-masking of the LSTM state would
    not pass: the reference, given a batch with no episode ends marked
    where the program's has them, disagrees beyond the tolerance."""
    import importlib

    import jax

    cell = _tiny("deep_lstm.learner")
    _, params, _, batch, state, _ = learner_driver.build(
        cell, 7, jax.devices()[:1]
    )
    reference = importlib.import_module(
        "perfbench.reference." + cell.config["reference"]
    )
    batch = dict(batch, done=batch["done"].at[1].set(True))
    unmasked = dict(batch, done=np.zeros_like(batch["done"]))
    a, scale = map(
        float, reference.loss_and_scale(params, batch, state, cell.config)
    )
    b = float(reference.loss(params, unmasked, state, cell.config))
    assert scale >= abs(a)
    assert abs(a - b) / scale > learner_driver.REFERENCE_RTOL


@pytest.mark.parametrize("workload,chips,ahead", [
    ("deep_lstm.learner", 1, None), ("deep_lstm.learner_dp4", 4, None),
    ("deep_lstm.learner", 1, 1),
])
def test_learner_cell_runs_end_to_end(workload, chips, ahead, monkeypatch):
    """`ahead` None is the traffic file's own depth of the queue."""
    import jax

    monkeypatch.setattr(common, "device_report", lambda devices: {
        "platform": devices[0].platform, "kind": "TPU v5 lite",
        "count": len(devices), "memory_peak_bytes": 2**30,
    })
    cell = _tiny(workload)
    if ahead is not None:
        cell = cell._replace(traffic=dict(cell.traffic, steps_ahead=ahead))
    meter = common.CompileMeter()
    result = learner_driver.run(
        cell, 11, 1.0, False, jax.devices()[:chips], meter
    )
    assert result["correct"], result["notes"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    frames = result["attempted"] * 3 * 4
    assert result["end_to_end"]["learn_frames_per_s"] == pytest.approx(
        frames / result["notes"]["window_s"]
    )
    assert result["end_to_end"]["setup_s"] > 0
    assert result["notes"]["steps_ahead"] == cell.traffic["steps_ahead"]
    assert result["notes"]["host_gap_max_s"] > 0
    assert result["facts"]["values"]["window_compiles"] == 0
    assert result["facts"]["trace"] is None
    if chips == 4:
        assert result["notes"]["check"]["dp"]["rel_diff"] < 1e-4
    json.dumps(result["end_to_end"])


def test_batch_is_the_seeds():
    import jax

    make = jax.jit(
        learner_driver._make_batch, static_argnums=(1, 2, 3, 4)
    )
    a = make(jax.random.PRNGKey(2**31 + 1), 3, 2, 6, (8, 8, 4))
    b = make(jax.random.PRNGKey(2**31 + 1), 3, 2, 6, (8, 8, 4))
    c = make(jax.random.PRNGKey(2**31 + 2), 3, 2, 6, (8, 8, 4))
    assert a["frame"].dtype == np.uint8 and a["frame"].shape == (3, 2, 8, 8, 4)
    assert a["done"].dtype == bool and a["action"].max() < 6
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["frame"], c["frame"])
