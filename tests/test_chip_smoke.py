"""chip_smoke.py on the CPU: it must fail without a chip, its last line
and phase selection are pinned, and every phase function runs here at a
tiny size — called directly, so the script needs no rehearsal switch. The phases that compile the deep ResNet+LSTM
or build the native extension take tens of seconds each on the CPU and
are `slow`; the chip run itself is their real test.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TINY = dict(t=3, b=2)


def test_without_a_chip_the_script_fails_and_says_ok_false():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120, cwd=REPO,
    )
    assert proc.returncode != 0
    lines = proc.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["ok"] is False and last["failed_phase"] == "device"
    first = json.loads(lines[0])
    assert first["phase"] == "device" and first["ok"] is False
    assert "no TPU" in first["error"]
    assert not any('"ok": true' in ln for ln in lines)


def _device_phase():
    return {"platform": "tpu", "device_kind": "TPU v5 lite", "count": 1}


def test_last_line_schema_and_phase_lines(capsys):
    code, last = chip_smoke.run(
        [("device", _device_phase), ("later", lambda: {"n": 3})]
    )
    assert code == 0
    # Exactly these keys, in this order: the driver reads them.
    assert json.dumps(last) == (
        '{"ok": true, "device": {"platform": "tpu", '
        '"kind": "TPU v5 lite", "count": 1}}'
    )
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    assert [ln.get("phase") for ln in lines] == ["device", "later", None]
    for ln in lines[:2]:
        assert ln["ok"] is True and ln["seconds"] >= 0
    assert lines[1]["checked"] == {"n": 3}
    assert lines[2]["phases"] == ["device", "later"]
    assert "total_seconds" in lines[2]


def test_a_failed_phase_stops_the_run(capsys):
    ran = []

    def boom():
        raise ValueError("wrong answer")

    code, last = chip_smoke.run([
        ("device", _device_phase), ("bad", boom),
        ("never", lambda: ran.append(1)),
    ])
    assert code == 1 and ran == []
    assert last["ok"] is False and last["failed_phase"] == "bad"
    lines = capsys.readouterr().out.splitlines()
    failed = json.loads(lines[-1])
    assert failed["phase"] == "bad" and failed["ok"] is False
    assert "ValueError: wrong answer" in failed["error"]


def test_phase_selection(tmp_path):
    names = lambda chips: [  # noqa: E731
        n for n, _ in chip_smoke.phases_for(chips, str(tmp_path), 0)
    ]
    assert names(1) == [
        "device", "native_build", "learner", "mono", "poly", "anakin",
    ]
    # Four chips: the multi-chip paths and what they need, nothing else.
    assert names(4) == ["device", "native_build", "dp4", "split"]


def test_device_phase_refuses_the_cpu():
    with pytest.raises(RuntimeError, match="no TPU"):
        chip_smoke.phase_device(1)


def test_anakin_phase_tiny(tmp_path):
    checked = chip_smoke.phase_anakin(str(tmp_path), updates=20)
    assert checked["step"] == 20 * 64 * 16
    assert np.isfinite(checked["total_loss"])


class _ProbeEnv:
    """Reports, as its observation, what the process it lives in knows
    about JAX: [JAX_PLATFORMS == "cpu", a backend is initialised]."""

    class _Space:
        n = 2

    action_space = _Space()

    def _obs(self):
        from jax._src import xla_bridge

        return np.asarray(
            [os.environ.get("JAX_PLATFORMS") == "cpu",
             xla_bridge.backends_are_initialized()],
            np.uint8,
        )

    def reset(self, **kwargs):
        return self._obs(), {}

    def step(self, action):
        return self._obs(), 0.0, False, False, {}

    def close(self):
        pass


def test_env_workers_start_cpu_pinned_and_never_touch_a_backend(
    monkeypatch,
):
    """The parent holds whatever device it has; its spawned env workers
    are handed JAX_PLATFORMS=cpu whatever the parent's own environment
    says, and stepping an env initialises no backend in them."""
    from torchbeast_tpu.envs.vec import ProcessEnvPool

    jax.devices()  # the parent's backend is up, as a driver's would be
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    pool = ProcessEnvPool([_ProbeEnv])
    try:
        pool.initial()
        frame = pool.step([0])["frame"][0]
    finally:
        pool.close()
    assert frame.tolist() == [1, 0]
    assert os.environ["JAX_PLATFORMS"] == "tpu,cpu"  # parent's restored


@pytest.mark.slow
def test_native_build_phase(tmp_path, monkeypatch):
    from torchbeast_tpu.runtime import native

    # The phase must import ITS build: drop whatever this process holds.
    monkeypatch.setattr(native, "_cached", False)
    monkeypatch.setattr(native, "_module", None)
    monkeypatch.delitem(sys.modules, "_tbt_core", raising=False)
    monkeypatch.setattr(sys, "path", list(sys.path))
    checked = chip_smoke.phase_native_build(str(tmp_path))
    assert checked["module"].startswith("native/_tbt_core")
    assert checked["api_version"] >= checked["required_api_version"]


@pytest.mark.slow
def test_learner_phase_tiny():
    checked = chip_smoke.phase_learner(
        steps=3, ref_t=3, ref_b=2, **TINY
    )
    assert set(checked) == {"f32", "bf16_train"}
    assert checked["f32"]["cpu_parity"]["rel_diff"] == 0.0


@pytest.mark.slow
def test_mono_phase_tiny(tmp_path):
    checked = chip_smoke.phase_mono(str(tmp_path), t=4, b=2, updates=3)
    assert checked["learner_updates"] >= 3
    assert checked["checkpoint_reloaded"] is True


@pytest.mark.slow
def test_poly_phase_tiny(tmp_path):
    from torchbeast_tpu.runtime import native

    if native.gap_reason() is not None:
        pytest.skip("the poly phase demands the native runtime; "
                    "build it first (python setup.py build_ext --inplace)")
    checked = chip_smoke.phase_poly(
        str(tmp_path), t=4, b=2, updates=3, actors=4, servers=2
    )
    assert checked["health"] == "HEALTHY"
    assert checked["state_table_dispatches"] > 0


@pytest.mark.slow
def test_dp_phase_on_four_virtual_devices():
    checked = chip_smoke.phase_dp(4, t=2, b=4)
    assert checked["mesh_device_ids"] == [0, 1, 2, 3]
    assert checked["grad_norm"]["rel_diff"] <= 1e-5
    assert checked["update"]["rel_l2_diff"] <= 1e-3


@pytest.mark.slow
def test_split_phase_on_four_virtual_devices(tmp_path, monkeypatch):
    from torchbeast_tpu.runtime import native

    if native.gap_reason() is not None:
        pytest.skip("the split phase demands the native runtime; "
                    "build it first (python setup.py build_ext --inplace)")
    four = jax.devices()[:4]
    monkeypatch.setattr(jax, "devices", lambda *a: four)
    checked = chip_smoke.phase_split(
        str(tmp_path), t=4, b=3, updates=3, actors=4, servers=2
    )
    assert checked["inference_device_ids"] == [0]
    assert checked["learner_device_ids"] == [1, 2, 3]
