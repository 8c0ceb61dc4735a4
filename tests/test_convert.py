"""Checkpoint conversion between transformer layouts (utils/convert.py):
a converted param tree must drive the OTHER model family to bit-for-close
identical outputs (same math, different parameter layout), both ways,
including the carried KV-cache state."""

import jax
import numpy as np
import pytest

from tests import family_scaffold as scaffold
from torchbeast_tpu.models import create_model
from torchbeast_tpu.utils.convert import (
    pipelined_to_transformer,
    transformer_to_pipelined,
)

T, B, A = 4, 3, 5
KW = dict(
    num_actions=A, num_layers=2, d_model=16, num_heads=2, memory_len=4
)


def _inputs(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "frame": rng.integers(0, 256, (T, B, 4, 4, 1), dtype=np.uint8),
        "reward": rng.standard_normal((T, B)).astype(np.float32),
        "done": rng.random((T, B)) < 0.2,
        "last_action": rng.integers(0, A, (T, B)).astype(np.int32),
    }


def _init(model, seed=0):
    return scaffold.init(
        model,
        {
            "params": jax.random.PRNGKey(seed),
            "action": jax.random.PRNGKey(seed + 1),
        },
        _inputs(),
        model.initial_state(B),
    )


def _assert_same_outputs(out_a, state_a, out_b, state_b):
    np.testing.assert_allclose(
        out_b.policy_logits, out_a.policy_logits, rtol=1e-5, atol=1e-6
    )
    np.testing.assert_allclose(
        out_b.baseline, out_a.baseline, rtol=1e-5, atol=1e-6
    )
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
        ),
        state_a,
        state_b,
    )


@pytest.mark.slow
def test_transformer_to_pipelined_same_outputs():
    seq = create_model("transformer", **KW)
    pipe = create_model("pipelined_transformer", **KW)
    params = _init(seq, seed=10)
    converted = transformer_to_pipelined(params)
    # Structure check: the converted tree is exactly what the pipelined
    # model would create.
    ref = _init(pipe, seed=99)
    assert jax.tree_util.tree_structure(
        converted
    ) == jax.tree_util.tree_structure(ref)
    inputs, state = _inputs(seed=3), seq.initial_state(B)
    out_s, st_s = scaffold.forward(seq)(params, inputs, state)
    out_p, st_p = scaffold.forward(pipe)(converted, inputs, state)
    _assert_same_outputs(out_s, st_s, out_p, st_p)


def test_pipelined_to_transformer_roundtrip():
    pipe = create_model("pipelined_transformer", **KW)
    seq = create_model("transformer", **KW)
    params = _init(pipe, seed=20)
    converted = pipelined_to_transformer(params)
    inputs, state = _inputs(seed=4), pipe.initial_state(B)
    out_p, st_p = scaffold.forward(pipe)(params, inputs, state)
    out_s, st_s = scaffold.forward(seq)(converted, inputs, state)
    _assert_same_outputs(out_p, st_p, out_s, st_s)
    # Round trip is the identity.
    back = transformer_to_pipelined(converted)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)
        ),
        back,
        params,
    )


def test_moe_blocks_refuse_conversion():
    model = create_model("transformer", num_experts=4, **KW)
    params = _init(model, seed=30)
    with pytest.raises(ValueError, match="MoE"):
        transformer_to_pipelined(params)


@pytest.mark.slow
def test_checkpoint_cli_roundtrip_through_driver(tmp_path):
    """Full workflow: train the pipelined transformer in the sync driver,
    convert the CHECKPOINT FILE (params + optimizer moments + recorded
    model flag) to the sequential layout, then (a) evaluate it and
    (b) resume TRAINING it as a TransformerNet — proving the optimizer
    state mapped, not just the params."""
    from torchbeast_tpu import monobeast
    from torchbeast_tpu.utils.convert import convert_checkpoint

    def flags_for(model, xpid, total_steps, **over):
        argv = [
            "--env", "Mock", "--model", model, "--xpid", xpid,
            "--num_actors", "2", "--batch_size", "2",
            "--unroll_length", "5", "--total_steps", str(total_steps),
            "--savedir", str(tmp_path), "--serial_envs",
            "--checkpoint_interval_s", "100000",
        ]
        for k, v in over.items():
            argv += [f"--{k}", str(v)]
        return monobeast.make_parser().parse_args(argv)

    # TransformerNet's default depth is 2 — build the pipelined tower to
    # match so the flag-constructed eval model lines up.
    stats = monobeast.train(
        flags_for(
            "pipelined_transformer", "src", 40, pipeline_stages=2
        )
    )
    assert stats["step"] >= 40

    src = tmp_path / "src" / "model.ckpt"
    dst = tmp_path / "dst" / "model.ckpt"
    # Drive the real CLI entry point, not just the library function.
    import subprocess
    import sys

    out = subprocess.run(
        [sys.executable, "-m", "torchbeast_tpu.utils.convert",
         "--input", str(src), "--output", str(dst),
         "--to", "sequential"],
        capture_output=True, text=True, timeout=300,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, out.stdout + out.stderr
    # Wrong direction / wrong checkpoint refuses loudly, writes nothing.
    with pytest.raises(ValueError, match="nothing was written"):
        convert_checkpoint(str(src), str(tmp_path / "x.ckpt"),
                           to="pipelined")
    assert not (tmp_path / "x.ckpt").exists()

    returns = monobeast.test(
        flags_for("transformer", "dst", 40, mode="test",
                  num_test_episodes="2")
    )
    assert len(returns) == 2

    # Resume TRAINING under the sequential layout from the converted
    # checkpoint (loads converted opt_state onto the optax template).
    stats2 = monobeast.train(flags_for("transformer", "dst", 80))
    assert stats2["step"] >= 80
