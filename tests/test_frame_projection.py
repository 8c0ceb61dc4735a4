"""The frame projection on integers (models/transformer.py
`frame_projection`): a uint8 frame enters `Dense_0` as the bfloat16
integers it is, the kernel in as many bfloat16 terms as the traced
precision states, the range's scale and shift on the result.

On the CPU the model takes the float expression (as the grouped matmul
keeps its float32 kernel there), so the cases that want the chip's
arrangement call `frame_projection` with `terms` forced, or trace the
plain transformer with `jax.default_backend` saying "tpu" (bfloat16 x
bfloat16 -> float32 dots run on the CPU too). Every function under test
is jitted, once a shape (tests/family_scaffold.py, THE RULE).
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests import family_scaffold as scaffold
from torchbeast_tpu.models import stats as stats_lib
from torchbeast_tpu.models import transformer
from torchbeast_tpu.models.transformer import TransformerNet

T, B, A = 5, 3, scaffold.A
FRAME = (8, 8, 4)
F, D = 256, 32
RANGES = {"unit": (0.0, 1.0), "centred": (-1.0, 1.0), "skew": (-0.5, 1.5)}
# What a product at `terms` bfloat16 terms of ONE operand owes, as a
# share of the product of the magnitudes.
BOUND = {1: 2.0**-8, 2: 2.0**-15, 3: 2.0**-21}
PRECISION = {1: None, 2: "high", 3: "highest"}


def _frames(seed, t=T, rows=B):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 256, (t, rows) + FRAME, dtype=np.uint8)


def _inputs(seed, t=T, rows=B, frame=None):
    rng = np.random.default_rng(seed + 1)
    return {
        "frame": jnp.asarray(_frames(seed, t, rows) if frame is None else frame),
        "reward": jnp.asarray(rng.standard_normal((t, rows)), jnp.float32),
        "done": jnp.zeros((t, rows), bool),
        "last_action": jnp.asarray(rng.integers(0, A, (t, rows))),
    }


def _net(**fields):
    return TransformerNet(
        num_actions=A, d_model=D, num_heads=4, num_layers=1, memory_len=4,
        **fields,
    )


def _params(model, rows=B):
    return scaffold.init_params(model, _inputs(0, rows=rows))


def _on_the_chip(monkeypatch):
    """The branch the chip takes, traced here: ask for a FRESH jitted
    function after it (a trace is cached by function)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


@pytest.mark.parametrize("terms", [1, 2, 3])
@pytest.mark.parametrize("frame_range", list(RANGES))
def test_product_on_integers_is_within_what_its_terms_owe(frame_range, terms):
    """Values, the kernel's gradient and the bias's against a float64
    product of the scaled frames, for a unit, a symmetric and a skew
    range: each within `terms`' bound of the product of the magnitudes
    (the frames are exact, so the error is the kernel's and the
    cotangent's rounding alone)."""
    low, high = RANGES[frame_range]
    frame = _frames(3)
    keys = jax.random.split(jax.random.PRNGKey(terms), 3)
    kernel = jax.random.normal(keys[0], (F, D)) * F ** -0.5
    bias = jax.random.normal(keys[1], (D,))
    dy = jax.random.normal(keys[2], (B, T, D))

    @jax.jit
    def run(frame, kernel, bias, dy):
        y, pull = jax.vjp(
            lambda k, b: transformer.frame_projection(
                frame, k, b, (low, high), terms
            ),
            kernel, bias,
        )
        return (y,) + pull(dy)

    y, d_kernel, d_bias = run(jnp.asarray(frame), kernel, bias, dy)
    assert y.shape == (B, T, D) and y.dtype == jnp.float32
    x = low + (high - low) * frame.reshape(T, B, F).astype(np.float64) / 255
    x = x.transpose(1, 0, 2).reshape(B * T, F)
    k64 = np.asarray(kernel, np.float64)
    dy64 = np.asarray(dy, np.float64).reshape(B * T, D)
    exact = x @ k64 + np.asarray(bias, np.float64)
    owed = BOUND[terms] * (np.abs(x) @ np.abs(k64) + 1.0)
    assert np.all(np.abs(np.asarray(y).reshape(B * T, D) - exact) <= owed)
    owed = BOUND[terms] * (np.abs(x).T @ np.abs(dy64))
    assert np.all(np.abs(np.asarray(d_kernel) - x.T @ dy64) <= owed)
    np.testing.assert_allclose(d_bias, dy64.sum(0), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("frame_range", list(RANGES))
def test_every_uint8_value_survives_the_cast_to_bfloat16(frame_range):
    """All 256 values, and the operand made of them (u, or 2u - 255
    where the range is symmetric), are exact in bfloat16's eight
    significant bits; `scale * operand + shift` is the range's value."""
    low, high = RANGES[frame_range]
    u = np.arange(256, dtype=np.uint8).reshape(1, 1, 256)
    integers_of = jax.jit(
        lambda u: transformer.frame_integers(u, (low, high))
    )
    operand = integers_of(u)
    scale, shift = transformer.integers_to_range((low, high))
    assert operand.dtype == jnp.bfloat16
    integers = np.asarray(operand, np.float64).reshape(256)
    values = np.arange(256, dtype=np.float64)
    want = 2 * values - 255 if low + high == 0 else values
    np.testing.assert_array_equal(integers, want)
    assert (shift == 0.0) == (low + high == 0 or low == 0)
    np.testing.assert_allclose(
        scale * integers + shift, low + (high - low) * values / 255,
        rtol=0, atol=1e-15,
    )


def test_float_frame_takes_the_float_path_bit_for_bit(monkeypatch):
    """A frame that is no uint8 goes through `Dense_0` itself, handed
    the expression it always was (cast, / 255, the range, merged
    time-major), on the chip's branch too; a uint8 one there does not
    call the module at all."""
    _on_the_chip(monkeypatch)
    model = _net(frame_range=(-1.0, 1.0))
    params = _params(model)
    floats = _inputs(5, frame=_frames(5).astype(np.float32))

    def first_layer(inputs):
        _, kept = model.apply(
            params, inputs, model.initial_state(B), sample_action=False,
            capture_intermediates=lambda module, _: module.name == "Dense_0",
            mutable=["intermediates"],
        )
        return kept.get("intermediates", {})

    first_layer = jax.jit(first_layer)
    kept = first_layer(floats)

    @jax.jit
    def as_it_was(frame):
        x = frame.reshape((T * B, -1)).astype(jnp.float32) / 255.0
        return nn.Dense(D).apply(
            {"params": params["params"]["Dense_0"]}, -1.0 + 2.0 * x
        )

    want = as_it_was(floats["frame"])
    np.testing.assert_array_equal(kept["Dense_0"]["__call__"][0], want)
    assert "Dense_0" not in first_layer(_inputs(5))


class _FirstLayerAsItWas(nn.Module):
    """`Dense_0` as the float expression made it, at the root."""

    width: int

    @nn.compact
    def __call__(self, frame):
        rows = frame.shape[0] * frame.shape[1]
        return nn.Dense(self.width)(
            frame.reshape((rows, -1)).astype(jnp.float32) / 255.0
        )


@pytest.mark.parametrize("family", ["olmoe", "qwen3next", "transformer"])
def test_first_layer_parameters_are_what_they_were(family, monkeypatch):
    """`params/Dense_0/kernel [F, d]` and `bias [d]`, float32, from the
    same initialisers on the same RNG path: a checkpoint written before
    the change loads, and the references read `p["Dense_0"]`."""
    if family == "transformer":
        _on_the_chip(monkeypatch)  # init makes them on either branch
        model, batch = _net(), _inputs(0)
        params = _params(model)
    else:
        model, params = scaffold.build(family)
        batch = scaffold.inputs(0, t=scaffold.FAMILIES[family].t)
    columns = int(np.prod(batch["frame"].shape[2:]))
    got = params["params"]["Dense_0"]
    assert sorted(got) == ["bias", "kernel"]
    assert got["kernel"].shape == (columns, model.d_model)
    assert got["bias"].shape == (model.d_model,)
    assert {leaf.dtype for leaf in got.values()} == {jnp.dtype("float32")}
    want = scaffold.init(
        _FirstLayerAsItWas(model.d_model),
        {"params": jax.random.PRNGKey(0)}, batch["frame"],
    )["params"]["Dense_0"]
    np.testing.assert_array_equal(got["kernel"], want["kernel"])
    np.testing.assert_array_equal(got["bias"], want["bias"])


def _forward(model):
    return jax.jit(lambda params, inputs, state: model.apply(
        params, inputs, state, sample_action=False
    ))


def test_integer_path_agrees_with_the_float_path(monkeypatch):
    """The same frames as uint8 (the integers, at `highest`'s three
    terms) and as float32 (the float expression at `highest`) give the
    same logits to float32's rounding."""
    _on_the_chip(monkeypatch)
    model = _net(frame_range=(-1.0, 1.0))
    params, inputs = _params(model), _inputs(11)

    def logits(inputs):
        with jax.default_matmul_precision("highest"):
            out, _ = model.apply(
                params, inputs, model.initial_state(B), sample_action=False
            )
        return out.policy_logits

    logits = jax.jit(logits)
    floats = dict(inputs, frame=inputs["frame"].astype(jnp.float32))
    np.testing.assert_allclose(
        logits(inputs), logits(floats), rtol=1e-5, atol=1e-6
    )


def test_acting_and_learning_agree_on_the_integer_path(monkeypatch):
    """T = 1 act steps and the learner's T = 5 forward over the same
    uint8 frames, both on the chip's branch: the same logits and the
    same state, as the float path's (tests/test_transformer.py)."""
    _on_the_chip(monkeypatch)
    model = _net()
    params, batch = _params(model), _inputs(13)
    forward = _forward(model)
    state = model.initial_state(B)
    full, full_state = forward(params, batch, state)
    logits = []
    for t in range(T):
        step = {k: v[t : t + 1] for k, v in batch.items()}
        out, state = forward(params, step, state)
        logits.append(out.policy_logits[0])
    np.testing.assert_allclose(
        np.stack(logits), full.policy_logits, rtol=2e-4, atol=2e-5
    )
    for got, want in zip(
        jax.tree_util.tree_leaves(state),
        jax.tree_util.tree_leaves(full_state),
    ):
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def _sown(model, params, inputs, precision=None):
    def run(inputs):
        with jax.default_matmul_precision(precision):
            _, sown = model.apply(
                params, inputs, model.initial_state(B), sample_action=False,
                mutable=list(stats_lib.COLLECTIONS),
            )
        return stats_lib.folded(sown)

    run = jax.jit(run)
    return run(inputs)


@pytest.mark.parametrize("terms", [1, 2, 3])
def test_counters_say_the_path_and_the_terms(terms, monkeypatch):
    """`obs_integer_applications` 1 and `obs_weight_terms` what the
    traced precision states, on the uint8 path; polybeast gauges them
    as `obs.*`."""
    _on_the_chip(monkeypatch)
    model = _net()
    sown = _sown(model, _params(model), _inputs(17), PRECISION[terms])
    assert float(sown["obs_integer_applications"]) == 1.0
    assert float(sown["obs_weight_terms"]) == terms
    assert stats_lib.gauge_name("obs_weight_terms") == "obs.weight_terms"


def test_half_width_compute_reads_the_kernel_in_one_term(monkeypatch):
    _on_the_chip(monkeypatch)
    model = _net(dtype=jnp.bfloat16)
    sown = _sown(model, _params(model), _inputs(17), "high")
    assert float(sown["obs_weight_terms"]) == 1.0


@pytest.mark.parametrize("where", ["float_frame_on_the_chip", "off_the_chip"])
def test_no_counter_on_the_float_path(where, monkeypatch):
    """Absent, not zero: a float frame anywhere, any frame off the
    chip."""
    inputs = _inputs(17)
    if where == "float_frame_on_the_chip":
        _on_the_chip(monkeypatch)
        inputs = dict(inputs, frame=inputs["frame"].astype(jnp.float32))
    model = _net()
    sown = _sown(model, _params(model), inputs)
    assert not [name for name in sown if name.startswith("obs_")]
