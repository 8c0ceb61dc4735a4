"""Model output shapes/signatures with and without LSTM, initial_state
shapes, sampling determinism, and LSTM done-reset semantics
(reference strategy: tests/polybeast_net_test.py:44-85 plus the agent-state
reset invariants of tests/core_agent_state_test.py)."""

import numpy as np
import pytest

import flax.linen as nn
import jax
import jax.numpy as jnp

from tests import family_scaffold as scaffold
from torchbeast_tpu import learner as learner_lib
from torchbeast_tpu.models import AtariNet, LSTMCore, ResNet, create_model
from torchbeast_tpu.types import AgentOutput

T, B, H, W, C = 4, 2, 84, 84, 4
NUM_ACTIONS = 6


def make_inputs(rng_seed=0, t=T, b=B):
    rng = np.random.default_rng(rng_seed)
    return {
        "frame": jnp.asarray(
            rng.integers(0, 256, size=(t, b, H, W, C), dtype=np.uint8)
        ),
        "reward": jnp.asarray(rng.standard_normal((t, b)).astype(np.float32)),
        "done": jnp.zeros((t, b), dtype=bool),
        "last_action": jnp.asarray(rng.integers(0, NUM_ACTIONS, size=(t, b))),
    }


@pytest.mark.parametrize("model_cls", [AtariNet, ResNet])
@pytest.mark.parametrize("use_lstm", [False, True])
def test_forward_shapes(model_cls, use_lstm):
    model = model_cls(num_actions=NUM_ACTIONS, use_lstm=use_lstm)
    inputs = make_inputs()
    core_state = model.initial_state(B)
    params = scaffold.init(
        model,
        {"params": jax.random.PRNGKey(0), "action": jax.random.PRNGKey(1)},
        inputs,
        core_state,
    )
    out, new_state = scaffold.apply(model)(
        params, inputs, core_state, rngs={"action": jax.random.PRNGKey(2)}
    )
    assert isinstance(out, AgentOutput)
    assert out.action.shape == (T, B)
    assert out.action.dtype == jnp.int32
    assert out.policy_logits.shape == (T, B, NUM_ACTIONS)
    assert out.baseline.shape == (T, B)
    if use_lstm:
        num_layers = 2 if model_cls is AtariNet else 1
        hidden = (
            512 + NUM_ACTIONS + 1 if model_cls is AtariNet else 256
        )
        for s in new_state:
            assert s.shape == (num_layers, B, hidden)
    else:
        assert new_state == ()


def test_initial_state_shapes():
    net = AtariNet(num_actions=NUM_ACTIONS, use_lstm=True)
    h, c = net.initial_state(batch_size=3)
    assert h.shape == (2, 3, 512 + NUM_ACTIONS + 1)
    assert (h == 0).all() and (c == 0).all()
    assert AtariNet(num_actions=NUM_ACTIONS).initial_state(3) == ()


def test_argmax_is_deterministic_and_sampling_varies():
    model = AtariNet(num_actions=NUM_ACTIONS)
    inputs = make_inputs()
    params = scaffold.init(
        model,
        {"params": jax.random.PRNGKey(0), "action": jax.random.PRNGKey(1)},
        inputs,
        (),
    )
    # Greedy path needs no action rng and is reproducible (reference eval
    # path, monobeast.py:621-623).
    greedy = scaffold.forward(model)
    out1, _ = greedy(params, inputs, ())
    out2, _ = greedy(params, inputs, ())
    np.testing.assert_array_equal(out1.action, out2.action)
    np.testing.assert_array_equal(
        out1.action, jnp.argmax(out1.policy_logits, axis=-1)
    )
    # Sampling path: different rng keys must give different action sequences
    # (with T*B=8 draws from 6 near-uniform actions, a collision across all
    # draws is astronomically unlikely).
    s1, _ = scaffold.apply(model)(
        params, inputs, (), rngs={"action": jax.random.PRNGKey(10)}
    )
    s2, _ = scaffold.apply(model)(
        params, inputs, (), rngs={"action": jax.random.PRNGKey(11)}
    )
    assert not np.array_equal(s1.action, s2.action)


def test_lstm_core_done_resets_state():
    # With done=True at every step and identical inputs, every step output
    # must be identical (state resets to zero before each step).
    core = LSTMCore(hidden_size=8, num_layers=2)
    inp = jnp.broadcast_to(jnp.arange(5.0), (6, 3, 5))
    notdone = jnp.zeros((6, 3))
    state = core.initial_state(3)
    params = scaffold.init(core, jax.random.PRNGKey(0), inp, notdone, state)
    out, _ = scaffold.apply(core)(params, inp, notdone, state)
    for t in range(1, 6):
        np.testing.assert_allclose(out[t], out[0], rtol=1e-6)

    # Without dones the state carries: outputs at t>0 differ from t=0.
    out2, _ = scaffold.apply(core)(params, inp, jnp.ones((6, 3)), state)
    assert not np.allclose(out2[1], out2[0])


def test_lstm_core_scan_matches_stepwise():
    # Scanning T steps at once == feeding one step at a time carrying state.
    core = LSTMCore(hidden_size=8, num_layers=1)
    rng = np.random.default_rng(7)
    inp = jnp.asarray(rng.standard_normal((5, 2, 3)).astype(np.float32))
    notdone = jnp.asarray((rng.random((5, 2)) > 0.3).astype(np.float32))
    state = core.initial_state(2)
    params = scaffold.init(core, jax.random.PRNGKey(0), inp, notdone, state)

    full_out, full_state = scaffold.apply(core)(params, inp, notdone, state)

    step_state = state
    outs = []
    for t in range(5):
        o, step_state = scaffold.apply(core)(
            params, inp[t : t + 1], notdone[t : t + 1], step_state
        )
        outs.append(o[0])
    np.testing.assert_allclose(full_out, np.stack(outs), rtol=1e-5, atol=1e-6)
    for a, b in zip(full_state, step_state):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_registry():
    assert isinstance(create_model("shallow", 4), AtariNet)
    assert isinstance(create_model("deep", 4, use_lstm=True), ResNet)
    with pytest.raises(ValueError):
        create_model("nope", 4)


def test_resnet_feature_size():
    # 84x84 -> 11x11x32 = 3872 going into the fc, matching the reference's
    # hard-coded nn.Linear(3872, 256) (polybeast_learner.py:195).
    model = ResNet(num_actions=NUM_ACTIONS)
    inputs = make_inputs()
    params = scaffold.init(
        model,
        {"params": jax.random.PRNGKey(0), "action": jax.random.PRNGKey(1)},
        inputs,
        (),
    )
    fc_kernel = params["params"]["trunk"]["fc"]["kernel"]
    assert fc_kernel.shape == (3872, 256)


@pytest.mark.parametrize(
    "remat",
    [False, True, (True, False, False), "front", ("front", True, False)],
)
def test_resnet_remat_variants_identical(remat):
    # Rematerialization is a scheduling choice, not a numerical one: every
    # remat setting must produce the same params tree, outputs, and
    # gradients as the un-remat'd trunk.
    inputs = make_inputs(t=3, b=2)
    outs = []
    for flag in (False, remat):
        model = ResNet(num_actions=NUM_ACTIONS, use_lstm=True, remat=flag)
        state = model.initial_state(2)
        params = scaffold.init(
            model,
            {"params": jax.random.PRNGKey(0), "action": jax.random.PRNGKey(1)},
            inputs,
            state,
        )

        def loss(p):
            out, _ = model.apply(p, inputs, state, sample_action=False)
            return jnp.sum(out.baseline ** 2) + jnp.sum(out.policy_logits ** 2)

        # beastlint: disable=JIT-HAZARD  per-config closure compared once each; one-shot compile by design
        l, g = jax.jit(jax.value_and_grad(loss))(params)
        outs.append((l, g))
    (l0, g0), (l1, g1) = outs
    assert jax.tree_util.tree_structure(g0) == jax.tree_util.tree_structure(g1)
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(g0), jax.tree_util.tree_leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_resnet_trunk_channels_variant():
    """Opt-in widened trunk (--trunk_channels): stage widths and the fc
    input dim (11*11*C2) follow the requested channels; forward runs and
    produces the usual heads."""
    model = create_model(
        "deep", num_actions=NUM_ACTIONS, use_lstm=True,
        trunk_channels=(32, 64, 64),
    )
    inputs = make_inputs(t=2, b=2)
    state = model.initial_state(2)
    params = scaffold.init(
        model,
        {"params": jax.random.PRNGKey(0), "action": jax.random.PRNGKey(1)},
        inputs,
        state,
    )
    trunk = params["params"]["trunk"]
    assert trunk["feat_conv_0"]["kernel"].shape[-1] == 32
    assert trunk["feat_conv_2"]["kernel"].shape[-1] == 64
    assert trunk["fc"]["kernel"].shape == (11 * 11 * 64, 256)
    out, _ = scaffold.forward(model)(params, inputs, state)
    assert out.policy_logits.shape == (2, 2, NUM_ACTIONS)


def test_resnet_remat_length_validated():
    model = ResNet(num_actions=NUM_ACTIONS, remat=(True, False))
    inputs = make_inputs(t=2, b=1)
    with pytest.raises(ValueError, match="one flag per stage"):
        scaffold.init(
            model,
            {"params": jax.random.PRNGKey(0), "action": jax.random.PRNGKey(1)},
            inputs,
            (),
        )


# ---- The merge of T and B: parity with the time-major formulation ----
#
# Until PR 28 every family merged [T, B] time-major ([T * B] rows) in
# front of its trunk and kept that axis through the head. Now the conv
# trunks merge batch-major unless the whole batch is on one device, the
# MLP does not merge, and the head takes [T, B, D] (models/cores.
# merge_time_batch says why). The functions below are the old
# formulation in plain jnp on the same parameters.


def _dense(p, x):
    return x @ p["kernel"] + p["bias"]


def _conv(p, x, stride=1, padding="SAME"):
    return jax.lax.conv_general_dilated(
        x, p["kernel"], (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    ) + p["bias"]


def _deep_rows(p, x):
    p = p["trunk"]
    for i in range(3):
        x = _conv(p[f"feat_conv_{i}"], x)
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding=((1, 1), (1, 1)))
        for j in range(2):
            y = _conv(p[f"res_{i}_{j}_conv1"], jax.nn.relu(x))
            x = x + _conv(p[f"res_{i}_{j}_conv2"], jax.nn.relu(y))
    x = jax.nn.relu(x).reshape(x.shape[0], -1)
    return jax.nn.relu(_dense(p["fc"], x))


def _shallow_rows(p, x):
    for i, stride in enumerate((4, 2, 1)):
        x = jax.nn.relu(_conv(p[f"Conv_{i}"], x, stride, "VALID"))
    return jax.nn.relu(_dense(p["Dense_0"], x.reshape(x.shape[0], -1)))


def _mlp_rows(p, x):
    x = x.reshape(x.shape[0], -1)
    return jax.nn.relu(_dense(p["Dense_1"], jax.nn.relu(_dense(p["Dense_0"], x))))


def _lstm(p, x, notdone, state):
    """[T, B, D] through the stacked cells, state reset where done."""
    h, c = state
    outs = []
    for t in range(x.shape[0]):
        nd = notdone[t][None, :, None]
        h, c, y = h * nd, c * nd, x[t]
        new_h, new_c = [], []
        for layer in range(h.shape[0]):
            q = p[f"layer_{layer}"]
            i, f, g, o = (
                y @ q["i" + k]["kernel"] + _dense(q["h" + k], h[layer])
                for k in "ifgo"
            )
            c_l = jax.nn.sigmoid(f) * c[layer] + (
                jax.nn.sigmoid(i) * jnp.tanh(g)
            )
            y = jax.nn.sigmoid(o) * jnp.tanh(c_l)
            new_h.append(y)
            new_c.append(c_l)
        h, c = jnp.stack(new_h), jnp.stack(new_c)
        outs.append(y)
    return jnp.stack(outs), (h, c)


def _time_major_forward(family, params, inputs, state):
    """(logits [T*B, A], baseline [T*B], core state): rows merged
    time-major from the frames to the projections."""
    p = params["params"]
    frame = inputs["frame"]
    T, B = frame.shape[:2]
    rows = frame.reshape((T * B,) + frame.shape[2:]).astype(jnp.float32)
    x = {"deep": _deep_rows, "shallow": _shallow_rows, "mlp": _mlp_rows}[
        family
    ](p, rows / 255.0)
    extras = [jnp.clip(inputs["reward"], -1, 1).reshape(T * B, 1)]
    if family != "deep":
        extras.append(
            jax.nn.one_hot(inputs["last_action"].reshape(T * B), NUM_ACTIONS)
        )
    x = jnp.concatenate([x] + extras, axis=-1)
    out, state = _lstm(
        p["head"]["core"]["Scan_StackedLSTMStep_0"],
        x.reshape(T, B, -1),
        1.0 - inputs["done"].astype(jnp.float32),
        state,
    )
    out = out.reshape(T * B, -1)
    return (
        _dense(p["head"]["policy"], out),
        _dense(p["head"]["baseline"], out)[:, 0],
        state,
    )


@pytest.mark.parametrize("t", [1, 5])
@pytest.mark.parametrize(
    "family,one_device",
    [("deep", False), ("deep", True), ("shallow", False),
     ("shallow", True), ("mlp", False)],
)
def test_forward_matches_time_major_formulation(family, one_device, t,
                                                monkeypatch):
    """Both merge orders of the conv families (the default, batch-major,
    and the one learner.one_device_model selects) and the MLP, which
    does not merge."""
    b = 3
    rng = np.random.default_rng(11)
    inputs = {
        "frame": jnp.asarray(
            rng.integers(0, 256, size=(t, b, 48, 48, C), dtype=np.uint8)
        ),
        "reward": jnp.asarray(
            2 * rng.standard_normal((t, b)).astype(np.float32)
        ),
        "done": jnp.asarray(rng.random((t, b)) < 0.3),
        "last_action": jnp.asarray(rng.integers(0, NUM_ACTIONS, size=(t, b))),
    }
    model = create_model(family, NUM_ACTIONS, use_lstm=True)
    if one_device:
        model = learner_lib.one_device_model(model)
        assert model.time_major_merge
    else:
        assert not getattr(model, "time_major_merge", False)
    state = jax.tree_util.tree_map(
        lambda s: jnp.asarray(
            rng.standard_normal(s.shape).astype(np.float32)
        ),
        model.initial_state(b),
    )
    params = scaffold.init(
        model,
        {"params": jax.random.PRNGKey(0), "action": jax.random.PRNGKey(1)},
        inputs,
        state,
    )

    drawn = []
    categorical = jax.random.categorical

    def spy(key, logits, axis=-1):
        drawn.append((key, logits))
        return categorical(key, logits, axis=axis)

    monkeypatch.setattr(jax.random, "categorical", spy)

    # A trace of the case's own (the spy is read at trace time), which
    # hands out what the spy saw of it.
    def run(params, inputs, state):
        out, new_state = model.apply(
            params, inputs, state, rngs={"action": jax.random.PRNGKey(2)}
        )
        return out, new_state, tuple(drawn)

    traced = jax.jit(run)
    out, new_state, drawn = traced(params, inputs, state)
    time_major = jax.jit(_time_major_forward, static_argnums=0)
    want_logits, want_baseline, want_state = time_major(
        family, params, inputs, state
    )

    close = dict(rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        out.policy_logits.reshape(t * b, NUM_ACTIONS), want_logits, **close
    )
    np.testing.assert_allclose(
        out.baseline.reshape(t * b), want_baseline, **close
    )
    for got, want in zip(new_state, want_state):
        np.testing.assert_allclose(got, want, **close)

    # The draw: one categorical over the rows in time-major order with
    # the head's key, as before; at T == 1 (the act step) bit for bit.
    ((key, logits),) = drawn
    assert np.array_equal(
        logits.reshape(t * b, NUM_ACTIONS),
        out.policy_logits.reshape(t * b, NUM_ACTIONS),
    )
    if t == 1:
        np.testing.assert_array_equal(
            out.action.reshape(b), categorical(key, want_logits, axis=-1)
        )


def test_one_device_model_keeps_a_model_without_a_merge():
    mlp = create_model("mlp", NUM_ACTIONS)
    assert learner_lib.one_device_model(mlp) is mlp
