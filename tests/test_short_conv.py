"""ops/short_conv.py: the short causal convolution over episode ends as
kernels, interpreted on the CPU at shapes the kernels take (channels of
1 and of 17 lane tiles, unrolls of whole sublane tiles), against the
`jax.numpy` form of models/nemotron3.py `conv_over_episodes`: the
convolution, the new tail and every gradient, with episode ends at the
unroll's first step, its last, two in a row, none and every step, a
non-zero tail, three and four taps, a bias and none; which shapes take
the kernels; `reach`; the families' counter."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tests import family_scaffold as scaffold
from torchbeast_tpu.models import nemotron3, stats as model_stats
from torchbeast_tpu.ops import short_conv

ROWS = 2
QUANTITIES = ("conv", "new_tail", "dinputs", "dtail", "dtaps", "dbias")
# Forward to 1e-6 of each result's scale, backward to 1e-5.
TOLERANCE = dict.fromkeys(QUANTITIES[:2], 1e-6) | dict.fromkeys(
    QUANTITIES[2:], 1e-5
)

# (step, row) of each episode end in an unroll of `steps` steps.
ENDS = {
    "first-step": lambda steps: [(0, 0), (5, 1)],
    "last-step": lambda steps: [(steps - 1, 0), (steps - 2, 1)],
    "two-in-a-row": lambda steps: [
        (steps // 2, 0), (steps // 2 + 1, 0), (steps // 2 - 1, 1),
        (steps // 2, 1),
    ],
    "none": lambda steps: [],
    "every-step": lambda steps: [(t, 0) for t in range(steps)] + [(1, 1)],
}
# (steps, channels): one lane tile under two turns of the loop; 17 lane
# tiles (Granite's 34 are two such cells) under one; a turn of 128 steps
# and its neighbour.
SHAPES = {"one-tile": (16, 128), "seventeen-tiles": (8, 17 * 128),
          "long": (256, 128)}
CASES = [
    (shape, taps, bias, ends)
    for shape, all_ends in (
        ("one-tile", list(ENDS)), ("seventeen-tiles", ["two-in-a-row"]),
        ("long", ["two-in-a-row", "none"]),
    )
    for taps in (3, 4) for bias in ("bias", "no-bias") for ends in all_ends
    if shape == "one-tile" or bias == "bias"
]


def _in_xla(*args):
    """`conv_over_episodes` as it runs where the kernels do not apply."""
    saved = short_conv.kernels_apply
    short_conv.kernels_apply = lambda *shape: False
    try:
        return nemotron3.conv_over_episodes(*args)
    finally:
        short_conv.kernels_apply = saved


@functools.lru_cache(maxsize=None)
def _both(conv, steps, channels, taps, with_bias):
    """(conv, new tail, dinputs, dtail, dtaps, dbias) of `conv`, jitted
    once a shape; `done` is an argument."""
    def run(inputs, tail, done, weights, bias, cotangents):
        results, pull = jax.vjp(
            lambda inputs, tail, weights, bias: conv(
                inputs, tail, done, weights, bias
            ), inputs, tail, weights, bias if with_bias else None,
        )
        return results + pull(cotangents)[: 4 if with_bias else 3]

    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _results(shape, taps, bias, ends):
    steps, channels = SHAPES[shape]
    keys = jax.random.split(jax.random.PRNGKey(taps), 6)
    done = np.zeros((ROWS, steps), bool)
    for step, row in ENDS[ends](steps):
        done[row, step] = True
    args = (
        jax.random.normal(keys[0], (ROWS, steps, channels)),
        jax.random.normal(keys[1], (taps - 1, ROWS, channels)),
        jnp.asarray(done),
        jax.random.normal(keys[2], (taps, channels)),
        jax.random.normal(keys[3], (channels,)),
        (jax.random.normal(keys[4], (ROWS, steps, channels)),
         jax.random.normal(keys[5], (taps - 1, ROWS, channels))),
    )
    assert short_conv.kernels_apply(steps, channels, taps)
    shape_of = (steps, channels, taps, bias == "bias")
    return tuple(
        dict(zip(QUANTITIES, map(np.asarray, _both(conv, *shape_of)(*args))))
        for conv in (nemotron3.conv_over_episodes, _in_xla)
    )


@pytest.mark.parametrize(
    "shape, taps, bias, ends, quantity", [
        case + (quantity,) for case in CASES for quantity in QUANTITIES
        if quantity != "dbias" or case[2] == "bias"
    ],
)
def test_kernels_equal_the_shifted_adds(shape, taps, bias, ends, quantity):
    """Float32 both ways: the forward adds the taps in the `jax.numpy`
    form's order, the backward's sums differ in theirs."""
    got, want = _results(shape, taps, bias, ends)
    scale = max(float(np.max(np.abs(want[quantity]))), 1e-30)
    np.testing.assert_allclose(
        got[quantity], want[quantity], rtol=0,
        atol=TOLERANCE[quantity] * scale,
    )


def test_an_episode_end_cuts_the_taps_and_the_tail():
    """The kernels' own arithmetic, by hand: after an end at step 2 of
    four taps, step 2 reads itself alone, step 3 itself and step 2; the
    steps before it read the carried tail; the new tail keeps what
    follows the unroll's last end."""
    steps, channels = 8, 128
    inputs = jnp.arange(1.0, steps + 1)[None, :, None] * jnp.ones(
        (1, steps, channels)
    )
    tail = -jnp.arange(3.0, 0.0, -1)[:, None, None] * jnp.ones(
        (3, 1, channels)
    )  # steps -3, -2, -1 hold -3, -2, -1
    done = jnp.zeros((1, steps), bool).at[0, 2].set(True).at[0, 6].set(True)
    taps = jnp.asarray([1000.0, 100.0, 10.0, 1.0])[:, None] * jnp.ones(
        (4, channels)
    )
    conv, new_tail = nemotron3.conv_over_episodes(
        inputs, tail, done, taps, None
    )
    np.testing.assert_array_equal(
        np.asarray(conv[0, :, 0]),
        [-3209.0, -2088.0, 3.0, 34.0, 345.0, 3456.0, 7.0, 78.0],
    )
    np.testing.assert_array_equal(np.asarray(new_tail[:, 0, 5]), [0, 7, 8])


@pytest.mark.parametrize("taps", [2, 3, 4, 8])
def test_reach_is_the_steps_since_the_last_end(taps):
    done = np.zeros((3, 12), bool)
    done[0, [0, 5, 6]] = True
    done[1, 11] = True
    got = np.asarray(short_conv.reach(jnp.asarray(done), taps))
    for row in range(3):
        since = taps  # the tail lies before every end
        for step in range(12):
            since = 0 if done[row, step] else since + 1
            assert got[row, step] == min(taps - 1, since), (row, step)


@pytest.mark.parametrize("shape, applies", [
    ((256, 8192, 4), True),  # Qwen3-Next's cell
    ((512, 4352, 4), True),  # Granite's: 34 lane tiles
    ((256, 2560, 4), True),  # Nemotron-3's
    ((256, 5120, 4), True),  # Phi-4-mini-flash's
    ((256, 2048, 3), True),  # LFM2's
    ((8, 128, 2), True),
    ((16, 17 * 128, 8), True),
    ((1, 8192, 4), False),  # acting
    ((6, 128, 4), False),  # an unroll of no whole sublane tile
    ((12, 128, 4), False),
    ((16, 24, 4), False),  # tier-1's toy widths
    ((16, 192, 3), False),  # channels of no whole lane tiles
    ((16, 128, 1), False),  # one tap is no convolution
    ((16, 128, 9), False),  # more taps than a tile's steps
    ((16384, 128, 4), False),  # a lane tile of a row over a cell's bytes
])
def test_which_shapes_take_the_kernels(shape, applies):
    """`kernels_apply` is a function of (steps, channels, taps) alone."""
    assert short_conv.kernels_apply(*shape) is applies


def test_the_kernels_refuse_shapes_that_are_not_theirs():
    with pytest.raises(ValueError, match="kernels' shapes"):
        short_conv.short_conv(
            jnp.zeros((2, 6, 24)), jnp.zeros((3, 2, 24)),
            jnp.zeros((2, 6), jnp.int32), jnp.zeros((4, 24)), None,
        )


@pytest.mark.parametrize("steps, channels, tiles", [
    (256, 8192, 32), (512, 4352, 17), (256, 2560, 20), (256, 5120, 40),
    (256, 2048, 16), (16, 17 * 128, 17), (8192, 256, 1),
])
def test_a_cell_is_the_most_lane_tiles_under_its_bytes(steps, channels, tiles):
    assert short_conv._tiles_a_cell(steps, channels) == tiles


def _acting_stats(model, params):
    jitted = jax.jit(lambda p, x, s: model.apply(
        p, x, s, mutable=model_stats.COLLECTIONS, sample_action=False
    ))
    _, sown = jitted(
        params, scaffold.inputs(1, t=1), model.initial_state(scaffold.B)
    )
    return model_stats.folded(sown)


# A family at widths whose convolution is whole lane tiles, the layers
# that call it there, and the counter that says how many there are.
WIDE = {
    "granite4": (
        dict(mamba_heads=2, mamba_head_dim=64, state_size=64),
        "ssm_applications",
    ),
    "nemotron3": (
        dict(mamba_heads=2, mamba_head_dim=64, mamba_groups=1, state_size=64),
        "ssm_applications",
    ),
    "qwen3next": (
        dict(delta_key_dim=32, delta_value_dim=32), "delta_applications"
    ),
    "phi4flash": (dict(d_model=64), "ssm_applications"),
    "lfm2": (dict(d_model=128), "conv_layers"),
}


@pytest.mark.parametrize("family", list(WIDE))
def test_the_family_counts_the_layers_its_kernels_ran(family):
    """`conv_kernel_applications`: every layer that calls the
    convolution over an unroll of 16 steps at widths of whole lane
    tiles, none for a step of acting, none at the toy widths."""
    wide, layers = WIDE[family]
    model, params = scaffold.build(family, **wide)
    stats = scaffold.forward_stats(model, params, scaffold.B, [(3, 0)], t=16)
    assert float(stats["conv_kernel_applications"]) == float(stats[layers])
    assert float(stats[layers]) > 0
    assert float(_acting_stats(model, params)["conv_kernel_applications"]) == 0
    toy, toy_params = scaffold.build(family)
    stats = scaffold.forward_stats(toy, toy_params, scaffold.B, [], t=16)
    assert float(stats["conv_kernel_applications"]) == 0
    assert float(stats[layers]) > 0
