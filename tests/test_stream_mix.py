"""ops/stream_mix.py: the residual streams' maps + pre-sum and the mix's
backward as Mosaic kernels (interpreted here), against the `jax.numpy`
body they replace (models/xing4.py `_StreamMaps` at a shape the kernels
refuse, `stream_mix.plain_mix`), float32 at the highest precision on
both sides: they differ by the order of their sums.

Every case runs WITH THE MAPS OFF THEIR START (`a` = 1, H_res's
off-diagonal logits around -4 / -2, as tests/test_xing4.py moves them):
at the assumed start (`a` 0.01) the maps hardly follow the token, and a
transposed H_res or a misread column of `Phi` reads under any limit
(PERF.md section 7)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tests import family_scaffold as scaffold
from torchbeast_tpu.models import xing4
from torchbeast_tpu.ops import stream_mix

N = 4
# Of the largest entry of what is compared. The two bodies read 2e-6 and
# under; a map's product at ONE bfloat16 pass reads 1e-3 and up
# (`test_a_one_pass_product_is_seen`).
LIMIT = 2e-5

# (B, T, d): tokens = B T against the cells' blocks of 128 (the maps)
# and 32 (the mix's backward).
SHAPES = {
    "padded-tail": (3, 50, 256),  # 150 = 128 + 22 = 4 x 32 + 22
    "acting-T1": (5, 1, 128),  # tokens = B, under a block: padded to one
    "one-block": (4, 32, 128),  # 128: one cell of the maps, four of the mix
    "several-blocks": (3, 100, 128),  # 300: dPhi summed over three cells
}


def _maps_module():
    return xing4._StreamMaps(
        rms_norm_eps=1e-6, sinkhorn_iters=20, hc_eps=1e-6,
        res_clamp=(-30.0, 30.0),
    )


def _operands(rows, steps, d, seed=0):
    """(the maps' parameters moved off their start, the streams, the
    stand-in sublayer's weights, the weights of the scalar read of X')."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    streams = jax.random.normal(keys[0], (N, rows, steps, d))
    params = scaffold.init(_maps_module(), keys[1], streams)["params"]
    edge = jnp.where(
        jnp.eye(N, dtype=bool), 0.0,
        -3.0 + jax.random.normal(keys[2], (N, N)),
    ).reshape(-1)
    params = dict(
        params, a=jnp.asarray([1.0, 1.0, 1.0]),
        b=params["b"].at[2 * N :].set(edge),
    )
    w = jax.random.normal(keys[3], (d, d)) / np.sqrt(d)
    read = jax.random.normal(keys[4], streams.shape)
    return {"params": params}, streams, w, read


def _sublayer(fused):
    """Jitted (params, streams, w, read) -> ((the scalar of X', (X', u,
    the maps)), its gradients in the maps' parameters, the streams and
    the sublayer's weights): `_StreamMaps`, a stand-in sublayer
    y = tanh(u w), the mix. `fused` False: the `jax.numpy` body at the
    same shapes (`kernels_apply` answering no while THIS function is
    traced)."""
    maps = _maps_module()

    def scalar(params, streams, w, read):
        x, u, (h_pre, h_post, h_res) = maps.apply(params, streams)
        y = jnp.tanh(jnp.einsum(
            "btd,de->bte", u, w, precision=jax.lax.Precision.HIGHEST
        ))
        mix = stream_mix.mix if fused else stream_mix.plain_mix
        mixed = mix(x, y, h_res, h_post)
        return jnp.sum(mixed * read), (mixed, u, h_pre, h_post, h_res)

    run = jax.jit(jax.value_and_grad(scalar, argnums=(0, 1, 2), has_aux=True))
    if fused:
        return run

    def plain(*operands):
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(stream_mix, "kernels_apply", lambda *a: False)
            return run(*operands)

    return plain


def _worst(got, want):
    """The largest difference of two trees' leaves, each leaf over its
    own largest entry."""
    return max(
        float(jnp.max(jnp.abs(g - w)) / jnp.max(jnp.abs(w)))
        for g, w in zip(
            jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)
        )
    )


def _kernels_in(function, *operands):
    return str(jax.make_jaxpr(function)(*operands)).count("pallas_call")


@pytest.mark.parametrize("shape", list(SHAPES))
def test_kernels_are_the_plain_body_forward_and_backward(shape):
    """X', u and every map, and every gradient of a scalar of X': dX
    (the mix's and the maps' into one array), dPhi (summed over the
    token blocks), da, db (H_pre's from the kernel, H_post's and
    H_res's through dH_post, dH_res), and the sublayer's weights'
    (through dy and du)."""
    operands = _operands(*SHAPES[shape])
    (got, got_out), got_grads = _sublayer(True)(*operands)
    (want, want_out), want_grads = _sublayer(False)(*operands)
    assert abs(float(got) - float(want)) < LIMIT * abs(float(want)) + 1e-3
    assert _worst(got_out, want_out) < LIMIT
    assert _worst(got_grads, want_grads) < LIMIT
    maps = got_grads[0]["params"]
    # Every gradient is there to be compared: none is all zeros.
    for leaf in (maps["phi"], maps["a"], maps["b"], got_grads[1], got_grads[2]):
        assert np.all(np.isfinite(leaf)) and np.any(leaf)
    assert np.all(np.asarray(maps["b"]))


def test_the_maps_are_off_their_start():
    """What the cases above stand on: H_res is far from the identity
    and H_pre follows the token."""
    _, (_, _, h_pre, _, h_res) = _sublayer(True)(
        *_operands(*SHAPES["one-block"])
    )[0]
    assert float(jnp.max(h_res[0, 1])) > 0.02
    assert float(jnp.max(h_pre) - jnp.min(h_pre)) > 0.3


@pytest.fixture
def traced_anew():
    """The kernels' calls are jitted: a fault planted in what they read
    when traced shows only in a fresh trace (the test calls what this
    gives once the fault is in place) and must not outlive the test in
    a cached one."""
    def clear():
        for call in (stream_mix._maps_forward, stream_mix._maps_backward,
                     stream_mix._mix_backward):
            call.clear_cache()

    yield clear
    clear()


def test_a_transposed_mix_is_seen(monkeypatch, traced_anew):
    """The limit tells H_res from its transpose in the backward."""
    operands = _operands(*SHAPES["one-block"])
    _, want = _sublayer(True)(*operands)
    right = stream_mix._token_tiles
    monkeypatch.setattr(
        stream_mix, "_token_tiles",
        lambda maps, tokens: right(
            [maps[0].swapaxes(0, 1), *maps[1:]], tokens
        ),
    )
    traced_anew()
    _, got = _sublayer(True)(*operands)
    assert _worst(got[1], want[1]) > 100 * LIMIT


def test_a_one_pass_product_is_seen(monkeypatch, traced_anew):
    """`Phi`'s products at one bfloat16 pass where six are stated: the
    maps, X' and the gradients move by more than the limit, so the
    precision cannot slip."""
    operands = _operands(*SHAPES["one-block"])
    (_, want_out), want_grads = _sublayer(True)(*operands)
    monkeypatch.setattr(stream_mix, "_TERMS", 1)
    traced_anew()
    (_, got_out), got_grads = _sublayer(True)(*operands)
    assert _worst(got_out, want_out) > 10 * LIMIT
    assert _worst(got_grads, want_grads) > 10 * LIMIT


@pytest.mark.parametrize(
    "streams, d, dtype",
    [(4, 48, jnp.float32), (4, 192, jnp.float32), (4, 128, jnp.bfloat16),
     (4, 128 * 128, jnp.float32), (8, 128, jnp.float32)],
    ids=["toy-width", "no-whole-lane-tiles", "bfloat16", "row-over-the-budget",
         "more-maps-than-rows"],
)
def test_a_refused_shape_keeps_the_plain_body(streams, d, dtype):
    assert not stream_mix.kernels_apply(streams, d, dtype)
    if d > 1024 or streams != N:
        return
    params, x, _, _ = _operands(2, 3, d)
    x = x.astype(dtype)
    maps = _maps_module()
    assert _kernels_in(lambda p, s: maps.apply(p, s)[1], params, x) == 0


def test_the_published_shape_is_taken():
    assert stream_mix.kernels_apply(4, 3584, jnp.float32)
    params, x, _, _ = _operands(2, 3, 128)
    maps = _maps_module()
    assert _kernels_in(lambda p, s: maps.apply(p, s)[1], params, x) == 1


def test_the_toy_family_counts_no_fused_sublayer():
    """`hc_fused_applications` at tier-1's width 48: the plain body ran
    in all six sublayers (tests/test_xing4.py has the width of 128)."""
    model, params = scaffold.build("xing4")
    stats = scaffold.forward_stats(model, params, scaffold.B, [], 6)
    assert float(stats["hc_fused_applications"]) == 0
