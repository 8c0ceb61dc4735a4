"""The `xing4` family (models/xing4.py: four residual streams mixed by
Sinkhorn-normalised maps around every sublayer; the compressed-query
path and YaRN on the rope key in models/kanana2.py's `attention_part`;
the walk's stream hooks in models/transformer.py): against the plain
reference on seeded weights, batch forward against stepwise acting
through the latent caches, the constraint on `H_res`, the maps at zero
as a plain pre-norm residual block, and the eight shares adding up to
the uncut reference's LAYER, attention and stream mixes counted once.
The routed experts' shares alone: an id of tests/test_families_shares.py."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from perfbench.reference import xing4_policy as reference
from tests import family_scaffold as scaffold
from torchbeast_tpu.models import create_model, kanana2, xing4

T, B = 6, scaffold.B
SMALL = scaffold.FAMILIES["xing4"].small
LAYERS, M = SMALL["num_layers"], SMALL["memory_len"]
# On the CPU both sides compute in float32 at full precision and differ
# by the order of their sums (the absorbed cache leg, the maps applied
# to the 24 products and not to the streams): what tests/test_kanana2.py
# holds that family to. A map in bfloat16 (8 bits of `H_res` into every
# later layer) reads 1e-3 and up, and a Sinkhorn step left out 4e-4 at
# the moved `a`: both cases below fail it.
RTOL = ATOL = 1e-5


@pytest.mark.parametrize(
    "share", [(0, 1), (0, 8)], ids=["all-16-experts", "share-0-of-8"]
)
def test_family_agrees_with_the_reference(share):
    """Outputs, the new latent caches, the loss and its gradients
    (`Phi`, `a`, `b` of all six sublayers, `q_a`, its norm and `q_b`
    among them) on a warm state across an episode end, the maps' `a`
    moved to 0.5 / 0.4 / 0.3 so that they follow the token."""
    model, params = scaffold.build("xing4", expert_share=share)
    state = scaffold.warm_state(model, params, seed=5)
    batch = scaffold.learner_batch(7, done_steps=[(3, 0)])
    stats, grads, ref_grads, _ = scaffold.assert_agrees_with_the_reference(
        model, params, state, batch, RTOL, ATOL
    )
    block = grads["params"]["block_1"]
    for leaf in (block["attn_hc"]["phi"], block["mlp_hc"]["a"],
                 block["q_a"]["kernel"], block["q_a_norm"]["scale"]):
        assert np.any(leaf)
    assert float(stats["attention_latent_applications"]) == LAYERS
    assert float(stats["moe_shared_applications"]) == LAYERS - 1
    # The residual path's counters: a row's streams a sublayer
    # ([4, T, 48] float32), summed over the six; the mean of H_post
    # over tokens, streams and sublayers; the worst row of any H_res.
    assert float(stats["hc_bytes_per_row"]) == 2 * LAYERS * 4 * 4 * T * 48
    assert 0.5 < float(stats["hc_post_mean"]) < 1.5
    # With `a_res` at 0.3 twenty steps leave the rows where they leave
    # them (the columns are the last step's): the counter says where.
    assert 0 < float(stats["hc_res_row_error_max"]) < 0.05
    steps = scaffold.reference_bias_steps(model)(params, batch, state)
    assert len(steps) == LAYERS - 1


def test_family_through_the_stream_kernels_agrees_with_the_reference():
    """At a width of whole lane tiles (128) every sublayer's maps,
    pre-sum and mix run ops/stream_mix.py's kernels (interpreted here;
    `hc_fused_applications` counts them: two a layer), and outputs,
    caches, loss and gradients are still the reference's, the maps
    moved as above."""
    model, params = scaffold.build("xing4", d_model=128)
    state = scaffold.warm_state(model, params, seed=5)
    batch = scaffold.learner_batch(7, done_steps=[(3, 0)])
    stats, grads, _, _ = scaffold.assert_agrees_with_the_reference(
        model, params, state, batch, RTOL, ATOL
    )
    assert float(stats["hc_fused_applications"]) == 2 * LAYERS
    block = grads["params"]["block_1"]
    for leaf in (block["attn_hc"]["phi"], block["mlp_hc"]["a"],
                 block["mlp_hc"]["b"]):
        assert np.any(leaf)


@pytest.mark.parametrize("fault", ["bf16_maps", "nineteen_steps"])
def test_the_tolerance_sees_a_rounded_map_and_a_skipped_step(
    fault, monkeypatch
):
    """What `RTOL` is for: the maps' product on bfloat16 operands, or
    one Sinkhorn step fewer than the row states, moves the loss or a
    gradient past it."""
    model, params = scaffold.build("xing4")
    state = scaffold.warm_state(model, params, seed=5)
    batch = scaffold.learner_batch(7, done_steps=[(3, 0)])
    if fault == "bf16_maps":
        right = xing4.stream_products

        def rounded(streams, kernel):
            return right(*(
                x.astype(jnp.bfloat16).astype(jnp.float32)
                for x in (streams, kernel)
            ))

        monkeypatch.setattr(xing4, "stream_products", rounded)
    else:
        right = xing4.sinkhorn
        monkeypatch.setattr(
            xing4, "sinkhorn", lambda s, iters, eps: right(s, iters - 1, eps)
        )
    # A trace of its own: the fault is read when the block is traced.
    run = jax.jit(scaffold.loss_and_grads.__wrapped__(model, jit=False))
    loss, _, grads = run(params, batch, state)
    ref_loss, scale, ref_grads = scaffold.reference_loss_and_grads(model)(
        params, batch, state
    )
    worst = float(jnp.max(jnp.abs(scaffold.flat(ref_grads))))
    off = max(
        abs(float(loss) - float(ref_loss)) / float(scale),
        float(jnp.max(jnp.abs(
            scaffold.flat(grads) - scaffold.flat(ref_grads)
        ))) / worst,
    )
    assert off > 5 * RTOL, off


@pytest.mark.parametrize("unrolls", [0, 1, 2], ids=["empty", "part", "full"])
def test_batch_forward_equals_stepwise_acting_through_the_latent_caches(
    unrolls
):
    """The learner's [T, B] forward and the actor's T=1 forwards, the
    streams [4, B, 1, d] made and summed inside every step, give the
    same logits and leave the same latents and rope keys."""
    model, params = scaffold.build("xing4")
    state = scaffold.warm_state(model, params, seed=2, unrolls=unrolls)
    scaffold.assert_stepwise_acting_equals_the_batch_forward(
        model, params, state, scaffold.inputs(3, done_steps=[(3, 1)])
    )


def _maps(a, b_res_edge=None, seed=0):
    """(the module, seeded streams [4, 3, 5, 48], its parameters) of
    one sublayer's maps alone, `a` as given and H_res's logits all at
    `b_res_edge` where that is given."""
    maps = xing4._StreamMaps(
        rms_norm_eps=1e-6, sinkhorn_iters=20, hc_eps=1e-6,
        res_clamp=(-30.0, 30.0),
    )
    streams = jax.random.normal(jax.random.PRNGKey(seed), (4, 3, 5, 48))
    params = scaffold.init(maps, jax.random.PRNGKey(1), streams)
    inner = dict(params["params"], a=jnp.asarray(a, jnp.float32))
    if b_res_edge is not None:
        inner["b"] = inner["b"].at[8:].set(b_res_edge)
    return maps, streams, {"params": inner}


def test_h_res_is_doubly_stochastic_after_twenty_steps():
    """At the assumed start (`a` 0.01, off-diagonal logits -12) every
    row and every column of H_res sums to 1 within 1e-5, H_pre is 1/4
    and H_post 1 within the 0.01 the token moves them by; the columns
    hold at ANY `a` (the last step is theirs), the rows as near as
    twenty steps bring them, which `hc_res_row_error_max` reports."""
    maps, streams, params = _maps([0.01, 0.01, 0.01])
    _, _, (h_pre, h_post, h_res) = scaffold.apply(maps)(params, streams)
    assert h_pre.shape == h_post.shape == (4, 3, 5)
    assert h_res.shape == (4, 4, 3, 5)
    np.testing.assert_allclose(h_res.sum(axis=1), 1.0, atol=1e-5)
    np.testing.assert_allclose(h_res.sum(axis=0), 1.0, atol=1e-5)
    np.testing.assert_allclose(h_pre, 0.25, atol=0.02)
    np.testing.assert_allclose(h_post, 1.0, atol=0.05)
    assert np.all(np.asarray(h_res) >= 0)
    # Near the identity: 1 on the diagonal but for e^-12 beside it.
    np.testing.assert_allclose(
        np.moveaxis(np.asarray(h_res), (0, 1), (-2, -1)),
        np.broadcast_to(np.eye(4), (3, 5, 4, 4)), atol=1e-3,
    )
    _, _, (_, _, moved) = scaffold.apply(maps)(
        _maps([1.0, 1.0, 1.0])[2], streams
    )
    np.testing.assert_allclose(moved.sum(axis=0), 1.0, atol=1e-5)
    assert 1e-4 < float(jnp.max(jnp.abs(moved.sum(axis=1) - 1.0))) < 0.1


@pytest.mark.parametrize("edge", [-40.0, 40.0], ids=["below", "above"])
def test_h_res_gradient_is_finite_at_the_clips_edges(edge):
    """Logits past `mhc_h_res_clamp_min` / `_max` (exp(30) = 1e13 in
    float32, and e^-30 beside 1): H_res and the gradient of a sum that
    reads it are finite, in `Phi`, `a`, `b` and the streams."""
    maps, streams, params = _maps([0.5, 0.4, 0.3], b_res_edge=edge)

    def read(params, streams):
        _, _, (h_pre, h_post, h_res) = maps.apply(params, streams)
        weights = jnp.arange(16.0).reshape(4, 4, 1, 1)
        return jnp.sum(h_res * weights) + jnp.sum(h_pre * h_post)

    value_and_grads = jax.jit(jax.value_and_grad(read, argnums=(0, 1)))
    value, grads = value_and_grads(params, streams)
    assert np.isfinite(float(value))
    for leaf in jax.tree_util.tree_leaves(grads):
        assert np.all(np.isfinite(leaf))


def _as_the_net_hands_them(streams):
    """[4, B, T, d] -> `xing4.Streams`, a token a row."""
    n, rows, steps, d = streams.shape
    return xing4.Streams(streams.reshape(n, rows * steps, d), rows, steps)


def _layer(model, layer, params):
    """(block, its parameters, its cache leaves, the two legs' masks)
    of one layer of the toy alone, on a warm state."""
    state = scaffold.warm_state(model, params, seed=5)
    cache_mask = jnp.ones((B, T, M), bool)
    seq_mask = jnp.broadcast_to(jnp.tril(jnp.ones((T, T), bool)), (B, T, T))
    return (
        model.make_block("block", layer),
        params["params"][f"block_{layer}"],
        state[layer][:2], cache_mask, seq_mask,
    )


def test_maps_at_zero_are_a_plain_pre_norm_residual_block():
    """With `a_*` = 0, the assumed `b` and four equal streams going in,
    every stream coming out of a layer is x + attention(norm x) + ffn(
    norm .): what models/kanana2.py's block (one stream, each part added
    to it) gives on the same weights, within 1e-5."""
    model, params = scaffold.build("xing4")
    streamed, weights, cache, cache_mask, seq_mask = _layer(model, 1, params)
    start = scaffold.init(
        xing4._StreamMaps(1e-6, 20, 1e-6, (-30.0, 30.0)),
        jax.random.PRNGKey(0), jnp.zeros((4, 1, 1, 48)),
    )["params"]
    at_zero = dict(start, a=jnp.zeros(3))
    plain = kanana2._Kanana2Block(**{
        f.name: getattr(streamed, f.name)
        for f in dataclasses.fields(kanana2._Kanana2Block)
        if f.name not in ("parent", "name")
    })
    x = jax.random.normal(jax.random.PRNGKey(4), (B, T, 48))
    got, c, k_r = scaffold.apply(streamed)(
        {"params": dict(weights, attn_hc=at_zero, mlp_hc=at_zero)},
        _as_the_net_hands_them(jnp.broadcast_to(x[None], (4, B, T, 48))),
        cache, cache_mask, seq_mask,
    )
    want, want_c, want_k_r = scaffold.apply(plain)(
        {"params": {
            k: v for k, v in weights.items() if not k.endswith("_hc")
        }},
        x, cache, cache_mask, seq_mask,
    )
    for stream in got.x:
        np.testing.assert_allclose(
            stream.reshape(want.shape), want, rtol=1e-5, atol=1e-5
        )
    np.testing.assert_allclose(c, want_c, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(k_r, want_k_r, rtol=1e-5, atol=1e-5)


def test_the_eight_shares_add_up_to_the_uncut_references_layer():
    """A MoE layer of the toy (16 experts) held in eight shares of two,
    the same streams going in on every chip. A share's layer output is
    what every chip computes alike (the streams' mix, attention's part,
    the shared expert) plus ITS experts' part, which reaches the four
    streams through the feed-forward sublayer's H_post: linear in the
    routed sum. So with `common` the output of a chip whose experts
    give nothing (the held experts' `w_down` at zero), common + sum_i
    (part_i - common) is the uncut layer: the reference's, on the same
    streams, with attention, the mixes and the shared expert counted
    once."""
    uncut_model, params = scaffold.build("xing4")
    last = LAYERS - 1
    whole_block, weights, cache, cache_mask, seq_mask = _layer(
        uncut_model, last, params
    )
    streams_in = jax.random.normal(jax.random.PRNGKey(9), (4, B, T, 48))

    def output(block, weights):
        return scaffold.apply(block)(
            {"params": weights}, _as_the_net_hands_them(streams_in), cache,
            cache_mask, seq_mask,
        )[0].x.reshape(streams_in.shape)

    stacked = ("w_gate", "w_up", "w_down")

    def held(i):
        """Share i's parameters: its own slice of the stacked experts,
        everything else as every chip holds it."""
        return dict(weights, moe=dict(weights["moe"], **{
            name: weights["moe"][name][2 * i : 2 * i + 2] for name in stacked
        }))

    parts = [
        output(
            scaffold.build("xing4", expert_share=(i, 8))[0].make_block(
                "block", last
            ),
            held(i),
        )
        for i in range(8)
    ]
    silent = dict(weights, moe=dict(
        weights["moe"], w_down=jnp.zeros_like(weights["moe"]["w_down"])
    ))
    common = output(whole_block, silent)

    # The reference's layer on the same streams, uncut.
    config = scaffold.reference_config(uncut_model)
    allowed = jnp.concatenate([cache_mask, seq_mask], axis=-1)
    cached = tuple(leaf[:, :, 0].transpose(1, 0, 2) for leaf in cache)

    def reference_layer(X):
        def attention(u):
            return reference._attention(
                reference._rmsnorm(u, weights["attn_norm"], 1e-6), weights,
                cached, allowed, config,
            )[0]

        def feed_forward(u):
            h = reference._rmsnorm(u, weights["mlp_norm"], 1e-6)
            return reference._experts(
                h.reshape(B * T, -1), weights["moe"], config
            ).reshape(B, T, -1)

        with jax.default_matmul_precision("highest"):
            X = reference._mixed(X, weights["attn_hc"], config, attention)
            return reference._mixed(X, weights["mlp_hc"], config, feed_forward)

    reference_layer = jax.jit(reference_layer)
    uncut = jnp.moveaxis(
        reference_layer(jnp.moveaxis(streams_in, 0, 2)), 2, 0
    )
    np.testing.assert_allclose(
        output(whole_block, weights), uncut, rtol=RTOL, atol=ATOL
    )
    np.testing.assert_allclose(
        common + sum(part - common for part in parts), uncut,
        rtol=2e-5, atol=2e-5,
    )
    # No share alone is the layer: each holds an eighth of the experts.
    assert float(jnp.max(jnp.abs(parts[0] - uncut))) > 1e-3


def test_registry_cut_and_whole_depths():
    """A cut keeps ONE leading dense layer; all 40 are the published
    two and 38 (`first_k_dense_replace` 2)."""
    cut = create_model("xing4", num_actions=6, num_layers=5)
    assert cut.leading_dense_layers() == 1
    assert [
        cut.latent_block_fields(layer)["dense"] for layer in range(5)
    ] == [True, False, False, False, False]
    whole = create_model("xing4", num_actions=6)
    assert whole.leading_dense_layers() == 2
    assert [
        whole.latent_block_fields(layer)["dense"] for layer in range(4)
    ] == [True, True, False, False]
    with pytest.raises(ValueError, match="at least one MoE layer"):
        create_model("xing4", num_actions=6, num_layers=1)
