"""MoE layer (models/moe.py) + expert parallelism (parallel/ep.py).

Oracles: with identical expert weights and ample capacity the mixture
must equal a single dense FFN (renormalized gates sum to 1); the
expert-sharded run must match the unsharded run bitwise-close; capacity
overflow must drop, not corrupt. Then `DroplessMoE`'s routing and its
layer with all or a share of its experts held, by hand. The window a
share moves and its sweeps: tests/test_moe_window.py; the grouped
matmuls that cut their operands in VMEM: tests/test_moe_kernels.py."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from tests import family_scaffold as scaffold
from torchbeast_tpu import learner as learner_lib
from torchbeast_tpu.models import create_model, stats
from torchbeast_tpu.models.moe import MoEFFN
from torchbeast_tpu.parallel.ep import (
    expert_param_shardings,
    place_expert_params,
)

D, FF, E = 8, 16, 4


def _init(key, moe, tokens=16):
    x = jax.random.normal(jax.random.PRNGKey(9), (tokens, D))
    params = scaffold.init(moe, key, x)
    return params, x


def test_identical_experts_equal_dense_ffn():
    moe = MoEFFN(
        d_model=D, d_ff=FF, num_experts=E, top_k=2, capacity_factor=16.0
    )
    params, x = _init(jax.random.PRNGKey(0), moe)
    p = params["params"]
    # Collapse every expert onto expert 0's weights.
    p = dict(
        p,
        w_in=jnp.broadcast_to(p["w_in"][:1], p["w_in"].shape),
        b_in=jnp.broadcast_to(p["b_in"][:1], p["b_in"].shape),
        w_out=jnp.broadcast_to(p["w_out"][:1], p["w_out"].shape),
        b_out=jnp.broadcast_to(p["b_out"][:1], p["b_out"].shape),
    )
    y = scaffold.apply(moe)({"params": p}, x)
    dense = (
        nn.gelu(x @ p["w_in"][0] + p["b_in"][0]) @ p["w_out"][0]
        + p["b_out"][0]
    )
    np.testing.assert_allclose(y, dense, rtol=1e-5, atol=1e-5)


def test_capacity_overflow_drops_tokens():
    """Router forced onto one expert with capacity 2: exactly 2 tokens
    get expert output, the rest fall back to zero (the residual around
    the layer carries them)."""
    tokens = 8
    moe = MoEFFN(
        d_model=D, d_ff=FF, num_experts=E, top_k=1, capacity_factor=1.0
    )
    params, x = _init(jax.random.PRNGKey(1), moe, tokens=tokens)
    p = dict(params["params"])
    router = np.zeros((D, E), np.float32)
    router[:, 0] = 0.0  # uniform logits -> top_k ties resolve to expert 0
    p["router"] = {"kernel": jnp.asarray(router)}
    # capacity = ceil(1 * 8 / 4 * 1.0) = 2
    y = scaffold.apply(moe)({"params": p}, x)
    nonzero_rows = np.flatnonzero(np.abs(np.asarray(y)).sum(axis=1) > 1e-9)
    assert len(nonzero_rows) == 2, nonzero_rows
    np.testing.assert_array_equal(nonzero_rows, [0, 1])  # token order wins


def test_expert_parallel_matches_unsharded():
    n_dev = 8
    mesh = Mesh(np.asarray(jax.devices()[:n_dev]), ("expert",))
    moe_plain = MoEFFN(d_model=D, d_ff=FF, num_experts=n_dev, top_k=2)
    moe_ep = MoEFFN(
        d_model=D, d_ff=FF, num_experts=n_dev, top_k=2, mesh=mesh
    )
    params, x = _init(jax.random.PRNGKey(2), moe_plain, tokens=32)
    y_plain = scaffold.apply(moe_plain)(params, x)

    placed = {
        "params": place_expert_params(mesh, params["params"])
    }
    shardings = expert_param_shardings(mesh, params["params"])
    assert not shardings["w_in"].is_fully_replicated
    assert shardings["router"]["kernel"].is_fully_replicated
    y_ep = scaffold.apply(moe_ep)(placed, x)
    np.testing.assert_allclose(y_ep, y_plain, rtol=1e-5, atol=1e-5)


def test_aux_loss_sown_and_balanced_floor():
    moe = MoEFFN(
        d_model=D, d_ff=FF, num_experts=E, top_k=2, aux_loss_weight=1.0
    )
    params, x = _init(jax.random.PRNGKey(3), moe, tokens=64)
    _, variables = scaffold.apply(moe, mutable=("losses",))(params, x)
    assert "losses" not in params  # init() must not materialize it
    aux = variables["losses"]["moe_load_balance"]
    # E * sum(f_e * p_e) >= 1 with equality iff perfectly uniform.
    assert float(aux) >= 0.99


@pytest.mark.slow
def test_transformer_moe_trains_and_aux_flows():
    T, B, A = 4, 4, 5
    model = create_model(
        "transformer", num_actions=A, num_layers=1, d_model=16,
        num_heads=2, memory_len=4, num_experts=4,
    )
    rng = np.random.default_rng(4)
    batch = {
        "frame": rng.integers(0, 256, (T + 1, B, 4, 4, 1), dtype=np.uint8),
        "reward": rng.standard_normal((T + 1, B)).astype(np.float32),
        "done": rng.random((T + 1, B)) < 0.2,
        "episode_return": rng.standard_normal((T + 1, B)).astype(
            np.float32
        ),
        "episode_step": rng.integers(0, 9, (T + 1, B)).astype(np.int32),
        "last_action": rng.integers(0, A, (T + 1, B)).astype(np.int32),
        "action": rng.integers(0, A, (T + 1, B)).astype(np.int32),
        "policy_logits": rng.standard_normal((T + 1, B, A)).astype(
            np.float32
        ),
        "baseline": rng.standard_normal((T + 1, B)).astype(np.float32),
    }
    state = model.initial_state(B)
    params = scaffold.init(
        model,
        {"params": jax.random.PRNGKey(5), "action": jax.random.PRNGKey(6)},
        batch,
        state,
    )
    hp = learner_lib.HParams(batch_size=B, unroll_length=T)
    optimizer = learner_lib.make_optimizer(hp)
    step = learner_lib.make_update_step(model, optimizer, hp, donate=False)
    new_params, _, stats = step(params, optimizer.init(params), batch, state)
    assert np.isfinite(float(stats["total_loss"]))
    assert float(stats["aux_loss"]) > 0.0
    # The aux loss must reach the router: its kernel has to move.
    r_old = params["params"]["block_0"]["moe"]["router"]["kernel"]
    r_new = new_params["params"]["block_0"]["moe"]["router"]["kernel"]
    assert float(jnp.abs(r_new - r_old).max()) > 0.0


def test_acting_path_unaffected_by_sow():
    """model.apply WITHOUT mutable (the act path) still works — sow is a
    no-op when the collection isn't mutable."""
    A = 5
    model = create_model(
        "transformer", num_actions=A, num_layers=1, d_model=16,
        num_heads=2, memory_len=4, num_experts=4,
    )
    B = 2
    rng = np.random.default_rng(7)
    inputs = {
        "frame": rng.integers(0, 256, (1, B, 4, 4, 1), dtype=np.uint8),
        "reward": np.zeros((1, B), np.float32),
        "done": np.zeros((1, B), bool),
        "last_action": np.zeros((1, B), np.int32),
    }
    state = model.initial_state(B)
    params = scaffold.init(
        model,
        {"params": jax.random.PRNGKey(7), "action": jax.random.PRNGKey(8)},
        dict(inputs, episode_return=np.zeros((1, B), np.float32),
             episode_step=np.zeros((1, B), np.int32),
             action=np.zeros((1, B), np.int32),
             policy_logits=np.zeros((1, B, A), np.float32),
             baseline=np.zeros((1, B), np.float32)),
        state,
    )
    out, new_state = scaffold.apply(model)(
        params, inputs, state, rngs={"action": jax.random.PRNGKey(9)}
    )
    assert out.action.shape == (1, B)


def _init_model_params(model, A, frame_shape=(4, 4, 1), B=2):
    rng = np.random.default_rng(11)
    dummy = {
        "frame": rng.integers(0, 256, (1, B) + frame_shape, dtype=np.uint8),
        "reward": np.zeros((1, B), np.float32),
        "done": np.zeros((1, B), bool),
        "last_action": np.zeros((1, B), np.int32),
    }
    state = model.initial_state(B)
    return scaffold.init(
        model,
        {"params": jax.random.PRNGKey(11), "action": jax.random.PRNGKey(12)},
        dummy,
        state,
    )


def test_expert_sharding_contract_on_real_transformer_tree():
    """The EP sharding rule must fire on exactly the expert kernels of
    the REAL transformer-MoE param tree — by name and count — so a
    rename in models/moe.py fails loudly here instead of silently
    degrading to fully-replicated experts (parallel/ep.py)."""
    num_layers, E = 2, 4
    mesh = Mesh(np.asarray(jax.devices()[:E]), ("expert",))
    model = create_model(
        "transformer", num_actions=5, num_layers=num_layers, d_model=16,
        num_heads=2, memory_len=4, num_experts=E,
    )
    params = _init_model_params(model, A=5)
    shardings = expert_param_shardings(mesh, params["params"])
    flat, _ = jax.tree_util.tree_flatten_with_path(shardings)
    sharded = sorted(
        jax.tree_util.keystr(path)
        for path, s in flat
        if not s.is_fully_replicated
    )
    expected = sorted(
        f"['block_{i}']['moe']['{k}']"
        for i in range(num_layers)
        for k in ("w_in", "w_out")
    )
    assert sharded == expected, (
        f"EP rule fired on {sharded}, expected exactly {expected} — "
        "did models/moe.py rename its expert kernels?"
    )


def test_pipelined_stage_params_not_expert_sharded():
    """PipelinedMLPNet reuses the leaf names w_in/w_out for its stage
    stack [S, d, ff]; the EP rule must NOT shard those over the expert
    axis (no router sibling = not a MoE scope)."""
    S = 4
    mesh = Mesh(np.asarray(jax.devices()[:S]), ("expert",))
    model = create_model(
        "pipelined_mlp", num_actions=5, num_stages=S, d_model=16,
    )
    params = _init_model_params(model, A=5)
    shardings = expert_param_shardings(mesh, params["params"])
    assert all(
        s.is_fully_replicated
        for s in jax.tree_util.tree_leaves(shardings)
    )


def test_expert_sharding_contract_covers_opt_state():
    """polybeast shards the donated optax state with the SAME rule
    (polybeast.py `opt_shardings`); the MoE structural signature must be
    found inside optax's tuple/namedtuple wrappers too, or the [E, d, ff]
    RMSProp moments silently replicate and EP's memory scaling is lost."""
    num_layers, E = 1, 4
    mesh = Mesh(np.asarray(jax.devices()[:E]), ("expert",))
    model = create_model(
        "transformer", num_actions=5, num_layers=num_layers, d_model=16,
        num_heads=2, memory_len=4, num_experts=E,
    )
    params = _init_model_params(model, A=5)
    hp = learner_lib.HParams(batch_size=2, unroll_length=4)
    opt_state = learner_lib.make_optimizer(hp).init(params)
    shardings = expert_param_shardings(mesh, opt_state)
    flat, _ = jax.tree_util.tree_flatten_with_path(shardings)
    sharded = [
        jax.tree_util.keystr(path)
        for path, s in flat
        if not s.is_fully_replicated
    ]
    # Every occurrence of an expert kernel inside the optimizer moments
    # must be sharded (rmsprop: one `nu` accumulator tree; momentum off).
    assert sharded, "no opt_state leaves expert-sharded"
    assert all("['moe']" in p for p in sharded)
    n_kernels_in_params = 2 * num_layers
    assert len(sharded) % n_kernels_in_params == 0


# --- DroplessMoE: all of its experts held, or a share of them ------------


def _old_dropless_layer(p, x, top_k):
    """DroplessMoE as it was before `held` and `renormalise` (PR 32):
    softmax, top-k, gates as they are, every expert's weights here."""
    from torchbeast_tpu.models import moe

    logits = jnp.dot(
        x, p["router"]["kernel"], precision=jax.lax.Precision.HIGHEST
    )
    gate, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    return moe.dropless_experts(
        x, idx, gate, p["w_gate"], p["w_up"], p["w_down"]
    )[0]


@pytest.mark.parametrize("fields", [
    {},
    dict(scoring="softmax", selection_bias=False, bias_update_rate=0.0,
         routed_scaling=1.0, shared_width=0),
    dict(bias_update_rate=0.5),
    dict(gated=True, activation="silu", latent_width=0),
], ids=["unnamed", "named", "a-rate-without-a-bias", "pr42-named"])
def test_router_fields_at_their_defaults_are_the_old_layer(fields):
    """PR 38's fields (sigmoid scores, a selection bias, a scaling of
    the gates, a shared expert) and PR 42's (experts that are not
    gated, their activation, a latent) at their defaults: the parameter tree,
    the output bit for bit and the sown collections of the layer as it
    was, with no `param_steps` and no new statistic."""
    from torchbeast_tpu.models.moe import DroplessMoE

    x = jax.random.normal(jax.random.PRNGKey(9), (24, D))
    layer = DroplessMoE(d_ff=FF, num_experts=E, top_k=2, **fields)
    params = scaffold.init(layer, jax.random.PRNGKey(0), x)
    assert sorted(params["params"]) == ["router", "w_down", "w_gate", "w_up"]
    apply = scaffold.apply(
        layer, mutable=("losses", "param_steps") + stats.COLLECTIONS
    )
    old_layer = jax.jit(lambda p, x: _old_dropless_layer(p, x, 2))
    y, sown = apply(params, x)
    np.testing.assert_array_equal(y, old_layer(params["params"], x))
    assert sorted(sown) == ["losses", "stats_max", "stats_sum"]
    assert sorted(stats.folded(sown)) == [
        "moe_assignments", "moe_load_max_over_mean",
    ]
    assert float(sown["losses"]["moe_load_balance"]) > 0


@pytest.mark.parametrize("renormalise", [False, True])
def test_sigmoid_router_by_hand(renormalise):
    """`scoring="sigmoid"` with a scaling and a shared expert, no bias:
    each token's two largest sigmoid scores gate its experts (over
    their sum + 1e-20 if renormalised), times the scaling, and the
    shared SwiGLU is added unscaled; with `aux_loss_weight` 0 nothing
    is sown into `losses`."""
    from torchbeast_tpu.models.moe import DroplessMoE

    x = jax.random.normal(jax.random.PRNGKey(3), (12, D))
    layer = DroplessMoE(
        d_ff=FF, num_experts=E, top_k=2, aux_loss_weight=0.0,
        renormalise=renormalise, scoring="sigmoid", routed_scaling=1.5,
        shared_width=6,
    )
    params = scaffold.init(layer, jax.random.PRNGKey(0), x)
    p = params["params"]
    assert p["shared_gate"]["kernel"].shape == (D, 6)
    y, sown = scaffold.apply(
        layer, mutable=("losses",) + stats.COLLECTIONS
    )(params, x)
    assert "losses" not in sown
    assert float(stats.folded(sown)["moe_shared_applications"]) == 1.0
    scores = np.asarray(jax.nn.sigmoid(x @ p["router"]["kernel"]))
    want = np.zeros_like(np.asarray(y))
    for t in range(12):
        chosen = np.argsort(-scores[t])[:2]
        gates = scores[t, chosen]
        if renormalise:
            gates = gates / (gates.sum() + 1e-20)
        for g, e in zip(1.5 * gates, chosen):
            hidden = jax.nn.silu(x[t] @ p["w_gate"][e]) * (x[t] @ p["w_up"][e])
            want[t] += g * np.asarray(hidden @ p["w_down"][e])
    shared = (
        jax.nn.silu(x @ p["shared_gate"]["kernel"])
        * (x @ p["shared_up"]["kernel"])
    ) @ p["shared_down"]["kernel"]
    np.testing.assert_allclose(y, want + shared, rtol=1e-5, atol=1e-6)
    with pytest.raises(ValueError, match="Unknown scoring"):
        scaffold.init(
            DroplessMoE(d_ff=FF, num_experts=E, top_k=2, scoring="tanh"),
            jax.random.PRNGKey(0), x,
        )


@pytest.mark.parametrize(
    "held", [None, (0, E), (0, 2), (2, 2)],
    ids=["all", "whole-range", "first-half", "second-half"],
)
def test_dropless_layer_with_its_experts_held(held):
    """`held` None or whole is the old layer bit for bit: same parameter
    tree, same output, same sown terms and no `held_*` ones unless a
    range was named. A half holds half the weights, and the two halves'
    outputs add up to the whole layer's."""
    from torchbeast_tpu.models.moe import DroplessMoE

    x = jax.random.normal(jax.random.PRNGKey(9), (24, D))
    whole = DroplessMoE(d_ff=FF, num_experts=E, top_k=2)
    params = scaffold.init(whole, jax.random.PRNGKey(0), x)
    p = params["params"]
    layer = DroplessMoE(d_ff=FF, num_experts=E, top_k=2, held=held)
    first, count = held or (0, E)
    mine = {"params": dict(p, **{
        k: p[k][first : first + count] for k in ("w_gate", "w_up", "w_down")
    })}
    shapes = jax.tree_util.tree_map(
        jnp.shape, scaffold.init(layer, jax.random.PRNGKey(0), x)
    )
    assert shapes == jax.tree_util.tree_map(jnp.shape, mine)
    mutable = ("losses",) + stats.COLLECTIONS
    as_it_was = jax.jit(lambda p, x: _old_dropless_layer(p, x, 2))
    (y, sown), old = (
        scaffold.apply(layer, mutable=mutable)(mine, x), as_it_was(p, x)
    )
    y_whole, sown_whole = scaffold.apply(whole, mutable=mutable)(params, x)
    assert float(sown["losses"]["moe_load_balance"]) == float(
        sown_whole["losses"]["moe_load_balance"]
    )
    assert float(stats.folded(sown)["moe_assignments"]) == 48.0
    if count == E:
        np.testing.assert_array_equal(y, old)
        np.testing.assert_array_equal(y, y_whole)
        assert ("moe_held_assignments" in stats.folded(sown)) == (
            held is not None
        )
    else:
        other = DroplessMoE(
            d_ff=FF, num_experts=E, top_k=2, held=(2 - first, 2)
        )
        theirs = {"params": dict(p, **{
            k: p[k][2 - first : 4 - first]
            for k in ("w_gate", "w_up", "w_down")
        })}
        np.testing.assert_allclose(
            y + scaffold.apply(other)(theirs, x), old, rtol=1e-5, atol=1e-6
        )
        held_rows = float(stats.folded(sown)["moe_held_assignments"])
        assert 0 < held_rows < 48
    with pytest.raises(ValueError, match="not a range"):
        scaffold.init(
            DroplessMoE(d_ff=FF, num_experts=E, top_k=2, held=(3, 2)),
            jax.random.PRNGKey(0), x,
        )


def _latent_by_hand(x, p, top_k, scaling, held=None):
    """The ungated relu^2 experts in a latent, a token at a time."""
    scores = np.asarray(jax.nn.sigmoid(x @ p["router"]["kernel"]))
    latent = x @ p["latent_down"]["kernel"]
    first, count = held or (0, scores.shape[1])
    routed = np.zeros(latent.shape, np.float32)
    for t in range(x.shape[0]):
        chosen = np.argsort(-scores[t], kind="stable")[:top_k]
        gates = scaling * scores[t, chosen] / (scores[t, chosen].sum() + 1e-20)
        for g, e in zip(gates, chosen):
            if first <= e < first + count:
                hidden = jnp.square(jax.nn.relu(
                    latent[t] @ p["w_up"][e - first]
                ))
                routed[t] += g * np.asarray(hidden @ p["w_down"][e - first])
    return routed @ p["latent_up"]["kernel"] + jnp.square(
        jax.nn.relu(x @ p["shared_up"]["kernel"])
    ) @ p["shared_down"]["kernel"]


def test_ungated_relu2_experts_in_a_latent_by_hand():
    """`gated=False`, `activation="relu2"`, `latent_width` 5: two
    matrices an expert and no `w_gate` / `shared_gate`; one projection
    into the latent before the dispatch and one out of it after the
    combine; the router and the shared expert read the full width, the
    shared expert unscaled; `latent_applications` is sown."""
    from torchbeast_tpu.models.moe import DroplessMoE

    x = jax.random.normal(jax.random.PRNGKey(3), (12, D))
    layer = DroplessMoE(
        d_ff=FF, num_experts=E, top_k=2, aux_loss_weight=0.0,
        renormalise=True, scoring="sigmoid", routed_scaling=5.0,
        shared_width=6, gated=False, activation="relu2", latent_width=5,
    )
    params = scaffold.init(layer, jax.random.PRNGKey(0), x)
    p = params["params"]
    assert jax.tree_util.tree_map(jnp.shape, p) == {
        "router": {"kernel": (D, E)},
        "latent_down": {"kernel": (D, 5)}, "latent_up": {"kernel": (5, D)},
        "w_up": (E, 5, FF), "w_down": (E, FF, 5),
        "shared_up": {"kernel": (D, 6)}, "shared_down": {"kernel": (6, D)},
    }
    y, sown = scaffold.apply(
        layer, mutable=("losses",) + stats.COLLECTIONS
    )(params, x)
    assert float(stats.folded(sown)["moe_latent_applications"]) == 1.0
    np.testing.assert_allclose(
        y, _latent_by_hand(x, p, 2, 5.0), rtol=1e-5, atol=1e-6
    )
    # relu^2 is not relu, and the activation is the shared expert's too.
    relu = scaffold.apply(layer.clone(activation="silu"))(params, x)
    assert float(jnp.max(jnp.abs(relu - y))) > 1e-3
    with pytest.raises(ValueError, match="Unknown activation"):
        scaffold.init(
            layer.clone(activation="gelu"), jax.random.PRNGKey(0), x
        )
