"""The `ouro` family (models/ouro.py; the pass walk in models/
transformer.py): against the plain reference on seeded weights, the loop
against an untied stack of copies, one pass against the plain stack,
the learner's batch forward against stepwise acting through the passes'
rolling caches (directly and through `DeviceStateTable`), and
rematerialised shared blocks."""

import numpy as np
import pytest

import jax
import jax.flatten_util
import jax.numpy as jnp

from perfbench.reference import ouro_policy as reference
from tests.seeded_pin import assert_seeded_outputs
from torchbeast_tpu import learner as learner_lib
from torchbeast_tpu import monobeast, polybeast
from torchbeast_tpu.models import OLMoENet, OuroNet, create_model, ouro
from torchbeast_tpu.runtime import remat_plan
from torchbeast_tpu.runtime.state_table import DeviceStateTable

T, B, A = 6, 2, 4
FRAME = (8, 8, 1)
# A shrunken `PUBLISHED`: 4 heads of 16, a SwiGLU of 96, 2 layers run 3
# times over 5-slot caches (T=6 evicts on the way).
SMALL = dict(
    d_model=64, num_heads=4, head_dim=16, mlp_width=96, num_layers=2,
    passes=3,
)
M = 5
# As tests/test_olmoe.py: on the CPU both sides compute in float32 at
# full precision and differ by the order of their sums (keys and logits
# reach 4-5 under the perturbed norm scales, hence the absolute part).
RTOL, ATOL = 1e-5, 3e-5


def _inputs(seed, done_steps=(), t=T, rows=B):
    rng = np.random.default_rng(seed)
    done = np.zeros((t, rows), bool)
    for step, row in done_steps:
        done[step, row] = True
    return {
        "frame": jnp.asarray(
            rng.integers(0, 256, (t, rows) + FRAME, dtype=np.uint8)
        ),
        "reward": jnp.asarray(rng.standard_normal((t, rows)), jnp.float32),
        "done": jnp.asarray(done),
        "last_action": jnp.asarray(rng.integers(0, A, (t, rows))),
    }


def _learner_batch(seed, done_steps):
    rng = np.random.default_rng(seed + 100)
    lead = (T, B)
    return dict(
        _inputs(seed, done_steps),
        episode_return=jnp.asarray(rng.standard_normal(lead), jnp.float32),
        episode_step=jnp.zeros(lead, jnp.int32),
        action=jnp.asarray(rng.integers(0, A, lead)),
        policy_logits=jnp.asarray(
            rng.standard_normal(lead + (A,)), jnp.float32
        ),
        baseline=jnp.asarray(rng.standard_normal(lead), jnp.float32),
    )


def _model(seed=0, **overrides):
    model = OuroNet(num_actions=A, memory_len=M, **dict(SMALL, **overrides))
    params = model.init(
        {"params": jax.random.PRNGKey(seed), "action": jax.random.PRNGKey(1)},
        _inputs(0), model.initial_state(B),
    )
    # Norm scales start at one and the gate's bias at zero: move them, so
    # that a norm left out or applied twice, or a gate misread, shows.
    flat, unravel = jax.flatten_util.ravel_pytree(params)
    noise = 0.2 * jax.random.normal(jax.random.PRNGKey(seed + 7), flat.shape)
    return model, unravel(flat + noise)


def _reference_config(**overrides):
    widths = dict(SMALL, **overrides)
    return {
        "num_attention_heads": widths["num_heads"],
        "head_dim": widths["head_dim"],
        "num_hidden_layers": widths["num_layers"],
        "total_ut_steps": widths["passes"],
        "rms_norm_eps": 1e-6, "rope_theta": 1000000.0,
        "memory_len": M, "num_actions": A,
        "discounting": 0.99, "baseline_cost": 0.5, "entropy_cost": 0.0006,
    }


def _warm_state(model, params, seed, unrolls=1):
    """Caches an actor would hold: `unrolls` unrolls in (each fills the
    5 slots), an episode end in the first."""
    state = model.initial_state(B)
    for i in range(unrolls):
        _, state = model.apply(
            params, _inputs(seed + i, done_steps=[(4, 1)] if i == 0 else ()),
            state, sample_action=False,
        )
    return state


def _loss_and_grads(model, params, batch, state):
    hp = learner_lib.HParams(batch_size=B, unroll_length=T - 1)
    (loss, stats), grads = jax.value_and_grad(
        lambda p: learner_lib.compute_loss(model, p, batch, state, hp),
        has_aux=True,
    )(params)
    return loss, stats, grads


def test_family_agrees_with_the_reference():
    """Outputs, the passes' new caches, the loss and its gradients, and
    the exit gates' distribution, on seeded weights from warm caches
    with an episode end inside the unroll. RTOL: f32 on both sides, the
    sums in another order."""
    model, params = _model()
    config = _reference_config()
    state = _warm_state(model, params, seed=5)
    batch = _learner_batch(7, done_steps=[(3, 0)])

    out, new_state = model.apply(params, batch, state, sample_action=False)
    logits, baseline, ref_state, gates = reference.forward(
        params, batch, state, config
    )
    np.testing.assert_allclose(out.policy_logits, logits, RTOL, ATOL)
    np.testing.assert_allclose(out.baseline, baseline, RTOL, ATOL)
    assert len(new_state) == len(ref_state) == 3 * 2
    for got, want in zip(
        jax.tree_util.tree_leaves(new_state),
        jax.tree_util.tree_leaves(ref_state),
    ):
        assert got.shape == want.shape
        np.testing.assert_allclose(got, want, RTOL, ATOL)

    loss, stats, grads = _loss_and_grads(model, params, batch, state)
    ref_loss, ref_grads = jax.value_and_grad(reference.loss)(
        params, batch, state, config
    )
    scale = float(reference.loss_and_scale(params, batch, state, config)[1])
    assert abs(float(loss) - float(ref_loss)) <= RTOL * scale
    flat, ref_flat = (
        jax.flatten_util.ravel_pytree(g)[0] for g in (grads, ref_grads)
    )
    np.testing.assert_allclose(
        flat, ref_flat, rtol=0, atol=RTOL * float(jnp.max(jnp.abs(ref_flat)))
    )
    # No gradient reaches the gate, in the program or in the reference.
    for tree in (grads, ref_grads):
        assert not np.any(
            jax.flatten_util.ravel_pytree(tree["params"]["exit_gate"])[0]
        )
    assert float(stats["aux_loss"]) == 0.0

    # The statistics the learner logs, against the reference's gates.
    p_exit = reference.exit_distribution(gates)  # [3, B, T]
    np.testing.assert_allclose(p_exit.sum(axis=0), 1.0, 1e-6)
    assert float(stats["loop_passes"]) == 3
    assert float(stats["loop_block_applications"]) == 6
    assert float(stats["attention_two_leg_applications"]) == 6
    assert "attention_fused_applications" not in stats
    assert float(stats["loop_cache_bytes_per_row"]) == 6 * 4 * M * (
        2 * 4 * 16 + 1
    )
    np.testing.assert_allclose(
        stats["loop_expected_exit_pass"],
        jnp.mean(jnp.sum(p_exit * jnp.arange(1, 4)[:, None, None], axis=0)),
        rtol=1e-5,
    )
    np.testing.assert_allclose(
        stats["loop_exit_p_last"], jnp.mean(p_exit[-1]), rtol=1e-5
    )
    assert 1.0 < float(stats["loop_expected_exit_pass"]) < 3.0


class _UntiedOuroNet(OuroNet):
    """The loop written out as a stack of `passes x num_layers` distinct
    blocks: entry i is served by block i, and the last norm still
    follows every `num_layers` of them."""

    def block_passes(self):
        L = self.num_layers
        return tuple(
            tuple(range(u * L, (u + 1) * L)) for u in range(self.passes)
        )


def test_looped_equals_untied_and_its_gradient_is_the_copies_sum():
    """Weight tying is the only difference between the loop and a stack
    of passes x L distinct blocks: given copies of the looped weights
    the untied stack gives the same outputs and caches, and its
    gradients summed over a block's copies are the looped gradient."""
    model, params = _model()
    L, P = SMALL["num_layers"], SMALL["passes"]
    untied = _UntiedOuroNet(num_actions=A, memory_len=M, **SMALL)
    inner = dict(params["params"])
    copies = {
        f"block_{u * L + layer}": jax.tree_util.tree_map(
            jnp.copy, inner[f"block_{layer}"]
        )
        for u in range(P) for layer in range(L)
    }
    untied_params = {"params": dict(inner, **copies)}
    # The untied net really builds P x L blocks of its own.
    shapes = jax.eval_shape(
        lambda: untied.init(
            {"params": jax.random.PRNGKey(0), "action": jax.random.PRNGKey(1)},
            _inputs(0), untied.initial_state(B),
        )
    )
    assert sorted(shapes["params"]) == sorted(untied_params["params"])

    state = _warm_state(model, params, seed=3)
    batch = _learner_batch(11, done_steps=[(2, 1)])
    out, new_state = model.apply(params, batch, state, sample_action=False)
    out_u, new_state_u = untied.apply(
        untied_params, batch, state, sample_action=False
    )
    np.testing.assert_allclose(out.policy_logits, out_u.policy_logits, RTOL, ATOL)
    for got, want in zip(
        jax.tree_util.tree_leaves(new_state),
        jax.tree_util.tree_leaves(new_state_u),
    ):
        np.testing.assert_allclose(got, want, RTOL, ATOL)

    loss, _, grads = _loss_and_grads(model, params, batch, state)
    loss_u, _, grads_u = _loss_and_grads(untied, untied_params, batch, state)
    assert float(loss) == pytest.approx(float(loss_u), rel=1e-6)
    g, gu = grads["params"], grads_u["params"]
    for layer in range(L):
        summed = jax.tree_util.tree_map(
            lambda *parts: sum(parts),
            *[gu[f"block_{u * L + layer}"] for u in range(P)],
        )
        flat, want = (
            jax.flatten_util.ravel_pytree(t)[0]
            for t in (summed, g[f"block_{layer}"])
        )
        # A sum of three gradients against one accumulated in the
        # backward pass: f32, another order.
        np.testing.assert_allclose(
            flat, want, rtol=0, atol=1e-5 * float(jnp.max(jnp.abs(want)))
        )
        # And no copy's gradient is the whole.
        assert float(jnp.max(jnp.abs(
            jax.flatten_util.ravel_pytree(gu[f"block_{layer}"])[0] - want
        ))) > 1e-4 * float(jnp.max(jnp.abs(want)))
    for name in ("Dense_0", "extras", "final_norm", "head"):
        np.testing.assert_allclose(
            jax.flatten_util.ravel_pytree(g[name])[0],
            jax.flatten_util.ravel_pytree(gu[name])[0],
            rtol=1e-4, atol=1e-6,
        )


def test_one_pass_is_the_plain_stack():
    """passes = 1: L caches, block i on entry i, the norm once at the
    end — the walk every other family on the scaffolding takes — and the
    reference's single pass agrees."""
    model, params = _model(passes=1)
    assert model.block_passes() == ((0, 1),)
    assert model.block_passes() == OLMoENet(
        num_actions=A, num_layers=2
    ).block_passes()
    assert len(model.initial_state(B)) == 2
    state = _warm_state(model, params, seed=1)
    batch = _learner_batch(2, done_steps=[(1, 0)])
    out, _ = model.apply(params, batch, state, sample_action=False)
    logits, baseline, _, gates = reference.forward(
        params, batch, state, _reference_config(passes=1)
    )
    np.testing.assert_allclose(out.policy_logits, logits, RTOL, ATOL)
    np.testing.assert_allclose(out.baseline, baseline, RTOL, ATOL)
    _, stats, _ = _loss_and_grads(model, params, batch, state)
    assert float(stats["loop_expected_exit_pass"]) == 1.0
    assert float(stats["loop_exit_p_last"]) == 1.0
    # More passes over the same weights give another policy.
    looped, _ = _model()
    out3, _ = looped.apply(
        params, batch, looped.initial_state(B), sample_action=False
    )
    out1, _ = model.apply(
        params, batch, model.initial_state(B), sample_action=False
    )
    assert float(jnp.max(jnp.abs(out3.policy_logits - out1.policy_logits))) > 1e-3


@pytest.mark.parametrize("unrolls", [0, 1], ids=["empty", "full"])
def test_batch_forward_equals_stepwise_acting_through_the_passes_caches(
    unrolls,
):
    """The learner's [T, B] forward and the actor's T=1 forwards, each
    of 3 passes through its 3 x 2 rolling caches (5 slots; T=6 evicts),
    give the same logits and leave the same caches, across an episode
    end. Tolerance: a softmax over another number of masked keys, f32."""
    model, params = _model()
    state = _warm_state(model, params, seed=2, unrolls=unrolls)
    inputs = _inputs(3, done_steps=[(3, 1)])
    full, full_state = model.apply(params, inputs, state, sample_action=False)
    logits = []
    for t in range(T):
        step = {k: v[t : t + 1] for k, v in inputs.items()}
        out, state = model.apply(params, step, state, sample_action=False)
        logits.append(out.policy_logits[0])
    np.testing.assert_allclose(
        np.stack(logits), full.policy_logits, rtol=2e-4, atol=2e-5
    )
    for got, want in zip(
        jax.tree_util.tree_leaves(state),
        jax.tree_util.tree_leaves(full_state),
    ):
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    # A pass's cache is its own: pass 0's and pass 1's keys of layer 0
    # differ.
    assert float(jnp.max(jnp.abs(full_state[0][0] - full_state[2][0]))) > 1e-3


def test_stepwise_acting_through_the_state_table_equals_the_batch_forward():
    """Three actors' slots in a `DeviceStateTable` whose rows hold the
    3 x 2 caches (a pytree of 18 leaves the table knows nothing of);
    the rows arrive in another order every step and one episode ends on
    the way. Every step's logits equal the learner's batch forward from
    empty caches over the same inputs, and what the table holds at the
    end is what that forward leaves. Tolerance as above."""
    model, params = _model()
    rows = 3
    inputs = _inputs(4, done_steps=[(3, 2)], rows=rows)
    full, full_state = model.apply(
        params, inputs, model.initial_state(rows), sample_action=False
    )

    def act(ctx, env_outputs, agent_state):
        out, new_state = model.apply(
            params, env_outputs, agent_state, sample_action=False
        )
        return {"logits": out.policy_logits}, new_state

    table = DeviceStateTable(
        model.initial_state(1), num_slots=rows, act_fn=act, batch_dim=1
    )
    assert len(table.read_slot(0)) == 3 * 2
    orders = [[0, 1, 2], [2, 0, 1], [1, 2, 0], [2, 1, 0], [0, 2, 1], [1, 0, 2]]
    for t, order in enumerate(orders):
        step = {
            k: np.asarray(v[t : t + 1])[:, order] for k, v in inputs.items()
        }
        out = table.step(
            np.asarray(order, np.int32), np.ones(rows, bool), step
        )
        np.testing.assert_allclose(
            table.fetch(out, rows)["logits"][0],
            np.asarray(full.policy_logits)[t][order],
            rtol=2e-4, atol=2e-5,
        )
    for slot in range(rows):
        for entry, held in enumerate(table.read_slot(slot)):
            for got, want in zip(held, full_state[entry]):
                np.testing.assert_allclose(
                    got, np.asarray(want)[:, slot : slot + 1],
                    rtol=2e-4, atol=2e-5,
                )
    # Resetting a slot empties all 6 of its caches and no other's.
    table.reset([1])
    assert not any(np.any(leaf) for e in table.read_slot(1) for leaf in e)
    assert all(np.any(e[2]) for e in table.read_slot(0))


def test_rematerialised_shared_blocks_give_the_same_loss_and_gradients():
    """`--remat all`: nn.remat around a block instance that is applied
    `passes` times changes no value."""
    model, params = _model()
    state = _warm_state(model, params, seed=5)
    batch = _learner_batch(9, done_steps=[(1, 1)])
    loss, stats, grads = _loss_and_grads(model, params, batch, state)
    loss_r, stats_r, grads_r = _loss_and_grads(
        model.clone(remat=True), params, batch, state
    )
    assert float(loss) == pytest.approx(float(loss_r), rel=1e-6)
    np.testing.assert_allclose(
        jax.flatten_util.ravel_pytree(grads)[0],
        jax.flatten_util.ravel_pytree(grads_r)[0], rtol=1e-5, atol=1e-6,
    )
    assert float(stats["loop_exit_p_last"]) == pytest.approx(
        float(stats_r["loop_exit_p_last"]), rel=1e-6
    )


def test_registry_builds_the_published_widths_and_refuses_lstm():
    model = create_model("ouro", num_actions=6, num_layers=8)
    assert isinstance(model, OuroNet)
    assert (model.d_model, model.num_heads, model.head_dim) == (2048, 16, 128)
    assert (model.mlp_width, model.passes, model.memory_len) == (5632, 4, 255)
    assert (model.rms_norm_eps, model.rope_theta) == (1e-6, 1e6)
    assert model.frame_range == (-1.0, 1.0)
    assert not model.zero_init_extras
    assert model.matmul_precision == "high"
    assert create_model("ouro", num_actions=6).num_layers == 48
    # 4 x 8 caches of [255, B, 16, 128] over 8 blocks, pass-major.
    assert model.layer_caches() == ((255, 16, 128),) * 32
    assert model.block_passes() == (tuple(range(8)),) * 4
    state = jax.eval_shape(lambda: model.initial_state(3))
    assert len(state) == 32 and state[31][0].shape == (255, 3, 16, 128)
    with pytest.raises(ValueError, match="use_lstm"):
        create_model("ouro", num_actions=6, use_lstm=True)


@pytest.mark.parametrize("driver", [monobeast, polybeast], ids=["mono", "poly"])
def test_parsers_take_the_family_and_its_flags(driver, monkeypatch):
    parse = driver.make_parser().parse_args
    flags = parse([
        "--model", "ouro", "--num_layers", "3", "--memory_len", "9",
        "--remat", "all",
    ])
    monkeypatch.setattr(ouro, "PUBLISHED", dict(ouro.PUBLISHED, **SMALL))
    model, _ = monobeast._init_model_and_params(
        flags, A, B, FRAME, init_params=False
    )
    assert isinstance(model, OuroNet)
    assert (model.num_layers, model.memory_len, model.d_model) == (3, 9, 64)
    # `passes` has no flag: the (shrunken) table's.
    assert model.passes == 3 and len(model.layer_caches()) == 9
    assert model.remat is True
    for flag, value in (("--num_experts", "4"), ("--expert_share", "0/4")):
        with pytest.raises(ValueError):
            monobeast._init_model_and_params(
                parse(["--model", "ouro", flag, value]),
                A, B, FRAME, init_params=False,
            )


@pytest.mark.parametrize("family,lever", [
    ("transformer", True), ("pipelined_transformer", True),
    ("mellum2", True), ("ouro", True), ("olmoe", False), ("deep", False),
    ("mlp", False), ("shallow", False), ("pipelined_mlp", False),
])
def test_a_familys_class_says_whether_remat_reaches_its_blocks(family, lever):
    """runtime/remat_plan.py asks the class (`remat_lever`), not a list
    of names: the membership PR 32 left, plus ouro; olmoe stays out."""
    names = [s.name for s in remat_plan.stages_for(family, use_lstm=False)]
    assert ("blocks" in names) == lever
    kwargs = remat_plan.model_kwargs(
        family, {name: True for name in names}
    )
    assert (kwargs.get("remat") is True) == lever


def test_every_matmul_is_traced_at_the_familys_precision():
    """Three bf16 passes (`high`) on every dot of the update, the
    gradient's and the rematerialised blocks' among them: what keeps the
    chip's loss within 2e-5 of the float32 reference's scale, not 2.5e-3
    (PERF.md, PR 34). The other families' dots say nothing (DEFAULT)."""
    import re

    def dots(model, params, state):
        batch = _learner_batch(1, done_steps=[])
        hp = learner_lib.HParams(batch_size=B, unroll_length=T - 1)
        text = jax.jit(jax.grad(
            lambda p: learner_lib.compute_loss(model, p, batch, state, hp)[0]
        )).lower(params).as_text()
        return re.findall(r"dot_general.*", text)

    model, params = _model()
    ours = dots(model.clone(remat=True), params, model.initial_state(B))
    assert len(ours) > 100 and all("HIGH" in d for d in ours)
    assert not any("HIGHEST" in d for d in ours)
    plain = OLMoENet(
        num_actions=A, num_layers=1, d_model=64, num_heads=4, memory_len=M,
        num_experts=4, experts_per_token=2, expert_width=32,
    )
    plain_params = plain.init(
        {"params": jax.random.PRNGKey(0), "action": jax.random.PRNGKey(1)},
        _inputs(0), plain.initial_state(B),
    )
    theirs = dots(plain, plain_params, plain.initial_state(B))
    assert theirs and not any("HIGH" in d for d in theirs if "HIGHEST" not in d)


def test_seeded_logits_are_what_they_were_before_pr_38():
    """PR 38 let a cache entry's two leaves differ (models/transformer.
    py `layer_caches`, `initial_state`) and gave `DroplessMoE` a second
    router: this family's tree, state and outputs at a seeded tiny size
    are the numbers the parent commit gave (tests/seeded_pin.py, run on
    both trees)."""
    assert_seeded_outputs(
        OuroNet(
            num_actions=4, num_layers=2, memory_len=5, d_model=32,
            num_heads=2, head_dim=16, mlp_width=48, passes=3,
        ),
        params=20166,
        logits=[
            -1.6723791360855103, -1.0398012399673462, -0.014137506484985352,
            -0.9085246920585632,
        ],
        baseline=0.43291524052619934,
        leaf_shapes=[[5, 2, 2, 16], [5, 2, 2, 16], [5, 2], [5, 2, 2, 16]],
        state_sum=3152.189697265625,
    )
