"""The update step's parts that no Flax module names carry
`jax.named_scope`s, so a device trace can be split by them: the names
reach the compiled HLO's `op_name` metadata (ISSUE 25)."""

import re

import jax
import optax
import pytest

from tests import family_scaffold as scaffold
from tests.test_learner import make_batch
from torchbeast_tpu import learner as learner_lib
from torchbeast_tpu.models import create_model

SCOPES = ("vtrace", "loss_terms", "optimizer", "grad_norm")


def _in_scope(op_name, scope):
    """Under jax.grad a scope shows as `jvp(<scope>)` and
    `transpose(jvp(<scope>))`; outside it, bare."""
    return re.search(rf"[/(]{scope}[/)]", op_name + "/") is not None


def _op_names(optimizer):
    model = create_model("shallow", num_actions=3)
    batch = make_batch()
    params = scaffold.init(
        model,
        {"params": jax.random.PRNGKey(0), "action": jax.random.PRNGKey(1)},
        batch, (),
    )
    update_step = learner_lib.make_update_step(
        model, optimizer, learner_lib.HParams(), donate=False
    )
    compiled = update_step.lower(
        params, optimizer.init(params), batch, ()
    ).compile()
    return re.findall(r'op_name="([^"]+)"', compiled.as_text())


@pytest.fixture(scope="module")
def op_names():
    """Plain SGD: the drivers' optimizer clips by the global norm, and
    XLA merges that identical computation with `grad_norm`'s, keeping
    the optimizer's name (see the last test)."""
    return _op_names(optax.sgd(0.1))


@pytest.mark.parametrize("scope", SCOPES)
def test_scope_reaches_the_compiled_hlo(op_names, scope):
    inside = [n for n in op_names if _in_scope(n, scope)]
    assert inside, f"no compiled op carries the scope {scope!r}"


def test_scopes_do_not_swallow_the_model(op_names):
    """The model's own ops keep their Flax module names: the scopes wrap
    only what follows the forward pass."""
    in_scopes = [
        n for n in op_names if any(_in_scope(n, s) for s in SCOPES)
    ]
    assert 0 < len(in_scopes) < len(op_names)
    assert any("AtariNet" in n for n in op_names)


def test_the_drivers_optimizer_keeps_three_scopes():
    """With the drivers' optimizer the clip's norm and the reported one
    are one computation after XLA's CSE: `grad_norm` may be absent from
    the compiled program, the other three are there."""
    names = _op_names(learner_lib.make_optimizer(learner_lib.HParams()))
    for scope in ("vtrace", "loss_terms", "optimizer"):
        assert any(_in_scope(n, scope) for n in names), scope


# --- a family's own scopes (PR 46: `--model qwen3next`) -----------------------

QWEN3NEXT_SCOPES = (
    "deltanet_in_proj", "deltanet_conv", "delta_scan", "delta_intra",
    "delta_solve", "delta_states", "delta_inter", "deltanet_gate_norm",
    "deltanet_out_proj", "attention_full", "attention_gate",
    "moe_shared_gate",
)


@pytest.fixture(scope="module")
def qwen3next_op_names():
    """The toy family's whole update, compiled (tests/family_scaffold.
    py): what a device trace of the cell is split by."""
    model, params = scaffold.build("qwen3next")
    t = scaffold.FAMILIES["qwen3next"].t
    batch = scaffold.learner_batch(1, [(4, 0), (5, 1)], t=t)
    hp = learner_lib.HParams(batch_size=scaffold.B, unroll_length=t - 1)
    optimizer = optax.sgd(0.1)
    compiled = learner_lib.make_update_step(
        model, optimizer, hp, donate=False
    ).lower(
        params, optimizer.init(params), batch,
        model.initial_state(scaffold.B),
    ).compile()
    return re.findall(r'op_name="([^"]+)"', compiled.as_text())


@pytest.mark.parametrize("scope", QWEN3NEXT_SCOPES)
def test_family_scope_reaches_the_compiled_hlo(qwen3next_op_names, scope):
    inside = [n for n in qwen3next_op_names if _in_scope(n, scope)]
    assert inside, f"no compiled op carries the scope {scope!r}"
    if scope in ("delta_intra", "delta_states", "delta_inter"):
        assert all(_in_scope(n, "delta_scan") for n in inside)
    if scope == "delta_solve":
        assert all(_in_scope(n, "delta_intra") for n in inside)


def test_the_solves_own_backward_is_under_its_calls_scopes(qwen3next_op_names):
    """`unit_lower_inverse` is a `custom_vjp`: its backward rule's two
    products carry the scopes of the call they are the backward of, so
    a trace split by scope charges them to `delta_solve`."""
    backward = [
        n for n in qwen3next_op_names
        if _in_scope(n, "delta_solve") and "transpose(" in n
        and n.endswith("dot_general")
    ]
    assert backward
    assert all(_in_scope(n, "delta_intra") for n in backward)


def _kernel_calls(jaxpr, under=""):
    """(name stack, kernel name) of every Pallas kernel call in a
    jaxpr, calls inside calls too: what the compiled module's
    `op_name` is made of."""
    for eqn in jaxpr.eqns:
        stack = f"{under}/{eqn.source_info.name_stack}"
        if eqn.primitive.name == "pallas_call":
            yield stack, stack.rsplit("/", 1)[-1]
        for inner in jax.core.jaxprs_in_params(eqn.params):
            yield from _kernel_calls(inner, stack)


def test_the_experts_own_kernels_are_under_their_scope(monkeypatch):
    """The grouped matmuls that cut their operands in VMEM (PR 50) are
    kernels of this repo's, called from a `custom_vjp`: the forward's
    three a SwiGLU and the backward rule's six carry `moe_experts`, so
    a trace split by scope charges all nine to it."""
    import jax.numpy as jnp

    from torchbeast_tpu.models import moe

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rows, d, width, held = 256, 128, 128, 2

    def loss(x, w_gate, w_up, w_down, sizes):
        with jax.default_matmul_precision("high"):
            y = moe._experts_on_rows(
                x, w_gate, w_up, w_down, sizes, 0, "silu",
                moe._terms_traced_under(),
            )
        return jnp.sum(jnp.sin(y))

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2, 3)))(
        jnp.ones((rows, d)), jnp.ones((held, d, width)),
        jnp.ones((held, d, width)), jnp.ones((held, width, d)),
        jnp.array([100, 100, 56], jnp.int32),
    )
    calls = list(_kernel_calls(jaxpr.jaxpr))
    assert sorted(name for _, name in calls) == (
        ["gmm_cut_in_vmem"] * 6 + ["tgmm_cut_in_vmem"] * 3
    )
    assert all(_in_scope(stack, "moe_experts") for stack, _ in calls)
    backward = [stack for stack, _ in calls if "transpose(" in stack]
    assert len(backward) == 6
