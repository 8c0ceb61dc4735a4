"""The update step's parts that no Flax module names carry
`telemetry.device_scope`s, so a device trace can be split by them: the
names reach the compiled HLO's `op_name` metadata (ISSUE 25) and the
by-scope account's set of known scopes (ISSUE 51)."""

import re

import jax
import optax
import pytest

from tests import family_scaffold as scaffold
from tests.test_learner import make_batch
from torchbeast_tpu import learner as learner_lib
from torchbeast_tpu import telemetry
from torchbeast_tpu.models import create_model
from torchbeast_tpu.telemetry import device_scopes

SCOPES = ("vtrace", "loss_terms", "optimizer", "grad_norm")


def _in_scope(op_name, scope):
    """Under jax.grad a scope shows as `jvp(<scope>)` and
    `transpose(jvp(<scope>))`; outside it, bare. Read as the by-scope
    account reads a path, the candidates the names `device_scope`
    noted while the program was traced: an expectation that holds
    says the helper knows the name too."""
    return scope in device_scopes.scopes_of(
        op_name, telemetry.known_device_scopes()
    )


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


# --- the conv family's scopes (PR 51: `--model deep --use_lstm`) -----------------

DEEP_SCOPES = (
    "trunk_input", "trunk_stage_0", "trunk_stage_1", "trunk_stage_2",
    "trunk_fc", "lstm_core", "policy_head",
)


@pytest.fixture(scope="module")
def deep_op_names():
    """The flagship net's update at a toy size: without these names
    all of a conv cell's step but the loss and the optimizer would
    read `unscoped`."""
    model = create_model("deep", num_actions=3, use_lstm=True)
    batch = make_batch(t=3, b=2)
    state = model.initial_state(2)
    params = scaffold.init(
        model,
        {"params": jax.random.PRNGKey(0), "action": jax.random.PRNGKey(1)},
        batch, state,
    )
    optimizer = optax.sgd(0.1)
    compiled = learner_lib.make_update_step(
        model, optimizer, learner_lib.HParams(), donate=False
    ).lower(params, optimizer.init(params), batch, state).compile()
    return re.findall(r'op_name="([^"]+)"', compiled.as_text())


@pytest.mark.parametrize("scope", DEEP_SCOPES)
def test_conv_scope_reaches_the_compiled_hlo(deep_op_names, scope):
    inside = [n for n in deep_op_names if _in_scope(n, scope)]
    assert inside, f"no compiled op carries the scope {scope!r}"
    if scope == "lstm_core":
        assert any("/while/body/" in n for n in inside)


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


# --- a family's own scopes (PR 53: `--model lfm2`) ---------------------------

LFM2_SCOPES = (
    "conv_operator", "conv_in_proj", "conv_gate_taps", "conv_out_proj",
    "attention", "dense_mlp", "moe_route", "moe_dispatch", "moe_experts",
    "moe_combine",
)


@pytest.fixture(scope="module")
def lfm2_op_names():
    """The toy family's whole update, compiled (tests/family_scaffold.
    py): what a device trace of the cell is split by."""
    model, params = scaffold.build("lfm2")
    t = scaffold.FAMILIES["lfm2"].t
    batch = scaffold.learner_batch(1, [(2, 0), (4, 1)], t=t)
    hp = learner_lib.HParams(batch_size=scaffold.B, unroll_length=t - 1)
    optimizer = optax.sgd(0.1)
    compiled = learner_lib.make_update_step(
        model, optimizer, hp, donate=False
    ).lower(
        params, optimizer.init(params), batch,
        model.initial_state(scaffold.B),
    ).compile()
    return re.findall(r'op_name="([^"]+)"', compiled.as_text())


@pytest.mark.parametrize("scope", LFM2_SCOPES)
def test_lfm2_scope_reaches_the_compiled_hlo(lfm2_op_names, scope):
    inside = [n for n in lfm2_op_names if _in_scope(n, scope)]
    assert inside, f"no compiled op carries the scope {scope!r}"
    if scope in ("conv_in_proj", "conv_gate_taps", "conv_out_proj"):
        assert all(_in_scope(n, "conv_operator") for n in inside)
    # The account knows the names from the lines that enter them.
    assert scope in device_scopes.known_device_scopes()


# --- a family's own scopes (PR 55: `--model phi4flash`) ----------------------

PHI4FLASH_SCOPES = (
    "mamba1_in_proj", "mamba1_conv", "mamba1_x_proj", "selective_scan",
    "mamba1_out_proj", "attention_sliding", "attention_full",
    "attention_cross", "attention_difference", "memory_unit", "mlp",
)


@pytest.fixture(scope="module")
def phi4flash_compiled():
    """The toy family's whole update, compiled (tests/family_scaffold.
    py): (the `op_name`s a device trace of the cell is split by, the
    update's stats from the same program's shapes)."""
    model, params = scaffold.build("phi4flash")
    t = scaffold.FAMILIES["phi4flash"].t
    batch = scaffold.learner_batch(1, [(2, 0), (4, 1)], t=t)
    hp = learner_lib.HParams(batch_size=scaffold.B, unroll_length=t - 1)
    optimizer = optax.sgd(0.1)
    update_step = learner_lib.make_update_step(
        model, optimizer, hp, donate=False
    )
    operands = (
        params, optimizer.init(params), batch,
        model.initial_state(scaffold.B),
    )
    compiled = update_step.lower(*operands).compile()
    stats = jax.eval_shape(update_step, *operands)[2]
    return re.findall(r'op_name="([^"]+)"', compiled.as_text()), stats


@pytest.mark.parametrize("scope", PHI4FLASH_SCOPES)
def test_phi4flash_scope_reaches_the_compiled_hlo(phi4flash_compiled, scope):
    op_names, _ = phi4flash_compiled
    inside = [n for n in op_names if _in_scope(n, scope)]
    assert inside, f"no compiled op carries the scope {scope!r}"
    if scope == "attention_difference":
        # Inside each of the three layers that attend, and nowhere else.
        for outer in ("attention_sliding", "attention_full",
                      "attention_cross"):
            assert any(_in_scope(n, outer) for n in inside), outer
        assert all(
            any(_in_scope(n, outer) for outer in PHI4FLASH_SCOPES[5:8])
            for n in inside
        )
    # The account knows the names from the lines that enter them.
    assert scope in device_scopes.known_device_scopes()


def test_phi4flash_counters_are_the_updates_stats(phi4flash_compiled):
    """What the layers sow reaches the update's stats, a gauge each:
    `ssm_*` as Nemotron-3's Mamba-2 layers sow them, the readers of the
    two handed-on values, the bytes a row hands on, the differential
    attentions."""
    from torchbeast_tpu.models import stats as model_stats

    _, stats = phi4flash_compiled
    for name in (
        "ssm_applications", "ssm_chunks", "ssm_resets_per_row",
        "ssm_state_bytes_per_row", "conv_kernel_applications",
        "shared_memory_readers",
        "shared_kv_readers", "shared_bytes_per_row",
        "attention_differential_applications",
    ):
        assert name in stats, name
        assert model_stats.gauge_name(name) == name.replace("_", ".", 1)


# --- a family's own scopes (PR 59: `--model xing4`) --------------------------

XING4_SCOPES = (
    "hc_maps", "hc_sinkhorn", "hc_pre", "hc_post", "mla_q_compress",
    "attention_latent", "mlp",
)


@pytest.fixture(scope="module")
def xing4_compiled():
    """As `phi4flash_compiled`, of the toy xing4."""
    model, params = scaffold.build("xing4")
    t = scaffold.FAMILIES["xing4"].t
    batch = scaffold.learner_batch(1, [(2, 0), (4, 1)], t=t)
    hp = learner_lib.HParams(batch_size=scaffold.B, unroll_length=t - 1)
    optimizer = optax.sgd(0.1)
    update_step = learner_lib.make_update_step(
        model, optimizer, hp, donate=False
    )
    operands = (
        params, optimizer.init(params), batch,
        model.initial_state(scaffold.B),
    )
    compiled = update_step.lower(*operands).compile()
    stats = jax.eval_shape(update_step, *operands)[2]
    return re.findall(r'op_name="([^"]+)"', compiled.as_text()), stats


@pytest.mark.parametrize("scope", XING4_SCOPES)
def test_xing4_scope_reaches_the_compiled_hlo(xing4_compiled, scope):
    op_names, _ = xing4_compiled
    inside = [n for n in op_names if _in_scope(n, scope)]
    assert inside, f"no compiled op carries the scope {scope!r}"
    if scope == "mla_q_compress":
        # The bottleneck is a part of the attention, and of nothing else.
        assert all("attention_latent" in n for n in inside)
    if scope.startswith("hc_"):
        # The residual path is no part of what it wraps.
        assert not any(
            "attention_latent" in n or "moe_" in n for n in inside
        )
    assert scope in device_scopes.known_device_scopes()


def test_xing4_counters_are_the_updates_stats(xing4_compiled):
    """What the residual path sows reaches the update's stats, a gauge
    each (`hc.res_row_error_max`, `hc.post_mean`, `hc.bytes_per_row`),
    beside the latent block's."""
    from torchbeast_tpu.models import stats as model_stats

    _, stats = xing4_compiled
    for name in (
        "hc_res_row_error_max", "hc_post_mean", "hc_bytes_per_row",
        "attention_latent_applications", "moe_shared_applications",
    ):
        assert name in stats, name
        assert model_stats.gauge_name(name) == name.replace("_", ".", 1)


# --- a family's own scopes (PR 62: `--model trinity`) ------------------------

TRINITY_SCOPES = (
    "attention_sliding", "attention_full", "attention_gate",
    "sublayer_post_norm", "dense_mlp", "moe_route", "moe_shared",
)


@pytest.fixture(scope="module")
def trinity_compiled():
    """As `phi4flash_compiled`, of the toy trinity."""
    model, params = scaffold.build("trinity")
    t = scaffold.FAMILIES["trinity"].t
    batch = scaffold.learner_batch(1, [(2, 0), (4, 1)], t=t)
    hp = learner_lib.HParams(batch_size=scaffold.B, unroll_length=t - 1)
    optimizer = optax.sgd(0.1)
    update_step = learner_lib.make_update_step(
        model, optimizer, hp, donate=False
    )
    operands = (
        params, optimizer.init(params), batch,
        model.initial_state(scaffold.B),
    )
    compiled = update_step.lower(*operands).compile()
    stats = jax.eval_shape(update_step, *operands)[2]
    return re.findall(r'op_name="([^"]+)"', compiled.as_text()), stats


@pytest.mark.parametrize("scope", TRINITY_SCOPES)
def test_trinity_scope_reaches_the_compiled_hlo(trinity_compiled, scope):
    op_names, _ = trinity_compiled
    inside = [n for n in op_names if _in_scope(n, scope)]
    assert inside, f"no compiled op carries the scope {scope!r}"
    if scope == "attention_gate":
        # The gate is a part of both kinds of attention, and of nothing
        # else.
        for outer in ("attention_sliding", "attention_full"):
            assert any(_in_scope(n, outer) for n in inside), outer
        assert all(
            _in_scope(n, "attention_sliding")
            or _in_scope(n, "attention_full")
            for n in inside
        )
    if scope == "sublayer_post_norm":
        # After attention inside its scope, and after the feed-forward
        # part outside every other.
        assert any(_in_scope(n, "attention_full") for n in inside)
        assert any(
            not _in_scope(n, "attention_sliding")
            and not _in_scope(n, "attention_full")
            for n in inside
        )
        assert not any(
            _in_scope(n, "dense_mlp") or _in_scope(n, "moe_shared")
            for n in inside
        )
    assert scope in device_scopes.known_device_scopes()


def test_trinity_counters_are_the_updates_stats(trinity_compiled):
    """What the block sows reaches the update's stats, a gauge each
    (`attention.gated_applications`, `attention.unrotated_
    applications`), beside the expert layer's."""
    from torchbeast_tpu.models import stats as model_stats

    _, stats = trinity_compiled
    for name in (
        "attention_gated_applications", "attention_unrotated_applications",
        "moe_bias_steps", "moe_bias_abs_max", "moe_shared_applications",
    ):
        assert name in stats, name
        assert model_stats.gauge_name(name) == name.replace("_", ".", 1)


# --- a family's own scopes (PR 64: `--model granite4`) -----------------------

GRANITE4_SCOPES = (
    "mamba_in_proj", "mamba_conv", "ssd_scan", "ssd_intra", "ssd_states",
    "ssd_inter", "mamba_gate_norm", "mamba_out_proj", "attention_full",
    "dense_mlp",
)


@pytest.fixture(scope="module")
def granite4_compiled():
    """As `trinity_compiled`, of the toy granite4."""
    model, params = scaffold.build("granite4")
    t = scaffold.FAMILIES["granite4"].t
    batch = scaffold.learner_batch(1, [(2, 0), (4, 1)], t=t)
    hp = learner_lib.HParams(batch_size=scaffold.B, unroll_length=t - 1)
    optimizer = optax.sgd(0.1)
    update_step = learner_lib.make_update_step(
        model, optimizer, hp, donate=False
    )
    operands = (
        params, optimizer.init(params), batch,
        model.initial_state(scaffold.B),
    )
    compiled = update_step.lower(*operands).compile()
    stats = jax.eval_shape(update_step, *operands)[2]
    return re.findall(r'op_name="([^"]+)"', compiled.as_text()), stats


@pytest.mark.parametrize("scope", GRANITE4_SCOPES)
def test_granite4_scope_reaches_the_compiled_hlo(granite4_compiled, scope):
    """Nemotron-3's names for the mixer's parts (one account compares
    the two cells), with the layer's SwiGLU outside every one of them."""
    op_names, _ = granite4_compiled
    inside = [n for n in op_names if _in_scope(n, scope)]
    assert inside, f"no compiled op carries the scope {scope!r}"
    if scope.startswith("ssd_") and scope != "ssd_scan":
        assert all(_in_scope(n, "ssd_scan") for n in inside)
    if scope == "dense_mlp":
        assert not any(
            _in_scope(n, other) for n in inside
            for other in GRANITE4_SCOPES if other != "dense_mlp"
        )
    assert scope in device_scopes.known_device_scopes()


def test_granite4_counters_are_the_updates_stats(granite4_compiled):
    """What the layers sow reaches the update's stats, a gauge each
    (`ssm.applications`, `mlp.applications`, ...)."""
    from torchbeast_tpu.models import stats as model_stats

    _, stats = granite4_compiled
    for name in (
        "ssm_applications", "ssm_chunks", "ssm_resets_per_row",
        "ssm_state_bytes_per_row", "conv_kernel_applications",
        "mlp_applications",
        "attention_unrotated_applications",
    ):
        assert name in stats, name
        assert model_stats.gauge_name(name) == name.replace("_", ".", 1)


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


def _loops(jaxpr, under=""):
    """(name stack, body) of every `while` in a jaxpr, calls inside
    calls too."""
    for eqn in jaxpr.eqns:
        stack = f"{under}/{eqn.source_info.name_stack}"
        if eqn.primitive.name == "while":
            yield stack, eqn.params["body_jaxpr"].jaxpr
        for inner in jax.core.jaxprs_in_params(eqn.params):
            yield from _loops(inner, stack)


def test_the_sweeps_loop_has_a_name_of_its_own():
    """The swept experts' loop holds a rung's dispatch, experts and
    combine, each under its scope; the loop's own work (a second
    rung's sums into the weights' gradients; until PR 58 every step's,
    from zeros: 7.3 ms a step in the Qwen3-Next cell) is `moe_sweep`'s,
    forward and backward. The first rung stands before the loop under
    the same name (PR 58): its kernels are `moe_sweep` >
    `moe_experts`, the further rungs' `moe_sweep` > `while` >
    `moe_experts`."""
    import jax.numpy as jnp

    from torchbeast_tpu.models import moe

    tokens, top_k, held, experts, d, width = 512, 2, 2, 16, 8, 16
    assert moe.window_rungs(tokens, top_k, held, experts) == (256, 1024)
    idx = jnp.stack(
        [jnp.arange(tokens) % experts, (jnp.arange(tokens) + 1) % experts],
        axis=1,
    )

    def loss(x, w_gate, w_up, w_down):
        y, _ = moe.dropless_experts(
            x, idx, jnp.ones((tokens, top_k)), w_gate, w_up, w_down,
            first_of=(0, experts),
        )
        return jnp.sum(y)

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2, 3)))(
        jnp.ones((tokens, d)), jnp.ones((held, d, width)),
        jnp.ones((held, d, width)), jnp.ones((held, width, d)),
    )
    sweeps = [
        (stack, body) for stack, body in _loops(jaxpr.jaxpr)
        if "searchsorted" not in stack
    ]
    assert len(sweeps) == 2  # the forward's and the backward's
    assert all(_in_scope(stack, "moe_sweep") for stack, _ in sweeps)
    # A rung's kernels (3 forward; those and 6 backward) stand twice,
    # before each loop and as its body, all under both names.
    kernels = [stack for stack, _ in _kernel_calls(jaxpr.jaxpr)]
    assert len(kernels) == 2 * (3 + 9)
    assert all(
        _in_scope(stack, "moe_sweep") and _in_scope(stack, "moe_experts")
        for stack in kernels
    )
    assert [len(list(_kernel_calls(body))) for _, body in sweeps] == [3, 9]


# --- a family's own scopes (PR 68: `--model ling3`) --------------------------

LING3_SCOPES = (
    "kda_in_proj", "kda_conv", "kda_gate", "kda_scan", "kda_intra",
    "kda_solve", "kda_states", "kda_inter", "kda_out", "attention_latent",
    "latent_head_gate", "router_groups", "moe_route", "mlp",
)


@pytest.fixture(scope="module")
def ling3_compiled():
    """As `granite4_compiled`, of the toy ling3."""
    model, params = scaffold.build("ling3")
    t = scaffold.FAMILIES["ling3"].t
    batch = scaffold.learner_batch(1, [(2, 0), (4, 1)], t=t)
    hp = learner_lib.HParams(batch_size=scaffold.B, unroll_length=t - 1)
    optimizer = optax.sgd(0.1)
    update_step = learner_lib.make_update_step(
        model, optimizer, hp, donate=False
    )
    operands = (
        params, optimizer.init(params), batch,
        model.initial_state(scaffold.B),
    )
    compiled = update_step.lower(*operands).compile()
    stats = jax.eval_shape(update_step, *operands)[2]
    return re.findall(r'op_name="([^"]+)"', compiled.as_text()), stats


@pytest.mark.parametrize("scope", LING3_SCOPES)
def test_ling3_scope_reaches_the_compiled_hlo(ling3_compiled, scope):
    """The family's own names (a decay a channel is no `delta_*` scope:
    one account tells Qwen3-Next's scan from this one), the chunk's
    parts inside `kda_scan`, the solve inside `kda_intra`, the head gate
    inside the latent block's scope and the groups inside the router's."""
    op_names, _ = ling3_compiled
    inside = [n for n in op_names if _in_scope(n, scope)]
    assert inside, f"no compiled op carries the scope {scope!r}"
    for inner, outer in (
        ("kda_intra", "kda_scan"), ("kda_states", "kda_scan"),
        ("kda_inter", "kda_scan"), ("kda_solve", "kda_intra"),
        ("latent_head_gate", "attention_latent"),
        ("router_groups", "moe_route"),
    ):
        if scope == inner:
            assert all(_in_scope(n, outer) for n in inside)
    assert not any(_in_scope(n, "delta_scan") for n in inside)
    assert scope in device_scopes.known_device_scopes()


def test_ling3_counters_are_the_updates_stats(ling3_compiled):
    """What the layers sow reaches the update's stats, a gauge each
    (`kda.log_decay_min`, `router.group_load_max_share`, ...)."""
    from torchbeast_tpu.models import stats as model_stats

    _, stats = ling3_compiled
    for name in (
        "kda_applications", "kda_chunks", "kda_sub_blocks",
        "kda_resets_per_row", "kda_state_bytes_per_row",
        "kda_log_decay_min", "kda_log_decay_mean",
        "kda_gate_at_floor_share", "conv_kernel_applications",
        "attention_latent_applications", "router_group_load_max_share",
        "experts_held_rows_mean", "moe_bias_steps",
    ):
        assert name in stats, name
        assert model_stats.gauge_name(name) == name.replace("_", ".", 1)
