"""The learner: loss, optimizer, and the single jitted update step.

This is where the TPU-native design departs hardest from the reference. The
reference splits the learner across Python threads sharing one model under a
lock (monobeast.py:226-296, polybeast_learner.py:295-389) with explicit
.to(device) transfers. Here the entire learner step — model forward over the
[T+1, B] batch, V-trace targets, three losses, gradient, RMSProp update, LR
schedule — is ONE XLA program produced by `make_update_step`, with donated
params/opt_state so updates happen in-place in HBM.

Algorithmic parity (reference learn(), monobeast.py:226-296):
bootstrap from the last baseline; time-shift batch[1:] vs outputs[:-1];
reward clipping to [-1, 1]; discounts = ~done * gamma; V-trace from logits;
pg + 0.5*baseline + entropy_cost*entropy losses (sum-reduced); grad-clip 40;
torch-style RMSProp (eps outside the sqrt); LR decayed linearly to zero over
total_steps environment frames.
"""

from typing import Any, Dict, NamedTuple, Tuple

import flax
import jax
import jax.numpy as jnp
import numpy as np
import optax

from torchbeast_tpu import precision as precision_lib
from torchbeast_tpu import telemetry
from torchbeast_tpu.models import stats as model_stats
from torchbeast_tpu.ops import (
    compute_entropy_loss,
    impact_policy_losses,
    vtrace_policy_losses,
)


class HParams(NamedTuple):
    """Learner hyperparameters (reference defaults, monobeast.py:57-94)."""

    discounting: float = 0.99
    baseline_cost: float = 0.5
    entropy_cost: float = 0.0006
    # Optional linear anneal: entropy cost moves from entropy_cost to
    # entropy_cost_final over total_steps env frames (None = constant,
    # the reference behavior). High-early/low-late escapes the Memory
    # probe's query-compliance collapse (lstm_learning.md §4/4b)
    # without paying a permanent entropy tax at convergence.
    entropy_cost_final: float = None
    reward_clipping: str = "abs_one"  # or "none"
    learning_rate: float = 4.8e-4
    rmsprop_alpha: float = 0.99
    rmsprop_eps: float = 0.01
    rmsprop_momentum: float = 0.0
    grad_norm_clipping: float = 40.0
    total_steps: int = 100_000_000
    unroll_length: int = 80
    batch_size: int = 8
    # RMSprop second-moment STORAGE dtype: "f32" or "bf16". The EMA is
    # always accumulated in f32 (the precision module's f32-accumulate
    # contract); bf16 halves the optimizer-state bytes each update
    # reads and writes. Set by --precision bf16_train.
    opt_state_dtype: str = "f32"
    # Resident param dtype: "f32", or "bf16" (--precision bf16_train) —
    # the params the forward/backward and the acting path read are
    # bfloat16 (halving every weight read AND the gradient arrays the
    # backward writes), while the optimizer state carries the float32
    # MASTER copy that every update reads-modifies-writes in f32
    # (learner._bf16_resident_params). Resident params are re-derived
    # from the master each update: bf16 rounding never compounds.
    param_dtype: str = "f32"
    # Opt-in factored second moment (row/col EMAs for matrices — an
    # Adafactor-style O(n+m) approximation of the O(nm) accumulator,
    # with the torch denominator form): the aggressive optimizer-state
    # compression lever beyond bf16 storage.
    opt_factored: bool = False
    # Objective family (--loss): "vtrace" (IMPALA, the default) or
    # "impact" — the clipped target-network surrogate (ops/impact.py)
    # that tolerates 10x the policy lag and unlocks K'-fold sample
    # reuse. Under "impact" the batch must carry the target network's
    # forward outputs (make_target_forward merges them in).
    loss: str = "vtrace"
    # The IMPACT surrogate's PPO-style clip epsilon (--impact_clip).
    impact_clip: float = 0.2
    # K'-fold sample reuse (--replay_reuse): each collected batch is
    # consumed this many times (BatchArena replay slots / repeated
    # dispatch in the sync driver). 1 = the on-policy default.
    replay_reuse: int = 1


def updates_horizon(hp: HParams) -> int:
    """Optimizer updates in a run: total_steps env frames at T*B frames
    per update, times the replay reuse factor (each collected batch is
    consumed replay_reuse times, so the run performs reuse-many more
    optimizer updates than env frames alone imply). The ONE schedule
    clock — the LR decay and the entropy anneal both divide by this, so
    they cannot drift apart."""
    return max(
        1, hp.total_steps // (hp.unroll_length * hp.batch_size)
    ) * max(1, hp.replay_reuse)


def _scale_by_rms_torch(
    decay: float, eps: float, state_dtype=None
) -> optax.GradientTransformation:
    """optax.scale_by_rms with TORCH denominator semantics:
    g / (sqrt(v) + eps), not g / sqrt(v + eps) (the two differ
    materially at this model's eps=0.01; see google-deepmind/optax#532).
    Pinned against torch.optim.RMSprop by
    test_rmsprop_matches_torch_semantics.

    `state_dtype` (e.g. jnp.bfloat16) compacts the STORED second moment;
    the EMA itself is accumulated in the gradient dtype (f32) every
    update — decay*nu + (1-decay)*g^2 runs full-width, only the write
    back to HBM narrows (the precision module's f32-accumulate
    contract; parity-to-tolerance pinned by test)."""

    def init_fn(params):
        return optax.ScaleByRmsState(
            nu=jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, state_dtype or p.dtype),
                params,
            )
        )

    def update_fn(updates, state, params=None):
        del params
        nu_f = jax.tree_util.tree_map(
            lambda g, n: decay * n.astype(jnp.float32)
            + (1.0 - decay) * jnp.square(g.astype(jnp.float32)),
            updates,
            state.nu,
        )
        updates = jax.tree_util.tree_map(
            lambda g, n: g.astype(jnp.float32) / (jnp.sqrt(n) + eps),
            updates, nu_f,
        )
        nu = (
            jax.tree_util.tree_map(
                lambda n: n.astype(state_dtype), nu_f
            )
            if state_dtype is not None
            else nu_f
        )
        return updates, optax.ScaleByRmsState(nu=nu)

    return optax.GradientTransformation(init_fn, update_fn)


class _FactoredLeaf(NamedTuple):
    """Per-leaf factored second moment: row/col EMAs for ndim>=2 leaves
    (O(n+m) state), the full accumulator for vectors/scalars (tiny
    anyway). Exactly one of (row, col) / nu is populated; the other side
    carries zero-size placeholders so the pytree structure is uniform."""

    row: jnp.ndarray
    col: jnp.ndarray
    nu: jnp.ndarray


class FactoredRmsState(NamedTuple):
    leaves: Tuple[_FactoredLeaf, ...]


def _scale_by_factored_rms_torch(
    decay: float, eps: float
) -> optax.GradientTransformation:
    """Factored torch-denominator RMS scaling (opt-in via
    HParams.opt_factored): matrices keep row- and column-mean EMAs of
    g^2 instead of the full elementwise accumulator — state shrinks
    from O(n*m) to O(n+m) — and the denominator uses the rank-1
    reconstruction v_hat = (r x c) / mean(r) (Adafactor's estimator,
    arXiv:1804.04235) inside the same g / (sqrt(v) + eps) form. NOT
    torch-parity (it is an approximation by construction); vectors and
    scalars keep the exact accumulator."""

    def _factored(shape) -> bool:
        return len(shape) >= 2

    def init_fn(params):
        leaves = []
        for p in jax.tree_util.tree_leaves(params):
            if _factored(p.shape):
                leaves.append(_FactoredLeaf(
                    row=jnp.zeros(p.shape[:-1], jnp.float32),
                    col=jnp.zeros(p.shape[:-2] + p.shape[-1:],
                                  jnp.float32),
                    nu=jnp.zeros((0,), jnp.float32),
                ))
            else:
                leaves.append(_FactoredLeaf(
                    row=jnp.zeros((0,), jnp.float32),
                    col=jnp.zeros((0,), jnp.float32),
                    nu=jnp.zeros(p.shape, jnp.float32),
                ))
        return FactoredRmsState(leaves=tuple(leaves))

    def update_fn(updates, state, params=None):
        del params
        flat, treedef = jax.tree_util.tree_flatten(updates)
        new_leaves = []
        new_flat = []
        for g, s in zip(flat, state.leaves):
            g2 = jnp.square(g.astype(jnp.float32))
            if _factored(g.shape):
                row = decay * s.row + (1.0 - decay) * g2.mean(axis=-1)
                col = decay * s.col + (1.0 - decay) * g2.mean(axis=-2)
                # Rank-1 reconstruction; mean(row) == mean(col) == the
                # EMA of mean(g^2), so the estimator is exact for
                # rank-1 g^2 and an upper-biased smooth estimate
                # otherwise.
                scale = jnp.maximum(
                    row.mean(axis=-1, keepdims=True), 1e-30
                )
                v_hat = (
                    (row / scale)[..., None] * col[..., None, :]
                )
                new_flat.append(
                    (g / (jnp.sqrt(v_hat) + eps)).astype(g.dtype)
                )
                new_leaves.append(_FactoredLeaf(row=row, col=col,
                                                nu=s.nu))
            else:
                nu = decay * s.nu + (1.0 - decay) * g2
                new_flat.append(
                    (g / (jnp.sqrt(nu) + eps)).astype(g.dtype)
                )
                new_leaves.append(_FactoredLeaf(row=s.row, col=s.col,
                                                nu=nu))
        return (
            jax.tree_util.tree_unflatten(treedef, new_flat),
            FactoredRmsState(leaves=tuple(new_leaves)),
        )

    return optax.GradientTransformation(init_fn, update_fn)


def _clip_by_global_norm_f32(
    max_norm: float,
) -> optax.GradientTransformation:
    """optax.clip_by_global_norm with the norm ACCUMULATED in float32
    and float32 outputs — the bf16-resident-grads path. The stock
    transform would sum squared bf16 values in bf16 (an f32-accumulate
    violation); here each grad leaf is read half-width and widened in
    registers before the reduction. The f32 policy keeps the stock
    transform (identical-by-construction there, so the torch-parity
    pins never depend on this code)."""

    def init_fn(params):
        del params
        return optax.EmptyState()

    def update_fn(updates, state, params=None):
        del params
        updates = jax.tree_util.tree_map(
            lambda t: t.astype(jnp.float32), updates
        )
        g_norm = optax.global_norm(updates)
        trigger = jnp.squeeze(g_norm < max_norm)

        def clip_fn(t):
            return jax.lax.select(trigger, t, (t / g_norm) * max_norm)

        return jax.tree_util.tree_map(clip_fn, updates), state

    return optax.GradientTransformation(init_fn, update_fn)


class MasterParamsState(NamedTuple):
    """Optimizer state for bf16-resident training: the float32 MASTER
    copy of the params plus the wrapped transform's own state."""

    master: Any
    inner: Any


def _bf16_resident_params(
    inner: optax.GradientTransformation,
) -> optax.GradientTransformation:
    """bf16-resident params with an f32 master (--precision bf16_train).

    The params the update step (and the acting path) carries are
    bfloat16 — every forward/backward weight read is half-width and the
    backward emits bf16 gradient arrays. The float32 master lives in
    the optimizer state: each update upcasts nothing wholesale (the
    inner transform reads the bf16 grads and accumulates in f32 — see
    _scale_by_rms_torch), applies the f32 update to the MASTER, and
    emits the delta that rebases the resident bf16 params onto the new
    master. Because the master never sees bf16 rounding, the resident
    params are always bf16(master) to f32-addition precision — rounding
    cannot compound across updates.

    NOT a drop-in optax transform: its `update` returns the NEW MASTER
    as the updates value (computing a params-dtype delta for the stock
    optax.apply_updates would round-trip every leaf through two extra
    converts and a subtract for nothing). Apply with
    learner.apply_updates — the dispatch helper update_body uses —
    which turns the master into resident params with ONE narrowing cast
    per leaf. The inner transform conditions on the MASTER (torch-
    RMSprop only reads params for structure, but momentum/weight-decay
    style transforms need the f32 view).
    """

    def init_fn(params):
        master = jax.tree_util.tree_map(
            lambda p: p.astype(jnp.float32), params
        )
        return MasterParamsState(master=master, inner=inner.init(master))

    def update_fn(updates, state, params=None):
        del params
        inner_updates, inner_state = inner.update(
            updates, state.inner, state.master
        )
        new_master = optax.apply_updates(state.master, inner_updates)
        return new_master, MasterParamsState(master=new_master,
                                             inner=inner_state)

    return optax.GradientTransformation(init_fn, update_fn)


def apply_updates(params, updates, opt_state):
    """optax.apply_updates, resident-aware: when the optimizer is the
    bf16-resident wrapper (its state is a MasterParamsState), `updates`
    IS the new f32 master and the resident params are one narrowing
    cast per leaf; otherwise the stock optax apply."""
    if isinstance(opt_state, MasterParamsState):
        return jax.tree_util.tree_map(
            lambda nm, p: nm.astype(p.dtype), updates, params
        )
    return optax.apply_updates(params, updates)


def _rmsprop_torch(
    learning_rate, decay: float, eps: float, momentum,
    state_dtype=None, factored: bool = False,
) -> optax.GradientTransformation:
    """torch.optim.RMSprop as an optax chain: torch-denominator RMS
    scaling (g / (sqrt(nu) + eps)), then momentum as a plain accumulator
    trace, then the LR (torch: buf = m*buf + update; param -= lr*buf).
    The installed optax's own rmsprop(eps_in_sqrt=False, momentum=m)
    applies the LR *before* the trace, which is a different optimizer
    once the LR is scheduled, so the chain is composed here for every
    configuration, compact state (`state_dtype`/`factored`) included."""
    if factored:
        parts = [_scale_by_factored_rms_torch(decay, eps)]
    else:
        parts = [_scale_by_rms_torch(decay, eps, state_dtype)]
    if momentum:
        parts.append(optax.trace(decay=momentum, nesterov=False))
    parts.append(optax.scale_by_learning_rate(learning_rate))
    return optax.chain(*parts)


def make_optimizer(hp: HParams) -> optax.GradientTransformation:
    """torch.optim.RMSprop semantics + grad clip + linear LR decay.

    torch RMSProp divides by (sqrt(v) + eps) and applies the LR after
    the momentum trace — _rmsprop_torch composes exactly that. The LR decays linearly to 0 over
    total_steps env frames; each optimizer step consumes T*B frames (the
    reference's LambdaLR closure, monobeast.py:395-398).

    Optimizer-state compaction (the HBM-roofline levers): hp.
    opt_state_dtype="bf16" stores the second moment half-width (f32
    accumulate, torch-parity to bf16 rounding), hp.opt_factored swaps in
    row/col factored EMAs (an approximation — opt-in).
    """
    if hp.opt_state_dtype not in ("f32", "bf16"):
        raise ValueError(
            f"opt_state_dtype must be 'f32' or 'bf16', got "
            f"{hp.opt_state_dtype!r}"
        )
    if hp.param_dtype not in ("f32", "bf16"):
        raise ValueError(
            f"param_dtype must be 'f32' or 'bf16', got "
            f"{hp.param_dtype!r}"
        )
    schedule = optax.linear_schedule(
        init_value=hp.learning_rate,
        end_value=0.0,
        transition_steps=updates_horizon(hp),
    )
    clip = (
        _clip_by_global_norm_f32(hp.grad_norm_clipping)
        if hp.param_dtype == "bf16"
        else optax.clip_by_global_norm(hp.grad_norm_clipping)
    )
    chain = optax.chain(
        clip,
        _rmsprop_torch(
            learning_rate=schedule,
            decay=hp.rmsprop_alpha,
            eps=hp.rmsprop_eps,
            momentum=hp.rmsprop_momentum,
            state_dtype=(
                jnp.bfloat16 if hp.opt_state_dtype == "bf16" else None
            ),
            factored=hp.opt_factored,
        ),
    )
    if hp.param_dtype == "bf16":
        chain = _bf16_resident_params(chain)
    return chain


# Batch keys the IMPACT loss consumes (merged in by make_target_forward,
# popped back out by compute_loss before the model forward). Full
# [T+1, B, ...] shapes mirroring the learner outputs: slot T supplies
# the target network's bootstrap value.
TARGET_LOGITS_KEY = "impact_target_logits"
TARGET_BASELINE_KEY = "impact_target_baseline"
# The collection a module sows into to move a parameter of its own by
# something other than a gradient, under the parameter's name; the key
# of `compute_loss`'s stats that carries the tree to `update_body`.
PARAM_STEPS_KEY = "param_steps"


def make_target_forward(model, superstep_k: int = 1):
    """Build the jitted target-network forward for --loss impact.

    (target_params, batch, initial_agent_state) ->
        (target_policy_logits, target_baseline)   # [T+1, B, ...]

    The driver merges the outputs into the batch dict under
    TARGET_LOGITS_KEY / TARGET_BASELINE_KEY before dispatching the
    update step, so the 4-arg (params, opt_state, batch, state) update
    signature — and everything built on it: supersteps, donation,
    consume_staged_inputs, the DP mesh — is untouched. Mathematically
    this equals threading target params into the loss (every target
    output is a constant w.r.t. theta).

    superstep_k > 1 vmaps over the leading [K] axis of a stacked
    superstep batch. The outputs are returned separately (not as an
    augmented batch) so jit never aliases the staged batch leaves into
    its outputs — the update step is free to donate them.
    """

    def forward(target_params, batch, initial_agent_state):
        (outs, _), _ = model.apply(
            target_params,
            batch,
            initial_agent_state,
            sample_action=False,
            mutable=["losses"],
        )
        return outs.policy_logits, outs.baseline

    if superstep_k > 1:
        forward = jax.vmap(forward, in_axes=(None, 0, 0))
    return jax.jit(forward)


def compute_loss(
    model, params, batch: Dict[str, jnp.ndarray], initial_agent_state,
    hp: HParams, entropy_cost=None,
):
    """Forward the full [T+1, B] batch and build the IMPALA loss.

    Models may `sow` regularization terms into the `losses` collection
    (e.g. the MoE load-balance loss, models/moe.py); every sown value is
    added to the objective. Models that sow nothing pay nothing.

    Precision contract (torchbeast_tpu/precision.py): the staged batch's
    float leaves may arrive bfloat16 (--precision bf16_train); every
    loss-side use upcasts to f32 at point of use — XLA reads the
    half-width array from HBM and widens in registers — and V-trace +
    the three losses accumulate in f32. Model outputs (logits/baseline)
    are f32 by the model head's own boundary contract.

    The V-trace targets and pg/baseline losses run FUSED
    (ops.vtrace_policy_losses, identical math to the composed
    from_logits + loss calls, pinned by test): one action_log_probs
    evaluation serves the importance weights and the pg cross-entropy,
    and the advantages are consumed by their reductions in place.
    """
    # --loss impact: the target network's forward outputs ride the
    # batch (TARGET_LOGITS_KEY / TARGET_BASELINE_KEY, merged in by
    # make_target_forward in the driver) — popped here so the model
    # forward and the episode bookkeeping below see the stock batch.
    batch = dict(batch)
    target_net_logits_full = batch.pop(TARGET_LOGITS_KEY, None)
    target_net_baseline_full = batch.pop(TARGET_BASELINE_KEY, None)
    (learner_outputs, _), variables = model.apply(
        params,
        batch,
        initial_agent_state,
        sample_action=False,
        mutable=("losses", PARAM_STEPS_KEY) + model_stats.COLLECTIONS,
    )
    aux_loss = sum(
        jnp.sum(leaf)
        for leaf in jax.tree_util.tree_leaves(variables.get("losses", {}))
    )
    bootstrap_value = learner_outputs.baseline[-1]

    # Shift: env/behavior fields drop slot 0, learner outputs drop slot T
    # (reference monobeast.py:244-245). f32 upcasts at point of use (see
    # docstring); int/bool leaves have no storage-dtype policy.
    target_logits = learner_outputs.policy_logits[:-1]
    values = learner_outputs.baseline[:-1]
    behavior_logits = batch["policy_logits"][1:].astype(jnp.float32)
    actions = batch["action"][1:]
    rewards = batch["reward"][1:].astype(jnp.float32)
    done = batch["done"][1:]

    if hp.reward_clipping == "abs_one":
        rewards = jnp.clip(rewards, -1.0, 1.0)
    discounts = (~done).astype(jnp.float32) * hp.discounting

    # Named scopes for the parts no Flax module names: they reach the
    # compiled HLO's op_name metadata, so a device trace can be split
    # by them (vtrace, loss_terms; optimizer and grad_norm in
    # update_body).
    with telemetry.device_scope("vtrace"):
        if hp.loss == "impact":
            if target_net_logits_full is None:
                raise ValueError(
                    "--loss impact requires the target network's outputs "
                    "on the batch (make_target_forward merges them in)"
                )
            pg_loss, baseline_loss = impact_policy_losses(
                behavior_policy_logits=behavior_logits,
                target_net_policy_logits=target_net_logits_full[:-1],
                learner_policy_logits=target_logits,
                actions=actions,
                discounts=discounts,
                rewards=rewards,
                target_net_values=target_net_baseline_full[:-1],
                values=values,
                target_net_bootstrap_value=target_net_baseline_full[-1],
                clip_epsilon=hp.impact_clip,
            )
        else:
            pg_loss, baseline_loss = vtrace_policy_losses(
                behavior_policy_logits=behavior_logits,
                target_policy_logits=target_logits,
                actions=actions,
                discounts=discounts,
                rewards=rewards,
                values=values,
                bootstrap_value=bootstrap_value,
            )
    with telemetry.device_scope("loss_terms"):
        baseline_loss = hp.baseline_cost * baseline_loss
        # entropy_cost may be a traced scalar (the annealed schedule
        # from make_update_step); None = the constant from hp.
        if entropy_cost is None:
            entropy_cost = hp.entropy_cost
        entropy_loss = entropy_cost * compute_entropy_loss(target_logits)
        total_loss = pg_loss + baseline_loss + entropy_loss + aux_loss

    # Episode stats: fixed-shape aggregates (a boolean-mask gather would be
    # dynamic-shaped and unjittable); the host divides sum by count.
    episode_returns_sum = jnp.sum(
        jnp.where(
            done,
            batch["episode_return"][1:].astype(jnp.float32),
            0.0,
        )
    )
    episode_count = jnp.sum(done)

    stats = {
        "total_loss": total_loss,
        "pg_loss": pg_loss,
        "baseline_loss": baseline_loss,
        "entropy_loss": entropy_loss,
        "aux_loss": jnp.asarray(aux_loss, jnp.float32),
        "episode_returns_sum": episode_returns_sum,
        "episode_count": episode_count,
        # What the model's layers sowed of themselves, each name
        # folded over the layers by the rule it was sown with
        # (models/stats.py); nothing for a model that sows none.
        **model_stats.folded(variables),
    }
    # What a model says its parameters move by beside their gradient
    # (models/moe.py: a router's selection bias, by the batch's load),
    # a tree under the parameters' own paths: `update_body` takes it
    # out of the stats and adds it after the optimizer's step.
    if variables.get(PARAM_STEPS_KEY):
        stats[PARAM_STEPS_KEY] = variables[PARAM_STEPS_KEY]
    return total_loss, stats


def add_param_steps(params, steps, opt_state=None):
    """`params` with every leaf of `steps` (the sown collection
    `PARAM_STEPS_KEY`, a tree under the parameters' own paths) added to
    the parameter at its path, and how many leaves that was. Such a
    leaf's gradient is zero (nothing differentiable reads it), so the
    optimizer leaves it where it is and this is all that moves it."""
    if isinstance(opt_state, MasterParamsState):
        raise NotImplementedError(
            "a model whose parameters move by what it sows (a router's "
            "selection bias) trains with float32 resident parameters: "
            "the bf16-resident optimizers keep a master this step does "
            "not reach"
        )
    flat = flax.traverse_util.flatten_dict(params)
    moved = flax.traverse_util.flatten_dict(steps)
    for path, step in moved.items():
        leaf = flat[("params",) + path]
        flat[("params",) + path] = leaf + step.astype(leaf.dtype)
    return flax.traverse_util.unflatten_dict(flat), len(moved)


def donate_argnums_for(donate, donate_batch: bool = False) -> tuple:
    """Donation policy -> donate_argnums for the update step's
    (params, opt_state, batch, initial_agent_state) signature.

    - True: donate params + opt_state (single-threaded drivers; the update
      is in-place on-device).
    - "opt_only": donate opt_state but NOT params. For async drivers:
      inference threads hold live references to params (donating them
      would invalidate an in-flight act dispatch), but nothing else reads
      the optimizer state, so its buffers alias the new opt_state output
      in place. Callers must serialize update dispatch with any host read
      of opt_state (checkpointing).
    - False: donate nothing.

    donate_batch additionally donates the batch + initial_agent_state
    args (2, 3). XLA donation is STRICTLY input-output buffer aliasing:
    this only pays off for a jitted computation that emits batch-shaped
    outputs for those buffers to alias. The stock update_body does not
    (its outputs are params/opt_state/stats), so the drivers leave this
    False — enabling it there frees nothing and XLA warns "Some donated
    buffers were not usable" on every update. The knob exists for
    derived update steps that DO return batch-shaped values (e.g.
    auxiliary reconstructions or per-step priorities); such callers must
    also never re-read a consumed batch (true for the
    runtime/queues.DevicePrefetcher staging contract).
    """
    if donate == "opt_only":
        base = (1,)
    elif not isinstance(donate, bool):
        # A typo'd policy string must not fall through to the params-
        # donating default — that is the one unsafe option for async
        # drivers whose inference threads hold live params references.
        raise ValueError(f"Unknown donation policy {donate!r}")
    else:
        base = (0, 1) if donate else ()
    return base + ((2, 3) if donate_batch else ())


def entropy_schedule(hp: HParams):
    """opt_state -> entropy cost for this update (None = constant).

    When `entropy_cost_final` is set, reuses the LR schedule's clock —
    the optimizer state's `count` ticks once per update — to anneal
    linearly over the same frames horizon as the reference's LR decay,
    so no extra step argument threads through driver signatures.
    """
    if hp.entropy_cost_final is None:
        return lambda opt_state: None
    total_updates = updates_horizon(hp)

    def entropy_cost_at(opt_state):
        count = optax.tree_utils.tree_get(opt_state, "count")
        frac = jnp.minimum(count.astype(jnp.float32) / total_updates, 1.0)
        return hp.entropy_cost + frac * (
            hp.entropy_cost_final - hp.entropy_cost
        )

    return entropy_cost_at


# beastlint: hot
def update_body(model, optimizer: optax.GradientTransformation, hp: HParams):
    """The UNJITTED learner step:

    (params, opt_state, batch, initial_agent_state) ->
        (new_params, new_opt_state, stats)

    One definition shared by the single-device jit (make_update_step)
    and the mesh-sharded jit (parallel/dp.make_parallel_update_step) —
    a loss-side knob added here (e.g. the entropy anneal) reaches every
    learner path or none, never one of the two.
    """
    entropy_cost_at = entropy_schedule(hp)

    def update_step(params, opt_state, batch, initial_agent_state):
        ecost = entropy_cost_at(opt_state)
        grad_fn = jax.grad(
            lambda p: compute_loss(
                model, p, batch, initial_agent_state, hp,
                entropy_cost=ecost,
            ),
            has_aux=True,
        )
        grads, stats = grad_fn(params)
        param_steps = stats.pop(PARAM_STEPS_KEY, None)
        with telemetry.device_scope("optimizer"):
            updates, new_opt_state = optimizer.update(
                grads, opt_state, params
            )
            # Resident-aware apply (module-level apply_updates): the
            # bf16-resident optimizer hands back the new f32 master and
            # the resident params are one narrowing cast; every other
            # optimizer takes the stock optax apply.
            params = apply_updates(params, updates, new_opt_state)
        if param_steps is not None:
            with telemetry.device_scope("load_moved"):
                params, moved = add_param_steps(
                    params, param_steps, new_opt_state
                )
            stats["moe_bias_steps"] = jnp.float32(moved)
        # f32 upcast before the norm reduction (no-op for f32 grads;
        # bf16-resident runs emit bf16 grad arrays).
        with telemetry.device_scope("grad_norm"):
            stats["grad_norm"] = optax.global_norm(
                jax.tree_util.tree_map(
                    lambda g: g.astype(jnp.float32), grads
                )
            )
        return params, new_opt_state, stats

    return update_step


def one_device_model(model):
    """`model` for a program that holds its whole batch on one device:
    a family whose trunk merges T and B (`time_major_merge`, models/
    cores.merge_time_batch) merges them time-major there; same
    parameters, same outputs. The default order is the one that stays
    divided under any sharding of B (parallel/dp.py compiles the same
    update_body with it), so a caller that forgets this loses a percent,
    not the division over chips."""
    if hasattr(model, "time_major_merge"):
        return model.clone(time_major_merge=True)
    return model


def make_update_step(
    model, optimizer: optax.GradientTransformation, hp: HParams,
    donate=True, donate_batch: bool = False,
):
    """Build the jitted learner step (see update_body for the contract).

    `donate` is a policy understood by donate_argnums_for: True (donate
    params+opt, single-threaded drivers), "opt_only" (async drivers —
    the shared params stay undonated), or False. `donate_batch` also
    donates the staged batch/agent-state inputs (prefetched drivers
    where nothing re-reads a consumed batch).
    """
    return jax.jit(
        update_body(one_device_model(model), optimizer, hp),
        donate_argnums=donate_argnums_for(donate, donate_batch),
        compiler_options=update_compiler_options(model),
    )


def update_compiler_options(model):
    """The XLA options a family names for its update step (a tuple of
    pairs, `update_compiler_options`: models/kanana2.py), on the chip
    alone (they are the TPU compiler's; the CPU's refuses what it does
    not know); None for a family that names none."""
    options = getattr(model, "update_compiler_options", ())
    if options and jax.default_backend() == "tpu":
        return dict(options)
    return None


# beastlint: hot
def superstep_body(
    model, optimizer: optax.GradientTransformation, hp: HParams
):
    """The UNJITTED learner superstep:

    (params, opt_state, batches, initial_agent_states) ->
        (new_params, new_opt_state, stacked_stats)

    `batches` / `initial_agent_states` carry a leading K axis
    ([K, T+1, B, ...] / [K, ...]); a `lax.scan` threads params/opt_state
    through K applications of the EXACT update_body — so one XLA
    dispatch performs K parameter updates, and the optimizer `count`
    ticks once per scanned update (the LR decay and the entropy anneal
    advance per-UPDATE, not per-dispatch; pinned by the superstep
    bit-identity tests). Stats come back as one [K]-stacked pytree: the
    host syncs once per K updates instead of once per update.

    Shared by the single-device jit (make_update_superstep) and the
    mesh-sharded jit (parallel/dp.make_parallel_update_step with
    superstep_k > 1) the same way update_body is.
    """
    step = update_body(model, optimizer, hp)

    def superstep(params, opt_state, batches, initial_agent_states):
        def scan_body(carry, xs):
            p, o = carry
            batch, state = xs
            p, o, stats = step(p, o, batch, state)
            return (p, o), stats

        (params, opt_state), stats = jax.lax.scan(
            scan_body, (params, opt_state),
            (batches, initial_agent_states),
        )
        return params, opt_state, stats

    return superstep


# beastlint: hot
def consume_staged_inputs(update_fn):
    """Wrap an update step so the staged batch/agent-state device arrays
    are DELETED right after dispatch — the host-side half of batch
    donation (`donate_batch=True`).

    XLA-level donation is strictly input-output buffer aliasing, and the
    superstep emits no batch-shaped outputs (its outputs are
    params/opt_state/[K]-stats), so handing the [K, T+1, B, ...] staging
    stack to donate_argnums would only draw the "donated buffers were
    not usable" warning every dispatch (the same physics
    donate_argnums_for documents for the single update step). What CAN
    be enforced is the DevicePrefetcher staging contract — each staged
    stack is consumed exactly once: `jax.Array.delete()` drops the host
    reference at dispatch, so the buffers free the moment the scan's
    execution retires (PJRT holds them alive until then) instead of
    whenever the consumer happens to drop its references, and any
    accidental re-read of a consumed stack raises
    "Array has been deleted" loudly instead of training on stale data.
    Pinned by tests: no XLA donation warning, use-after-free raises.
    """

    def wrapped(params, opt_state, batch, initial_agent_state):
        out = update_fn(params, opt_state, batch, initial_agent_state)
        for leaf in jax.tree_util.tree_leaves(
            (batch, initial_agent_state)
        ):
            if isinstance(leaf, jax.Array) and not leaf.is_deleted():
                leaf.delete()
        return out

    # AOT surface passthrough: the bytes-accessed accounting
    # (instrument_update_step's learner.hbm_bytes_per_update gauge,
    # precision.bytes_accessed) lowers the jitted inner step from
    # ShapeDtypeStructs — the wrapper must not hide it.
    wrapped.lower = getattr(update_fn, "lower", None)
    return wrapped


def make_update_superstep(
    model, optimizer: optax.GradientTransformation, hp: HParams, k: int,
    donate=True, donate_batch: bool = False,
):
    """Build the jitted K-update superstep (see superstep_body).

    One dispatch = K SGD updates over a [K, T+1, B, ...] batch stack,
    bit-identical (CPU backend, pinned by test) to K sequential
    make_update_step dispatches on the same batches. `donate` is the
    donate_argnums_for policy for params/opt_state. `donate_batch=True`
    enforces the consume-once staging contract on the stacked batch via
    consume_staged_inputs (host-side deletion — see there for why the
    stack is NOT handed to donate_argnums).
    """
    if k < 1:
        raise ValueError(f"superstep k must be >= 1, got {k}")
    jitted = jax.jit(
        superstep_body(one_device_model(model), optimizer, hp),
        # Batch/state never go to donate_argnums here — no batch-shaped
        # outputs exist to alias (consume_staged_inputs has the story).
        donate_argnums=donate_argnums_for(donate, donate_batch=False),
    )
    if donate_batch:
        return consume_staged_inputs(jitted)
    return jitted


def stack_superstep_columns(
    batch: Dict[str, Any], initial_agent_state, k: int, columns: int,
    offset: int = 0, batch_dim: int = 1,
):
    """Host-side superstep staging for the sync driver: slice `k`
    consecutive `columns`-wide groups out of a wide [T+1, B_total, ...]
    unroll batch (starting at column `offset`) and stack them into the
    [K, T+1, columns, ...] superstep layout (states [K, ...] likewise).

    np.stack materializes fresh contiguous arrays, so the staged stack
    aliases nothing the collector still owns — safe to hand to a
    donate_batch superstep. Values are bit-identical to dispatching the
    k column groups sequentially (pure copies; pinned by test).
    """

    def stack(v):
        v = np.asarray(v)
        head = (slice(None),) * batch_dim
        return np.stack([
            v[head + (slice(offset + j * columns,
                            offset + (j + 1) * columns),)]
            for j in range(k)
        ])

    return (
        {key: stack(v) for key, v in batch.items()},
        jax.tree_util.tree_map(stack, initial_agent_state),
    )


# beastlint: hot
def instrument_update_step(update_step, registry=None, superstep_k=1):
    """Wrap a (jitted) update step with learner-side telemetry:

    - learner.update_dispatch_s: host time to hand XLA the update (the
      dispatch is async — device compute shows up in the driver's
      dequeue/learn stage histograms, not here);
    - learner.batch_bytes: host->device transfer volume of the batch +
      initial agent state per dispatch (the learner-side wire-accounting
      analog of the acting path's bytes_per_step gauges);
    - learner.updates: +superstep_k per dispatch (a superstep dispatch
      IS K updates — the counter counts updates, never dispatches);
    - learner.superstep_k (gauge) and learner.updates_per_dispatch
      (histogram: count = dispatches, mean = amortization factor) make
      the superstep amortization visible in telemetry.jsonl;
    - learner.host_syncs: counts host round-trips for update stats. The
      flush happens in the driver, so the wrapper exposes it as
      `wrapped.count_host_sync()` — drivers call it per stats fetch
      (once per K updates under supersteps, the K-fold reduction the
      learner_bench acceptance pins);
    - learner.hbm_bytes_per_update (gauge): XLA's bytes-accessed figure
      for ONE update, from the lowered HLO of the first dispatched
      signature (precision.bytes_accessed — the dtype-faithful
      accounting the --precision policies move; the lowered HLO counts
      a superstep's scan body ONCE, so the figure is per-update at any
      K). Computed once on a daemon thread at the first dispatch
      (lowering is compile-free but traces the net), and only when the
      inner jitted step is reachable (.lower).

    - `wrapped.compiled_text()`: the text of the program the first
      dispatch compiled (jit answers from its cache; None before a
      dispatch or without `.lower`): what `--profile_dir`'s account
      joins a device trace's ops on (telemetry/device_scopes.py).

    Signature-transparent: drivers swap `update_step =
    instrument_update_step(update_step, superstep_k=k)` and nothing
    else changes.
    """
    reg = registry if registry is not None else telemetry.get_registry()
    sp_dispatch = telemetry.get_tracer().span(
        "learner.update_dispatch",
        histogram=reg.histogram("learner.update_dispatch_s"),
    )
    h_per_dispatch = reg.histogram("learner.updates_per_dispatch")
    c_bytes = reg.counter("learner.batch_bytes")
    c_updates = reg.counter("learner.updates")
    c_host_syncs = reg.counter("learner.host_syncs")
    reg.gauge("learner.superstep_k").set(superstep_k)
    g_hbm = reg.gauge("learner.hbm_bytes_per_update")
    hbm_pending = [getattr(update_step, "lower", None) is not None]
    first_signature = []

    def wrapped(params, opt_state, batch, initial_agent_state):
        nbytes = sum(
            int(getattr(leaf, "nbytes", 0))
            for leaf in jax.tree_util.tree_leaves(
                (batch, initial_agent_state)
            )
        )
        if hbm_pending[0]:
            # Single-consumer hot path (the learner thread): the flag
            # flip is ordinary sequential code, no lock needed.
            hbm_pending[0] = False
            args = (params, opt_state, batch, initial_agent_state)
            first_signature.append(
                precision_lib.shape_structs(args, placed=True)
            )
            precision_lib.hbm_gauge_async(update_step, args, g_hbm)
        with sp_dispatch:
            out = update_step(
                params, opt_state, batch, initial_agent_state
            )
        c_bytes.inc(nbytes)
        c_updates.inc(superstep_k)
        h_per_dispatch.observe(superstep_k)
        return out

    def compiled_text():
        if not first_signature:
            return None
        return update_step.lower(*first_signature[0]).compile().as_text()

    wrapped.count_host_sync = lambda: c_host_syncs.inc()
    wrapped.compiled_text = compiled_text
    return wrapped


# beastlint: hot
def act_body(model, params, rng, env_output, agent_state):
    """Unjitted T=1 acting step on `[B, ...]` env outputs: adds/strips the
    time axis around the time-major model. Shared by make_act_step (jitted
    host path) and the anakin trainer (called inside its outer jit)."""
    batched = {k: v[None] for k, v in env_output.items()}
    out, new_state = model.apply(
        params, batched, agent_state, rngs={"action": rng}
    )
    out = jax.tree_util.tree_map(lambda x: x[0], out)
    return out, new_state


# beastlint: hot
def make_act_step(model):
    """Build the jitted batched acting step.

    (params, rng, env_output [B,...] dict, agent_state) ->
        (AgentOutput [B,...], new_agent_state)

    Adds/strips the T=1 time axis around the model, which is written
    time-major. Used by the sync driver and by the inference server.

    agent_state is NOT donated: the rollout collector keeps a reference to
    the state entering each unroll (the learner consumes it as
    initial_agent_state), so its buffer must outlive the call.
    """

    @jax.jit
    def act_step(params, rng, env_output, agent_state):
        return act_body(model, params, rng, env_output, agent_state)

    return act_step


def episode_stat_postprocess(stats: Dict[str, Any]) -> Dict[str, Any]:
    """Host-side: turn sum/count aggregates into mean_episode_return.

    Leaves may be scalars (one update) or [K]-stacked arrays (a
    superstep's scanned stats): episode sums/counts SUM over the stack
    and loss-like keys MEAN, matching exactly what K sequential flushes
    would have aggregated to — no /K undercount, no double count
    (pinned by test).
    """
    out = {}
    for key, v in stats.items():
        arr = np.asarray(jax.device_get(v), np.float64)
        if key in ("episode_returns_sum", "episode_count"):
            out[key] = float(arr.sum())
        else:
            out[key] = float(arr.mean())
    count = out.pop("episode_count", 0.0)
    returns_sum = out.pop("episode_returns_sum", 0.0)
    if count > 0:
        out["mean_episode_return"] = returns_sum / count
    out["episodes_finished"] = count
    return out
