"""IMPALA loss functions.

The reference duplicates these in both drivers
(/root/reference/torchbeast/monobeast.py:107-125 and
polybeast_learner.py:113-131); here they live once. All reductions are sums
over every element, matching the reference exactly (the total loss is then
scaled by the driver's cost coefficients).
"""

import jax
import jax.numpy as jnp
from jax import lax

from torchbeast_tpu.ops import vtrace as vtrace_lib
from torchbeast_tpu.ops.vtrace import action_log_probs


def compute_baseline_loss(advantages):
    """0.5 * sum((vs - V)^2)  (reference polybeast_learner.py:113-114)."""
    return 0.5 * jnp.sum(jnp.square(advantages))


def compute_entropy_loss(logits):
    """Negative entropy, sum(p * log p)  (polybeast_learner.py:117-121)."""
    policy = jax.nn.softmax(logits, axis=-1)
    log_policy = jax.nn.log_softmax(logits, axis=-1)
    return jnp.sum(policy * log_policy)


def compute_policy_gradient_loss(logits, actions, advantages):
    """sum(-log pi(a) * stop_grad(advantage))  (polybeast_learner.py:124-131).

    Advantages never receive gradient (reference uses .detach(); verified by
    its grad-flow test, tests/polybeast_loss_functions_test.py:165-177).
    """
    cross_entropy = -action_log_probs(logits, actions)
    return jnp.sum(cross_entropy * lax.stop_gradient(advantages))


def vtrace_policy_losses(
    behavior_policy_logits,
    target_policy_logits,
    actions,
    discounts,
    rewards,
    values,
    bootstrap_value,
    clip_rho_threshold=1.0,
    clip_pg_rho_threshold=1.0,
    scan_impl="associative",
):
    """Fused V-trace targets + pg/baseline losses: (pg_loss,
    baseline_loss), both sum-reduced scalars.

    The learner's default update path. Identical math (forward AND
    gradient) to composing `vtrace.from_logits` with
    `compute_policy_gradient_loss`/`compute_baseline_loss` — pinned by
    test — but fused: one `action_log_probs` evaluation of the target
    logits serves both the importance weights and the pg cross-entropy
    (the composed path computes it twice), the 5-field
    VTraceFromLogitsReturns is never built, and the advantages are
    consumed by their sum-reductions in place instead of surviving the
    target computation as named arrays — nothing here can escape to HBM
    between the scan and the losses.

    `baseline_loss` comes back WITHOUT the driver's cost coefficient
    (same contract as compute_baseline_loss). Everything accumulates in
    f32 whatever the input dtypes (the precision contract); gradients
    flow only through `target_policy_logits` (the pg cross-entropy) and
    `values` (the baseline regression), exactly like the composed path.
    """
    vtrace_lib._check_impl(scan_impl)
    target_alp = action_log_probs(
        target_policy_logits.astype(jnp.float32), actions
    )
    behavior_alp = action_log_probs(
        behavior_policy_logits.astype(jnp.float32), actions
    )
    # Gradients never flow through the importance weights (the composed
    # path stops the scan OUTPUTS, which blocks the same paths); the
    # early stop keeps the backward from even building them.
    log_rhos = lax.stop_gradient(target_alp - behavior_alp)
    discounts, rewards, values, bootstrap_value = vtrace_lib._f32(
        discounts, rewards, values, bootstrap_value
    )

    rhos = jnp.exp(log_rhos)
    clipped_rhos = (
        jnp.minimum(rhos, clip_rho_threshold)
        if clip_rho_threshold is not None else rhos
    )
    cs = jnp.minimum(rhos, 1.0)
    values_sg = lax.stop_gradient(values)
    values_t_plus_1 = jnp.concatenate(
        [values_sg[1:], bootstrap_value[None]], axis=0
    )
    deltas = clipped_rhos * (
        rewards + discounts * values_t_plus_1 - values_sg
    )
    clipped_pg_rhos = (
        jnp.minimum(rhos, clip_pg_rho_threshold)
        if clip_pg_rho_threshold is not None else rhos
    )

    vs = vtrace_lib._vs_minus_v(
        deltas, discounts, cs, bootstrap_value, scan_impl
    ) + values_sg
    vs_t_plus_1 = jnp.concatenate([vs[1:], bootstrap_value[None]], axis=0)
    pg_advantages = clipped_pg_rhos * (
        rewards + discounts * vs_t_plus_1 - values_sg
    )

    vs = lax.stop_gradient(vs)
    pg_advantages = lax.stop_gradient(pg_advantages)
    pg_loss = jnp.sum(-target_alp * pg_advantages)
    baseline_loss = compute_baseline_loss(vs - values)
    return pg_loss, baseline_loss
