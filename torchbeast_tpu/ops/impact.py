"""IMPACT surrogate objective (Luo et al., PAPERS.md): a clipped
target-network policy loss that tolerates policy lag far beyond
V-trace's budget while samples are REUSED K'-fold from the replay
arena.

Three policies meet in this loss:

- the *behavior* policy mu — whatever snapshot served the rollout
  (stamped into the batch as `policy_logits`, exactly like V-trace);
- the *target network* pi_target — a lagged copy of the learner params
  refreshed every `--target_refresh_updates` updates (it rides the
  PolicySnapshotStore versioning; its forward outputs arrive on the
  batch as `impact_target_logits` / `impact_target_baseline`);
- the *learner* policy pi_theta — the params being optimized.

The V-trace correction runs between mu and pi_target (both
constants w.r.t. theta, so the whole scan is gradient-free and the
fused machinery in ops/vtrace.py — sequential / associative — is
reused as-is), producing corrected value targets `vs` and clipped
advantages from the TARGET network's values. The policy gradient then
flows through a PPO-style clipped surrogate on the pi_theta/pi_target
ratio:

    rho      = exp(log pi_target(a) - log mu(a))        (V-trace clip)
    vs, A    = vtrace(rho, rewards, V_target)           (no gradient)
    ratio    = exp(log pi_theta(a) - log pi_target(a))
    pg_loss  = -sum min(ratio * A, clip(ratio, 1-eps, 1+eps) * A)
    baseline = 0.5 * sum (vs - V_theta)^2

At zero lag (theta == theta_target) the ratio is identically 1, so
with the clip wide open the surrogate's gradient equals V-trace's
exactly — d/dtheta[ratio * A] = A * d/dtheta[log pi_theta(a)] at
ratio == 1 — which is what tests/test_impact.py pins (gradient
equivalence; the forward VALUES differ by construction, the surrogate
is `ratio * A`, not `-log pi * A`).

Precision contract: like `vtrace_policy_losses`, every input is
upcast to f32 at entry (`_f32` / `.astype(f32)`), so the ratio/clip
exponentials accumulate in f32 under `--precision bf16_train`.
"""

import jax.numpy as jnp
from jax import lax

from torchbeast_tpu.ops import vtrace as vtrace_lib
from torchbeast_tpu.ops.losses import compute_baseline_loss
from torchbeast_tpu.ops.vtrace import action_log_probs


def impact_policy_losses(
    behavior_policy_logits,
    target_net_policy_logits,
    learner_policy_logits,
    actions,
    discounts,
    rewards,
    target_net_values,
    values,
    target_net_bootstrap_value,
    clip_rho_threshold=1.0,
    clip_pg_rho_threshold=1.0,
    clip_epsilon=0.2,
    scan_impl="associative",
):
    """Fused IMPACT targets + clipped-surrogate pg / baseline losses:
    (pg_loss, baseline_loss), both sum-reduced scalars.

    Mirrors `vtrace_policy_losses`' layout: [T, B(, A)] inputs, the
    same scan_impl passthrough, and `baseline_loss` returned WITHOUT
    the driver's cost coefficient. Gradients flow
    only through `learner_policy_logits` (the clipped surrogate) and
    `values` (the baseline regression against the corrected targets);
    everything derived from mu / the target network is a constant.

    `clip_epsilon=None` disables the surrogate clip (the wide-open
    configuration the equivalence pin uses).
    """
    vtrace_lib._check_impl(scan_impl)
    target_alp = lax.stop_gradient(
        action_log_probs(
            target_net_policy_logits.astype(jnp.float32), actions
        )
    )
    behavior_alp = lax.stop_gradient(
        action_log_probs(
            behavior_policy_logits.astype(jnp.float32), actions
        )
    )
    learner_alp = action_log_probs(
        learner_policy_logits.astype(jnp.float32), actions
    )
    # The V-trace correction runs target-network-vs-behavior — both
    # batch constants, so (unlike vtrace_policy_losses, where the
    # importance weights merely have their gradient stopped) the whole
    # recurrence is structurally gradient-free here.
    log_rhos = target_alp - behavior_alp
    discounts, rewards, values = vtrace_lib._f32(
        discounts, rewards, values
    )
    target_values, bootstrap_value = vtrace_lib._f32(
        target_net_values, target_net_bootstrap_value
    )
    target_values = lax.stop_gradient(target_values)
    bootstrap_value = lax.stop_gradient(bootstrap_value)

    rhos = jnp.exp(log_rhos)
    clipped_rhos = (
        jnp.minimum(rhos, clip_rho_threshold)
        if clip_rho_threshold is not None else rhos
    )
    cs = jnp.minimum(rhos, 1.0)
    values_t_plus_1 = jnp.concatenate(
        [target_values[1:], bootstrap_value[None]], axis=0
    )
    deltas = clipped_rhos * (
        rewards + discounts * values_t_plus_1 - target_values
    )
    clipped_pg_rhos = (
        jnp.minimum(rhos, clip_pg_rho_threshold)
        if clip_pg_rho_threshold is not None else rhos
    )

    vs = vtrace_lib._vs_minus_v(
        deltas, discounts, cs, bootstrap_value, scan_impl
    ) + target_values
    vs_t_plus_1 = jnp.concatenate([vs[1:], bootstrap_value[None]], axis=0)
    pg_advantages = clipped_pg_rhos * (
        rewards + discounts * vs_t_plus_1 - target_values
    )

    vs = lax.stop_gradient(vs)
    pg_advantages = lax.stop_gradient(pg_advantages)

    ratio = jnp.exp(learner_alp - target_alp)
    surrogate = ratio * pg_advantages
    if clip_epsilon is not None:
        clipped_surrogate = (
            jnp.clip(ratio, 1.0 - clip_epsilon, 1.0 + clip_epsilon)
            * pg_advantages
        )
        surrogate = jnp.minimum(surrogate, clipped_surrogate)
    pg_loss = jnp.sum(-surrogate)
    baseline_loss = compute_baseline_loss(vs - values)
    return pg_loss, baseline_loss
