"""V-trace off-policy actor-critic targets (IMPALA, arXiv:1802.01561).

TPU-native formulation: the backward recursion

    acc_t = delta_t + discount_t * c_t * acc_{t+1},   vs = acc + V

is a first-order linear recurrence, so it runs by default as a
`lax.associative_scan` over the affine maps f_t(x) = a_t x + b_t —
O(log T) depth, 2.56x over the sequential scan at T=4000 and within
noise at T=80 (2026-07-31 chip record, deleted in PR 21) — fused into
the learner's XLA program. The reference's sequential `lax.scan`
formulation stays available (`scan_impl="sequential"`): the oracle the
tests call directly.

Numerics contract: V-trace is part of the f32-accumulate surface
(torchbeast_tpu/precision.py) — inputs are upcast to float32 on entry
whatever the batch's storage dtype, so a bf16_train run solves the
recurrence at full precision. The two impls agree to float-
reassociation tolerance (pinned by the tests/test_vtrace.py parity
matrix). Behavioral parity with the reference
(/root/reference/torchbeast/core/vtrace.py:50-139): same clipping rules
(rho-bar for deltas, 1.0 for c, pg-rho-bar for advantages), same
namedtuple returns, and gradients are stopped through both outputs (the
reference wraps everything in torch.no_grad, vtrace.py:91-102).
"""

import collections

import jax
import jax.numpy as jnp
from jax import lax

VTraceFromLogitsReturns = collections.namedtuple(
    "VTraceFromLogitsReturns",
    [
        "vs",
        "pg_advantages",
        "log_rhos",
        "behavior_action_log_probs",
        "target_action_log_probs",
    ],
)

VTraceReturns = collections.namedtuple("VTraceReturns", "vs pg_advantages")

SCAN_IMPLS = ("sequential", "associative")


def action_log_probs(policy_logits, actions):
    """log pi(a_t | x_t) for integer actions.

    Equivalent to the reference's -nll_loss(log_softmax(...)) construction
    (vtrace.py:50-55), expressed as a gather over the action axis. Works for
    any leading shape: logits [..., A], actions [...] integer.
    """
    log_pi = jax.nn.log_softmax(policy_logits, axis=-1)
    return jnp.take_along_axis(
        log_pi, actions[..., None].astype(jnp.int32), axis=-1
    ).squeeze(-1)


def _f32(*arrays):
    """The f32-accumulate entry cast (see module docstring)."""
    return tuple(jnp.asarray(a).astype(jnp.float32) for a in arrays)


def _vs_minus_v(deltas, discounts, cs, bootstrap_value, scan_impl):
    """Solve the backward recurrence; returns acc ([T, ...]) with
    vs = acc + values. The shared core of the unfused targets and the
    fused loss path."""
    if scan_impl == "sequential":

        def scan_fn(acc, xs):
            delta_t, discount_t, c_t = xs
            acc = delta_t + discount_t * c_t * acc
            return acc, acc

        _, vs_minus_v_xs = lax.scan(
            scan_fn,
            jnp.zeros_like(bootstrap_value),
            (deltas, discounts, cs),
            reverse=True,
        )
        return vs_minus_v_xs
    # Suffix-compose the affine maps f_t(x) = a_t x + b_t:
    # acc_t = (f_t o f_{t+1} o ... o f_{T-1})(0). Flip to a prefix
    # problem, combine with (q o p) (p = already-accumulated earlier
    # flipped indices = LATER time, applied first), flip back.
    a = jnp.flip(discounts * cs, 0)
    b = jnp.flip(deltas, 0)

    def combine(p, q):
        pa, pb = p
        qa, qb = q
        return qa * pa, qa * pb + qb

    _, acc = lax.associative_scan(combine, (a, b), axis=0)
    return jnp.flip(acc, 0)


def _check_impl(scan_impl):
    if scan_impl not in SCAN_IMPLS:
        raise ValueError(
            f"scan_impl {scan_impl!r} must be one of {SCAN_IMPLS}"
        )


def from_logits(
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
    """V-trace for softmax policies (reference vtrace.py:58-88)."""
    target_action_log_probs = action_log_probs(target_policy_logits, actions)
    behavior_action_log_probs = action_log_probs(behavior_policy_logits, actions)
    log_rhos = target_action_log_probs - behavior_action_log_probs
    vtrace_returns = from_importance_weights(
        log_rhos=log_rhos,
        discounts=discounts,
        rewards=rewards,
        values=values,
        bootstrap_value=bootstrap_value,
        clip_rho_threshold=clip_rho_threshold,
        clip_pg_rho_threshold=clip_pg_rho_threshold,
        scan_impl=scan_impl,
    )
    return VTraceFromLogitsReturns(
        log_rhos=log_rhos,
        behavior_action_log_probs=behavior_action_log_probs,
        target_action_log_probs=target_action_log_probs,
        **vtrace_returns._asdict(),
    )


def from_importance_weights(
    log_rhos,
    discounts,
    rewards,
    values,
    bootstrap_value,
    clip_rho_threshold=1.0,
    clip_pg_rho_threshold=1.0,
    scan_impl="associative",
):
    """V-trace from log importance weights (reference vtrace.py:91-139).

    All inputs are time-major `[T, B, ...]`; `bootstrap_value` is `[B, ...]`.
    Returns VTraceReturns(vs, pg_advantages), both gradient-stopped and
    float32 (inputs are upcast on entry — the f32-accumulate contract).

    `scan_impl` picks how the backward recursion runs on device:

    - "associative" (default): `lax.associative_scan` over the affine
      maps f_t(x) = a_t x + b_t with a_t = discount_t * c_t, b_t =
      delta_t — the recursion is a first-order linear recurrence, so
      suffix composition solves it in O(log T) depth instead of O(T).
      2.56x at T=4000, within noise at the usual T<=80
      (2026-07-31 chip record, deleted in PR 21). Differs from
      sequential only by float reassociation (parity matrix in
      tests/test_vtrace.py).
    - "sequential": `lax.scan(reverse=True)` — T dependent steps, the
      reference formulation.
    """
    _check_impl(scan_impl)
    log_rhos, discounts, rewards, values, bootstrap_value = _f32(
        log_rhos, discounts, rewards, values, bootstrap_value
    )
    rhos = jnp.exp(log_rhos)
    if clip_rho_threshold is not None:
        clipped_rhos = jnp.minimum(rhos, clip_rho_threshold)
    else:
        clipped_rhos = rhos

    cs = jnp.minimum(rhos, 1.0)
    # [V_1, ..., V_{T}, bootstrap] shifted: values at t+1.
    values_t_plus_1 = jnp.concatenate(
        [values[1:], bootstrap_value[None]], axis=0
    )
    deltas = clipped_rhos * (rewards + discounts * values_t_plus_1 - values)

    if clip_pg_rho_threshold is not None:
        clipped_pg_rhos = jnp.minimum(rhos, clip_pg_rho_threshold)
    else:
        clipped_pg_rhos = rhos

    vs = _vs_minus_v(deltas, discounts, cs, bootstrap_value,
                     scan_impl) + values

    vs_t_plus_1 = jnp.concatenate([vs[1:], bootstrap_value[None]], axis=0)
    pg_advantages = clipped_pg_rhos * (
        rewards + discounts * vs_t_plus_1 - values
    )

    return VTraceReturns(
        vs=lax.stop_gradient(vs),
        pg_advantages=lax.stop_gradient(pg_advantages),
    )
