"""IMPACT lag-tolerant loss (ISSUE 18, ops/impact.py).

The load-bearing pin is GRADIENT equivalence with V-trace at the
degenerate configuration — target network == learner (lag 0), replay
reuse 1, surrogate clip wide open. The forward VALUES differ by
construction (`-sum(ratio * A)` vs `sum(-log pi * A)`), but at
ratio == 1 both objectives have the identical gradient field:
d/dtheta[ratio * A] = A * d/dtheta[log pi_theta(a)]. Anything that
perturbs the reductions, the stop-gradient placement, the f32 upcast
points, or the target-threading through the batch keys breaks this pin.

The version-skew tests pin the other half of the tentpole: the target
network rides PolicySnapshotStore versioning at FULL precision, and a
stale target changes the objective in exactly the surrogate-ratio way
(not through the V-trace correction, which runs target-vs-behavior)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tests import family_scaffold as scaffold
from torchbeast_tpu import learner as learner_lib
from torchbeast_tpu.models import create_model
from torchbeast_tpu.ops import impact_policy_losses, vtrace_policy_losses
from torchbeast_tpu.serving.snapshot import PolicySnapshotStore

T, B, A = 5, 3, 4


def _inputs(seed=0, t=T, b=B):
    rng = np.random.default_rng(seed)
    return {
        "behavior_logits": rng.standard_normal((t, b, A)).astype(
            np.float32
        ),
        "learner_logits": rng.standard_normal((t, b, A)).astype(
            np.float32
        ),
        "actions": rng.integers(0, A, (t, b)).astype(np.int32),
        "discounts": (rng.random((t, b)) < 0.9).astype(np.float32) * 0.99,
        "rewards": rng.standard_normal((t, b)).astype(np.float32),
        "values": rng.standard_normal((t, b)).astype(np.float32),
        "bootstrap": rng.standard_normal((b,)).astype(np.float32),
    }


class TestOpsGradientEquivalence:
    @pytest.mark.parametrize("impl", ["sequential", "associative"])
    def test_grads_match_vtrace_at_zero_lag(self, impl):
        """Lag 0 (target net == learner), clip wide open: d/dlogits and
        d/dvalues of the IMPACT losses equal V-trace's exactly."""
        x = _inputs(1)

        def vtrace_total(logits, values):
            pg, bl = vtrace_policy_losses(
                behavior_policy_logits=x["behavior_logits"],
                target_policy_logits=logits,
                actions=x["actions"],
                discounts=x["discounts"],
                rewards=x["rewards"],
                values=values,
                bootstrap_value=x["bootstrap"],
                scan_impl=impl,
            )
            return pg + bl

        def impact_total(logits, values):
            # Zero lag: the target network IS the learner snapshot —
            # same logits, same values — as constants (the driver's
            # target forward output).
            pg, bl = impact_policy_losses(
                behavior_policy_logits=x["behavior_logits"],
                target_net_policy_logits=jax.lax.stop_gradient(logits),
                learner_policy_logits=logits,
                actions=x["actions"],
                discounts=x["discounts"],
                rewards=x["rewards"],
                target_net_values=jax.lax.stop_gradient(values),
                values=values,
                target_net_bootstrap_value=x["bootstrap"],
                clip_epsilon=None,  # wide open
                scan_impl=impl,
            )
            return pg + bl

        args = (jnp.asarray(x["learner_logits"]), jnp.asarray(x["values"]))
        g_vt_fn = jax.jit(jax.grad(vtrace_total, argnums=(0, 1)))
        g_vt = g_vt_fn(*args)
        g_im_fn = jax.jit(jax.grad(impact_total, argnums=(0, 1)))
        g_im = g_im_fn(*args)
        for a, b in zip(g_vt, g_im):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)

    def test_clip_engages_only_off_policy(self):
        """At ratio == 1 any finite epsilon is inert; with a lagged
        target the clip floor binds and the loss moves."""
        x = _inputs(2)
        common = dict(
            behavior_policy_logits=x["behavior_logits"],
            actions=x["actions"],
            discounts=x["discounts"],
            rewards=x["rewards"],
            values=x["values"],
        )
        # Lag 0: epsilon irrelevant.
        for eps in (0.05, 0.2, None):
            pg, _ = impact_policy_losses(
                target_net_policy_logits=x["learner_logits"],
                learner_policy_logits=x["learner_logits"],
                target_net_values=x["values"],
                target_net_bootstrap_value=x["bootstrap"],
                clip_epsilon=eps,
                **common,
            )
            if eps == 0.05:
                ref = pg
            np.testing.assert_allclose(pg, ref, rtol=1e-6)
        # Lagged target: clipped vs unclipped differ (min() binds
        # somewhere for a big enough perturbation).
        lagged = x["learner_logits"] + np.float32(2.0)
        lagged[..., 0] -= 4.0  # reshape the distribution, not a shift
        pg_open, _ = impact_policy_losses(
            target_net_policy_logits=lagged,
            learner_policy_logits=x["learner_logits"],
            target_net_values=x["values"],
            target_net_bootstrap_value=x["bootstrap"],
            clip_epsilon=None,
            **common,
        )
        pg_clipped, _ = impact_policy_losses(
            target_net_policy_logits=lagged,
            learner_policy_logits=x["learner_logits"],
            target_net_values=x["values"],
            target_net_bootstrap_value=x["bootstrap"],
            clip_epsilon=0.2,
            **common,
        )
        assert not np.allclose(
            np.asarray(pg_open), np.asarray(pg_clipped), rtol=1e-6
        )
        # min(surrogate, clipped) can only remove positive terms.
        assert float(pg_clipped) >= float(pg_open) - 1e-5

    def test_targets_carry_no_gradient(self):
        """Nothing flows into the target net's logits/values or the
        behavior logits — the scan is structurally constant."""
        x = _inputs(3)

        def total(t_logits, t_values, b_logits):
            pg, bl = impact_policy_losses(
                behavior_policy_logits=b_logits,
                target_net_policy_logits=t_logits,
                learner_policy_logits=x["learner_logits"],
                actions=x["actions"],
                discounts=x["discounts"],
                rewards=x["rewards"],
                target_net_values=t_values,
                values=x["values"],
                target_net_bootstrap_value=x["bootstrap"],
                scan_impl="associative",
            )
            return pg + bl

        grads_fn = jax.jit(jax.grad(total, argnums=(0, 1, 2)))
        grads = grads_fn(
            jnp.asarray(x["learner_logits"]),
            jnp.asarray(x["values"]),
            jnp.asarray(x["behavior_logits"]),
        )
        for g in grads:
            np.testing.assert_array_equal(np.asarray(g), 0.0)


def _batch(seed=0, t=T, b=B):
    rng = np.random.default_rng(seed)
    return {
        "frame": rng.integers(0, 256, (t + 1, b, 48, 48, 1), dtype=np.uint8),
        "reward": rng.standard_normal((t + 1, b)).astype(np.float32),
        "done": rng.random((t + 1, b)) < 0.2,
        "episode_return": rng.standard_normal((t + 1, b)).astype(np.float32),
        "episode_step": rng.integers(0, 100, (t + 1, b)).astype(np.int32),
        "last_action": rng.integers(0, A, (t + 1, b)).astype(np.int32),
        "action": rng.integers(0, A, (t + 1, b)).astype(np.int32),
        "policy_logits": rng.standard_normal((t + 1, b, A)).astype(
            np.float32
        ),
        "baseline": rng.standard_normal((t + 1, b)).astype(np.float32),
    }


@pytest.fixture(scope="module")
def model_and_params():
    model = create_model("shallow", num_actions=A)
    batch = _batch()
    params = scaffold.init(
        model,
        {"params": jax.random.PRNGKey(0), "action": jax.random.PRNGKey(1)},
        batch,
        (),
    )
    return model, params


def _with_target(model, target_params, batch, superstep_k=1):
    """The driver-side merge: target forward outputs ride the batch."""
    fwd = learner_lib.make_target_forward(model, superstep_k=superstep_k)
    t_logits, t_base = fwd(target_params, batch, ())
    return {
        **batch,
        learner_lib.TARGET_LOGITS_KEY: t_logits,
        learner_lib.TARGET_BASELINE_KEY: t_base,
    }


class TestComputeLossEquivalence:
    def test_param_grads_match_vtrace_at_zero_lag(self, model_and_params):
        """End-to-end through compute_loss and the batch-key threading:
        with the target forward run on the CURRENT params, the impact
        param gradient equals the vtrace one (entropy/aux included —
        they are shared terms)."""
        model, params = model_and_params
        batch = _batch(1)
        hp_vt = learner_lib.HParams()
        hp_im = learner_lib.HParams(loss="impact")

        traced = jax.jit(jax.grad(
            lambda p: learner_lib.compute_loss(model, p, batch, (), hp_vt),
            has_aux=True,
        ))
        g_vt, _ = traced(params)
        merged = _with_target(model, params, batch)
        traced = jax.jit(jax.grad(
            lambda p: learner_lib.compute_loss(
                model, p, merged, (), hp_im
            ),
            has_aux=True,
        ))
        g_im, _ = traced(params)
        for a, b in zip(
            jax.tree_util.tree_leaves(g_vt),
            jax.tree_util.tree_leaves(g_im),
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6
            )

    def test_impact_without_target_keys_raises(self, model_and_params):
        model, params = model_and_params
        hp = learner_lib.HParams(loss="impact")
        with pytest.raises(ValueError, match="target network"):
            learner_lib.compute_loss(model, params, _batch(2), (), hp)

    def test_vtrace_ignores_target_keys(self, model_and_params):
        """Stray target keys on the batch must not change the vtrace
        loss (compute_loss pops them before the model forward)."""
        model, params = model_and_params
        batch = _batch(3)
        hp = learner_lib.HParams()
        loss_plain, _ = learner_lib.compute_loss(
            model, params, batch, (), hp
        )
        loss_merged, _ = learner_lib.compute_loss(
            model, params, _with_target(model, params, batch), (), hp
        )
        np.testing.assert_allclose(loss_plain, loss_merged, rtol=1e-6)

    def test_superstep_target_forward_vmaps(self, model_and_params):
        """K>1: the vmapped target forward equals per-column forwards."""
        model, params = model_and_params
        k = 2
        cols = [_batch(10 + i, b=B) for i in range(k)]
        stacked = {
            key: np.stack([c[key] for c in cols]) for key in cols[0]
        }
        fwd_k = learner_lib.make_target_forward(model, superstep_k=k)
        fwd_1 = learner_lib.make_target_forward(model, superstep_k=1)
        logits_k, base_k = fwd_k(params, stacked, ())
        for i, col in enumerate(cols):
            logits_1, base_1 = fwd_1(params, col, ())
            np.testing.assert_allclose(
                np.asarray(logits_k[i]), np.asarray(logits_1),
                rtol=1e-5, atol=1e-6,
            )
            np.testing.assert_allclose(
                np.asarray(base_k[i]), np.asarray(base_1),
                rtol=1e-5, atol=1e-6,
            )


class TestTargetVersioning:
    """The target network rides PolicySnapshotStore at full precision
    under the learner.target namespace."""

    def test_full_precision_roundtrip_bit_exact(self):
        rng = np.random.default_rng(0)
        params = {
            "w": rng.standard_normal((7, 5)).astype(np.float32),
            "b": rng.standard_normal((5,)).astype(np.float32),
        }
        store = PolicySnapshotStore(
            4, namespace="learner.target", cast_bf16=False
        )
        store.publish(0, params)
        _, restored = store.latest()
        # Bit-exact, not bf16-rounded: f32 through a bf16 cast would
        # lose mantissa bits and break the lag-0 equivalence pin.
        for key in params:
            np.testing.assert_array_equal(
                np.asarray(restored[key]), params[key]
            )

    def test_publish_copies_so_donation_cannot_invalidate(self):
        """The learner donates its params buffers into the next update
        dispatch; the stamped snapshot must be an independent copy."""
        params = {"w": jnp.arange(6, dtype=jnp.float32)}
        store = PolicySnapshotStore(
            1, namespace="learner.target", cast_bf16=False
        )
        store.publish(0, params)
        # Simulate donation: delete the original buffer.
        params["w"].delete()
        _, restored = store.latest()
        np.testing.assert_array_equal(
            np.asarray(restored["w"]), np.arange(6, dtype=np.float32)
        )

    def test_refresh_cadence_in_updates(self):
        store = PolicySnapshotStore(
            8, namespace="learner.target", cast_bf16=False
        )
        store.publish(0, {"w": np.zeros(2, np.float32)})
        due_at = [
            v for v in range(1, 20) if store.note_update(v)
            and store.publish(v, {"w": np.zeros(2, np.float32)})
        ]
        assert due_at == [8, 16]

    def test_version_skew_changes_objective(self, model_and_params):
        """A stale target (params perturbed since the stamp) must move
        the impact loss: the ratio departs from 1. This is the skew the
        relaxed snapshot cadence trades on — pinned so a silent
        'always use live params' regression cannot pass."""
        model, params = model_and_params
        batch = _batch(4)
        hp = learner_lib.HParams(loss="impact")
        store = PolicySnapshotStore(
            4, namespace="learner.target", cast_bf16=False
        )
        store.publish(0, params)
        _, stale = store.latest()

        # "Train" past the stamp: perturb the learner params.
        live = jax.tree_util.tree_map(
            lambda a: a + 0.05 * jnp.asarray(
                np.random.default_rng(5).standard_normal(a.shape),
                a.dtype,
            ) if jnp.issubdtype(a.dtype, jnp.floating) else a,
            params,
        )
        loss_lag0, _ = learner_lib.compute_loss(
            model, live, _with_target(model, live, batch), (), hp
        )
        loss_skew, _ = learner_lib.compute_loss(
            model, live, _with_target(model, stale, batch), (), hp
        )
        assert not np.allclose(
            np.asarray(loss_lag0), np.asarray(loss_skew), rtol=1e-6
        )


def test_updates_horizon_scales_with_reuse():
    """--replay_reuse multiplies the schedule clock: LR decay and
    entropy anneal must span env-frames x reuse updates."""
    hp1 = learner_lib.HParams(
        total_steps=1000, unroll_length=10, batch_size=10
    )
    hp2 = learner_lib.HParams(
        total_steps=1000, unroll_length=10, batch_size=10, replay_reuse=3
    )
    assert learner_lib.updates_horizon(hp1) == 10
    assert learner_lib.updates_horizon(hp2) == 30
