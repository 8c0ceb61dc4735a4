"""Precision-policy layer (torchbeast_tpu/precision.py + the learner's
bf16-resident training path): policy resolution incl. the deprecated
--model_dtype alias, staging casts, the f32-accumulate optimizer
contracts (bf16 second moment, f32 master params, factored state), the
fused-loss parity pin, and the bytes-accessed accounting."""

import argparse
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tests import family_scaffold as scaffold
from torchbeast_tpu import learner as learner_lib
from torchbeast_tpu import precision as precision_lib
from torchbeast_tpu.models import create_model

T, B, A = 8, 4, 3
FRAME = (4, 4, 1)


def make_batch(rng, t=T, b=B):
    return {
        "frame": rng.integers(0, 256, (t + 1, b) + FRAME, dtype=np.uint8),
        "reward": rng.standard_normal((t + 1, b)).astype(np.float32),
        "done": rng.random((t + 1, b)) < 0.1,
        "episode_return": rng.standard_normal((t + 1, b)).astype(
            np.float32
        ),
        "episode_step": rng.integers(0, 200, (t + 1, b)).astype(np.int32),
        "last_action": rng.integers(0, A, (t + 1, b)).astype(np.int32),
        "action": rng.integers(0, A, (t + 1, b)).astype(np.int32),
        "policy_logits": rng.standard_normal((t + 1, b, A)).astype(
            np.float32
        ),
        "baseline": rng.standard_normal((t + 1, b)).astype(np.float32),
    }


def _flags(**kw):
    ns = argparse.Namespace(precision="f32", model_dtype=None)
    for k, v in kw.items():
        setattr(ns, k, v)
    return ns


def _build(precision, use_lstm=False, **hp_kw):
    pol = precision_lib.get(precision)
    hp = learner_lib.HParams(
        unroll_length=T, batch_size=B, total_steps=1_000_000,
        opt_state_dtype=pol.opt_state_dtype,
        param_dtype=pol.param_dtype, **hp_kw,
    )
    model = create_model(
        "mlp", num_actions=A, use_lstm=use_lstm,
        dtype=pol.compute_dtype, head_dtype=pol.head_dtype,
    )
    rng = np.random.default_rng(0)
    params = scaffold.init(
        model,
        {
            "params": jax.random.PRNGKey(0),
            "action": jax.random.PRNGKey(1),
        },
        make_batch(rng, t=0),
        model.initial_state(B),
    )
    params = precision_lib.cast_params(params, pol)
    optimizer = learner_lib.make_optimizer(hp)
    return pol, hp, model, params, optimizer, rng


class TestPolicyResolution:
    def test_table(self):
        assert precision_lib.get("f32").compute_dtype == jnp.float32
        bt = precision_lib.get("bf16_train")
        assert bt.compute_dtype == jnp.bfloat16
        assert bt.head_dtype == jnp.bfloat16
        assert bt.param_dtype == "bf16"
        assert bt.opt_state_dtype == "bf16"
        with pytest.raises(ValueError, match="Unknown precision"):
            precision_lib.get("fp8")

    def test_legacy_model_dtype_aliases_bf16_compute(self, caplog):
        precision_lib.resolve_flags._warned_model_dtype = False
        with caplog.at_level("WARNING"):
            pol = precision_lib.resolve_flags(
                _flags(model_dtype="bfloat16")
            )
        assert pol.name == "bf16_compute"
        assert any(
            "deprecated" in r.message for r in caplog.records
        )

    def test_legacy_conflicts_with_explicit_bf16_train(self):
        with pytest.raises(ValueError, match="conflicts"):
            precision_lib.resolve_flags(
                _flags(precision="bf16_train", model_dtype="bfloat16")
            )

    def test_float32_legacy_is_silent_noop(self):
        pol = precision_lib.resolve_flags(
            _flags(model_dtype="float32")
        )
        assert pol.name == "f32"


class TestCasts:
    def test_cast_batch_converts_only_f32(self):
        rng = np.random.default_rng(1)
        batch = make_batch(rng)
        cast = precision_lib.cast_batch(
            batch, precision_lib.get("bf16_train").batch_dtype
        )
        import ml_dtypes

        assert cast["reward"].dtype == ml_dtypes.bfloat16
        assert cast["policy_logits"].dtype == ml_dtypes.bfloat16
        assert cast["frame"].dtype == np.uint8
        assert cast["action"].dtype == np.int32
        assert cast["done"].dtype == bool
        # None policy: identity.
        same = precision_lib.cast_batch(batch, None)
        assert same["reward"].dtype == np.float32

    def test_cast_params_bf16_resident(self):
        pol, _, _, params, _, _ = _build("bf16_train")
        for leaf in jax.tree_util.tree_leaves(params):
            assert leaf.dtype in (jnp.bfloat16, jnp.int32), leaf.dtype

    def test_arena_float_dtype_staging(self):
        """BatchArena(float_dtype=bf16): the write-through copy IS the
        cast; non-float leaves keep their dtype."""
        import ml_dtypes

        from torchbeast_tpu.runtime.queues import (
            BatchArena,
            BatchingQueue,
        )

        rng = np.random.default_rng(2)
        queue = BatchingQueue(batch_dim=1)
        arena = BatchArena(
            k=2, rows=2, batch_dim=1, float_dtype=ml_dtypes.bfloat16
        )
        items = [
            {
                "x": rng.standard_normal((3, 1)).astype(np.float32),
                "n": rng.integers(0, 9, (3, 1)).astype(np.int32),
            }
            for _ in range(4)
        ]
        for item in items:
            queue.enqueue(item)
        stacked, release = arena.assemble_from(queue)
        assert stacked["x"].dtype == ml_dtypes.bfloat16
        assert stacked["n"].dtype == np.int32
        # Values equal to a direct cast of the concatenated columns.
        want = np.stack([
            np.concatenate([items[0]["x"], items[1]["x"]], axis=1),
            np.concatenate([items[2]["x"], items[3]["x"]], axis=1),
        ]).astype(ml_dtypes.bfloat16)
        np.testing.assert_array_equal(
            np.asarray(stacked["x"]), want
        )
        release()


class TestOptimizerState:
    def test_bf16_second_moment_tracks_f32_within_tolerance(self):
        """bf16 nu storage with f32 EMA accumulate: a short update
        trajectory stays within bf16 rounding of the all-f32 one."""
        hp32 = learner_lib.HParams(
            unroll_length=T, batch_size=B, total_steps=1_000_000
        )
        hp16 = hp32._replace(opt_state_dtype="bf16")
        grads = {
            "w": jnp.asarray(
                np.random.default_rng(0).standard_normal((8, 8)),
                jnp.float32,
            )
        }
        params = {"w": jnp.zeros((8, 8), jnp.float32)}
        o32 = learner_lib.make_optimizer(hp32)
        o16 = learner_lib.make_optimizer(hp16)
        s32, s16 = o32.init(params), o16.init(params)
        p32, p16 = params, params
        import optax

        def stepped(optimizer):
            def step(p, s):
                u, s = optimizer.update(grads, s, p)
                return optax.apply_updates(p, u), s

            return jax.jit(step)

        step32, step16 = stepped(o32), stepped(o16)
        for _ in range(5):
            p32, s32 = step32(p32, s32)
            p16, s16 = step16(p16, s16)
        np.testing.assert_allclose(
            p16["w"], p32["w"], rtol=2e-2, atol=1e-4
        )

    def test_bf16_nu_stored_half_width(self):
        hp16 = learner_lib.HParams(
            unroll_length=T, batch_size=B, total_steps=1_000_000,
            opt_state_dtype="bf16",
        )
        params = {"w": jnp.zeros((4, 4), jnp.float32)}
        state = learner_lib.make_optimizer(hp16).init(params)
        nus = [
            leaf for leaf in jax.tree_util.tree_leaves(state)
            if getattr(leaf, "shape", None) == (4, 4)
        ]
        assert nus and all(n.dtype == jnp.bfloat16 for n in nus)

    def test_factored_state_is_row_col(self):
        hp = learner_lib.HParams(
            unroll_length=T, batch_size=B, total_steps=1_000_000,
            opt_factored=True,
        )
        params = {
            "w": jnp.zeros((6, 4), jnp.float32),
            "b": jnp.zeros((4,), jnp.float32),
        }
        opt = learner_lib.make_optimizer(hp)
        state = opt.init(params)
        leaves = [
            s for s in jax.tree_util.tree_leaves(state)
            if hasattr(s, "shape")
        ]
        shapes = {tuple(leaf.shape) for leaf in leaves}
        # Matrix leaf: row (6,) + col (4,) EMAs, NO (6, 4) accumulator;
        # vector leaf keeps its full (4,) nu.
        assert (6,) in shapes and (4,) in shapes
        assert (6, 4) not in shapes
        # And it optimizes: a few steps shrink a quadratic.
        import optax

        def loss(p):
            return jnp.sum(jnp.square(p["w"] - 1.0)) + jnp.sum(
                jnp.square(p["b"] + 2.0)
            )

        @jax.jit
        def step(p, state):
            u, state = opt.update(jax.grad(loss)(p), state, p)
            return optax.apply_updates(p, u), state

        p = params
        before = float(loss(p))
        for _ in range(20):
            p, state = step(p, state)
        assert float(loss(p)) < before

    def test_bf16_resident_master_round_trip(self):
        """Resident params after an update == bf16(new f32 master); the
        master itself never sees bf16 rounding."""
        pol, hp, model, params, optimizer, rng = _build("bf16_train")
        opt_state = optimizer.init(params)
        assert isinstance(opt_state, learner_lib.MasterParamsState)
        for leaf in jax.tree_util.tree_leaves(opt_state.master):
            assert leaf.dtype == jnp.float32
        update_step = learner_lib.make_update_step(
            model, optimizer, hp, donate=False
        )
        batch = precision_lib.cast_batch(
            make_batch(rng), pol.batch_dtype
        )
        new_params, new_opt, stats = update_step(
            params, opt_state, batch, ()
        )
        assert np.isfinite(float(stats["total_loss"]))
        for got, master in zip(
            jax.tree_util.tree_leaves(new_params),
            jax.tree_util.tree_leaves(new_opt.master),
        ):
            assert got.dtype == jnp.bfloat16
            np.testing.assert_array_equal(
                np.asarray(got),
                np.asarray(master.astype(jnp.bfloat16)),
            )

    def test_bf16_train_close_to_f32_one_step(self):
        """One bf16_train update lands within bf16 tolerance of the f32
        update from the same start — the policy changes precision, not
        the algorithm."""
        _, hp32, model32, params32, opt32, rng32 = _build("f32")
        pol, hp16, model16, params16, opt16, rng16 = _build(
            "bf16_train"
        )
        batch = make_batch(np.random.default_rng(7))
        step32 = learner_lib.make_update_step(
            model32, opt32, hp32, donate=False
        )
        step16 = learner_lib.make_update_step(
            model16, opt16, hp16, donate=False
        )
        p32, _, s32 = step32(
            params32, opt32.init(params32), batch, ()
        )
        p16, _, s16 = step16(
            params16, opt16.init(params16),
            precision_lib.cast_batch(batch, pol.batch_dtype), (),
        )
        assert np.isfinite(float(s16["total_loss"]))
        np.testing.assert_allclose(
            float(s16["total_loss"]), float(s32["total_loss"]),
            rtol=5e-2,
        )
        w32 = jax.tree_util.tree_leaves(p32)[0]
        w16 = jax.tree_util.tree_leaves(p16)[0]
        np.testing.assert_allclose(
            np.asarray(w16, np.float32), np.asarray(w32),
            rtol=3e-2, atol=3e-2,
        )


class TestFusedLoss:
    def test_fused_equals_composed_values_and_grads(self):
        """ops.vtrace_policy_losses == from_logits + the two composed
        losses, in value AND gradient (the default-update-path fusion
        must be a pure refactor)."""
        from torchbeast_tpu.ops import losses as losses_lib
        from torchbeast_tpu.ops import vtrace

        rng = np.random.default_rng(3)
        t, b = 9, 4
        behavior = jnp.asarray(
            rng.standard_normal((t, b, A)).astype(np.float32)
        )
        target = jnp.asarray(
            rng.standard_normal((t, b, A)).astype(np.float32)
        )
        actions = jnp.asarray(rng.integers(0, A, (t, b)))
        discounts = jnp.asarray(
            ((rng.random((t, b)) > 0.1) * 0.99).astype(np.float32)
        )
        rewards = jnp.asarray(
            rng.standard_normal((t, b)).astype(np.float32)
        )
        values = jnp.asarray(
            rng.standard_normal((t, b)).astype(np.float32)
        )
        boot = jnp.asarray(rng.standard_normal((b,)).astype(np.float32))

        def composed(tl, vals):
            vr = vtrace.from_logits(
                behavior, tl, actions, discounts, rewards, vals, boot,
                scan_impl="associative",
            )
            return (
                losses_lib.compute_policy_gradient_loss(
                    tl, actions, vr.pg_advantages
                )
                + 0.5 * losses_lib.compute_baseline_loss(vr.vs - vals)
            )

        def fused(tl, vals):
            pg, base = losses_lib.vtrace_policy_losses(
                behavior, tl, actions, discounts, rewards, vals, boot,
                scan_impl="associative",
            )
            return pg + 0.5 * base

        traced = jax.jit(jax.value_and_grad(composed, argnums=(0, 1)))
        v1, g1 = traced(
            target, values
        )
        traced = jax.jit(jax.value_and_grad(fused, argnums=(0, 1)))
        v2, g2 = traced(
            target, values
        )
        np.testing.assert_allclose(float(v1), float(v2), rtol=1e-6)
        np.testing.assert_allclose(g1[0], g2[0], rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(g1[1], g2[1], rtol=1e-6, atol=1e-7)


class TestBytesAccounting:
    def test_bytes_accessed_sees_dtype(self):
        """The lowered-HLO figure must be dtype-faithful: a bf16 matmul
        reads half the bytes of the f32 one."""
        x32 = jax.ShapeDtypeStruct((128, 128), jnp.float32)
        x16 = jax.ShapeDtypeStruct((128, 128), jnp.bfloat16)
        f = jax.jit(lambda a: a @ a)
        b32 = precision_lib.bytes_accessed(f, x32)
        b16 = precision_lib.bytes_accessed(f, x16)
        assert b32 and b16 and b32 == pytest.approx(2 * b16)

    def test_bytes_accessed_none_on_unloweraable(self):
        assert precision_lib.bytes_accessed(lambda x: x, 1) is None

    def _measure_k1_gauge(self):
        from torchbeast_tpu import telemetry

        pol, hp, model, params, optimizer, rng = _build("f32")
        registry = telemetry.MetricsRegistry()
        update_step = learner_lib.instrument_update_step(
            learner_lib.make_update_step(
                model, optimizer, hp, donate=False
            ),
            registry=registry,
        )
        batch = make_batch(rng)
        update_step(params, optimizer.init(params), batch, ())
        gauge = registry.gauge("learner.hbm_bytes_per_update")
        deadline = time.time() + 20
        while time.time() < deadline and gauge.value() == 0:
            time.sleep(0.05)
        return gauge.value()

    def test_hbm_gauge_via_instrument(self):
        """instrument_update_step publishes learner.hbm_bytes_per_update
        from the first dispatch (daemon thread — poll briefly)."""
        assert self._measure_k1_gauge() > 0

    def test_hbm_gauge_superstep_is_per_update(self):
        """The lowered HLO counts the superstep scan body ONCE, so the
        K=2 gauge must be ~the K=1 figure (per-update), NOT half of it
        — the regression the /K division bug produced."""
        from torchbeast_tpu import telemetry

        k1 = self._measure_k1_gauge()
        pol, hp, model, params, optimizer, rng = _build("f32")
        registry = telemetry.MetricsRegistry()
        k = 2
        update_step = learner_lib.instrument_update_step(
            learner_lib.make_update_superstep(
                model, optimizer, hp, k, donate=False
            ),
            registry=registry,
            superstep_k=k,
        )
        b1 = make_batch(rng)
        batch = {key: np.stack([v] * k) for key, v in b1.items()}
        update_step(params, optimizer.init(params), batch, ())
        gauge = registry.gauge("learner.hbm_bytes_per_update")
        deadline = time.time() + 20
        while time.time() < deadline and gauge.value() == 0:
            time.sleep(0.05)
        # Body-once semantics: within the K-stack staging margin of the
        # K=1 figure, and far above the /K-bug's halved value.
        assert gauge.value() == pytest.approx(k1, rel=0.15)
        assert gauge.value() > 0.75 * k1


class TestTransformerBF16Head:
    """ISSUE 13: the transformer families thread head_dtype — bf16_train
    no longer silently falls back to bf16-trunk-only (the PR 8 logged
    exception is gone)."""

    def _tiny_transformer_batch(self, rng, t=4, b=2):
        return {
            "frame": rng.integers(0, 256, (t, b) + FRAME, dtype=np.uint8),
            "reward": rng.standard_normal((t, b)).astype(np.float32),
            "done": rng.random((t, b)) < 0.2,
            "last_action": rng.integers(0, A, (t, b)).astype(np.int32),
        }

    @pytest.mark.parametrize(
        "family", ["transformer", "pipelined_transformer"]
    )
    def test_bf16_head_outputs_stay_f32(self, family):
        pol = precision_lib.get("bf16_train")
        model = create_model(
            family, num_actions=A, dtype=pol.compute_dtype,
            head_dtype=pol.head_dtype, num_layers=1, d_model=16,
            num_heads=2, memory_len=4,
        )
        assert model.head_dtype == jnp.bfloat16
        rng = np.random.default_rng(0)
        batch = self._tiny_transformer_batch(rng)
        state = model.initial_state(2)
        params = scaffold.init(
            model,
            {
                "params": jax.random.PRNGKey(0),
                "action": jax.random.PRNGKey(1),
            },
            batch,
            state,
        )
        (out, _), _ = scaffold.apply(
            model, sample_action=False, mutable=("losses",)
        )(params, batch, state)
        # The head boundary contract: compute bf16, outputs f32 (the
        # loss side, wire schema, and sampling never see bf16).
        assert out.policy_logits.dtype == jnp.float32
        assert out.baseline.dtype == jnp.float32

    def test_driver_threads_transformer_head_dtype(self):
        """_init_model_and_params under --precision bf16_train builds
        the transformer with a bf16 head (no fallback branch left)."""
        from torchbeast_tpu import monobeast

        flags = monobeast.make_parser().parse_args([
            "--model", "transformer", "--precision", "bf16_train",
            "--unroll_length", "4", "--batch_size", "2",
            "--num_actors", "2",
        ])
        model, _ = monobeast._init_model_and_params(
            flags, A, 2, FRAME, init_params=False
        )
        assert model.head_dtype == jnp.bfloat16
        assert model.dtype == jnp.bfloat16
