"""Learner superstep contracts (ISSUE 4): K scanned updates must be
BIT-identical (CPU backend) to K sequential make_update_step dispatches
on the same batches — including the optimizer `count` clock that the LR
decay and entropy anneal divide by (the easy off-by-K bug) — plus the
consume-once batch-donation semantics and the host-side staging
helpers."""

import warnings

import numpy as np
import pytest

import jax

from tests import family_scaffold as scaffold
from torchbeast_tpu import learner as learner_lib
from torchbeast_tpu.models import create_model

T, B, A = 4, 2, 3
FRAME = (4, 4, 1)


def make_batch(rng, t=T, b=B):
    return {
        "frame": rng.integers(0, 256, (t + 1, b) + FRAME, dtype=np.uint8),
        "reward": rng.standard_normal((t + 1, b)).astype(np.float32),
        "done": rng.random((t + 1, b)) < 0.2,
        "episode_return": rng.standard_normal((t + 1, b)).astype(
            np.float32
        ),
        "episode_step": rng.integers(0, 100, (t + 1, b)).astype(np.int32),
        "last_action": rng.integers(0, A, (t + 1, b)).astype(np.int32),
        "action": rng.integers(0, A, (t + 1, b)).astype(np.int32),
        "policy_logits": rng.standard_normal((t + 1, b, A)).astype(
            np.float32
        ),
        "baseline": rng.standard_normal((t + 1, b)).astype(np.float32),
    }


def _setup(use_lstm, entropy_anneal, seed=0):
    # A short total_steps horizon makes the schedules move VISIBLY
    # between consecutive updates, so a schedule clock that ticked
    # per-dispatch instead of per-update could not stay bit-identical.
    hp = learner_lib.HParams(
        unroll_length=T,
        batch_size=B,
        total_steps=20 * T * B,
        entropy_cost_final=0.00001 if entropy_anneal else None,
    )
    model = create_model("mlp", num_actions=A, use_lstm=use_lstm)
    state = model.initial_state(B)
    rng = np.random.default_rng(seed)
    dummy = make_batch(rng, t=0)
    params = scaffold.init(
        model,
        {"params": jax.random.PRNGKey(seed),
         "action": jax.random.PRNGKey(seed + 1)},
        dummy,
        state,
    )
    optimizer = learner_lib.make_optimizer(hp)
    opt_state = optimizer.init(params)
    return hp, model, optimizer, params, opt_state, rng


def _np_state(model, b=B):
    return jax.tree_util.tree_map(
        np.asarray, model.initial_state(b)
    )


def _stack(trees):
    return jax.tree_util.tree_map(lambda *xs: np.stack(xs), *trees)


def assert_trees_bit_equal(a, b, what):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y), err_msg=what
        )


@pytest.mark.parametrize("use_lstm", [False, True],
                         ids=["mlp", "lstm"])
@pytest.mark.parametrize("entropy_anneal", [False, True],
                         ids=["const", "anneal"])
def test_superstep_bit_identical_to_sequential(use_lstm, entropy_anneal):
    """K in {1, 2, 4} scanned updates == the first K sequential
    dispatches of the same batch sequence, bit for bit: params,
    opt_state, AND every per-update stats leaf (scan slot i ==
    sequential update i)."""
    hp, model, optimizer, params, opt_state, rng = _setup(
        use_lstm, entropy_anneal
    )
    ks = (1, 2, 4)
    n = max(ks)
    batches = [make_batch(rng) for _ in range(n)]
    states = [_np_state(model) for _ in range(n)]

    update_step = learner_lib.make_update_step(
        model, optimizer, hp, donate=False
    )
    seq_params, seq_opt = [], []
    seq_stats = []
    p, o = params, opt_state
    for i in range(n):
        p, o, st = update_step(p, o, batches[i], states[i])
        seq_params.append(p)
        seq_opt.append(o)
        seq_stats.append(jax.device_get(st))

    for k in ks:
        superstep = learner_lib.make_update_superstep(
            model, optimizer, hp, k, donate=False
        )
        stacked_b = {
            key: np.stack([batches[i][key] for i in range(k)])
            for key in batches[0]
        }
        stacked_s = _stack(states[:k])
        p_k, o_k, stats_k = superstep(
            params, opt_state, stacked_b, stacked_s
        )
        assert_trees_bit_equal(
            p_k, seq_params[k - 1], f"params diverge at K={k}"
        )
        assert_trees_bit_equal(
            o_k, seq_opt[k - 1],
            f"opt_state (incl. schedule count) diverges at K={k}",
        )
        stats_k = jax.device_get(stats_k)
        for i in range(k):
            for key, v in seq_stats[i].items():
                np.testing.assert_array_equal(
                    np.asarray(stats_k[key])[i], np.asarray(v),
                    err_msg=f"stats[{key}] scan slot {i} at K={k}",
                )


def test_superstep_schedule_ticks_per_update_not_per_dispatch():
    """After one K=4 dispatch the optimizer count must read 4: a clock
    that ticked once per dispatch would anneal the LR/entropy 4x too
    slowly (the off-by-K bug the issue calls out)."""
    import optax

    hp, model, optimizer, params, opt_state, rng = _setup(
        use_lstm=False, entropy_anneal=True
    )
    superstep = learner_lib.make_update_superstep(
        model, optimizer, hp, 4, donate=False
    )
    batches = [make_batch(rng) for _ in range(4)]
    stacked_b = {
        key: np.stack([b[key] for b in batches]) for key in batches[0]
    }
    stacked_s = _stack([_np_state(model) for _ in range(4)])
    _, opt_after, _ = superstep(params, opt_state, stacked_b, stacked_s)
    count = optax.tree_utils.tree_get(jax.device_get(opt_after), "count")
    assert int(count) == 4


def test_donate_batch_superstep_no_warning_and_use_after_free():
    """donate_batch=True on the superstep must (a) produce the same
    numbers as the undonated run, (b) emit NO 'donated buffers were not
    usable' XLA warning (the staging stack is consumed host-side, never
    handed to donate_argnums — it has no batch-shaped output to alias),
    and (c) enforce consume-once: re-reading the staged stack after
    dispatch raises instead of silently training on stale data."""
    hp, model, optimizer, params, opt_state, rng = _setup(
        use_lstm=True, entropy_anneal=False
    )
    k = 2
    batches = [make_batch(rng) for _ in range(k)]
    stacked_b = {
        key: np.stack([b[key] for b in batches]) for key in batches[0]
    }
    stacked_s = _stack([_np_state(model) for _ in range(k)])

    ref = learner_lib.make_update_superstep(
        model, optimizer, hp, k, donate=False
    )
    p_ref, o_ref, stats_ref = ref(params, opt_state, stacked_b, stacked_s)

    donating = learner_lib.make_update_superstep(
        model, optimizer, hp, k, donate=True, donate_batch=True
    )
    staged_b = jax.device_put(stacked_b)
    staged_s = jax.device_put(stacked_s)
    p_in = jax.device_put(params)
    o_in = jax.device_put(opt_state)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        p_d, o_d, stats_d = donating(p_in, o_in, staged_b, staged_s)
        jax.block_until_ready(p_d)
    donation_warnings = [
        str(w.message) for w in caught
        if "donated buffers were not usable" in str(w.message).lower()
    ]
    assert donation_warnings == []

    assert_trees_bit_equal(p_d, p_ref, "donated params differ")
    assert_trees_bit_equal(o_d, o_ref, "donated opt_state differs")
    assert_trees_bit_equal(
        jax.device_get(stats_d), jax.device_get(stats_ref),
        "donated stats differ",
    )

    # Consume-once: every staged batch leaf is dead after dispatch.
    # beastlint: disable=DONATE-USE  this test IS the use-after-free pin: reads must raise
    for leaf in jax.tree_util.tree_leaves((staged_b, staged_s)):
        with pytest.raises(RuntimeError, match="deleted"):
            np.asarray(leaf)


def test_make_update_superstep_rejects_bad_k():
    hp, model, optimizer, *_ = _setup(False, False)
    with pytest.raises(ValueError, match="superstep k"):
        learner_lib.make_update_superstep(model, optimizer, hp, 0)


def test_stack_superstep_columns_matches_slices():
    """The sync driver's staging helper: [K, T+1, cols] stacks must be
    exactly the K consecutive column-group slices, and the staged
    arrays must be fresh (not views of the collector's batch)."""
    rng = np.random.default_rng(3)
    wide = make_batch(rng, b=8)
    state = (rng.standard_normal((1, 8, 6)).astype(np.float32),)
    stacked, stacked_state = learner_lib.stack_superstep_columns(
        wide, state, k=2, columns=2, offset=4
    )
    for key, v in wide.items():
        assert stacked[key].shape[:2] == (2, T + 1)
        np.testing.assert_array_equal(stacked[key][0], v[:, 4:6])
        np.testing.assert_array_equal(stacked[key][1], v[:, 6:8])
        assert not np.shares_memory(stacked[key], v)
    np.testing.assert_array_equal(stacked_state[0][0], state[0][:, 4:6])
    np.testing.assert_array_equal(stacked_state[0][1], state[0][:, 6:8])


def test_episode_stat_postprocess_scalar_and_stacked_agree():
    """[K]-stacked stats must aggregate to exactly what K per-update
    flushes would have produced: episode sums/counts SUM, losses MEAN."""
    per_update = [
        {"total_loss": 2.0, "episode_returns_sum": 3.0,
         "episode_count": 2.0},
        {"total_loss": 4.0, "episode_returns_sum": 1.0,
         "episode_count": 0.0},
    ]
    stacked = {
        key: np.asarray([s[key] for s in per_update])
        for key in per_update[0]
    }
    out = learner_lib.episode_stat_postprocess(stacked)
    assert out["total_loss"] == pytest.approx(3.0)
    assert out["episodes_finished"] == pytest.approx(2.0)
    # Sum over the stack / sum of counts — not mean-of-means.
    assert out["mean_episode_return"] == pytest.approx(4.0 / 2.0)
    # Scalar leaves keep their exact legacy behavior.
    legacy = learner_lib.episode_stat_postprocess(
        {"total_loss": 2.0, "episode_returns_sum": 3.0,
         "episode_count": 2.0}
    )
    assert legacy["total_loss"] == 2.0
    assert legacy["mean_episode_return"] == 1.5


def test_instrument_update_step_superstep_accounting():
    """K updates per dispatch must land in the counters as K (no /K
    undercount), with the amortization visible: superstep_k gauge,
    updates_per_dispatch histogram, and a host_syncs counter the driver
    ticks per stats flush."""
    from torchbeast_tpu.telemetry import MetricsRegistry

    reg = MetricsRegistry()
    calls = []

    def fake_update(params, opt_state, batch, state):
        calls.append(1)
        return params, opt_state, {}

    wrapped = learner_lib.instrument_update_step(
        fake_update, registry=reg, superstep_k=4
    )
    batch = {"x": np.zeros((4, 5, 2), np.float32)}
    for _ in range(3):
        wrapped(None, None, batch, ())
        wrapped.count_host_sync()
    assert len(calls) == 3
    assert reg.counter("learner.updates").value() == 12
    assert reg.counter("learner.host_syncs").value() == 3
    stats = reg.histogram("learner.updates_per_dispatch").stats()
    assert stats["count"] == 3 and stats["mean"] == pytest.approx(4.0)
    assert reg.gauge("learner.superstep_k").value() == 4
