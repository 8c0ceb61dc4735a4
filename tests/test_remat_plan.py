"""Remat planner (runtime/remat_plan.py): lattice mechanics, the
synthetic-headroom planning contract, driver flag resolution, and the
model-level remat levers.

The planning contract pinned here (the ISSUE 13 acceptance): over a
synthetic headroom matrix the chosen plan (a) NEVER exceeds the budget
whenever any candidate fits, (b) has the minimum recompute among
fitting candidates — strictly fewer recompute bytes than all-remat
whenever the budget allows anything less, and (c) falls back to
all-remat (today's static default) when nothing fits.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tests import family_scaffold as scaffold
from torchbeast_tpu.runtime import remat_plan as rp

# ---------------------------------------------------------------------------
# Lattice mechanics


def test_stages_for_families():
    deep = rp.stages_for("deep", use_lstm=True)
    assert [s.name for s in deep] == [
        "stage0", "stage1", "stage2", "core",
    ]
    assert deep[0].options == (False, "front", True)
    assert deep[-1].options == (False, True)
    assert [s.name for s in rp.stages_for("transformer", False)] == [
        "blocks"
    ]
    assert rp.stages_for("mlp", use_lstm=False) == []
    assert [s.name for s in rp.stages_for("mlp", True)] == ["core"]


def test_model_kwargs_mapping():
    assert rp.model_kwargs("deep", {
        "stage0": "front", "stage1": True, "stage2": False,
        "core": True,
    }) == {"remat": ("front", True, False), "core_remat": True}
    assert rp.model_kwargs("transformer", {"blocks": True}) == {
        "remat": True
    }
    assert rp.model_kwargs("mlp", {"core": False}) == {
        "core_remat": False
    }
    assert rp.model_kwargs("mlp", {}) == {}


def test_enumerate_order_min_recompute_first():
    stages = rp.stages_for("deep", use_lstm=False)
    cands = rp.enumerate_assignments(stages)
    assert len(cands) == 27
    assert cands[0] == rp.no_remat(stages)
    assert cands[-1] == rp.all_remat(stages)
    # Rank (sum of option indices) is non-decreasing along the order.
    def rank(a):
        return sum(
            s.options.index(a[s.name]) for s in stages
        )
    ranks = [rank(c) for c in cands]
    assert ranks == sorted(ranks)


def test_parse_spec_round_trip_and_errors():
    stages = rp.stages_for("deep", use_lstm=True)
    spec = "stage0=front,stage1=all,stage2=none,core=all"
    parsed = rp.parse_spec(spec, stages)
    assert parsed == {
        "stage0": "front", "stage1": True, "stage2": False,
        "core": True,
    }
    assert rp.parse_spec(rp.spell(parsed), stages) == parsed
    with pytest.raises(ValueError, match="unknown stage"):
        rp.parse_spec("bogus=all", stages)
    with pytest.raises(ValueError, match="must be one of"):
        rp.parse_spec("stage0=sometimes", stages)
    with pytest.raises(ValueError, match="misses stages"):
        rp.parse_spec("stage0=all", stages)
    with pytest.raises(ValueError, match="repeats"):
        rp.parse_spec(
            "stage0=all,stage0=none,stage1=all,stage2=all,core=all",
            stages,
        )
    with pytest.raises(ValueError, match="no 'front' option"):
        rp.parse_spec(
            "stage0=all,stage1=all,stage2=all,core=front", stages
        )


# ---------------------------------------------------------------------------
# The synthetic-headroom planning contract


def _synthetic_cost(stages):
    """Deterministic headroom matrix: each remat level frees 10 units
    of peak and costs 7 units of recompute; the no-remat peak is 100."""
    def cost(assignment):
        level = sum(
            s.options.index(assignment[s.name]) for s in stages
        )
        return float(100 - 10 * level), float(7 * level)
    return cost


@pytest.mark.parametrize("budget", [25.0, 45.0, 65.0, 85.0, 100.0, 500.0])
def test_never_exceeds_budget_and_min_recompute(budget):
    stages = rp.stages_for("deep", use_lstm=True)  # 54 candidates
    cost = _synthetic_cost(stages)
    plan = rp.plan_remat(stages, cost, budget)
    peak, recompute = cost(plan.assignment)
    all_peak, all_recompute = cost(rp.all_remat(stages))
    fits_exist = any(
        cost(a)[0] <= budget
        for a in rp.enumerate_assignments(stages)
    )
    if fits_exist:
        assert plan.source == "auto"
        # (a) never exceeds the budget
        assert peak <= budget
        assert plan.peak_bytes == peak
        # (b) true minimum recompute among fitting candidates
        best = min(
            cost(a)[1]
            for a in rp.enumerate_assignments(stages)
            if cost(a)[0] <= budget
        )
        assert recompute == best
        # The ISSUE gate: strictly fewer recompute bytes than
        # all-remat whenever the budget allows anything less.
        if budget > all_peak:
            assert recompute < all_recompute
    else:
        # (c) all-remat fallback
        assert plan.source == "fallback"
        assert plan.assignment == rp.all_remat(stages)


def test_fallback_when_nothing_fits():
    stages = rp.stages_for("mlp", use_lstm=True)
    plan = rp.plan_remat(stages, _synthetic_cost(stages), 1.0)
    assert plan.source == "fallback"
    assert plan.assignment == rp.all_remat(stages)
    # The fallback's own figures surface in the result (it was
    # evaluated as a candidate even though it does not fit).
    assert plan.peak_bytes is not None


def test_unmeasurable_candidates_never_chosen():
    stages = rp.stages_for("mlp", use_lstm=True)

    def cost(assignment):
        if not assignment["core"]:
            return None, None  # oracle failure for the tempting plan
        return 10.0, 7.0

    plan = rp.plan_remat(stages, cost, 1000.0)
    assert plan.assignment == {"core": True}
    table = {r["assignment"]: r for r in plan.table}
    assert table["core=none"]["fits"] is False


def test_lazy_walk_stops_at_first_fit():
    stages = rp.stages_for("deep", use_lstm=False)
    calls = []
    cost = _synthetic_cost(stages)

    def counting(assignment):
        calls.append(dict(assignment))
        return cost(assignment)

    plan = rp.plan_remat(stages, counting, 500.0, lazy=True)
    assert len(calls) == 1  # huge budget: the first candidate fits
    assert plan.assignment == rp.no_remat(stages)


# ---------------------------------------------------------------------------
# Real-model lever sanity + driver flag resolution


def test_lstm_core_remat_is_numerically_transparent():
    from torchbeast_tpu.models import create_model

    rng = np.random.default_rng(0)
    t, b, a = 5, 3, 4
    batch = {
        "frame": rng.integers(0, 256, (t, b, 4, 4, 1), dtype=np.uint8),
        "reward": rng.standard_normal((t, b)).astype(np.float32),
        "done": rng.random((t, b)) < 0.2,
        "last_action": rng.integers(0, a, (t, b)).astype(np.int32),
    }
    outs = {}
    for remat in (False, True):
        model = create_model(
            "mlp", num_actions=a, use_lstm=True, core_remat=remat
        )
        state = model.initial_state(b)
        params = scaffold.init(
            model,
            {
                "params": jax.random.PRNGKey(0),
                "action": jax.random.PRNGKey(1),
            },
            batch,
            state,
        )

        def loss(p):
            (out, _), _ = model.apply(
                p, batch, state, sample_action=False,
                mutable=["losses"],
            )
            return (
                jnp.sum(out.policy_logits ** 2) + jnp.sum(out.baseline)
            )

        # beastlint: disable=JIT-HAZARD  per-config closure compared once each; one-shot compile by design
        value, grads = jax.jit(jax.value_and_grad(loss))(params)
        outs[remat] = (value, grads)
    # Same params tree either way (nn.remat must not rescope), same
    # forward, same grads to reassociation tolerance.
    assert (
        jax.tree_util.tree_structure(outs[False][1])
        == jax.tree_util.tree_structure(outs[True][1])
    )
    np.testing.assert_allclose(
        float(outs[False][0]), float(outs[True][0]), rtol=1e-6
    )
    for g0, g1 in zip(
        jax.tree_util.tree_leaves(outs[False][1]),
        jax.tree_util.tree_leaves(outs[True][1]),
    ):
        np.testing.assert_allclose(
            np.asarray(g0), np.asarray(g1), rtol=1e-4, atol=1e-6
        )


def _flags(args):
    from torchbeast_tpu import monobeast

    return monobeast.make_parser().parse_args(args)


def _resolve(flags):
    from torchbeast_tpu import monobeast
    from torchbeast_tpu import precision as precision_lib
    from torchbeast_tpu.models import create_model

    policy = precision_lib.resolve_flags(flags)
    hp = monobeast.hparams_from_flags(flags)
    return rp.resolve_from_flags(
        flags, hp, 4, (4, 4, 1), np.uint8, policy,
        build_model=lambda kw: create_model(
            flags.model, num_actions=4, use_lstm=flags.use_lstm,
            dtype=policy.compute_dtype, **kw,
        ),
    )


def test_resolve_default_matches_pre_planner_behavior():
    plan = _resolve(_flags(["--model", "deep", "--use_lstm"]))
    assert plan.source == "default"
    assert plan.assignment == {
        "stage0": True, "stage1": True, "stage2": True, "core": False,
    }
    # --transformer_remat keeps working as the deprecated spelling.
    plan = _resolve(_flags(["--model", "transformer"]))
    assert plan.assignment == {"blocks": False}
    plan = _resolve(
        _flags(["--model", "transformer", "--transformer_remat"])
    )
    assert plan.assignment == {"blocks": True}


def test_resolve_all_none_spec_and_conflict():
    plan = _resolve(_flags(["--model", "deep", "--remat", "none"]))
    assert plan.source == "none"
    assert plan.assignment == {
        "stage0": False, "stage1": False, "stage2": False,
    }
    plan = _resolve(_flags(["--model", "deep", "--remat", "all"]))
    assert plan.assignment == {
        "stage0": True, "stage1": True, "stage2": True,
    }
    plan = _resolve(_flags([
        "--model", "deep", "--remat",
        "stage0=front,stage1=all,stage2=none",
    ]))
    assert plan.source == "spec"
    assert plan.assignment == {
        "stage0": "front", "stage1": True, "stage2": False,
    }
    with pytest.raises(ValueError, match="deprecated"):
        _resolve(_flags([
            "--model", "transformer", "--transformer_remat",
            "--remat", "all",
        ]))


def test_resolve_auto_runs_planner_and_caches():
    """`--remat auto` on the tiny LSTM picks the no-recompute plan
    under the huge default budget, exports a non-empty table, and the
    second resolution (polybeast's acting-twin rebuild) is served from
    the cache."""
    flags = _flags([
        "--model", "mlp", "--use_lstm", "--remat", "auto",
        "--unroll_length", "4", "--batch_size", "2",
        "--num_actors", "2",
    ])
    plan = _resolve(flags)
    assert plan.source == "auto"
    assert plan.assignment == {"core": False}
    assert plan.peak_bytes is not None and plan.peak_bytes > 0
    assert plan.table
    assert rp.last_plan() is plan
    assert _resolve(flags) is plan  # memoized


def test_driver_model_init_applies_plan():
    """_init_model_and_params threads the resolved plan into the
    constructed model for both a spec and the legacy default."""
    from torchbeast_tpu import monobeast

    flags = _flags([
        "--model", "mlp", "--use_lstm", "--remat", "core=all",
        "--unroll_length", "4", "--batch_size", "2",
        "--num_actors", "2",
    ])
    model, params = monobeast._init_model_and_params(
        flags, 4, 2, (4, 4, 1)
    )
    assert model.core_remat is True
    assert params is not None
    flags = _flags(["--model", "mlp", "--use_lstm"])
    model, _ = monobeast._init_model_and_params(
        flags, 4, 2, (4, 4, 1), init_params=False
    )
    assert model.core_remat is False


def test_superstep_cost_oracle_reports_peak_and_recompute():
    """The driver's cost oracle measures the real (super)step: peak and
    recompute both populated, and the all-remat LSTM plan reads MORE
    pre-opt bytes (the recompute is visible) while saving temp
    allocation."""
    from torchbeast_tpu import learner as learner_lib
    from torchbeast_tpu.models import create_model

    hp = learner_lib.HParams(unroll_length=8, batch_size=4)
    stages = rp.stages_for("mlp", use_lstm=True)
    cost_fn = rp.superstep_cost_fn(
        lambda kw: create_model(
            "mlp", num_actions=4, use_lstm=True, **kw
        ),
        hp, 2,
        rp.learner_batch_structs(hp, 4, (4, 4, 1), np.uint8),
        hp.batch_size, "mlp",
    )
    peak_none, rec_none = cost_fn({"core": False})
    peak_all, rec_all = cost_fn({"core": True})
    assert all(
        v is not None for v in (peak_none, rec_none, peak_all, rec_all)
    )
    assert rec_all > rec_none  # recompute shows up in pre-opt bytes
