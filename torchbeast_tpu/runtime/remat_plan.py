"""Remat auto-tuning under supersteps: choose per-stage remat =
f(HBM headroom, K) instead of the static all-remat default.

The learner's rematerialization levers were, until ISSUE 13, static
booleans chosen for the worst case: the ResNet trunk remats every stage
(the configuration that fits a 15.75 GB v5e at the flagship shape), the
transformer never remats unless --transformer_remat, and the LSTM scan
always saves its gate activations. But remat trades HBM for recompute —
on a run whose (K, T, B, precision) leaves headroom, recomputing is
pure waste, and on one that does not, a single under-remat'd stage
OOMs. This module makes the choice a measured decision:

- Every model family exposes a small per-stage lattice of remat
  settings (stages_for): the ResNet trunk's per-stage False/"front"/
  True (models/resnet.py), the transformer families' block remat, and
  the LSTM scan's step remat (models/cores.LSTMCore.remat) — each
  option list ordered by increasing recompute.
- The planner (plan_remat) picks the MINIMUM-RECOMPUTE assignment
  whose peak HBM fits a budget. Peak comes from XLA itself:
  precision.memory_stats lowers the exact superstep the driver will
  dispatch (same K/T/B/precision) and reads the compiled module's
  temp/argument/output allocation — the `bytes_accessed` machinery
  extended to peak allocation. Recompute is compared through the same
  lowering's pre-opt bytes-accessed figure (rematerialized ops appear
  as real reads in the pre-opt HLO, so more remat == more bytes there).
- Nothing fits -> fall back to all-remat (the save-everything-
  recompute-everything configuration, today's static default) with the
  failure visible in the plan table.

Exposed on both drivers as `--remat {auto,all,none,<spec>}` +
`--hbm_budget_gb`; the chosen plan is logged and exported as the
`learner.remat_plan` telemetry static. `<spec>` pins stages by hand:
a comma list of `stage=setting` with settings {none,front,all}, e.g.
`--remat stage0=front,stage1=all,stage2=all,core=none`.

Budget semantics: the envelope covers ONE live update dispatch
(params + optimizer state + staged [K, T+1, B, ...] stack + XLA temp
buffers). The planner's peak is measured on the ambient backend's
compiled module — on the chipless container that is XLA:CPU, which
widens bf16 to f32 emulation, so the figure is an UPPER bound for the
bf16 policies (the safe direction for a fits-in-budget decision). On a
real TPU the same call reads the true HBM assignment.
"""

import itertools
import logging
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

log = logging.getLogger(__name__)

# Flag-spelling <-> model-kwarg values, ordered nowhere (the ORDER
# lives in the per-stage option tuples below).
SETTING_NAMES = {"none": False, "front": "front", "all": True}
_SETTING_SPELLING = {False: "none", "front": "front", True: "all"}

# Budget the CPU backend plans against when --hbm_budget_gb is 0/unset
# (it reports no memory limit): a v5e's 16 GB minus the runtime reserve.
# Accelerators are never given this number (default_budget_bytes).
DEFAULT_BUDGET_GB = 15.75


class Stage(NamedTuple):
    """One remat lever: `options` ordered by INCREASING recompute
    (index 0 saves everything, the last entry remats the most)."""

    name: str
    options: Tuple[Any, ...]


def stages_for(model: str, use_lstm: bool) -> List[Stage]:
    """The remat lattice of one model family (empty = nothing to plan:
    the feed-forward MLP/AtariNet trunks are not remat-able levers)."""
    stages: List[Stage] = []
    if model == "deep":
        for i in range(3):
            stages.append(Stage(f"stage{i}", (False, "front", True)))
    if _has_blocks_lever(model):
        stages.append(Stage("blocks", (False, True)))
    if use_lstm:
        stages.append(Stage("core", (False, True)))
    return stages


def _has_blocks_lever(model: str) -> bool:
    """Whether the family's class says its `remat` field rematerialises
    each block (`remat_lever = "blocks"`: the transformer families whose
    `make_block` reads it)."""
    from torchbeast_tpu import models

    return models.remat_lever(model) == "blocks"


def model_kwargs(model: str, assignment: Dict[str, Any]) -> Dict[str, Any]:
    """Assignment -> create_model(**kwargs) for the family's levers."""
    kwargs: Dict[str, Any] = {}
    if model == "deep":
        kwargs["remat"] = tuple(
            assignment[f"stage{i}"] for i in range(3)
        )
    if _has_blocks_lever(model):
        kwargs["remat"] = bool(assignment["blocks"])
    if "core" in assignment:
        kwargs["core_remat"] = bool(assignment["core"])
    return kwargs


def _level_assignment(stages: List[Stage], level: int) -> Dict[str, Any]:
    """Every stage at `level` clamped to its own option count."""
    return {
        s.name: s.options[min(level, len(s.options) - 1)] for s in stages
    }


def all_remat(stages: List[Stage]) -> Dict[str, Any]:
    """The save-everything fallback (today's static default)."""
    return _level_assignment(stages, max(
        (len(s.options) for s in stages), default=1
    ))


def no_remat(stages: List[Stage]) -> Dict[str, Any]:
    return _level_assignment(stages, 0)


def enumerate_assignments(stages: List[Stage]) -> List[Dict[str, Any]]:
    """Every per-stage combination, ordered by ascending recompute RANK
    (sum of per-stage option indices, ties broken by the index tuple) —
    minimum recompute first, all-remat last. The rank is the lazy
    walk's evaluation order; the exhaustive planner re-orders by the
    cost model's measured recompute."""
    if not stages:
        return [{}]
    level_sets = [range(len(s.options)) for s in stages]
    combos = sorted(
        itertools.product(*level_sets),
        key=lambda levels: (sum(levels), levels),
    )
    return [
        {s.name: s.options[lv] for s, lv in zip(stages, levels)}
        for levels in combos
    ]


def spell(assignment: Dict[str, Any]) -> str:
    return ",".join(
        f"{name}={_SETTING_SPELLING[val]}"
        for name, val in sorted(assignment.items())
    )


def parse_spec(spec: str, stages: List[Stage]) -> Dict[str, Any]:
    """`stage0=front,core=all` -> assignment, validated against the
    family's stages and each stage's own option set."""
    by_name = {s.name: s for s in stages}
    assignment: Dict[str, Any] = {}
    for part in spec.split(","):
        if "=" not in part:
            raise ValueError(
                f"--remat spec entry {part!r} is not stage=setting "
                f"(stages for this model: {sorted(by_name) or 'none'})"
            )
        name, _, setting = part.partition("=")
        name, setting = name.strip(), setting.strip()
        if name not in by_name:
            raise ValueError(
                f"--remat spec names unknown stage {name!r} "
                f"(stages for this model: {sorted(by_name) or 'none'})"
            )
        if setting not in SETTING_NAMES:
            raise ValueError(
                f"--remat spec setting {setting!r} for stage {name!r} "
                f"must be one of {sorted(SETTING_NAMES)}"
            )
        value = SETTING_NAMES[setting]
        if value not in by_name[name].options:
            raise ValueError(
                f"--remat stage {name!r} has no {setting!r} option "
                f"(choices: "
                f"{[_SETTING_SPELLING[o] for o in by_name[name].options]})"
            )
        if name in assignment:
            raise ValueError(f"--remat spec repeats stage {name!r}")
        assignment[name] = value
    missing = set(by_name) - set(assignment)
    if missing:
        raise ValueError(
            f"--remat spec misses stages {sorted(missing)} "
            "(every stage needs a setting)"
        )
    return assignment


class PlanResult(NamedTuple):
    """One planning outcome. `assignment` is the chosen per-stage
    setting; `source` records how it was chosen ("auto", "all",
    "none", "spec", "default", or "fallback" when no candidate fit the
    budget); `table` carries every evaluated candidate (assignment
    spelling, peak, recompute, fits) for the telemetry static."""

    assignment: Dict[str, Any]
    source: str
    budget_bytes: Optional[float]
    peak_bytes: Optional[float]
    recompute_bytes: Optional[float]
    table: Tuple[Dict[str, Any], ...]

    def summary(self, include_table: bool = False) -> Dict[str, Any]:
        """JSON-able form for the `learner.remat_plan` static + logs.
        The per-candidate table is opt-in: the static re-serializes
        into EVERY telemetry.jsonl line, and up to 64 identical table
        rows per 5-second snapshot is pure bloat — the table is logged
        once at resolution instead."""
        out = {
            "assignment": {
                k: _SETTING_SPELLING[v]
                for k, v in sorted(self.assignment.items())
            },
            "source": self.source,
            "budget_bytes": self.budget_bytes,
            "peak_bytes": self.peak_bytes,
            "recompute_bytes": self.recompute_bytes,
            "evaluated": len(self.table),
        }
        if include_table:
            out["table"] = list(self.table)
        return out


def plan_remat(
    stages: List[Stage],
    cost_fn: Callable[[Dict[str, Any]], Tuple[Optional[float],
                                              Optional[float]]],
    budget_bytes: float,
    lazy: bool = False,
    max_evals: int = 64,
) -> PlanResult:
    """Pick the minimum-recompute assignment whose peak fits the budget.

    `cost_fn(assignment) -> (peak_bytes, recompute_bytes)`; a None peak
    means the oracle could not measure that candidate (it is skipped —
    never chosen on faith). `lazy=True` walks candidates in ascending
    recompute-RANK order and stops at the first fit (the driver path,
    where each evaluation lowers+compiles the real superstep);
    `lazy=False` evaluates everything and picks the true measured
    minimum (tests and the bench). Nothing fits -> all-remat fallback,
    the one case whose peak may exceed the budget (it is also today's
    static default, so the fallback never regresses the pre-planner
    behavior)."""
    candidates = enumerate_assignments(stages)[:max_evals]
    table: List[Dict[str, Any]] = []
    fitting: List[Tuple[float, int, Dict[str, Any], float]] = []
    for idx, assignment in enumerate(candidates):
        peak, recompute = cost_fn(assignment)
        fits = peak is not None and peak <= budget_bytes
        table.append({
            "assignment": spell(assignment),
            "peak_bytes": peak,
            "recompute_bytes": recompute,
            "fits": bool(fits),
        })
        if fits:
            rec = recompute if recompute is not None else float("inf")
            fitting.append((rec, idx, assignment, peak))
            if lazy:
                break
    if fitting:
        rec, _, assignment, peak = min(fitting, key=lambda t: t[:2])
        return PlanResult(
            assignment=assignment,
            source="auto",
            budget_bytes=float(budget_bytes),
            peak_bytes=peak,
            recompute_bytes=None if rec == float("inf") else rec,
            table=tuple(table),
        )
    fallback = all_remat(stages)
    peak = recompute = None
    for row in table:
        if row["assignment"] == spell(fallback):
            peak, recompute = row["peak_bytes"], row["recompute_bytes"]
            break
    return PlanResult(
        assignment=fallback,
        source="fallback",
        budget_bytes=float(budget_bytes),
        peak_bytes=peak,
        recompute_bytes=recompute,
        table=tuple(table),
    )


def default_budget_bytes() -> float:
    """--hbm_budget_gb unset: the device's own `bytes_limit`. An
    accelerator that reports none raises — a plan fitted to an assumed
    envelope would be a guess about a chip nobody named. The CPU
    backend has no device memory to report; it keeps the v5e-sized
    default so the CPU tests plan against a stable number."""
    import jax

    device = jax.devices()[0]
    limit = (device.memory_stats() or {}).get("bytes_limit")
    if limit:
        return float(limit)
    if device.platform != "cpu":
        raise RuntimeError(
            f"{device.device_kind} reports no memory bytes_limit; pass "
            "--hbm_budget_gb to plan rematerialization on it"
        )
    return DEFAULT_BUDGET_GB * (1 << 30)


def superstep_cost_fn(
    build_model: Callable[[Dict[str, Any]], Any],
    hp,
    superstep_k: int,
    batch_structs: Dict[str, Any],
    state_batch_size: int,
    model_name: str,
) -> Callable[[Dict[str, Any]], Tuple[Optional[float], Optional[float]]]:
    """The driver's cost oracle: build the candidate model, eval_shape
    its params/opt-state (no compute, no buffers), and read
    precision.memory_stats off the EXACT jitted (super)step the run
    will dispatch. All inputs are ShapeDtypeStructs — a candidate
    evaluation allocates nothing but the compile itself."""
    import jax

    from torchbeast_tpu import learner as learner_lib
    from torchbeast_tpu import precision as precision_lib

    rngs = {
        "params": jax.random.PRNGKey(0),
        "action": jax.random.PRNGKey(1),
    }
    # A [1, B] dummy in the env-output schema (model init never sees
    # the learner's T; dtypes ride along from the staged batch — the
    # models astype at use either way).
    dummy = {
        key: jax.ShapeDtypeStruct(
            (1, state_batch_size) + tuple(s.shape[2:]), s.dtype
        )
        for key, s in batch_structs.items()
        if key in ("frame", "reward", "done", "last_action")
    }

    def cost_fn(assignment):
        try:
            model = build_model(model_kwargs(model_name, assignment))
            state = jax.eval_shape(
                lambda: model.initial_state(state_batch_size)
            )
            params = jax.eval_shape(
                lambda d, s: model.init(rngs, d, s), dummy, state
            )
            optimizer = learner_lib.make_optimizer(hp)
            opt_state = jax.eval_shape(optimizer.init, params)
            if superstep_k > 1:
                update = learner_lib.make_update_superstep(
                    model, optimizer, hp, superstep_k, donate=False
                )
                stack = lambda s: jax.ShapeDtypeStruct(  # noqa: E731
                    (superstep_k,) + tuple(s.shape), s.dtype
                )
                batch = {
                    k: stack(s) for k, s in batch_structs.items()
                }
                states = jax.tree_util.tree_map(stack, state)
            else:
                update = learner_lib.make_update_step(
                    model, optimizer, hp, donate=False
                )
                batch = dict(batch_structs)
                states = state
            stats = precision_lib.memory_stats(
                update, params, opt_state, batch, states
            )
            return stats.peak_bytes, stats.bytes_accessed
        except Exception:
            log.debug(
                "remat cost evaluation failed for %s",
                spell(assignment), exc_info=True,
            )
            return None, None

    return cost_fn


def learner_batch_structs(
    hp, num_actions: int, frame_shape, frame_dtype, batch_dtype=None
):
    """ShapeDtypeStructs of one [T+1, B] learner batch in the actor-pool
    schema, float leaves in the precision policy's staging dtype."""
    import jax
    import numpy as np

    t1 = hp.unroll_length + 1
    b = hp.batch_size
    f32 = np.dtype(batch_dtype) if batch_dtype is not None else (
        np.dtype(np.float32)
    )
    return {
        "frame": jax.ShapeDtypeStruct(
            (t1, b) + tuple(frame_shape), np.dtype(frame_dtype)
        ),
        "reward": jax.ShapeDtypeStruct((t1, b), f32),
        "done": jax.ShapeDtypeStruct((t1, b), np.dtype(bool)),
        "episode_return": jax.ShapeDtypeStruct((t1, b), f32),
        "episode_step": jax.ShapeDtypeStruct(
            (t1, b), np.dtype(np.int32)
        ),
        "last_action": jax.ShapeDtypeStruct(
            (t1, b), np.dtype(np.int32)
        ),
        "action": jax.ShapeDtypeStruct((t1, b), np.dtype(np.int32)),
        "policy_logits": jax.ShapeDtypeStruct(
            (t1, b, num_actions), f32
        ),
        "baseline": jax.ShapeDtypeStruct((t1, b), f32),
    }


# Memoized driver-resolution results: polybeast builds the model twice
# (learner + unmeshed acting twin) from identical flags, and an auto
# plan compiles candidates — the second resolution must be free. Also
# the hook DriverTelemetry reads for the `learner.remat_plan` static.
_RESOLVED: Dict[Tuple, PlanResult] = {}
_LAST: List[Optional[PlanResult]] = [None]


def last_plan() -> Optional[PlanResult]:
    """The most recent resolution in this process (driver startup is
    single-threaded; the drivers read this right after model init to
    log + export the `learner.remat_plan` static)."""
    return _LAST[0]


def resolve_from_flags(
    flags, hp, num_actions: int, frame_shape, frame_dtype,
    policy, build_model: Callable[[Dict[str, Any]], Any],
) -> PlanResult:
    """Driver entry: flags.remat -> the plan + model kwargs.

    - None (flag unset): the pre-ISSUE-13 static defaults — ResNet
      all-remat, transformer blocks per --transformer_remat, LSTM scan
      un-remat'd (source="default"; no planning cost).
    - "all" / "none": every stage at its max-save / no-remat setting.
    - "auto": plan_remat over the family lattice with the superstep
      cost oracle against --hbm_budget_gb (0 = the device limit, else
      the v5e default envelope). Lazy first-fit walk in recompute-rank
      order: big budgets evaluate ONE candidate.
    - anything else: a per-stage spec (parse_spec).
    """
    model_name = flags.model
    use_lstm = bool(getattr(flags, "use_lstm", False))
    stages = stages_for(model_name, use_lstm)
    remat_flag = getattr(flags, "remat", None)
    transformer_remat = bool(getattr(flags, "transformer_remat", False))
    if remat_flag is not None and transformer_remat:
        raise ValueError(
            "--transformer_remat is the deprecated spelling of "
            "--remat all (blocks stage); pass only --remat"
        )
    budget_gb = float(getattr(flags, "hbm_budget_gb", 0.0) or 0.0)
    superstep_k = int(getattr(flags, "superstep_k", 1) or 1)
    # hp rides the key WHOLE (a hashable NamedTuple): optimizer-shape
    # knobs (momentum adds a params-sized trace, factored/bf16 state
    # change opt_state bytes) move the measured peak, so an auto plan
    # is only reusable for an identical learner configuration.
    key = (
        remat_flag, transformer_remat, budget_gb, model_name, use_lstm,
        policy.name, superstep_k, hp,
        num_actions, tuple(frame_shape), str(frame_dtype),
    )
    cached = _RESOLVED.get(key)
    if cached is not None:
        _LAST[0] = cached
        return cached

    if remat_flag is None:
        assignment = all_remat(stages)
        if "blocks" in assignment:
            assignment["blocks"] = transformer_remat
        if "core" in assignment:
            assignment["core"] = False
        plan = PlanResult(
            assignment=assignment, source="default",
            budget_bytes=None, peak_bytes=None, recompute_bytes=None,
            table=(),
        )
    elif remat_flag == "all":
        plan = PlanResult(
            assignment=all_remat(stages), source="all",
            budget_bytes=None, peak_bytes=None, recompute_bytes=None,
            table=(),
        )
    elif remat_flag == "none":
        plan = PlanResult(
            assignment=no_remat(stages), source="none",
            budget_bytes=None, peak_bytes=None, recompute_bytes=None,
            table=(),
        )
    elif remat_flag == "auto":
        budget = (
            budget_gb * (1 << 30) if budget_gb > 0
            else default_budget_bytes()
        )
        cost_fn = superstep_cost_fn(
            build_model, hp, superstep_k,
            learner_batch_structs(
                hp, num_actions, frame_shape, frame_dtype,
                policy.batch_dtype,
            ),
            hp.batch_size, model_name,
        )
        plan = plan_remat(stages, cost_fn, budget, lazy=True)
    else:
        plan = PlanResult(
            assignment=parse_spec(remat_flag, stages), source="spec",
            budget_bytes=None, peak_bytes=None, recompute_bytes=None,
            table=(),
        )
    _RESOLVED[key] = plan
    _LAST[0] = plan
    if plan.source == "fallback":
        log.warning(
            "remat auto-tuning: no candidate fits the %.2f GB budget; "
            "falling back to all-remat (%s)",
            (plan.budget_bytes or 0) / (1 << 30),
            spell(plan.assignment),
        )
    elif remat_flag is not None:
        log.info(
            "remat plan (%s): %s", plan.source,
            spell(plan.assignment) or "<no remat-able stages>",
        )
    if plan.table:
        # The evaluation table is logged ONCE here; the telemetry
        # static carries only the compact summary (see summary()).
        log.info("remat plan candidates: %s", list(plan.table))
    return plan
