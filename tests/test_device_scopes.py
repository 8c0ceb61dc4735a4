"""The by-scope account of a program's device time (torchbeast_tpu/
telemetry/device_scopes.py): its arithmetic on a recorded trace, the
helper that names a scope, and the two ways in (the script's function
on a toy update, a driver's `--profile_dir`).

`tests/data/device_scopes_trace.json` and `device_scopes_program.txt`
are cut out of a real trace and compiled text (Qwen3-Next's update on
a v5e, seed 7, PR 51's first chip call): real instruction names and
lengths packed end to end, two steps of `jit_update_step` that hold a
swept backward `while` (its own path without a scope), a `while` of
`delta_inter` with a copy the compiler made inside, forward /
backward / rematerialised ops, `copy-done`s without a path, and two
steps of a `jit_step` the text does not cover.
"""

import copy
import hashlib
import json
import os
import re

import jax
import jax.numpy as jnp
import optax
import pytest

from tests import family_scaffold as scaffold
from tests.test_learner import make_batch
from tests.test_monobeast import make_flags
from torchbeast_tpu import learner as learner_lib
from torchbeast_tpu import learner_setup, monobeast, telemetry
from torchbeast_tpu.models import create_model
from torchbeast_tpu.telemetry import device_scopes

DATA = os.path.join(os.path.dirname(__file__), "data")
KNOWN = frozenset({
    "obs_embed", "deltanet_in_proj", "deltanet_conv", "delta_scan",
    "delta_intra", "delta_solve", "delta_inter", "moe_dispatch",
    "moe_experts", "moe_combine", "optimizer",
})


@pytest.fixture(scope="module")
def trace():
    with open(os.path.join(DATA, "device_scopes_trace.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def program():
    with open(os.path.join(DATA, "device_scopes_program.txt")) as f:
        return device_scopes.read_program_text(f.read())


@pytest.fixture(scope="module")
def report(trace, program):
    return device_scopes.account(
        trace, [program], KNOWN, counters={"moe_window_rows": 20480.0}
    )


@pytest.fixture(scope="module")
def update(report):
    return next(
        p for p in report["programs"] if p["program"] == "jit_update_step"
    )


# --- a path's scope, phase and kind ----------------------------------------------


@pytest.mark.parametrize("path, scopes", [
    ("jit(update_step)/optimizer/reduce_sum", ["optimizer"]),
    ("jit(update_step)/jvp(vtrace)/mul", []),
    ("jit(f)/jvp(N)/N/block_0/delta_scan/delta_intra/delta_solve/dot",
     ["delta_scan", "delta_intra", "delta_solve"]),
    ("jit(f)/transpose(jvp(N))/N/moe/while/body/"
     "transpose(jvp(moe_experts))/jit(gmm)/gmm_cut_in_vmem/pallas_call",
     ["moe_experts"]),
    ("jit(f)/transpose(jvp(N))/N/final_norm/add_any", []),
    ("", []),
])
def test_scopes_are_read_through_their_wrappers(path, scopes):
    assert device_scopes.scopes_of(path, KNOWN) == scopes


@pytest.mark.parametrize("path, phase", [
    ("jit(f)/jvp(N)/N/block_0/deltanet_conv/mul", "forward"),
    ("jit(f)/optimizer/reduce_sum", "forward"),
    ("", "forward"),
    ("jit(f)/transpose(jvp(N))/N/jvp(N)/N/checkpoint/block_4/"
     "deltanet_conv/reduce_sum", "backward"),
    ("jit(f)/transpose(jvp(N))/N/jvp(N)/N/checkpoint/"
     "rematted_computation/block_4/deltanet_conv/mul", "rematerialised"),
])
def test_phase_is_what_the_path_says(path, phase):
    assert device_scopes.phase_of(path) == phase


@pytest.mark.parametrize("instruction, path, kind", [
    ("fusion.310", "jit(f)/jvp(N)/mlp/dot_general", "fusion"),
    ("broadcast.429.clone", "", "broadcast"),
    ("multiply_add_fusion", "", "multiply_add_fusion"),
    ("custom-call.12", "jit(f)/moe_experts/jit(gmm)/gmm_cut_in_vmem",
     "gmm_cut_in_vmem"),
    ("custom-call.11", "", "custom-call"),
])
def test_an_op_kind_is_the_name_less_its_numbering(instruction, path, kind):
    assert device_scopes.op_kind(instruction, path) == kind


# --- the compiled text ------------------------------------------------------------------


def test_the_text_gives_each_instruction_its_path(program):
    assert program.module == "jit_update_step"
    assert program.op_names["multiply_reduce_fusion.20"] == (
        "jit(update_step)/optimizer/reduce_sum"
    )
    assert "copy-done.138" not in program.op_names


def test_an_op_the_compiler_made_is_read_by_the_value_it_moves(program):
    """By its operand (a prefetch of the solve's product), and where no
    operand has a path by its user (a relayout before the einsum)."""
    assert program.moves["copy-done.138"].endswith(
        "delta_intra/delta_solve/dot_general"
    )
    assert program.moves["copy.5589"].endswith(
        "delta_inter/while/body/closed_call/bhpde,bhpev->bhpdv/dot_general"
    )
    # A parameter's op_name is its argument's name: no path to take.
    text = (
        'HloModule jit_f\n'
        '%p = f32[8] parameter(0), metadata={op_name="params[\'w\']"}\n'
        '%copy.1 = f32[8] copy(%p)\n'
        '%add.2 = f32[8] add(%copy.1, %copy.1), '
        'metadata={op_name="jit(f)/mlp/add"}\n'
    )
    assert device_scopes.read_program_text(text).moves == {
        "copy.1": "jit(f)/mlp/add"
    }


# --- the account of the recorded trace --------------------------------------------


def test_two_programs_give_two_accounts(report):
    assert [p["program"] for p in report["programs"]] == [
        "jit_update_step", "jit_step",
    ]
    assert report["counters"] == {"moe_window_rows": 20480.0}


def test_totals_of_a_program(update):
    assert update["steps"] == 2
    assert update["module_ms"] == pytest.approx(10.499853)
    # Start to start: the step, the act step after it and two gaps.
    assert update["period_ms"] == pytest.approx(10.678329)
    assert update["sum_self_ms"] == pytest.approx(10.492748)
    assert update["residual_pct"] == pytest.approx(0.06767, abs=1e-4)
    assert sum(
        row["ms"] for row in update["scopes"].values()
    ) == pytest.approx(update["sum_self_ms"])


def test_a_while_is_charged_its_own_time_not_its_bodys(update):
    """`while.448` lasts 2.23 ms and holds 2.23 ms of ops: its row is
    the 500 ns that are its own."""
    loose = update["unscoped_under"]["none"]["ops"]
    assert loose["while"] == {"ms": pytest.approx(0.0005), "calls": 1.0}
    assert update["scopes"]["moe_combine"]["ms"] == pytest.approx(1.404862)


def test_rows_by_phase(update):
    conv = update["scopes"]["deltanet_conv"]
    assert conv["forward_ms"] == pytest.approx(0.936880)
    assert conv["backward_ms"] == pytest.approx(1.156447)
    assert conv["rematerialised_ms"] == pytest.approx(1.170654)
    assert conv["ms"] == pytest.approx(3.263981)
    assert conv["share_pct"] == pytest.approx(31.086, abs=1e-3)
    assert update["scopes"]["optimizer"]["forward_ms"] == pytest.approx(
        0.179387
    )


def test_a_custom_call_is_listed_by_its_kernel_with_calls_a_step(update):
    ops = update["scopes"]["moe_experts"]["ops"]
    assert ops["gmm_cut_in_vmem"] == {
        "ms": pytest.approx(0.371472), "calls": 1.0,
    }
    assert ops["tgmm_cut_in_vmem"]["ms"] == pytest.approx(0.333483)
    assert update["scopes"]["moe_experts"]["backward_ms"] == pytest.approx(
        0.718732
    )


def test_unscoped_is_listed_under_what_holds_or_makes_it(update):
    under = update["unscoped_under"]
    # No path, inside `delta_inter`'s loop in time.
    assert under["delta_inter"]["ops"]["copy"]["ms"] == pytest.approx(
        0.044878
    )
    # No path and no loop: by the value it moves.
    assert under["delta_solve"]["ms"] == pytest.approx(0.102700)
    # The swept loop's own path has no scope: its prefetch by its user.
    assert under["moe_experts"]["ms"] == pytest.approx(0.097082)
    # A path without a scope stays loose, and is named.
    assert under["none"]["ms"] == pytest.approx(0.058350)
    assert update["unscoped_paths"][0][0].endswith("final_norm/add_any")
    assert update["scopes"]["unscoped"]["ms"] == pytest.approx(
        sum(row["ms"] for row in under.values())
    )


def test_a_row_with_the_scopes_inside_it(update):
    assert update["scopes"]["delta_scan"]["ms"] == 0.0
    assert update["scopes"]["delta_scan"]["inclusive_ms"] == pytest.approx(
        update["scopes"]["delta_inter"]["ms"]
    )


def test_a_program_without_its_text_is_all_unscoped(report):
    act = report["programs"][1]
    assert act["steps"] == 2
    assert list(act["scopes"]) == ["unscoped"]
    assert act["scopes"]["unscoped"]["ops"]["fusion"]["calls"] == 1.0


def _overlapping(trace):
    """The same trace with a second stream's op laid over the first
    step's first op."""
    planted = copy.deepcopy(trace)
    ops = planted["planes"][0]["lines"][1]["events"]
    first = ops[0]
    ops.append(["fusion.9999", first[1] + 1000.0, first[2] - 2000.0])
    return planted


def test_a_planted_overlap_raises(trace, program):
    with pytest.raises(device_scopes.AccountError, match="residual"):
        device_scopes.account(_overlapping(trace), [program], KNOWN)


def test_a_runs_shutdown_goes_on_past_a_program_that_does_not_add_up(
    trace, program
):
    report = device_scopes.account(
        _overlapping(trace), [program], KNOWN, strict=False
    )
    update, act = report["programs"]
    assert "residual" in update["error"] and "scopes" not in update
    assert act["scopes"]["unscoped"]["ms"] == pytest.approx(0.17)
    assert "residual" in device_scopes.render(report)


def test_a_program_in_flight_when_the_trace_began_is_left_out(
    trace, program
):
    """A trace that starts inside a step (a driver's `--profile_dir`,
    the benchmark's traced window) cuts that step's module event to
    what it saw of it: on a v5e one event of 342.05 ms among 23 of
    383.9 (PR 51). The cut event starts WITH its first op; a whole one
    7-9 us before it."""
    cut = copy.deepcopy(trace)
    modules, ops = (line["events"] for line in cut["planes"][0]["lines"])
    whole = modules[0][2]
    seen_from = ops[4][1]  # the trace began at the step's fifth op
    del ops[:4]
    modules[0][2] -= seen_from - modules[0][1]
    modules[0][1] = seen_from
    update = device_scopes.account(cut, [program], KNOWN)["programs"][0]
    assert update["steps"] == 1
    assert update["module_ms"] == pytest.approx(whole / 1e6)
    assert update["period_ms"] is None
    assert update["scopes"]["obs_embed"]["ms"] == pytest.approx(0.898284)


def test_a_short_programs_idle_time_is_reported_at_a_runs_end(trace):
    """An act step leaves the device idle between its ops (4.5% of
    `jit_act_step` on a v5e): refused by the script, a number in the
    table a `--profile_dir` run ends with."""
    spread = copy.deepcopy(trace)
    for event in spread["planes"][0]["lines"][1]["events"]:
        if event[0] == "copy.2":
            event[2] -= 15000.0
    with pytest.raises(device_scopes.AccountError, match="jit_step"):
        device_scopes.account(spread, scopes=KNOWN)
    act = device_scopes.account(
        spread, scopes=KNOWN, strict=False
    )["programs"][1]
    assert act["residual_pct"] == pytest.approx(9.3, abs=0.1)
    assert act["scopes"]["unscoped"]["ops"]["copy"]["ms"] == pytest.approx(
        0.005
    )


def test_the_table_names_every_row(report):
    table = device_scopes.render(report)
    for expected in (
        "jit_update_step on /device:TPU:0: 2 steps, module 10.500 ms",
        "deltanet_conv", "gmm_cut_in_vmem 1x 0.37",
        "unscoped, under delta_inter", "moe_window_rows",
    ):
        assert expected in table


# --- a profile brought into the plain form ----------------------------------------


class _Fake:
    def __init__(self, **fields):
        self.__dict__.update(fields)


def _event(name, start, duration, **stats):
    return _Fake(
        name=name, start_ns=start, duration_ns=duration,
        stats=list(stats.items()),
    )


def test_both_layouts_come_into_the_plain_form():
    """A TPU's plane keeps its two lines and an op's instruction name;
    a CPU's thread lines keep the events that name their module."""
    profile = _Fake(planes=[
        _Fake(name="/device:TPU:0", lines=[
            _Fake(name="XLA Modules", events=[
                _event("jit_f(1)", 0, 1000000, run_id=3),
            ]),
            _Fake(name="XLA Ops", events=[
                _event("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p)", 8000,
                       992000),
            ]),
            _Fake(name="Async XLA Ops", events=[_event("x", 0, 1)]),
        ]),
        _Fake(name="/host:CPU", lines=[
            _Fake(name="tf_XLAEigen/1", events=[
                _event("ThunkExecutor::Execute", 0, 50),
                _event("add.1", 10, 20, hlo_op="add.1", hlo_module="jit_g",
                       program_id=7, run_id=9, device_ordinal=0),
            ]),
        ]),
    ])
    plain = device_scopes.plain_trace(profile)
    device, host = plain["planes"]
    assert [line["name"] for line in device["lines"]] == [
        "XLA Modules", "XLA Ops",
    ]
    assert device["lines"][1]["events"] == [
        ["fusion.3", 8000.0, 992000.0, {}]
    ]
    assert host["lines"][0]["events"] == [["add.1", 10.0, 20.0, {
        "hlo_op": "add.1", "hlo_module": "jit_g",
        "run_id": 9,
    }]]
    report = device_scopes.account(plain, scopes=())
    assert [
        (p["program"], p["layout"], p["residual_pct"])
        for p in report["programs"]
    ] == [
        ("jit_f", "device", pytest.approx(0.8)),
        ("jit_g", "host_threads", None),
    ]


# --- the helper that names a scope ------------------------------------------------


def _stripped_sha(lowered):
    text = lowered.as_text(debug_info=False)
    return hashlib.sha256(text.encode()).hexdigest()


def _name_stacks(lowered):
    """The names the ops carry (their locations hold the line of the
    `lower` call too, which differs)."""
    return sorted(re.findall(r'"(jit\(update\)[^"]*)"', lowered.as_text(
        debug_info=True
    )))


def test_the_helper_notes_a_name_once_and_lowers_the_same_text():
    """`device_scope` is `jax.named_scope` under the same name: the toy
    update spelt either way lowers to one text, debug info stripped,
    and with it the same names."""
    def update_with(scope):
        def update(w, x):
            def loss(w):
                with scope("toy_forward"):
                    y = jnp.tanh(x @ w)
                with scope("toy_loss"):
                    return jnp.sum(y * y)
            grad = jax.grad(loss)(w)
            with scope("toy_optimizer"):
                return w - 0.1 * grad
        return jax.jit(update)

    w, x = jnp.ones((4, 4)), jnp.ones((2, 4))
    ours = update_with(telemetry.device_scope).lower(w, x)
    theirs = update_with(jax.named_scope).lower(w, x)
    assert _stripped_sha(ours) == _stripped_sha(theirs)
    assert _name_stacks(ours) == _name_stacks(theirs)
    assert any("toy_forward" in name for name in _name_stacks(ours))
    noted = telemetry.known_device_scopes()
    assert {"toy_forward", "toy_loss", "toy_optimizer"} <= noted
    update_with(telemetry.device_scope).lower(w, x)
    assert telemetry.known_device_scopes() == noted


# --- the two ways in -----------------------------------------------------------------------


def test_the_scripts_account_of_a_toy_update_on_the_cpu():
    """Three traced steps of the toy `shallow` update through the
    script's own function. A CPU profile has no module events and runs
    a program's ops on several threads at once, so no residual is
    taken there (`layout` says so); the scopes and shares are."""
    from scripts import device_time_account as script

    model = create_model("shallow", num_actions=3)
    batch = make_batch()
    params = scaffold.init(
        model,
        {"params": jax.random.PRNGKey(0), "action": jax.random.PRNGKey(1)},
        batch, (),
    )
    optimizer = optax.sgd(0.1)
    update_step = learner_lib.make_update_step(
        model, optimizer, learner_lib.HParams(), donate=False
    )
    report = script.traced_account(
        update_step, params, optimizer.init(params), batch, (), steps=3
    )
    (update,) = [
        p for p in report["programs"] if p["program"] == "jit_update_step"
    ]
    assert update["layout"] == "host_threads"
    assert update["steps"] == 3
    assert update["residual_pct"] is None
    for scope in ("vtrace", "loss_terms", "optimizer"):
        assert update["scopes"][scope]["ms"] > 0, scope
    assert sum(
        row["share_pct"] for row in update["scopes"].values()
    ) == pytest.approx(100.0)
    assert report["counters"] == {}  # the toy sows none


def _last_telemetry_line(tmp_path, xpid="smoke"):
    with open(tmp_path / xpid / "telemetry.jsonl") as f:
        return json.loads(f.read().splitlines()[-1])


def test_a_profiled_run_ends_with_its_account(tmp_path):
    """`--profile_dir`: the run's last telemetry line holds one account
    a program, the update's by its scopes."""
    flags = make_flags(tmp_path, profile_dir=str(tmp_path / "profile"))
    monobeast.train(flags)
    report = _last_telemetry_line(tmp_path)["device_scopes"]
    programs = {p["program"]: p for p in report["programs"]}
    assert {"jit_update_step", "jit_act_step"} <= set(programs)
    update = programs["jit_update_step"]
    assert update["steps"] == 4
    for scope in ("vtrace", "loss_terms", "optimizer", "policy_head"):
        assert update["scopes"][scope]["ms"] > 0, scope
    assert programs["jit_act_step"]["steps"] >= 20


def test_a_run_without_the_flag_reads_no_trace(tmp_path, monkeypatch):
    def refuse(*args, **kwargs):
        raise AssertionError("no profile was asked for")

    monkeypatch.setattr(jax.profiler.ProfileData, "from_file", refuse)
    monkeypatch.setattr(learner_setup, "device_time_account", refuse)
    monobeast.train(make_flags(tmp_path))
    assert "device_scopes" not in _last_telemetry_line(tmp_path)
