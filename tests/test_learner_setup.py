"""`learner_setup`: the one module that turns flags into a learner.

What the builder builds is covered where it was (test_monobeast,
test_polybeast, test_anakin, test_precision, test_remat_plan,
test_olmoe, through `monobeast._init_model_and_params`). Here: that the
three parsers still say, option for option, what they said when each
declared its own flags; that the shared flags are one declaration; and
that a family's flags are read from its class.
"""

import argparse
import ast
import importlib
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from tests import family_scaffold as scaffold
from tests.test_monobeast_families import THROUGH_MAIN
from torchbeast_tpu import anakin, learner_setup, models, monobeast, polybeast
from torchbeast_tpu import learner as learner_lib

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DRIVERS = {"monobeast": monobeast, "polybeast": polybeast, "anakin": anakin}
A, B, FRAME = 3, 2, (4, 4, 1)

with open(os.path.join(REPO, "tests", "parser_tables.json")) as _f:
    PARENT_TABLES = json.load(_f)


def _options(parser):
    """{option string: the action that takes it}, but for --help."""
    return {
        flag: action
        for action in parser._actions
        for flag in action.option_strings
        if not isinstance(action, argparse._HelpAction)
    }


def _source(module):
    with open(module.__file__) as f:
        return f.read()


# (a) every option of every parser, against the parent commit's table


@pytest.mark.parametrize("driver", sorted(DRIVERS))
def test_parser_equals_the_parents_option_for_option(driver):
    want = PARENT_TABLES[driver]
    have = {
        flag: {
            "dest": action.dest,
            "type": action.type.__name__ if action.type else None,
            "default": action.default,
            "choices": (
                sorted(action.choices) if action.choices is not None else None
            ),
            "action": type(action).__name__,
            "nargs": action.nargs,
        }
        for flag, action in _options(DRIVERS[driver].make_parser()).items()
    }
    assert sorted(have) == sorted(want)
    different = {
        flag: (want[flag], have[flag])
        for flag in want if want[flag] != have[flag]
    }
    assert not different


# (b) one declaration behind mono's and poly's shared flags


def test_mono_and_poly_share_declarations_but_for_two_defaults():
    mono = _options(monobeast.make_parser())
    poly = _options(polybeast.make_parser())
    shared = sorted(set(mono) & set(poly))
    assert len(shared) >= 53

    def declared(action):
        return {
            k: v for k, v in vars(action).items() if k != "container"
        }

    differs = {
        flag: sorted(
            k for k in declared(mono[flag])
            if declared(mono[flag])[k] != declared(poly[flag])[k]
        )
        for flag in shared
        if declared(mono[flag]) != declared(poly[flag])
    }
    assert differs == {"--model": ["default"], "--num_actors": ["default"]}
    assert (mono["--model"].default, poly["--model"].default) == (
        "shallow", "deep"
    )
    assert (mono["--num_actors"].default, poly["--num_actors"].default) == (
        8, None
    )


def test_no_flag_is_declared_in_two_files():
    """A flag string is an `add_argument` literal in one of the four
    files at most, and `--model`'s choices are typed in none of them."""
    seen = {}
    for module in (monobeast, polybeast, anakin, learner_setup):
        for node in ast.walk(ast.parse(_source(module))):
            if (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "add_argument"
                and node.args
                and isinstance(node.args[0], ast.Constant)
            ):
                seen.setdefault(node.args[0].value, []).append(
                    module.__name__
                )
    assert len(seen) >= 90
    assert {f: m for f, m in seen.items() if len(m) > 1} == {}
    for module in (monobeast, polybeast, learner_setup):
        assert '"pipelined_transformer"]' not in _source(module)


def test_only_and_overrides_must_name_learner_flags():
    with pytest.raises(ValueError, match="--no_such_flag"):
        learner_setup.add_learner_arguments(
            argparse.ArgumentParser(), model_default="mlp",
            only=("--env", "--no_such_flag"),
        )
    parser = argparse.ArgumentParser()
    learner_setup.add_learner_arguments(
        parser, model_default="mlp",
        only=("--env", "--seed"), overrides={"--seed": dict(default=5)},
    )
    assert sorted(_options(parser)) == ["--env", "--seed"]
    assert parser.parse_args([]).seed == 5


# (c) --model's choices are the registry's names


@pytest.mark.parametrize("driver", sorted(DRIVERS))
def test_model_choices_come_from_the_registry(driver):
    choices = _options(DRIVERS[driver].make_parser())["--model"].choices
    if driver == "anakin":
        assert set(choices) < set(models.MODEL_NAMES)
        assert len(choices) == 5
    else:
        assert list(choices) == list(models.MODEL_NAMES)
    for name in choices:
        models.create_model(name, num_actions=A)


# (d) a family's flags are read from its class

FAMILY_FIELD_CASES = {
    # flag: (argv value, field value, a family that takes it, one that
    # does not, what that one is told: the parent's text)
    "num_layers": (
        "3", 3, "olmoe", "pipelined_transformer",
        "--num_layers is a positive depth or window of --model "
        "transformer or olmoe or mellum2 or ouro or kanana2 or nemotron3 "
        "or qwen3next or lfm2 or phi4flash or xing4 or trinity or "
        "granite4 or ling3",
    ),
    "memory_len": (
        "9", 9, "transformer", "deep",
        "--memory_len is a positive depth or window of --model "
        "transformer or olmoe or mellum2 or ouro or kanana2 or nemotron3 "
        "or qwen3next or lfm2 or phi4flash or xing4 or trinity or "
        "granite4 or ling3",
    ),
    "num_experts": (
        "4", 4, "transformer", "olmoe",
        "--num_experts applies to --model transformer only (the "
        "conv/MLP families have no MoE formulation)",
    ),
    "expert_share": (
        "1/4", (1, 4), "mellum2", "olmoe",
        "--expert_share i/n (share i of the n chips that divide each "
        "layer's experts) applies to --model mellum2 or kanana2 or "
        "nemotron3 or qwen3next or lfm2 or xing4 or trinity or ling3 "
        "only",
    ),
    "mixer_share": (
        "1/2", (1, 2), "nemotron3", "kanana2",
        "--mixer_share i/n (share i of the n chips that divide each "
        "mixer's heads) applies to --model nemotron3 only",
    ),
    "trunk_channels": (
        "32,64,64", (32, 64, 64), "deep", "mlp",
        "--trunk_channels applies to --model deep only (the knob "
        "widens the ResNet conv trunk)",
    ),
}


def test_family_field_cases_cover_the_flags():
    assert sorted(FAMILY_FIELD_CASES) == sorted(
        learner_setup.FAMILY_FIELD_FLAGS
    )


@pytest.mark.parametrize("flag", sorted(FAMILY_FIELD_CASES))
def test_family_field_flag_follows_the_class(flag, monkeypatch):
    argv, value, takes, refuses, told = FAMILY_FIELD_CASES[flag]
    monkeypatch.setattr(
        models.olmoe, "PUBLISHED",
        dict(models.olmoe.PUBLISHED, d_model=64, num_heads=2,
             num_experts=4, experts_per_token=2, expert_width=32),
    )
    parse = monobeast.make_parser().parse_args
    model, _ = learner_setup.init_model_and_params(
        parse(["--model", takes, f"--{flag}", argv]),
        A, B, FRAME, init_params=False,
    )
    assert getattr(model, flag) == value
    assert models.takes_flag(takes, flag)
    assert not models.takes_flag(refuses, flag)
    with pytest.raises(ValueError) as refused:
        learner_setup.init_model_and_params(
            parse(["--model", refuses, f"--{flag}", argv]),
            A, B, FRAME, init_params=False,
        )
    assert str(refused.value) == told


@pytest.mark.parametrize(
    "family",
    ["mellum2", "kanana2", "nemotron3", "qwen3next", "lfm2", "xing4",
     "trinity", "ling3"],
)
def test_expert_share_reaches_every_family_that_declares_it(family):
    """`--expert_share` is no family's by name: a class that declares
    the field takes the flag (`models.takes_flag`), and the refusal's
    text lists the takers from the registry. PR 38 added a second taker
    and edited neither `_FAMILY_FIELD_REFUSALS` nor the check; PR 42 a
    third, and its sibling `--mixer_share` with a refusal of its own; PR
    46 a fourth; PR 53 a fifth; PR 59 a sixth; PR 62 a seventh; PR 68
    an eighth, whose router's groups a quarter share keeps whole."""
    assert models.families_taking("expert_share") == [
        "mellum2", "kanana2", "nemotron3", "qwen3next", "lfm2", "xing4",
        "trinity", "ling3",
    ]
    assert models.families_taking("mixer_share") == ["nemotron3"]
    layers = {
        "mellum2": "4", "kanana2": "2", "nemotron3": "11", "qwen3next": "4",
        "lfm2": "5", "xing4": "2", "trinity": "5", "ling3": "7",
    }[family]
    model, _ = learner_setup.init_model_and_params(
        monobeast.make_parser().parse_args([
            "--model", family, "--num_layers", layers,
            "--expert_share", "3/4",
        ]),
        A, B, FRAME, init_params=False,
    )
    assert model.expert_share == (3, 4)
    quarter = model.num_experts // 4
    assert model.held_experts() == (3 * quarter, quarter)
    assert "{families}" in learner_setup._FAMILY_FIELD_REFUSALS["expert_share"]
    assert family not in learner_setup._FAMILY_FIELD_REFUSALS["expert_share"]


def test_refusals_are_stated_on_the_class():
    """A class that has the field and still refuses the flag says so
    itself; nothing else does."""
    refusing = {
        name: models._REGISTRY[name].flag_refused_fields
        for name in models.MODEL_NAMES
        if hasattr(models._REGISTRY[name], "flag_refused_fields")
    }
    assert refusing == {
        "pipelined_transformer": ("num_layers", "memory_len"),
        "olmoe": ("num_experts",),
        "mellum2": ("num_experts",),
        "ouro": ("num_experts",),
        "kanana2": ("num_experts",),
        "nemotron3": ("num_experts",),
        "qwen3next": ("num_experts",),
        "lfm2": ("num_experts",),
        "phi4flash": ("num_experts",),
        "xing4": ("num_experts",),
        "trinity": ("num_experts",),
        "granite4": ("num_experts",),
        "ling3": ("num_experts",),
    }
    kv_cache = [
        name for name in models.MODEL_NAMES
        if getattr(models._REGISTRY[name], "memory_is_kv_cache", False)
    ]
    assert kv_cache == [
        "transformer", "pipelined_transformer", "olmoe", "mellum2", "ouro",
        "kanana2", "nemotron3", "qwen3next", "lfm2", "phi4flash", "xing4",
        "trinity", "granite4", "ling3",
    ]
    for name in models.MODEL_NAMES:
        # test_families has the published families'
        if name in kv_cache and name not in (
            "olmoe", "mellum2", "ouro", "kanana2", "nemotron3", "qwen3next",
            "lfm2", "phi4flash", "xing4", "trinity", "granite4", "ling3",
        ):
            with pytest.raises(ValueError, match="KV cache"):
                models.create_model(name, num_actions=A, use_lstm=True)
        elif name not in kv_cache:
            models.create_model(name, num_actions=A, use_lstm=True)


# (e) anakin through the shared path


def test_anakin_defaults_give_the_hparams_it_set_itself():
    flags = anakin.make_parser().parse_args([])
    hp = learner_setup.hparams_from_flags(flags)
    set_by_anakin = dict(
        discounting=0.99, baseline_cost=0.5, entropy_cost=0.0006,
        entropy_cost_final=None, reward_clipping="abs_one",
        learning_rate=4.8e-4, rmsprop_alpha=0.99, rmsprop_eps=0.01,
        rmsprop_momentum=0.0, grad_norm_clipping=40.0, total_steps=200000,
        unroll_length=16, batch_size=64,
    )
    assert len(set_by_anakin) == 13
    defaults = learner_lib.HParams(
        **{k: None for k in learner_lib.HParams._fields
           if k not in learner_lib.HParams._field_defaults}
    )._asdict()
    assert hp._asdict() == {**defaults, **set_by_anakin}
    model, params = learner_setup.init_model_and_params(
        flags, A, flags.batch_size, (10, 5, 1), init_params=False
    )
    assert params is None
    assert model == models.create_model("mlp", num_actions=A)
    assert "HParams(" not in _source(anakin)


# (f) the module sits below the drivers


def test_learner_setup_imports_no_driver():
    drivers = {"torchbeast_tpu." + name for name in DRIVERS}
    tree = ast.parse(_source(learner_setup))
    direct = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            direct.update(alias.name for alias in node.names)
        elif isinstance(node, ast.ImportFrom):
            direct.add(node.module)
            direct.update(f"{node.module}.{a.name}" for a in node.names)
    assert not direct & drivers
    # ... and nothing it imports does: in a fresh interpreter, with the
    # builder's lazy imports made too.
    loaded = subprocess.run(
        [sys.executable, "-c",
         "import sys\n"
         "from torchbeast_tpu import learner_setup\n"
         "import torchbeast_tpu.parallel.pp\n"
         "import torchbeast_tpu.runtime.remat_plan\n"
         "print([m for m in sys.modules if m.startswith('torchbeast_tpu')])"],
        capture_output=True, text=True, cwd=REPO, check=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    ).stdout
    assert "torchbeast_tpu.learner_setup" in loaded
    assert not [d for d in drivers if f"'{d}'" in loaded]


def test_drivers_take_the_builder_from_the_module():
    assert monobeast._init_model_and_params is (
        learner_setup.init_model_and_params
    )
    assert monobeast.hparams_from_flags is learner_setup.hparams_from_flags
    assert monobeast.dummy_env_outputs is learner_setup.dummy_env_outputs
    taken = [
        (node.module, alias.name)
        for node in ast.walk(ast.parse(_source(polybeast)))
        if isinstance(node, ast.ImportFrom)
        and "monobeast" in (node.module or "")
        for alias in node.names
    ] + [
        alias.name
        for node in ast.walk(ast.parse(_source(polybeast)))
        if isinstance(node, ast.ImportFrom) and node.module == "torchbeast_tpu"
        for alias in node.names if alias.name == "monobeast"
    ]
    # `from torchbeast_tpu import monobeast` once, for `monobeast.test`.
    assert taken == ["monobeast"]
    uses = {
        node.attr
        for node in ast.walk(ast.parse(_source(polybeast)))
        if isinstance(node, ast.Attribute)
        and isinstance(node.value, ast.Name) and node.value.id == "monobeast"
    }
    assert uses == {"test"}


# (g) a flax module is traced, never run op by op


def _assert_the_eager_trees_leaves(traced, eager):
    """The same tree, every leaf within 1 ulp: to the bit on the
    builder's CPU (PR 49), the ulp for one whose fused multiply rounds
    an initialiser's scale otherwise."""
    flat, eager_flat = (
        dict(jax.tree_util.tree_flatten_with_path(tree)[0])
        for tree in (traced, eager)
    )
    assert sorted(map(str, flat)) == sorted(map(str, eager_flat))
    for path, leaf in flat.items():
        want = eager_flat[path]
        assert leaf.dtype == want.dtype and leaf.shape == want.shape, path
        np.testing.assert_array_max_ulp(
            np.asarray(leaf), np.asarray(want), maxulp=1
        )


@pytest.mark.parametrize("case", list(THROUGH_MAIN))
def test_first_parameters_are_one_traced_program(monkeypatch, case):
    """`init_model_and_params` of every toy family that trains through
    `main` hands XLA its `init` as one program (at most 10 requests
    with the two keys' and the empty state's small ones; op by op
    Qwen3-Next's was 421, minutes of a cell's `setup_s`), and the
    parameters are `model.init`'s from the flags' seeds over one dummy
    step and the empty state."""
    family, widths, flags, _ = THROUGH_MAIN[case]
    module = importlib.import_module(f"torchbeast_tpu.models.{family}")
    monkeypatch.setattr(
        module, "PUBLISHED", dict(module.PUBLISHED, **widths)
    )
    argv = ["--model", family, "--memory_len", "6"]
    for flag, value in flags.items():
        argv += [f"--{flag}", str(value)]
    parsed = monobeast.make_parser().parse_args(argv)
    requests = scaffold.compile_requests(monkeypatch)
    model, params = learner_setup.init_model_and_params(parsed, A, B, FRAME)
    assert len(requests) <= 10
    _assert_the_eager_trees_leaves(params, scaffold.init(
        model,
        {
            "params": jax.random.PRNGKey(parsed.seed),
            "action": jax.random.PRNGKey(parsed.seed + 1),
        },
        learner_setup.dummy_env_outputs(1, B, FRAME, np.uint8),
        model.initial_state(B),
    ))


def test_anakins_first_parameters_are_the_same_traced_program(monkeypatch):
    """`anakin.initial_carry` makes its parameters by the same function,
    from its own keys and the environments' first outputs, and primes
    the boundary output through the jitted act step: 38 requests, all
    but two of them the keys' and the environments' first steps' (with
    the MLP run op by op it was 84)."""
    from torchbeast_tpu.envs.jax_env import create_jax_env

    flags = anakin.make_parser().parse_args([])
    env = create_jax_env(flags.env)
    model, _ = learner_setup.init_model_and_params(
        flags, env.num_actions, B, env.frame_shape, init_params=False
    )
    requests = scaffold.compile_requests(monkeypatch)
    made = []
    initial_params = learner_setup.initial_params

    def spy(*args):
        before = len(requests)
        made.append(args)
        params = initial_params(*args)
        assert len(requests) - before == 1
        return params

    monkeypatch.setattr(anakin, "initial_params", spy)
    params, carry = anakin.initial_carry(env, model, B, jax.random.PRNGKey(3))
    assert len(requests) <= 45
    ((module, rngs, env_outputs, agent_state),) = made
    assert module is model
    _assert_the_eager_trees_leaves(
        params, model.init(rngs, env_outputs, agent_state)
    )
    assert carry.agent_out["action"].shape == (B,)
