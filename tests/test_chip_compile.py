"""Compile for the chip, without the chip: the flagship's programs.

The TPU compiler is installed here and compiles for a v5e that is
described, not attached (`jax.experimental.topologies`). Interpret-mode
tests cannot see what it refuses: a scoped-VMEM overflow, a bf16
compare the v5e VPU lacks, a program that does not fit the device. These tests compile the flagship act step at the largest
inference bucket and the flagship update (unroll 80, batch 32) over the
four chips against its one-chip quarter; the whole one-chip update step
is the `slow` case. The routed families' grouped matmuls and windows are `tests/test_
chip_compile_moe.py`, the fused attention kernels `tests/test_chip_
compile_attention.py`, and the families' whole-cell compiles have a
file each (`tests/test_chip_compile_<family>.py`); the described chip
all of them share is `tests/chip_fixtures.py`. Nothing runs: a compile
that passes says nothing about results or times.

Code that asks `jax.default_backend()` still sees the CPU here, so the
whole-step cases patch the backend name for the duration of the trace.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import pytest  # noqa: E402

import jax  # noqa: E402

import __graft_entry__  # noqa: E402
from tests.chip_fixtures import (  # noqa: E402, F401
    B,
    NUM_ACTIONS,
    T,
    on as _on,
    one_chip,
    topo,
)
from torchbeast_tpu import learner as learner_lib  # noqa: E402

MAX_INFERENCE_BATCH = 64  # polybeast --max_inference_batch_size default


def test_flagship_act_step_compiles_for_v5e(one_chip, monkeypatch):
    """The acting program at the largest inference bucket."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model, params = __graft_entry__._flagship_param_structs()
    n = MAX_INFERENCE_BATCH
    env_output = {
        k: v[0] for k, v in __graft_entry__._make_batch(
            0, n, NUM_ACTIONS
        ).items() if k in ("frame", "reward", "done", "last_action")
    }
    compiled = learner_lib.make_act_step(model).lower(
        _on(one_chip, params),
        _on(one_chip, jax.random.PRNGKey(0)),
        _on(one_chip, env_output),
        _on(one_chip, model.initial_state(n)),
    ).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 16e9


def test_flagship_dp4_update_divides_over_v5e_2x2(topo, one_chip,
                                                  monkeypatch):
    """`deep_lstm.learner_dp4`'s program, [81, 64] over a 2x2 mesh: each
    chip's share is the one-chip program at B = 16, and what crosses the
    chips is the gradients' all-reduce and scalar sums. (A time-major
    merge in the trunk makes the partitioner all-gather the frames,
    `bf16[81,64,84,84,4]`, and every chip run all 5,184 rows.)"""
    import chip_smoke
    from tests.test_parallel import COLLECTIVES, result_dims
    from torchbeast_tpu.parallel import (
        create_mesh,
        make_parallel_update_step,
    )
    from torchbeast_tpu.parallel import mesh as mesh_lib

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    chips, rows = len(topo.devices), 64

    def compiled(b, make_step, shardings):
        _, hp, model, params, optimizer, staged = (
            chip_smoke.build_flagship_learner([], T, b)
        )
        repl, batch_sh, state_sh = shardings
        batch, state = staged(T, b)
        return make_step(model, optimizer, hp).lower(
            _on(repl, params),
            _on(repl, jax.eval_shape(optimizer.init, params)),
            _on(batch_sh, batch),
            _on(state_sh, state),
        ).compile()

    mesh = create_mesh(devices=topo.devices)
    dp4 = compiled(
        rows,
        lambda model, optimizer, hp: make_parallel_update_step(
            model, optimizer, hp, mesh
        ),
        (mesh_lib.replicated(mesh), mesh_lib.batch_sharding(mesh),
         mesh_lib.state_sharding(mesh)),
    )
    quarter = compiled(
        rows // chips, learner_lib.make_update_step, (one_chip,) * 3
    )

    collectives = result_dims(dp4.as_text(), *COLLECTIVES)
    assert {kind for kind, _ in collectives} == {"all-reduce"}, collectives
    # Nothing a chip receives has a batch axis: gradients, stats, counts.
    batch_shaped = [
        dims for _, dims in collectives
        if dims[:2] in ((T + 1, rows), (T + 1, rows // chips))
    ]
    assert not batch_shaped, batch_shaped

    def flops(program):
        cost = program.cost_analysis()
        return (cost[0] if isinstance(cost, (list, tuple)) else cost)["flops"]

    assert flops(dp4) == pytest.approx(flops(quarter), rel=0.05)


def flagship_update_memory(chip, argv):
    """Compile the flagship update step exactly as chip_smoke.py builds
    it from `argv`, for the described chip; returns memory_analysis().
    Call with jax.default_backend patched to "tpu"."""
    import chip_smoke

    _, hp, model, params, optimizer, staged = (
        chip_smoke.build_flagship_learner(argv, T, B)
    )
    return learner_lib.make_update_step(model, optimizer, hp).lower(
        _on(chip, params),
        _on(chip, jax.eval_shape(optimizer.init, params)),
        *_on(chip, staged(T, B)),
    ).compile().memory_analysis()


@pytest.mark.slow
@pytest.mark.parametrize(
    "argv",
    [[], ["--precision", "bf16_train"]],
    ids=["f32", "bf16_train"],
)
def test_flagship_update_step_compiles_for_v5e(one_chip, monkeypatch,
                                               argv):
    """The whole learner program, TPU branches taken (ops/pool.py's
    SelectAndScatter backward), inside the chip's 16 GB."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    memory = flagship_update_memory(one_chip, argv)
    total = (
        memory.temp_size_in_bytes + memory.argument_size_in_bytes
        + memory.output_size_in_bytes - memory.alias_size_in_bytes
    )
    assert total < 16e9, memory
