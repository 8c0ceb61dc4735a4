"""Device mesh construction and sharding vocabulary.

The reference has NO collective layer at all — its learner is a single
process and its only multi-device trick is putting the inference model on a
second GPU (SURVEY.md §2.3). This module is the missing piece built
first-class: a `jax.sharding.Mesh` over TPU chips (ICI) and hosts (DCN),
with named axes and `NamedSharding` helpers that the learner step is jitted
against. XLA inserts the gradient all-reduce (psum over the `data` axis)
because params are replicated while the batch is sharded.

Axes:
- `data`: batch-dimension sharding for the learner (gradient all-reduce
  rides ICI).
- `model` (optional, size 1 by default): reserved for sharding wide layers;
  the IMPALA conv nets don't need it, but the axis exists so the same mesh
  recipe scales to models that do.
"""

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def create_mesh(
    n_devices: Optional[int] = None,
    model_parallelism: int = 1,
    devices: Optional[Sequence] = None,
    expert_parallelism: int = 1,
    seq_parallelism: int = 1,
    pipe_parallelism: int = 1,
) -> Mesh:
    """(data[, model][, seq][, expert]) mesh over the first n devices.

    `n_devices` is the TOTAL device count; the data axis gets
    n / (model_parallelism * expert_parallelism * seq_parallelism). The
    `expert`/`seq` axes only exist when their parallelism is > 1 (so
    plain meshes keep their two-axis shape), letting ONE mesh carry a
    data-parallel learner with expert-sharded MoE layers (all-to-alls on
    `expert`) or sequence-sharded attention (ppermute ring / all-to-alls
    on `seq`) — or BOTH at once on a (data, model, seq, expert) mesh:
    the attention shard_maps partition over (`data`, `seq`) and the MoE
    constraints over `expert`, each leaving the other's axis unmentioned
    (= replicated), so gradients still all-reduce over `data` and the
    two collective families never collide. The compute duplicated across
    an unmentioned axis (attention x expert, MoE x seq) is the standard
    cost of not further sharding those dims; correctness is pinned by
    tests/test_composite_mesh.py. The inner axes are innermost so their
    collectives stay within a data replica group on neighboring chips.
    """
    if devices is None:
        devices = jax.devices()
    if n_devices is not None:
        if n_devices > len(devices):
            raise ValueError(
                f"Requested a {n_devices}-device mesh but only "
                f"{len(devices)} devices are visible"
            )
        devices = devices[:n_devices]
    if pipe_parallelism > 1 and (
        expert_parallelism > 1 or seq_parallelism > 1
    ):
        raise ValueError(
            "pipe_parallelism does not combine with expert/seq axes "
            "(the GPipe shard_map owns its schedule; only a data axis "
            "composes with it)"
        )
    n = len(devices)
    inner = (
        model_parallelism * expert_parallelism * seq_parallelism
        * pipe_parallelism
    )
    if n % inner != 0:
        raise ValueError(
            f"{n} devices not divisible by model_parallelism="
            f"{model_parallelism} x expert_parallelism="
            f"{expert_parallelism} x seq_parallelism={seq_parallelism}"
            f" x pipe_parallelism={pipe_parallelism}"
        )
    if pipe_parallelism > 1:
        grid = np.asarray(devices).reshape(
            n // inner, model_parallelism, pipe_parallelism
        )
        return Mesh(grid, ("data", "model", "pipe"))
    if expert_parallelism > 1 and seq_parallelism > 1:
        grid = np.asarray(devices).reshape(
            n // inner, model_parallelism, seq_parallelism,
            expert_parallelism,
        )
        return Mesh(grid, ("data", "model", "seq", "expert"))
    if expert_parallelism > 1:
        grid = np.asarray(devices).reshape(
            n // inner, model_parallelism, expert_parallelism
        )
        return Mesh(grid, ("data", "model", "expert"))
    if seq_parallelism > 1:
        grid = np.asarray(devices).reshape(
            n // inner, model_parallelism, seq_parallelism
        )
        return Mesh(grid, ("data", "model", "seq"))
    grid = np.asarray(devices).reshape(n // inner, model_parallelism)
    return Mesh(grid, ("data", "model"))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def batch_sharding(mesh: Mesh, leading_axes: int = 0) -> NamedSharding:
    """Time-major [T, B, ...] arrays: shard the batch axis over `data`.

    `leading_axes` prepends unsharded axes — 1 for the superstep's
    [K, T, B, ...] batch stacks, where B is still the sharded axis.
    A model that merges T and B keeps this sharding only if B is the
    merged axis's major factor (models/cores.merge_time_batch).
    """
    return NamedSharding(mesh, P(*([None] * (leading_axes + 1)), "data"))


def state_sharding(mesh: Mesh, leading_axes: int = 0) -> NamedSharding:
    """Recurrent state [L, B, H]: shard the batch axis over `data`
    (`leading_axes=1` for [K, L, B, H] superstep stacks)."""
    return NamedSharding(mesh, P(*([None] * (leading_axes + 1)), "data"))
