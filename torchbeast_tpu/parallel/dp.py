"""Data-parallel learner: the jitted update step sharded over the mesh.

Replaces what the reference would have needed NCCL/torch.distributed for
(it has neither — single learner process, SURVEY.md §2.3). Design: params
and optimizer state live replicated on every chip; each learner batch
[T+1, B, ...] is sharded along B over the `data` axis; `jax.jit` with these
shardings makes XLA compute per-shard gradients and insert the ICI
all-reduce that keeps params replicated. No hand-written collectives — the
compiler lays them on the ICI rings.

"Per-shard" holds only while every op of the model keeps B a separate
axis, or the MAJOR factor of a merged one: the SPMD partitioner cannot
tile the minor factor of a merged axis, so it all-gathers the operand and
every chip computes every row behind the merge. The conv trunks need one
merged batch axis and merge it batch-major for that reason, and the
shared head takes [T, B, D] (models/cores.py merge_time_batch,
RecurrentPolicyHead); only the one-device update steps ask for the
time-major merge, which is cheaper there (learner.one_device_model:
this module compiles update_body with the model as it is handed in).
The compiled program is pinned by
tests/test_parallel.py::test_data_parallel_update_divides_the_model and,
for the described v5e:2x2, tests/test_chip_compile.py: all-reduces only,
a chip's FLOPs those of the one-chip program at B / n. (The transformer
families' frame projection merges nothing on the chip: uint8 frames are
contracted with batch and time both free, models/transformer.py
`frame_projection`, so a sharded batch axis stays whole rows; the float
expression that float frames and the CPU keep merges time-major. No
cell runs either across chips, PERF.md §7.)

Multi-host: call `initialize_distributed()` first (jax.distributed over
DCN), then build the mesh over `jax.devices()` (global). Each host feeds
its local shard of the batch via `make_global_batch` (device_put to local
addressable shards + jax.make_array_from_single_device_arrays).
"""

import logging
import os
from typing import Any, Dict, Optional

import jax
import numpy as np

from torchbeast_tpu import learner as learner_lib
from torchbeast_tpu.parallel import mesh as mesh_lib

log = logging.getLogger(__name__)


def initialize_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """jax.distributed.initialize with env-var fallbacks.

    The DCN analog of the reference's "anything gRPC accepts works across
    machines" story (SURVEY.md §5.8): one coordinator address, N learner
    processes, each seeing its local TPU chips; collectives ride ICI within
    a host and DCN across.
    """
    coordinator_address = coordinator_address or os.environ.get(
        "TORCHBEAST_COORDINATOR"
    )
    if coordinator_address is None:
        log.info("No coordinator configured; single-process mode.")
        return
    if num_processes is None:
        num_processes = int(os.environ.get("TORCHBEAST_NUM_PROCESSES", 1))
    if process_id is None:  # NB: 0 is a valid id — test None explicitly
        process_id = int(os.environ.get("TORCHBEAST_PROCESS_ID", 0))
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=int(num_processes),
        process_id=int(process_id),
    )


def fleet_strategy(backend: Optional[str] = None) -> str:
    """How a multi-host fleet composes its learner (ISSUE 17).

    "xla": the backend executes cross-process computations — TPU (DCN
    collectives) and GPU (NCCL). jax.distributed rendezvous, one global
    mesh whose `data` axis spans hosts, `make_parallel_update_step`
    compiles over it unchanged, `shard_batch` takes its
    make_array_from_process_local_data branch.

    "wire": CPU — XLA has no multiprocess CPU runtime (a jitted
    computation over a cross-host mesh fails at dispatch with
    "Multiprocess computations aren't implemented on the CPU backend"),
    so jax.distributed is never initialized; each host compiles over
    its LOCAL learner devices and the fleet coordinator's control plane
    composes parameters by synchronous averaging
    (fleet.FleetCoordinator.sync_params). This is the CI strategy: it
    exercises every fleet control surface (rendezvous, health folding,
    snapshot wire, telemetry) on forced-CPU hosts.

    Selection is by BACKEND, not a runtime probe: probing would require
    an irreversible jax.distributed.initialize before knowing whether
    the backend can use it.
    """
    backend = backend if backend is not None else jax.default_backend()
    return "xla" if backend in ("tpu", "gpu") else "wire"


def make_parallel_update_step(
    model, optimizer, hp: "learner_lib.HParams", mesh, donate=True,
    param_shardings: Optional[Any] = None,
    opt_shardings: Optional[Any] = None,
    donate_batch: bool = False,
    superstep_k: int = 1,
):
    """Data/tensor-parallel version of learner.make_update_step.

    Same signature and semantics; gradients are averaged over the `data`
    axis implicitly by XLA's all-reduce (sum-reduced losses over a sharded
    batch == the reference's single-learner loss over the full batch).
    `donate` is a policy understood by learner.donate_argnums_for: True
    (params+opt, single-threaded drivers), "opt_only" (async drivers —
    the shared params stay undonated), or False. `donate_batch` enforces
    the consume-once staging contract on the batch/agent-state args
    (learner.consume_staged_inputs — host-side deletion after dispatch;
    the stock body has no batch-shaped outputs for XLA-level aliasing).

    `superstep_k > 1` builds the SAME scan wrapper the single-device
    learner.make_update_superstep uses (learner.superstep_body): one
    dispatch runs K scanned updates over a [K, T+1, B, ...] stack whose
    B axis is sharded over `data` — DP-sharded learners amortize
    dispatch overhead identically to single-device ones. The grad
    all-reduce happens inside every scan iteration (each scanned update
    consumes its own full global batch), so K scanned collective updates
    match K sequential parallel dispatches. The Sebulba device split
    (runtime/placement.py) compiles its learner superstep through this
    exact path over a mesh spanning only the split's learner devices
    (`create_mesh(devices=split.learner_devices)`) — K=1-vs-K=2 parity
    on a 2-device mesh is pinned by tests/test_sebulba.py. (A 1-device
    learner group deliberately does NOT come here: polybeast pins the
    plain-jit update by explicit placement instead — the SPMD
    partitioner costs ~1.7x on a partition-of-one.)

    Precision (--precision bf16_train, torchbeast_tpu/precision.py):
    the staged stack's float leaves may arrive bfloat16 — shardings are
    dtype-agnostic, shard_batch places whatever dtype the arena staged,
    and the shared update_body upcasts at point of use (f32-accumulate;
    grads and the all-reduce run f32). The compact optimizer state
    (hp.opt_state_dtype="bf16") flows in through the caller's
    make_optimizer, so opt_shardings derived by mapping leaf-wise rules
    over opt_state keep working; the FACTORED state (hp.opt_factored)
    does NOT mirror params leaf-wise — callers deriving EP/TP opt
    shardings must reject that combination (polybeast does).

    param_shardings (optional): a params-pytree of NamedShardings (see
    parallel/tp.py) to shard weights over the mesh's `model` axis;
    defaults to fully replicated params. Optimizer state follows the same
    sharding (optax state mirrors the params structure leaf-wise).
    """
    if superstep_k < 1:
        raise ValueError(f"superstep_k must be >= 1, got {superstep_k}")
    repl = mesh_lib.replicated(mesh)
    leading = 1 if superstep_k > 1 else 0
    bsh = mesh_lib.batch_sharding(mesh, leading_axes=leading)
    ssh = mesh_lib.state_sharding(mesh, leading_axes=leading)
    psh = repl if param_shardings is None else param_shardings

    # The exact single-device update body (incl. the entropy-anneal
    # schedule); only the jit wrapping — shardings + donation — differs.
    # superstep_k > 1 swaps in the K-scan superstep body, same sharing.
    if superstep_k > 1:
        update_step = learner_lib.superstep_body(model, optimizer, hp)
    else:
        update_step = learner_lib.update_body(model, optimizer, hp)

    # A single NamedSharding acts as a pytree prefix: it applies to every
    # leaf of the batch dict (all leaves are [T+1, B, ...]). Optimizer
    # state shardings: explicit when the caller derives them (donation
    # requires input placement == output placement, so donating drivers
    # must pin them — optax state mirrors the params leaf-wise, so
    # expert_param_shardings works on it directly); otherwise left to the
    # compiler when params are sharded.
    if opt_shardings is not None:
        opt_sh = opt_shardings
    else:
        opt_sh = repl if param_shardings is None else None
    # Batch/state args never reach donate_argnums: the body has no
    # batch-shaped outputs to alias (learner.consume_staged_inputs
    # documents the physics), so donate_batch is enforced host-side.
    donate_args = learner_lib.donate_argnums_for(donate, False)
    if opt_sh is None and 1 in donate_args:
        # Donation aliases the input buffer to the output, which requires
        # input placement == output sharding. With opt placement left to
        # the compiler, the output sharding it picks can disagree with
        # wherever the caller staged opt_state (XLA then fails with an
        # aliased-size mismatch at dispatch), so skip donating it.
        log.warning(
            "opt_state sharding left to the compiler with sharded params; "
            "disabling opt_state donation (pass opt_shardings to donate)."
        )
        donate_args = tuple(a for a in donate_args if a != 1)
    jitted = jax.jit(
        update_step,
        in_shardings=(psh, opt_sh, bsh, ssh),
        out_shardings=(psh, opt_sh, repl),
        donate_argnums=donate_args,
    )
    if donate_batch:
        return learner_lib.consume_staged_inputs(jitted)
    return jitted


def shard_batch(mesh, batch: Dict[str, np.ndarray], initial_agent_state: Any,
                leading_axes: int = 0):
    """Host -> device: place a batch with the DP shardings.

    Single-process: jax.device_put splits across local devices. Multi-host
    (jax.process_count() > 1): each process passes its LOCAL batch shard
    (local_batch_size = global / process_count) and
    jax.make_array_from_process_local_data assembles the global array —
    device_put with a global sharding would fail on non-addressable
    devices.

    `leading_axes=1` places [K, T+1, B, ...] superstep stacks (the B
    axis stays the sharded one) — must match the superstep_k the update
    step was jitted with.
    """
    bsh = mesh_lib.batch_sharding(mesh, leading_axes=leading_axes)
    ssh = mesh_lib.state_sharding(mesh, leading_axes=leading_axes)
    if jax.process_count() > 1:
        put_b = lambda v: jax.make_array_from_process_local_data(bsh, v)  # noqa: E731
        put_s = lambda v: jax.make_array_from_process_local_data(ssh, v)  # noqa: E731
    else:
        put_b = lambda v: jax.device_put(v, bsh)  # noqa: E731
        put_s = lambda v: jax.device_put(v, ssh)  # noqa: E731
    batch = {k: put_b(np.asarray(v)) for k, v in batch.items()}
    initial_agent_state = jax.tree_util.tree_map(
        lambda s: put_s(np.asarray(s)), initial_agent_state
    )
    return batch, initial_agent_state


def replicate(mesh, tree):
    """Place params/opt_state replicated on every mesh device."""
    return jax.device_put(tree, mesh_lib.replicated(mesh))
