"""Worker body for the multi-process data-parallel test (run by
test_distributed.py via subprocess, 2 processes x 2 virtual CPU devices).

Each process: initialize jax.distributed (gloo CPU collectives), build the
same model/batch deterministically, feed its LOCAL batch shard through
parallel.shard_batch (the make_array_from_process_local_data path), run one
DP update over the 4-device global mesh, and compare the result against a
locally-computed single-device reference update. Exits 0 on match.

SURVEY.md §4: multi-host logic needs a multi-process CPU-backend test —
no reference counterpart exists.
"""

import os
import sys

proc_id = int(sys.argv[1])
port = sys.argv[2]

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_cpu_collectives_implementation", "gloo")

from torchbeast_tpu.parallel import initialize_distributed  # noqa: E402

initialize_distributed(
    f"127.0.0.1:{port}", num_processes=2, process_id=proc_id
)
assert len(jax.devices()) == 4, jax.devices()
assert len(jax.local_devices()) == 2

import numpy as np  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from torchbeast_tpu import learner as learner_lib  # noqa: E402
from torchbeast_tpu.models import create_model  # noqa: E402
from torchbeast_tpu.parallel import (  # noqa: E402
    create_mesh,
    make_parallel_update_step,
    replicate,
    shard_batch,
)

T, B, A = 3, 8, 4  # B=8 over a 4-way data axis: 2 rows/device, 4/process


def make_batch():
    rng = np.random.default_rng(7)
    return {
        "frame": rng.integers(0, 256, (T + 1, B, 48, 48, 1), dtype=np.uint8),
        "reward": rng.standard_normal((T + 1, B)).astype(np.float32),
        "done": rng.random((T + 1, B)) < 0.2,
        "episode_return": rng.standard_normal((T + 1, B)).astype(np.float32),
        "episode_step": rng.integers(0, 9, (T + 1, B)).astype(np.int32),
        "last_action": rng.integers(0, A, (T + 1, B)).astype(np.int32),
        "action": rng.integers(0, A, (T + 1, B)).astype(np.int32),
        "policy_logits": rng.standard_normal((T + 1, B, A)).astype(np.float32),
        "baseline": rng.standard_normal((T + 1, B)).astype(np.float32),
    }


model = create_model("shallow", num_actions=A, use_lstm=True)
batch = make_batch()
state = model.initial_state(B)
params_fn = jax.jit(model.init)
params = params_fn(
    {"params": jax.random.PRNGKey(0), "action": jax.random.PRNGKey(1)},
    batch,
    state,
)
hp = learner_lib.HParams(batch_size=B, unroll_length=T)
optimizer = learner_lib.make_optimizer(hp)

# Single-device reference (local to this process; same on both).
single = learner_lib.make_update_step(model, optimizer, hp, donate=False)
ref_params, _, ref_stats = single(params, optimizer.init(params), batch, state)
ref_leaves = [np.asarray(x) for x in jax.tree_util.tree_leaves(ref_params)]

# Distributed: global 4-device mesh, this process feeds its local columns.
mesh = create_mesh(4)
par = make_parallel_update_step(model, optimizer, hp, mesh, donate=False)
params_r = replicate(mesh, params)
opt_r = replicate(mesh, optimizer.init(params))

lo, hi = proc_id * (B // 2), (proc_id + 1) * (B // 2)
local_batch = {k: v[:, lo:hi] for k, v in batch.items()}
local_state = jax.tree_util.tree_map(lambda s: s[:, lo:hi], state)
batch_s, state_s = shard_batch(mesh, local_batch, local_state)

new_params, _, stats = par(params_r, opt_r, batch_s, state_s)

np.testing.assert_allclose(
    float(stats["total_loss"]), float(ref_stats["total_loss"]), rtol=2e-4
)
for a, b in zip(jax.tree_util.tree_leaves(new_params), ref_leaves):
    # Replicated outputs are fully addressable on every process.
    np.testing.assert_allclose(np.asarray(a), b, rtol=2e-3, atol=2e-5)

print(f"worker {proc_id}: distributed update matches single-device OK")

# --- Phase 2: composite (data x expert) mesh across the same 2 processes.
# MoE transformer with experts sharded over the inner `expert` axis while
# the batch shards over `data` — the update must still match the
# single-device reference.
from torchbeast_tpu.parallel import expert_param_shardings  # noqa: E402

mesh2 = create_mesh(4, expert_parallelism=2)
assert mesh2.shape == {"data": 2, "model": 1, "expert": 2}

T2 = 3
model2_kwargs = dict(
    num_actions=A, num_layers=1, d_model=16, num_heads=2, memory_len=4,
    num_experts=4,
)
model2_single = create_model("transformer", **model2_kwargs)
model2 = create_model("transformer", moe_mesh=mesh2, **model2_kwargs)

rng2 = np.random.default_rng(11)
batch2 = {
    "frame": rng2.integers(0, 256, (T2 + 1, B, 6, 6, 1), dtype=np.uint8),
    "reward": rng2.standard_normal((T2 + 1, B)).astype(np.float32),
    "done": rng2.random((T2 + 1, B)) < 0.2,
    "episode_return": rng2.standard_normal((T2 + 1, B)).astype(np.float32),
    "episode_step": rng2.integers(0, 9, (T2 + 1, B)).astype(np.int32),
    "last_action": rng2.integers(0, A, (T2 + 1, B)).astype(np.int32),
    "action": rng2.integers(0, A, (T2 + 1, B)).astype(np.int32),
    "policy_logits": rng2.standard_normal((T2 + 1, B, A)).astype(
        np.float32
    ),
    "baseline": rng2.standard_normal((T2 + 1, B)).astype(np.float32),
}
state2 = model2_single.initial_state(B)
params2_fn = jax.jit(model2_single.init)
params2 = params2_fn(
    {"params": jax.random.PRNGKey(2), "action": jax.random.PRNGKey(3)},
    batch2,
    state2,
)
hp2 = learner_lib.HParams(batch_size=B, unroll_length=T2)
single2 = learner_lib.make_update_step(
    model2_single, optimizer, hp2, donate=False
)
ref2_params, _, ref2_stats = single2(
    params2, optimizer.init(params2), batch2, state2
)
ref2_leaves = [
    np.asarray(x) for x in jax.tree_util.tree_leaves(ref2_params)
]

shardings2 = expert_param_shardings(mesh2, params2)
par2 = make_parallel_update_step(
    model2, optimizer, hp2, mesh2, donate=False,
    param_shardings=shardings2,
)
params2_np = jax.tree_util.tree_map(np.asarray, params2)
params2_p = jax.tree_util.tree_map(
    jax.device_put, params2_np, shardings2
)
opt2 = optimizer.init(params2_p)

local_batch2 = {k: v[:, lo:hi] for k, v in batch2.items()}
local_state2 = jax.tree_util.tree_map(lambda s: s[:, lo:hi], state2)
batch2_s, state2_s = shard_batch(mesh2, local_batch2, local_state2)

new2_params, _, stats2 = par2(params2_p, opt2, batch2_s, state2_s)

np.testing.assert_allclose(
    float(stats2["total_loss"]), float(ref2_stats["total_loss"]), rtol=2e-4
)
np.testing.assert_allclose(
    float(stats2["aux_loss"]), float(ref2_stats["aux_loss"]), rtol=2e-4
)
for a, b in zip(jax.tree_util.tree_leaves(new2_params), ref2_leaves):
    np.testing.assert_allclose(np.asarray(a), b, rtol=2e-3, atol=2e-5)

print(
    f"worker {proc_id}: composite data x expert update matches "
    "single-device OK"
)
