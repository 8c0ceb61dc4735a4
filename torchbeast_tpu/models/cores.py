"""Recurrent cores.

The reference steps its LSTM in a Python loop over T with done-masking of the
carried state (/root/reference/torchbeast/monobeast.py:599-611,
polybeast_learner.py:237-249). On TPU that loop becomes `nn.scan` (lax.scan
under jit): one compiled region, unrolled by XLA, state carried in registers/
HBM without host sync.

Core state layout matches the reference: a tuple `(h, c)`, each
`[num_layers, B, hidden_size]` (torch nn.LSTM convention, monobeast.py:574-580).
"""

from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from torchbeast_tpu.telemetry import device_scope
from torchbeast_tpu.types import AgentOutput


class _StackedLSTMStep(nn.Module):
    """One time-step of a multi-layer LSTM with episode-boundary reset."""

    hidden_size: int
    num_layers: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, carry, xs):
        inp, notdone = xs  # inp: [B, D], notdone: [B] float
        h, c = carry  # each [L, B, H]
        # Reset state to zero wherever an episode ended before this step
        # (reference monobeast.py:603-607).
        nd = notdone[None, :, None]
        h = h * nd
        c = c * nd
        new_h = []
        new_c = []
        y = inp
        for layer in range(self.num_layers):
            (c_l, h_l), y = nn.OptimizedLSTMCell(
                self.hidden_size, dtype=self.dtype, name=f"layer_{layer}"
            )((c[layer], h[layer]), y)
            new_h.append(h_l)
            new_c.append(c_l)
        return (jnp.stack(new_h), jnp.stack(new_c)), y


class LSTMCore(nn.Module):
    """Scan a stacked LSTM over the time axis.

    __call__(core_input [T,B,D], notdone [T,B], core_state (h,c)) ->
        (core_output [T,B,H], new_core_state)

    `dtype` is the COMPUTE/activation dtype (--precision bf16_train runs
    the cell in bf16 — the T-step scan's carried state and saved
    activations are then half-width in HBM); params stay float32 (flax
    casts at use) and the returned core_state is upcast back to f32 at
    the module boundary, so the slot-table/wire/checkpoint state schema
    never changes.

    `remat` rematerializes each scanned step in the backward (nn.remat
    around the step module, inside nn.scan): only the T carried states
    are saved and the gate activations recompute — the LSTM-scan lever
    of the remat planner (runtime/remat_plan.py; `--remat` on the
    drivers). Forward math is identical either way.
    """

    hidden_size: int
    num_layers: int = 1
    dtype: Any = jnp.float32
    remat: bool = False

    @nn.compact
    def __call__(self, core_input, notdone, core_state):
        step_cls = (
            nn.remat(_StackedLSTMStep) if self.remat
            else _StackedLSTMStep
        )
        scan = nn.scan(
            step_cls,
            variable_broadcast="params",
            split_rngs={"params": False},
            in_axes=0,
            out_axes=0,
        )(
            self.hidden_size, self.num_layers, self.dtype,
            # Pinned to the historical auto-generated scope so the
            # param tree (and every existing checkpoint) is identical
            # whether or not the step remats — remat is a backward-pass
            # schedule, never a parameter change.
            name="Scan_StackedLSTMStep_0",
        )
        # Cast the whole carry to the compute dtype so the scanned
        # carry's input/output types agree (a mixed-dtype carry is a
        # lax.scan type error, not a silent promotion).
        core_state = jax.tree_util.tree_map(
            lambda s: s.astype(self.dtype), core_state
        )
        core_state, core_output = scan(
            core_state,
            (core_input.astype(self.dtype), notdone.astype(self.dtype)),
        )
        core_state = jax.tree_util.tree_map(
            lambda s: s.astype(jnp.float32), core_state
        )
        return core_output, core_state

    def initial_state(self, batch_size: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
        return lstm_initial_state(
            True, self.num_layers, self.hidden_size, batch_size
        )


def lstm_initial_state(
    use_lstm: bool, num_layers: int, hidden_size: int, batch_size: int
):
    """Zero (h, c) state, or () for feed-forward nets — the shared
    `initial_state` implementation of every model family."""
    if not use_lstm:
        return ()
    shape = (num_layers, batch_size, hidden_size)
    return (jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32))


def merge_time_batch(x, time_major=False):
    """`[T, B, ...] -> [B * T, ...]`: the one merged axis the conv
    trunks need (a convolution has one batch dimension).

    Batch-major unless told otherwise: B is the MAJOR factor of the
    merged axis. The learner batch is sharded along B over the mesh's
    `data` axis (parallel/mesh.batch_sharding), and a tiled sharding of
    a merged axis can only follow its major factor: merged time-major
    (`[T * B]`) the SPMD partitioner all-gathers the frames and every
    chip runs the whole trunk on all T * B rows. For T == 1, the act
    step, both orders are the same reshape.

    `time_major=True` is for a program whose whole batch is on one
    device (learner.one_device_model): there the time-major merge is a
    bitcast of the frames, while the batch-major one moves them once
    more and leaves the first convolution a slower layout — 1.09 ms of
    the flagship update's 116.8 on a v5e (PERF.md §6, PR 28).
    """
    T, B = x.shape[:2]
    if not time_major:
        x = jnp.swapaxes(x, 0, 1)
    return x.reshape((T * B,) + x.shape[2:])


def split_time_batch(x, T, B, time_major=False):
    """Inverse of merge_time_batch: `[B * T, ...] -> [T, B, ...]`."""
    if time_major:
        return x.reshape((T, B) + x.shape[1:])
    return jnp.swapaxes(x.reshape((B, T) + x.shape[1:]), 0, 1)


def clipped_reward_input(reward, dtype):
    """The reward as a core input: clipped to [-1, 1], `[T, B, 1]`."""
    return jnp.clip(reward.astype(jnp.float32), -1, 1)[..., None].astype(
        dtype
    )


class RecurrentPolicyHead(nn.Module):
    """Optional LSTM core + policy/baseline heads + action selection.

    Shared tail of every model family (the reference duplicates this block
    across AtariNet and the deep Net, monobeast.py:594-632 /
    polybeast_learner.py:235-264). Takes `[T, B, D]` core inputs plus the
    `[T, B]` done mask, returns (AgentOutput, new_core_state) with
    `[T, B, ...]` outputs. Nothing here merges T and B: the LSTM scans
    `[T, B, D]`, the projections contract the last axis, so a B axis
    sharded over `data` stays sharded from the trunk to the losses (see
    merge_time_batch).

    `dtype` is the head's compute/activation dtype (--precision
    bf16_train extends bf16 past the trunk through the LSTM core and the
    policy/baseline projections). The OUTPUT boundary is always float32:
    logits and baseline upcast before sampling/return, so the loss side
    (f32-accumulate, torchbeast_tpu/precision.py), the wire schema, and
    action sampling see identical dtypes under every policy.

    `remat` threads to the LSTM core's scan (see LSTMCore.remat) — the
    `core` stage of the remat planner's per-family lattice.
    """

    num_actions: int
    use_lstm: bool
    hidden_size: int
    num_layers: int
    dtype: Any = jnp.float32
    remat: bool = False
    # What the policy logits (not the baseline) are multiplied by, for a
    # family whose config states one (models/granite4.py: 1 / `logits_
    # scaling`); at 1 nothing is multiplied.
    logits_scale: float = 1.0

    @nn.compact
    def __call__(self, core_input, done, core_state, sample_action):
        core_output = core_input.astype(self.dtype)
        if self.use_lstm:
            notdone = 1.0 - done.astype(jnp.float32)
            with device_scope("lstm_core"):
                core_output, core_state = LSTMCore(
                    hidden_size=self.hidden_size,
                    num_layers=self.num_layers,
                    dtype=self.dtype,
                    remat=self.remat,
                    name="core",
                )(core_output, notdone, core_state)
        else:
            core_state = ()

        with device_scope("policy_head"):
            policy_logits = nn.Dense(
                self.num_actions, dtype=self.dtype, name="policy"
            )(core_output).astype(jnp.float32)
            if self.logits_scale != 1.0:
                policy_logits = policy_logits * self.logits_scale
            baseline = nn.Dense(
                1, dtype=self.dtype, name="baseline"
            )(core_output).astype(jnp.float32)

            if sample_action:
                action = jax.random.categorical(
                    self.make_rng("action"), policy_logits, axis=-1
                )
            else:
                action = jnp.argmax(policy_logits, axis=-1)

        return (
            AgentOutput(
                action=action.astype(jnp.int32),
                policy_logits=policy_logits,
                baseline=baseline[..., 0],
            ),
            core_state,
        )
