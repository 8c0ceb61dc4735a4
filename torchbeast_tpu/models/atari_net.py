"""Shallow Atari network (the reference's MonoBeast `AtariNet`,
/root/reference/torchbeast/monobeast.py:545-635), re-designed for TPU.

Differences from the reference that are deliberate TPU-first choices:
- NHWC frame layout (`[T, B, H, W, C]`) — XLA's native conv layout on TPU;
  the env adapter produces HWC frames instead of torch's CHW.
- A `dtype` knob: conv/fc compute can run in bfloat16 on the MXU while params
  and the loss stay float32.
- The per-timestep LSTM Python loop is an `nn.scan` (models/cores.py).

API: `model.apply(vars, inputs, core_state, sample_action=..., rngs=...)
-> (AgentOutput(action, policy_logits, baseline), core_state)` where `inputs`
is a dict of time-major arrays: frame [T,B,H,W,C] uint8, reward [T,B],
done [T,B] bool, last_action [T,B] int32.
"""

from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from torchbeast_tpu.models.cores import (
    RecurrentPolicyHead,
    clipped_reward_input,
    lstm_initial_state,
    merge_time_batch,
    split_time_batch,
)


class AtariNet(nn.Module):
    num_actions: int
    use_lstm: bool = False
    dtype: Any = jnp.float32
    # Recurrent-core + policy-head compute dtype (--precision
    # bf16_train sets bfloat16; outputs upcast at the head boundary).
    head_dtype: Any = jnp.float32
    # Rematerialize the LSTM scan's backward (the `core` stage of the
    # remat planner, runtime/remat_plan.py; no-op without --use_lstm).
    core_remat: bool = False
    time_major_merge: bool = False  # learner.one_device_model sets it

    @property
    def core_output_size(self) -> int:
        # fc output + clipped reward + one-hot last action
        # (reference monobeast.py:564-566).
        return 512 + self.num_actions + 1

    @nn.compact
    def __call__(self, inputs, core_state=(), *, sample_action: bool = True):
        frame = inputs["frame"]  # [T, B, H, W, C] uint8
        T, B = frame.shape[:2]
        # Batch-major keeps B's sharding (cores.merge_time_batch).
        x = merge_time_batch(frame, self.time_major_merge)
        x = x.astype(self.dtype) / 255.0

        conv = lambda feat, k, s: nn.Conv(  # noqa: E731
            feat, (k, k), strides=(s, s), padding="VALID", dtype=self.dtype
        )
        x = nn.relu(conv(32, 8, 4)(x))
        x = nn.relu(conv(64, 4, 2)(x))
        x = nn.relu(conv(64, 3, 1)(x))
        x = x.reshape((B * T, -1))  # 7*7*64 = 3136 for 84x84 input
        x = nn.relu(nn.Dense(512, dtype=self.dtype)(x))
        # Trunk -> head boundary in the head's dtype (old behavior =
        # astype(float32); bf16_train keeps the activation half-width).
        x = split_time_batch(
            x.astype(self.head_dtype), T, B, self.time_major_merge
        )

        one_hot_last_action = jax.nn.one_hot(
            inputs["last_action"], self.num_actions, dtype=self.head_dtype
        )
        core_input = jnp.concatenate(
            [
                x,
                clipped_reward_input(inputs["reward"], self.head_dtype),
                one_hot_last_action,
            ],
            axis=-1,
        )

        return RecurrentPolicyHead(
            num_actions=self.num_actions,
            use_lstm=self.use_lstm,
            hidden_size=self.core_output_size,
            num_layers=2,
            dtype=self.head_dtype,
            remat=self.core_remat,
            name="head",
        )(core_input, inputs["done"], core_state, sample_action)

    def initial_state(self, batch_size: int) -> Tuple:
        return lstm_initial_state(
            self.use_lstm, 2, self.core_output_size, batch_size
        )
