"""Small MLP policy — for low-dimensional / tiny-frame envs (e.g. the
jittable Catch env used by the Anakin trainer). Not a reference model
family (the reference ships only conv nets); same interface: flatten the
frame, optional reward/last-action inputs, shared RecurrentPolicyHead.
"""

from typing import Any, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from torchbeast_tpu.models.cores import (
    RecurrentPolicyHead,
    clipped_reward_input,
    lstm_initial_state,
)


class MLPNet(nn.Module):
    num_actions: int
    use_lstm: bool = False
    hidden_sizes: Sequence[int] = (128, 128)
    dtype: Any = jnp.float32
    # Recurrent-core + policy-head compute dtype (--precision
    # bf16_train sets bfloat16; outputs upcast at the head boundary).
    head_dtype: Any = jnp.float32
    # Rematerialize the LSTM scan's backward (the `core` stage of the
    # remat planner, runtime/remat_plan.py; no-op without --use_lstm).
    core_remat: bool = False

    @property
    def core_size(self) -> int:
        return self.hidden_sizes[-1] + self.num_actions + 1

    @nn.compact
    def __call__(self, inputs, core_state=(), *, sample_action: bool = True):
        frame = inputs["frame"]  # [T, B, ...]
        T, B = frame.shape[:2]
        # No merge of T and B: a Dense contracts the last axis of
        # [T, B, D] as it stands, and B keeps its sharding (models/cores).
        x = frame.reshape((T, B, -1)).astype(self.dtype) / 255.0
        for size in self.hidden_sizes:
            x = nn.relu(nn.Dense(size, dtype=self.dtype)(x))
        # Trunk -> head boundary in the HEAD's dtype: under bf16_train
        # the [T, B, D] activation (and its backward cotangent) never
        # round-trips through f32; under the f32/bf16_compute policies
        # this is exactly the old astype(float32) boundary.
        x = x.astype(self.head_dtype)

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
            hidden_size=self.core_size,
            num_layers=1,
            dtype=self.head_dtype,
            remat=self.core_remat,
            name="head",
        )(core_input, inputs["done"], core_state, sample_action)

    def initial_state(self, batch_size: int) -> Tuple:
        return lstm_initial_state(self.use_lstm, 1, self.core_size, batch_size)
