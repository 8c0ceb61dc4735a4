"""Deep IMPALA ResNet (the reference's PolyBeast `Net`,
/root/reference/torchbeast/polybeast_learner.py:134-266), TPU-native.

Three sections of [3x3 conv -> 3x3/2 maxpool -> 2 residual double-conv
blocks] with 16/32/32 channels, fc to 256, reward appended to the core input
(no last-action input, unlike AtariNet), optional 1-layer LSTM(256). NHWC
layout, optional bfloat16 trunk; the residual blocks use pre-activation ReLU
ordering exactly as the reference (ReLU-conv-ReLU-conv then add).
"""

from typing import Any, Sequence, Tuple

import flax.linen as nn
import jax.numpy as jnp

from torchbeast_tpu.models.cores import (
    RecurrentPolicyHead,
    clipped_reward_input,
    lstm_initial_state,
    merge_time_batch,
    split_time_batch,
)
from torchbeast_tpu.ops.pool import max_pool2d
from torchbeast_tpu.telemetry import device_scope


class ResNetBase(nn.Module):
    """Conv trunk shared by actor/learner: [T, B, H, W, C] frames in,
    [T, B, 256] features out. Inside, T and B are one merged axis
    (cores.merge_time_batch: batch-major keeps B's sharding)."""

    channels: Sequence[int] = (16, 32, 32)
    dtype: Any = jnp.float32
    # Dtype of the returned features — the trunk -> head boundary
    # (f32 default; the head's dtype under --precision bf16_train).
    out_dtype: Any = jnp.float32
    # Per-stage rematerialization: one value for all stages or a tuple of
    # per-stage values, each False (save everything), True (remat the whole
    # stage), or "front" (remat only the conv+pool front — drops the
    # stage's pre-pool activation, the memory hog at ~1.1 GB for stage 0
    # at T=80 B=32, while the cheap post-pool res-block activations stay
    # saved; recompute is just one conv+pool instead of the whole stage).
    # Default: remat everything — the configuration whose fit on a
    # 15.75 GB v5e is measured.
    remat: Any = True
    time_major_merge: bool = False  # learner.one_device_model sets it

    def _conv3(self, feat, name):
        return nn.Conv(
            feat, (3, 3), strides=(1, 1), padding="SAME", dtype=self.dtype,
            name=name,
        )

    def _stage_front(self, x, i):
        """conv + pool: produces (and under 'front' remat, re-produces)
        the stage's only pre-pool-resolution activation — the memory hog."""
        x = self._conv3(self.channels[i], f"feat_conv_{i}")(x)
        # ops.pool.max_pool2d: forward-identical to nn.max_pool, but
        # its custom VJP avoids SelectAndScatter (10x the forward's
        # cost on XLA:CPU, slow on some TPU gens) in the backward.
        return max_pool2d(
            x, (3, 3), strides=(2, 2), padding=((1, 1), (1, 1))
        )

    def _stage_rest(self, x, i):
        num_ch = self.channels[i]
        for j in range(2):
            res_input = x
            x = nn.relu(x)
            x = self._conv3(num_ch, f"res_{i}_{j}_conv1")(x)
            x = nn.relu(x)
            x = self._conv3(num_ch, f"res_{i}_{j}_conv2")(x)
            x = x + res_input
        return x

    def _stage(self, x, i):
        return self._stage_rest(self._stage_front(x, i), i)

    @nn.compact
    def __call__(self, frame):
        T, B = frame.shape[:2]
        with device_scope("trunk_input"):
            x = merge_time_batch(frame, self.time_major_merge)
            x = x.astype(self.dtype) / 255.0

        # Rematerialize stages in the backward pass: at the reference's
        # T=80 x B=32 the stage-0 activations alone are ~1.1 GB f32 each
        # and the fully un-remat'd backward needs >22 GB — past a v5e's
        # 16 GB HBM. A remat'd stage saves only its input and recomputes
        # inside during the backward. Wrapping the *method* keeps the
        # `name=` scopes, so param paths (trunk/feat_conv_0, ...) are
        # identical either way.
        flags = (
            tuple(self.remat)
            if isinstance(self.remat, (tuple, list))
            else (self.remat,) * len(self.channels)
        )
        if len(flags) != len(self.channels):
            raise ValueError(
                f"remat={self.remat!r} must have one flag per stage "
                f"({len(self.channels)})"
            )
        for f in flags:
            if f not in (False, True, "front"):
                raise ValueError(
                    f"remat flag {f!r} must be False, True, or 'front'"
                )
        whole = nn.remat(ResNetBase._stage, static_argnums=(2,))
        front = nn.remat(ResNetBase._stage_front, static_argnums=(2,))
        for i, flag in enumerate(flags):
            with device_scope(f"trunk_stage_{i}"):
                if flag == "front":
                    x = self._stage_rest(front(self, x, i), i)
                elif flag:
                    x = whole(self, x, i)
                else:
                    x = ResNetBase._stage(self, x, i)

        with device_scope("trunk_fc"):
            x = nn.relu(x)
            x = x.reshape((B * T, -1))  # 11*11*32 = 3872 for 84x84 input
            x = nn.relu(nn.Dense(256, dtype=self.dtype, name="fc")(x))
            return split_time_batch(
                x.astype(self.out_dtype), T, B, self.time_major_merge
            )


class ResNet(nn.Module):
    num_actions: int
    use_lstm: bool = False
    dtype: Any = jnp.float32
    # Recurrent-core + policy-head compute dtype (--precision
    # bf16_train sets bfloat16: activations stay half-width past the
    # trunk; logits/baseline/state upcast at the head boundary).
    head_dtype: Any = jnp.float32
    remat: Any = True  # bool or per-stage tuple, see ResNetBase.remat
    # Rematerialize the LSTM scan's backward (the `core` stage of the
    # remat planner, runtime/remat_plan.py; no-op without --use_lstm).
    core_remat: bool = False

    hidden_size: int = 256
    # Opt-in trunk widths. The reference's 16/32/32 (polybeast_learner.py
    # :140-147) keeps parity but wastes most of an MXU tile: a v5e
    # contracts 128x128, and a 16-channel conv's im2col matmul fills 16
    # of 128 output lanes. Wider trunks (e.g. 32/64/64 or 64/128/128)
    # buy model capacity at far less than proportional step-time on the
    # chip — benchmarks/mfu_ablation.py measures exactly that scaling.
    trunk_channels: Sequence[int] = (16, 32, 32)
    time_major_merge: bool = False  # learner.one_device_model sets it

    @nn.compact
    def __call__(self, inputs, core_state=(), *, sample_action: bool = True):
        x = ResNetBase(
            channels=tuple(self.trunk_channels),
            dtype=self.dtype, out_dtype=self.head_dtype,
            remat=self.remat, time_major_merge=self.time_major_merge,
            name="trunk",
        )(inputs["frame"])  # [T, B, H, W, C] uint8 -> [T, B, 256]
        core_input = jnp.concatenate(
            [x, clipped_reward_input(inputs["reward"], self.head_dtype)],
            axis=-1,
        )

        return RecurrentPolicyHead(
            num_actions=self.num_actions,
            use_lstm=self.use_lstm,
            hidden_size=self.hidden_size,
            num_layers=1,
            dtype=self.head_dtype,
            remat=self.core_remat,
            name="head",
        )(core_input, inputs["done"], core_state, sample_action)

    def initial_state(self, batch_size: int) -> Tuple:
        return lstm_initial_state(
            self.use_lstm, 1, self.hidden_size, batch_size
        )
