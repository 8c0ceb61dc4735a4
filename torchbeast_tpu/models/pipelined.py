"""Deep residual-MLP policy whose torso runs as a pipeline over a `pipe`
mesh axis.

Not a reference model family (the reference's nets are 3-block convs that
would never warrant pipelining, SURVEY.md §2.3) — this is the model that
makes pipeline parallelism a FULL-training-step capability rather than an
op demo: the same IMPALA learner step (V-trace loss, RMSProp,
make_update_step) trains it with stage parameters sharded one-per-chip
and activations rotating over ICI (parallel/pp.py GPipe schedule).

Without a mesh the identical parameters run the tower sequentially, which
is the parity oracle pinned by tests/test_pp_model.py: dense path and
pipelined path agree bit-for-close on outputs and gradients.
"""

from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from torchbeast_tpu.models.cores import RecurrentPolicyHead, lstm_initial_state
from torchbeast_tpu.parallel.pp import can_pipeline, pipeline_apply_multi


def _layer_norm(x, scale, bias, eps=1e-6):
    mean = x.mean(axis=-1, keepdims=True)
    var = ((x - mean) ** 2).mean(axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def _stage_fn(p, x, carry, shared):
    """One residual block: LN -> Dense(4d) -> gelu -> Dense(d) -> +x.
    Written over explicit param arrays (not submodules) because stage
    params carry a leading stage axis the pipeline shards over."""
    h = _layer_norm(x, p["ln_scale"], p["ln_bias"])
    h = nn.gelu(h @ p["w_in"] + p["b_in"])
    h = h @ p["w_out"] + p["b_out"]
    return x + h, carry


class PipelinedMLPNet(nn.Module):
    """Standard model interface (inputs dict -> (AgentOutput, state)) with
    a pipeline-parallel torso of `num_stages` residual blocks."""

    # The stage-stacked param names ([S, ...] leaves that shard over the
    # `pipe` axis) — the single source of truth for placement code
    # (__graft_entry__ dryrun, tests) deciding what to pipe-shard.
    STAGE_PARAM_NAMES = (
        "ln_scale", "ln_bias", "w_in", "b_in", "w_out", "b_out",
    )

    num_actions: int
    use_lstm: bool = False
    num_stages: int = 4
    d_model: int = 128
    mesh: Optional[Any] = None  # Mesh with a `pipe` axis -> pipelined
    pipe_axis: str = "pipe"
    n_microbatches: Optional[int] = None
    batch_axis: Optional[str] = None  # composite (data x pipe) mesh: the
    # axis each microbatch's rows shard over (one GPipe per data group)
    dtype: Any = jnp.float32
    # Recurrent-core + policy-head compute dtype (--precision
    # bf16_train sets bfloat16; outputs upcast at the head boundary)
    # and the LSTM-scan remat lever (runtime/remat_plan.py).
    head_dtype: Any = jnp.float32
    core_remat: bool = False

    @nn.compact
    def __call__(self, inputs, core_state=(), *, sample_action: bool = True):
        frame = inputs["frame"]  # [T, B, ...]
        T, B = frame.shape[:2]
        S, d = self.num_stages, self.d_model
        if (
            self.mesh is not None
            and S % self.mesh.shape[self.pipe_axis] != 0
        ):
            raise ValueError(
                f"num_stages={S} must be a multiple of the "
                f"`{self.pipe_axis}` axis size "
                f"{self.mesh.shape[self.pipe_axis]} (k stages per device "
                "run as k pipeline passes)"
            )

        x = frame.reshape((T * B, -1)).astype(jnp.float32) / 255.0
        x = nn.Dense(d, name="encoder")(x)
        one_hot = jax.nn.one_hot(
            inputs["last_action"].reshape(T * B), self.num_actions
        )
        reward = jnp.clip(
            inputs["reward"].astype(jnp.float32), -1, 1
        ).reshape(T * B, 1)
        x = x + nn.Dense(d, name="extras")(
            jnp.concatenate([reward, one_hot], axis=-1)
        )

        ff = 4 * d
        kernel_init = nn.initializers.lecun_normal()
        stage_params = {
            "ln_scale": self.param("ln_scale", nn.initializers.ones, (S, d)),
            "ln_bias": self.param("ln_bias", nn.initializers.zeros, (S, d)),
            "w_in": self.param("w_in", kernel_init, (S, d, ff)),
            "b_in": self.param("b_in", nn.initializers.zeros, (S, ff)),
            "w_out": self.param("w_out", kernel_init, (S, ff, d)),
            "b_out": self.param("b_out", nn.initializers.zeros, (S, d)),
        }

        # Acting/eval batches (B=1 test mode, small inference buckets)
        # need not divide into microbatches; they fall back to the
        # sequential stage loop below — same params, same math — exactly
        # like the transformer's T=1 dense-attention fallback. Pipelining
        # only ever pays off on the big learner batches, and the drivers
        # validate learner-batch divisibility up front so training can
        # never land here silently (monobeast.py).
        if self.mesh is not None and can_pipeline(
            self.mesh, T * B, self.pipe_axis, self.n_microbatches,
            self.batch_axis,
        ):
            x, _ = pipeline_apply_multi(
                _stage_fn,
                stage_params,
                x,
                mesh=self.mesh,
                axis=self.pipe_axis,
                n_microbatches=self.n_microbatches,
                batch_axis=self.batch_axis,
            )
        else:
            for s in range(S):
                p = jax.tree_util.tree_map(
                    lambda leaf: leaf[s], stage_params
                )
                x, _ = _stage_fn(p, x, None, None)

        x = _layer_norm(
            x,
            self.param("final_scale", nn.initializers.ones, (d,)),
            self.param("final_bias", nn.initializers.zeros, (d,)),
        )

        return RecurrentPolicyHead(
            num_actions=self.num_actions,
            use_lstm=self.use_lstm,
            hidden_size=d,
            num_layers=1,
            dtype=self.head_dtype,
            remat=self.core_remat,
            name="head",
        )(x.reshape(T, B, d), inputs["done"], core_state, sample_action)

    def initial_state(self, batch_size: int) -> Tuple:
        return lstm_initial_state(
            self.use_lstm, 1, self.d_model, batch_size
        )
