"""Pipeline-parallel transformer policy.

The transformer tower IS stage-uniform — every block maps [B, T, d] ->
[B, T, d] with a per-layer KV cache — so it pipelines under the GPipe
schedule (parallel/pp.py) with the cache as resident stage carry. This
module restructures the TransformerNet stack for that: all block
parameters are explicit stacked arrays with a leading `[L, ...]` layer
axis (sharded one layer-group per chip over the `pipe` mesh axis), and
the per-microbatch stage function is a pure function over one layer's
slice. No reference counterpart (the reference's nets are 3-block convs,
SURVEY.md §2.3) — this closes the framework's own "scales deep towers
across chips" claim for its long-context family.

Attention semantics are IDENTICAL to models/transformer.py's dense path:
band-windowed causal attention over [cache; unroll] with segment masking,
rolling per-layer KV cache carried as recurrent state, learned relative
position bias (the shared body `ops/attention.dense_transformer_attend`
keeps the numerics pinned to the same code the dense TransformerNet
uses). Acting (T=1, any bucket size) and eval batches whose batch dim
doesn't divide into microbatches fall back to a sequential loop over the
SAME stacked parameters — the parity oracle pinned by
tests/test_pp_model.py::test_pipelined_transformer_*.

Out of scope by construction: sequence parallelism and MoE inside the
pipelined stack (the drivers reject those flag combinations; composing
pp with sp/ep needs a multi-axis mesh schedule, parallel/mesh.py is
where one would grow).
"""

from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from torchbeast_tpu.models.cores import RecurrentPolicyHead
from torchbeast_tpu.ops.attention import (
    band_relative_offsets,
    dense_transformer_attend,
    roll_kv_cache,
    segment_ids_from_done,
)
from torchbeast_tpu.parallel.pp import can_pipeline, pipeline_apply_multi


def _layer_norm(x, scale, bias, eps=1e-6):
    x = x.astype(jnp.float32)
    mean = x.mean(axis=-1, keepdims=True)
    var = ((x - mean) ** 2).mean(axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def _make_stage_fn(band, offsets, memory_len, dtype):
    """One transformer block over explicit param arrays.

    `band`/`offsets` are trace-time constants (functions of T and M
    only), so they close over the stage rather than ride the microbatch
    plumbing. Shapes: x [b, T, d]; carry (k [b, M, H, hd], v likewise,
    valid [b, M]); shared (seg [b, T], no_done [b, T])."""
    M = memory_len

    def stage_fn(p, x, carry, shared):
        k_cache, v_cache, valid = carry
        seg, no_done = shared

        # --- attention ---
        h = _layer_norm(x, p["ln1_scale"], p["ln1_bias"]).astype(dtype)
        q = jnp.einsum("btd,dhk->bthk", h, p["wq"]) + p["bq"]
        k = jnp.einsum("btd,dhk->bthk", h, p["wk"]) + p["bk"]
        v = jnp.einsum("btd,dhk->bthk", h, p["wv"]) + p["bv"]

        cache_mask = (
            band[None, :, :M]
            & valid[:, None, :].astype(bool)
            & no_done[:, :, None]
        )  # [b, T, M]
        same = seg[:, :, None] == seg[:, None, :]
        seq_mask = band[None, :, M:] & same  # [b, T, T]
        mask = jnp.concatenate([cache_mask, seq_mask], axis=-1)

        k_all = jnp.concatenate([k_cache.astype(k.dtype), k], axis=1)
        v_all = jnp.concatenate([v_cache.astype(v.dtype), v], axis=1)
        attended = dense_transformer_attend(
            q, k_all, v_all, mask, offsets, p["rel_bias"]
        )
        x = x + (
            jnp.einsum("bthk,hkd->btd", attended, p["wo"]) + p["bo"]
        ).astype(jnp.float32)

        # --- FFN ---
        h = _layer_norm(x, p["ln2_scale"], p["ln2_bias"]).astype(dtype)
        h = nn.gelu(h @ p["w1"] + p["b1"])
        x = x + (h @ p["w2"] + p["b2"]).astype(jnp.float32)

        # --- roll the cache (shared helper, ops/attention.py — the same
        # code path TransformerNet uses, so semantics cannot drift) ---
        new_carry = roll_kv_cache(
            k_cache, v_cache, valid,
            k.astype(jnp.float32), v.astype(jnp.float32),
            seg, no_done,
        )
        return x, new_carry

    return stage_fn


class PipelinedTransformerNet(nn.Module):
    """Standard model interface (inputs dict -> (AgentOutput, state)) with
    the block stack runnable as a pipeline over a `pipe` mesh axis. State
    convention matches TransformerNet: a tuple per layer of
    (k [M, B, H, hd], v [M, B, H, hd], valid [M, B])."""

    # Stacked `[L, ...]` leaves that shard over the `pipe` axis — the
    # single source of truth for placement code (drivers, dryrun, tests).
    STAGE_PARAM_NAMES = (
        "ln1_scale", "ln1_bias", "wq", "bq", "wk", "bk", "wv", "bv",
        "rel_bias", "wo", "bo", "ln2_scale", "ln2_bias",
        "w1", "b1", "w2", "b2",
    )

    memory_is_kv_cache = True  # --use_lstm does not apply
    remat_lever = "blocks"  # --remat reaches `remat` (remat_plan.py)
    # Depth is --pipeline_stages' to set (it must divide over the pipe
    # mesh), and the window has no flag here: --num_layers and
    # --memory_len are refused (models/__init__.py `takes_flag`).
    flag_refused_fields = ("num_layers", "memory_len")

    num_actions: int
    use_lstm: bool = False  # accepted for registry uniformity; unused
    num_layers: int = 4
    d_model: int = 128
    num_heads: int = 4
    memory_len: int = 64
    dtype: Any = jnp.float32
    mesh: Optional[Any] = None  # Mesh with a `pipe` axis -> pipelined
    pipe_axis: str = "pipe"
    n_microbatches: Optional[int] = None
    batch_axis: Optional[str] = None  # composite (data x pipe) mesh: the
    # axis each microbatch's rows shard over (one GPipe per data group)
    remat: bool = False  # jax.checkpoint around each stage invocation
    # (saves the stage input only — the standard memory lever for deep
    # towers; applies to both the pipelined and the sequential path so
    # the parity oracle stays exact)
    # Policy-head compute dtype (--precision bf16_train sets bfloat16;
    # same boundary contract as TransformerNet.head_dtype).
    head_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, inputs, core_state, *, sample_action: bool = True):
        frame = inputs["frame"]  # [T, B, ...]
        T, B = frame.shape[:2]
        L, d, H, M = (
            self.num_layers, self.d_model, self.num_heads, self.memory_len
        )
        hd = d // H
        if self.mesh is not None:
            P_dev = self.mesh.shape[self.pipe_axis]
            if L % P_dev != 0:
                raise ValueError(
                    f"num_layers={L} must be a multiple of the "
                    f"`{self.pipe_axis}` axis size {P_dev}"
                )

        x = frame.reshape((T * B, -1)).astype(self.dtype) / 255.0
        x = nn.Dense(d, name="encoder", dtype=self.dtype)(x)
        one_hot = jax.nn.one_hot(
            inputs["last_action"].reshape(T * B), self.num_actions
        )
        reward = jnp.clip(
            inputs["reward"].astype(jnp.float32), -1, 1
        ).reshape(T * B, 1)
        x = x.astype(jnp.float32) + nn.Dense(d, name="extras")(
            jnp.concatenate([reward, one_hot], axis=-1)
        )
        x = x.reshape(T, B, d).transpose(1, 0, 2)  # [B, T, d]

        done = inputs["done"]  # [T, B]
        seg = segment_ids_from_done(done).T  # [B, T]
        no_done = jnp.cumsum(done.astype(jnp.int32), axis=0).T == 0

        # Band mask / relative offsets — the same shared implementation
        # TransformerNet consumes (ops/attention.py).
        band, offsets = band_relative_offsets(T, M)

        vs = nn.initializers.variance_scaling
        stage_params = {
            "ln1_scale": self.param(
                "ln1_scale", nn.initializers.ones, (L, d)
            ),
            "ln1_bias": self.param(
                "ln1_bias", nn.initializers.zeros, (L, d)
            ),
            "wq": self.param(
                "wq",
                vs(1.0, "fan_in", "truncated_normal",
                   in_axis=1, out_axis=(2, 3), batch_axis=0),
                (L, d, H, hd),
            ),
            "bq": self.param("bq", nn.initializers.zeros, (L, H, hd)),
            "wk": self.param(
                "wk",
                vs(1.0, "fan_in", "truncated_normal",
                   in_axis=1, out_axis=(2, 3), batch_axis=0),
                (L, d, H, hd),
            ),
            "bk": self.param("bk", nn.initializers.zeros, (L, H, hd)),
            "wv": self.param(
                "wv",
                vs(1.0, "fan_in", "truncated_normal",
                   in_axis=1, out_axis=(2, 3), batch_axis=0),
                (L, d, H, hd),
            ),
            "bv": self.param("bv", nn.initializers.zeros, (L, H, hd)),
            "rel_bias": self.param(
                "rel_bias", nn.initializers.zeros, (L, H, M + 1)
            ),
            "wo": self.param(
                "wo",
                vs(1.0, "fan_in", "truncated_normal",
                   in_axis=(1, 2), out_axis=3, batch_axis=0),
                (L, H, hd, d),
            ),
            "bo": self.param("bo", nn.initializers.zeros, (L, d)),
            "ln2_scale": self.param(
                "ln2_scale", nn.initializers.ones, (L, d)
            ),
            "ln2_bias": self.param(
                "ln2_bias", nn.initializers.zeros, (L, d)
            ),
            "w1": self.param(
                "w1",
                vs(1.0, "fan_in", "truncated_normal",
                   in_axis=1, out_axis=2, batch_axis=0),
                (L, d, 4 * d),
            ),
            "b1": self.param("b1", nn.initializers.zeros, (L, 4 * d)),
            "w2": self.param(
                "w2",
                vs(1.0, "fan_in", "truncated_normal",
                   in_axis=1, out_axis=2, batch_axis=0),
                (L, 4 * d, d),
            ),
            "b2": self.param("b2", nn.initializers.zeros, (L, d)),
        }

        stage_fn = _make_stage_fn(band, offsets, M, self.dtype)
        if self.remat:
            stage_fn = jax.checkpoint(stage_fn)
        shared = (seg, no_done)

        # state tuple (k [M, B, H, hd], ...) -> stage layout [b, M, ...]
        caches_b = [
            (
                k.transpose(1, 0, 2, 3),
                v.transpose(1, 0, 2, 3),
                valid.T,
            )
            for (k, v, valid) in core_state
        ]

        # Acting/eval batches whose B doesn't divide into microbatches
        # fall back to the sequential layer loop — same params, same math
        # (pipelining only pays off on the big learner batches, and the
        # drivers validate learner-batch divisibility up front so
        # training can never land here silently, monobeast.py).
        if self.mesh is not None and can_pipeline(
            self.mesh, B, self.pipe_axis, self.n_microbatches,
            self.batch_axis,
        ):
            stage_carry = jax.tree_util.tree_map(
                lambda *leaves: jnp.stack(leaves, axis=0), *caches_b
            )
            x, new_carry = pipeline_apply_multi(
                stage_fn,
                stage_params,
                x,
                mesh=self.mesh,
                axis=self.pipe_axis,
                n_microbatches=self.n_microbatches,
                stage_carry=stage_carry,
                shared=shared,
                batch_axis=self.batch_axis,
            )
            new_caches_b = [
                jax.tree_util.tree_map(lambda leaf: leaf[layer], new_carry)
                for layer in range(L)
            ]
        else:
            new_caches_b = []
            for layer in range(L):
                p = jax.tree_util.tree_map(
                    lambda leaf: leaf[layer], stage_params
                )
                x, c = stage_fn(p, x, caches_b[layer], shared)
                new_caches_b.append(c)

        new_state = tuple(
            (
                k.transpose(1, 0, 2, 3),
                v.transpose(1, 0, 2, 3),
                valid.T,
            )
            for (k, v, valid) in new_caches_b
        )

        x = _layer_norm(
            x,
            self.param("final_scale", nn.initializers.ones, (d,)),
            self.param("final_bias", nn.initializers.zeros, (d,)),
        )
        core_output = x.transpose(1, 0, 2)  # [T, B, d], the head's layout

        out, _ = RecurrentPolicyHead(
            num_actions=self.num_actions,
            use_lstm=False,
            hidden_size=d,
            num_layers=1,
            dtype=self.head_dtype,
            name="head",
        )(core_output, done, (), sample_action)
        return out, new_state

    def initial_state(self, batch_size: int) -> Tuple:
        hd = self.d_model // self.num_heads
        M = self.memory_len
        return tuple(
            (
                jnp.zeros((M, batch_size, self.num_heads, hd), jnp.float32),
                jnp.zeros((M, batch_size, self.num_heads, hd), jnp.float32),
                jnp.zeros((M, batch_size), jnp.float32),
            )
            for _ in range(self.num_layers)
        )
