"""Plain reference of the Granite-4.0-H-Micro-period policy and its
IMPALA loss.

Written from the model's description, not from the program: the layers
of ibm-granite/granite-4.0-h-micro (config.json, `model_type`
granitemoehybrid; the Mamba-2 mixer of Dao and Gu, arXiv:2405.21060,
section 7) and the V-trace actor-critic loss of Espeholt et al. 2018
(arXiv:1802.01561, section 4), in straightforward `jax.numpy` and
float32 at the highest matmul precision. The state-space mixer is the
RECURRENCE, one step at a time (`lax.scan` over the unroll's steps, the
state and the convolution's window zeroed at a step where `done` is
set; the mixer is `nemotron3_policy._mamba`, the one plain Mamba-2 of
these references); the convolution is four shifted adds over that
window; attention
is one masked matrix over the cached and the unrolled steps, a row of
the batch at a time. No chunks, no decay matrix, no cache roll, no
fused pass. It reads the program's parameter tree (flax names) so that
both can be given the same weights, and imports nothing from the
program.

Float32, rmsnorm eps `rms_norm_eps`, no bias but the convolution's:

    x0 = embedding_multiplier * encoder(observation)
    layer l, mixer by layer_types[l]:
      x = x + residual_multiplier * mixer_l(rmsnorm(x))
      [g | v] = W_in rmsnorm(x);  x = x + residual_multiplier * W_out
                (silu(g) * v)      `shared_mlp`, gate's half first
    mamba:  [z | xBC | dt] = W_inproj h
            xBC = silu(conv_depthwise(xBC) + b_conv);  x [H, P], B [G, N],
            C [G, N], head h reading group h // (H / G) (G = 1: all)
            dt = softplus(dt + dt_bias);  A = -exp(A_log)
            h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T;  y_t = h_t C_t + D x_t
            out = W_outproj (rmsnorm_per_group(y * silu(z)) * w)
    attention: q [Hq, hd], k, v [Hkv, hd] = W h;  no positional
            embedding;  out = W_o softmax(q k^T * attention_multiplier) v
            over [cache; unroll], causal
    y = rmsnorm(x);  policy logits = (W_pi y + b) / logits_scaling
    baseline = W_v y + b

Departures from the published model, each marked `# DEPARTURE` below:
an observation projection replaces the token embedding; policy and
baseline heads replace the LM head (the logits' division stays on the
policy's); the attention layer attends over a rolling cache cut at
episode ends, not over 131,072 positions; a Mamba layer's state and
window are zeroed where an episode ends.
"""

import jax
import jax.numpy as jnp

from perfbench.reference.mellum2_policy import _may_attend, _rmsnorm
from perfbench.reference.nemotron3_policy import _mamba as _mamba2
from perfbench.reference.phi4flash_policy import _impala_loss


def _mamba(h, done, p, state, config):
    """h [T, B, d] (already normed), done [T, B] -> (out [T, B, d], the
    state and the window after the last step): the Mamba-2 mixer of
    `nemotron3_policy._mamba`, the recurrence a step at a time and the
    convolution from its window, read under granitemoehybrid's names
    for the same sizes (one B/C group: every head reads it; the gated
    norm then runs over all of d_inner)."""
    H, P = config["mamba_n_heads"], config["mamba_d_head"]
    assert H * P == config["mamba_expand"] * config["hidden_size"]
    return _mamba2(h, done, p, state, {
        "mamba_num_heads": H, "mamba_head_dim": P,
        "n_groups": config["mamba_n_groups"],
        "ssm_state_size": config["mamba_d_state"],
        "conv_kernel": config["mamba_d_conv"],
        "use_conv_bias": config["mamba_conv_bias"],
        "mamba_proj_bias": config["mamba_proj_bias"],
        "mamba_hidden_act": config["hidden_act"],
        "layer_norm_epsilon": config["rms_norm_eps"],
    })


def _attention(h, p, cache, allowed, config):
    """h [B, T, d] (already normed) -> (attended [B, T, d], k, v)."""
    rows, steps, d = h.shape
    Hq, Hkv = config["num_attention_heads"], config["num_key_value_heads"]
    # ASSUMED: hidden_size / num_attention_heads; there is no `head_dim`.
    hd = d // Hq
    assert not config["attention_bias"]
    assert config["position_embedding_type"] == "nope"
    q = (h @ p["q"]["kernel"]).reshape(rows, steps, Hq, hd)
    k = (h @ p["k"]["kernel"]).reshape(rows, steps, Hkv, hd)
    v = (h @ p["v"]["kernel"]).reshape(rows, steps, Hkv, hd)
    k_all = jnp.concatenate([cache[0], k], axis=1)  # [B, M+T, Hkv, hd]
    v_all = jnp.concatenate([cache[1], v], axis=1)

    def one_row(args):
        q, k_all, v_all, allowed = args
        # Every query head with its key/value head, repeated.
        keys = jnp.repeat(k_all, Hq // Hkv, axis=1)
        values = jnp.repeat(v_all, Hq // Hkv, axis=1)
        # The config's constant, not hd ** -0.5.
        scores = (
            jnp.einsum("qhd,khd->hqk", q, keys)
            * config["attention_multiplier"]
        )
        scores = jnp.where(allowed[None], scores, -1e30)
        return jnp.einsum(
            "hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), values
        )

    attended = jax.lax.map(one_row, (q, k_all, v_all, allowed))
    return attended.reshape(rows, steps, Hq * hd) @ p["o"]["kernel"], k, v


def _shared_mlp(h, p, config):
    assert config["hidden_act"] == "silu"
    width = config["shared_intermediate_size"]
    joined = h @ p["input_linear"]["kernel"]
    return (
        jax.nn.silu(joined[..., :width]) * joined[..., width:]
    ) @ p["output_linear"]["kernel"]


def forward(params, batch, core_state, config):
    """(policy_logits [T, B, A], baseline [T, B], new state, aux loss).
    `core_state` has an item a layer, in order: (k, v, valid) for an
    attention layer, (h, window) for a Mamba layer."""
    p = params["params"]
    eps = config["rms_norm_eps"]
    M = config["memory_len"]
    residual = config["residual_multiplier"]
    assert config["normalization_function"] == "rmsnorm"
    assert config["num_local_experts"] == 0  # no router, no expert
    frame = batch["frame"]
    steps, rows = frame.shape[:2]
    # DEPARTURE: a linear projection of the frame (scaled to [-1, 1]),
    # plus one of the clipped reward and the last action, in place of the
    # token embedding; `embedding_multiplier` on what stands in its place.
    # Contracted with steps and rows both free: merged into one axis
    # first, the chip's compiler laid the float frames of 4 rows x 512
    # steps out with the rows on the lanes, 7.4 GB where they are 0.23.
    x = 2.0 * frame.reshape(steps, rows, -1).astype(jnp.float32) / 255.0 - 1.0
    x = jnp.einsum("tbf,fd->tbd", x, p["Dense_0"]["kernel"])
    x = (x + p["Dense_0"]["bias"]).reshape(steps * rows, -1)
    reward = jnp.clip(batch["reward"].astype(jnp.float32), -1, 1)
    extras = jnp.concatenate([
        reward.reshape(-1, 1),
        jax.nn.one_hot(
            batch["last_action"].reshape(-1), config["num_actions"]
        ),
    ], axis=-1)
    x = x + extras @ p["extras"]["kernel"] + p["extras"]["bias"]
    x = config["embedding_multiplier"] * x
    x = x.reshape(steps, rows, -1).transpose(1, 0, 2)  # [B, T, d]

    done = batch["done"]
    ends = jnp.cumsum(done.astype(jnp.int32), axis=0).T  # [B, T]
    carried = iter(core_state)
    new_state = []
    kinds = config["layer_types"]
    assert len(kinds) == config["num_hidden_layers"]
    for layer, kind in enumerate(kinds):
        block = p[f"block_{layer}"]
        h = _rmsnorm(x, block["norm"], eps)
        if kind == "mamba":
            out, state = _mamba(
                h.transpose(1, 0, 2), done, block, next(carried), config
            )
            x = x + residual * out.transpose(1, 0, 2)
            new_state.append(state)
        else:
            assert kind == "attention", kind
            k_cache, v_cache, valid = next(carried)
            # DEPARTURE: a rolling cache of M keys and values, cut at
            # episode ends, in place of 131,072 positions.
            assert k_cache.shape[0] == M
            allowed = _may_attend(done, valid, M)
            cache = (
                k_cache.transpose(1, 0, 2, 3), v_cache.transpose(1, 0, 2, 3)
            )
            attended, k, v = _attention(h, block, cache, allowed, config)
            x = x + residual * attended
            # The cache the actor would carry on: the last M of [cache;
            # this unroll], of which only the last episode's stay valid.
            last = ends[:, -1:]
            kept = jnp.concatenate([
                (valid.T > 0) & (last == 0), ends == last,
            ], axis=1)
            new_state.append((
                jnp.concatenate([cache[0], k], axis=1)[:, -M:].transpose(
                    1, 0, 2, 3
                ),
                jnp.concatenate([cache[1], v], axis=1)[:, -M:].transpose(
                    1, 0, 2, 3
                ),
                kept[:, -M:].astype(jnp.float32).T,
            ))
        x = x + residual * _shared_mlp(
            _rmsnorm(x, block["mlp_norm"], eps), block, config
        )
    x = _rmsnorm(x, p["final_norm"], eps)
    x = x.transpose(1, 0, 2).reshape(steps * rows, -1)
    # DEPARTURE: policy and baseline heads in place of the LM head;
    # `logits_scaling` divides the policy's logits as it does the LM's.
    head = p["head"]
    logits = (
        x @ head["policy"]["kernel"] + head["policy"]["bias"]
    ) / config["logits_scaling"]
    baseline = x @ head["baseline"]["kernel"] + head["baseline"]["bias"]
    return (
        logits.reshape(steps, rows, -1), baseline.reshape(steps, rows),
        tuple(new_state), 0.0,
    )


def loss_and_scale(params, batch, core_state, config):
    """The total loss of one [T+1, B] batch (IMPALA's three terms; this
    model has no auxiliary term), and the sum of the magnitudes of its
    terms, which a difference between two computations is judged by."""
    with jax.default_matmul_precision("highest"):
        logits, baseline, _, _ = forward(params, batch, core_state, config)
        return _impala_loss(logits, baseline, batch, config)


def loss(params, batch, core_state, config):
    """The total loss of one [T+1, B] batch."""
    return loss_and_scale(params, batch, core_state, config)[0]
