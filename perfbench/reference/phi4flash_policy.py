"""Plain reference of the Phi-4-mini-flash-cut policy and its IMPALA loss.

Written from the model's description, not from the program: the layers
of Phi-4-mini-flash-reasoning (Microsoft; config.json, `model_type`
phi4flash; SambaY, arXiv:2507.06607; differential attention, arXiv:
2410.05258; Mamba-1, arXiv:2312.00752) and the V-trace actor-critic
loss of Espeholt et al. 2018 (arXiv:1802.01561, section 4; the
recursion is `olmoe_policy.vtrace`), in straightforward `jax.numpy` and
float32 at the highest matmul precision. A Mamba layer is computed ONE
STEP AT A TIME (`lax.scan` over the unroll's steps) over an explicit
state [B, D, N] and an explicit tail of the convolution's three inputs
before the step, both zeroed at a step where `done` is set. Attention is
two explicit softmaxes a query pair over the concatenated [cache; keys],
a row of the batch at a time, each key pair and value repeated for its
two query pairs. What layers 18 and 19 read of layers 16 and 17 the
forward pass keeps in two plain variables (`memory`, `keys_values`). No
chunks, no rematerialisation, no wide heads, no cache roll, no fused
pass, no walk. It reads the program's parameter tree (flax names) so
that both can be given the same weights, and imports nothing from the
program (what kind a layer is, is read as the counts read it:
`perfbench/flops_phi4flash.py` `layers_run`).

`LN(x) = (x - mean) / sqrt(var + eps) * w + b`. A layer is `h = x +
mixer(LN1(x)); y = h + W_down(silu(W_gate u) * (W_up u)), u = LN2(h)`.
`layers_run` lists the published layers that are run, in order; with
`L/2` = `published_num_hidden_layers` // 2, published layer i is

  mamba    i even, i <= L/2: [a, z] = W_in u; a' = silu(conv4(a) + b);
           [d, B_t, C_t] = W_x a'; dt = softplus(W_dt d + b_dt); A =
           -exp(A_log); s_t = exp(dt_t A) s_{t-1} + (dt_t a'_t) B_t^T;
           m_t = s_t C_t + D a'_t; W_out (m_t silu(z_t)). Layer L/2's
           m is the MEMORY.
  sliding  i odd, i < L/2: differential attention over the last
           `sliding_window` keys
  full     i = L/2 + 1: the same over `memory_len` + 1 keys; its keys,
           values and what each query may attend are the KEYS_VALUES
  memory   i even, i > L/2: W_out (MEMORY silu(W_in u))
  cross    i odd, i > L/2 + 1: differential attention with its own
           queries over KEYS_VALUES

differential attention: q = 40 heads of 64, k and v = 20 heads of 64;
query pair j = heads (2j, 2j + 1), key pair g = key heads (2g, 2g + 1),
value g = value heads (2g, 2g + 1) side by side; pair j reads key pair
and value j // 2; o_j = softmax(q1 k1^T / 8) v - lambda softmax(q2 k2^T
/ 8) v; lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init(i),
lambda_init(i) = 0.8 - 0.6 exp(-0.3 i); o_j / sqrt(mean(o_j^2) + eps) *
w * (1 - lambda_init(i)); out_proj + b.

Departures from the published model, each marked `# DEPARTURE` below:
an observation projection replaces the token embedding; policy and
baseline heads replace the LM head; an attention layer attends over a
rolling cache cut at episode ends, not over 262,144 positions; a Mamba
layer's state and tail are zeroed where an episode ends.
"""

import math

import jax
import jax.numpy as jnp

from perfbench.flops_phi4flash import (
    CROSS,
    FULL,
    MAMBA,
    MEMORY,
    SLIDING,
    layers_run,
)
from perfbench.reference.mellum2_policy import _may_attend
from perfbench.reference.olmoe_policy import vtrace


def _layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _mamba(u, done, p, state, tail, config):
    """u [T, B, d] (already normed), done [T, B], state [B, D, N], tail
    [K - 1, B, D] -> (out [T, B, d], m [T, B, D], state, tail)."""
    K, N, R = config["d_conv"], config["d_state"], config["dt_rank"]
    taps = p["conv_kernel"]  # [K, D], the last the step's own
    A = -jnp.exp(p["A_log"]).T  # the program keeps [N, D]

    def step(carry, inputs):
        s, tail = carry
        u_t, done_t = inputs  # [B, d], [B]
        # DEPARTURE: an episode's first step reads nothing from before.
        s = jnp.where(done_t[:, None, None], 0.0, s)
        tail = jnp.where(done_t[None, :, None], 0.0, tail)
        a, z = jnp.split(u_t @ p["in_proj"]["kernel"], 2, axis=-1)
        conv = p["conv_bias"] + taps[K - 1] * a
        for tap in range(K - 1):
            conv = conv + taps[tap] * tail[tap]
        tail = jnp.concatenate([tail[1:], a[None]], axis=0)
        a = jax.nn.silu(conv)
        joined = a @ p["x_proj"]["kernel"]
        d, B_t, C_t = joined[:, :R], joined[:, R : R + N], joined[:, R + N :]
        dt = jax.nn.softplus(
            d @ p["dt_proj"]["kernel"] + p["dt_proj"]["bias"]
        )
        s = jnp.exp(dt[:, :, None] * A) * s + (
            (dt * a)[:, :, None] * B_t[:, None, :]
        )
        m = jnp.einsum("bdn,bn->bd", s, C_t) + p["D"] * a
        return (s, tail), ((m * jax.nn.silu(z)) @ p["out_proj"]["kernel"], m)

    (state, tail), (out, m) = jax.lax.scan(step, (state, tail), (u, done))
    return out, m, state, tail


def lambda_init(published_index):
    return 0.8 - 0.6 * math.exp(-0.3 * published_index)


def _difference(q, k_all, v_all, allowed, p, index, config):
    """q [B, T, Hq, hd]; k_all, v_all [B, K, Hkv, hd]; allowed [B, T, K]
    -> the mixer's output [B, T, d]."""
    rows, steps, Hq, hd = q.shape
    init = lambda_init(index)
    lam = (
        jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"]))
        - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"]))
        + init
    )

    def one_row(args):
        q, k_all, v_all, allowed = args
        q1, q2 = q[:, 0::2], q[:, 1::2]  # [T, pairs, hd]
        # Two query pairs read a key pair: each key pair twice.
        k1 = jnp.repeat(k_all[:, 0::2], 2, axis=1)
        k2 = jnp.repeat(k_all[:, 1::2], 2, axis=1)
        v = jnp.repeat(
            jnp.concatenate([v_all[:, 0::2], v_all[:, 1::2]], axis=-1),
            2, axis=1,
        )  # [K, pairs, 2 hd]

        def attend(q, k):
            scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
            scores = jnp.where(allowed[None], scores, -1e30)
            return jnp.einsum(
                "hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v
            )

        return attend(q1, k1) - lam * attend(q2, k2)

    o = jax.lax.map(one_row, (q, k_all, v_all, allowed))  # [B, T, pairs, 2hd]
    o = o * jax.lax.rsqrt(
        jnp.mean(o * o, axis=-1, keepdims=True) + config["layer_norm_eps"]
    ) * p["subln"]["scale"] * (1.0 - init)
    return (
        o.reshape(rows, steps, -1) @ p["out_proj"]["kernel"]
        + p["out_proj"]["bias"]
    )


def _impala_loss(logits, baseline, batch, config):
    """(total, scale) of IMPALA's three terms, as the reference
    implementation sums them, over one [T+1, B] batch."""
    bootstrap_value = baseline[-1]
    logits, values = logits[:-1], baseline[:-1]
    actions = batch["action"][1:]
    rewards = jnp.clip(batch["reward"][1:].astype(jnp.float32), -1, 1)
    discounts = (
        (~batch["done"][1:]).astype(jnp.float32) * config["discounting"]
    )

    def log_prob(lg):
        chosen = jnp.take_along_axis(
            jax.nn.log_softmax(lg), actions[..., None], axis=-1
        )
        return chosen[..., 0]

    behaviour = batch["policy_logits"][1:].astype(jnp.float32)
    log_rhos = log_prob(logits) - log_prob(behaviour)
    # The targets are constants of the loss: no gradient flows through
    # them (section 4.2 of the IMPALA paper).
    vs, advantages = jax.lax.stop_gradient(vtrace(
        log_rhos, discounts, rewards, values, bootstrap_value
    ))
    pg_terms = -log_prob(logits) * advantages
    baseline_loss = 0.5 * jnp.sum(jnp.square(vs - values))
    policy = jax.nn.softmax(logits)
    entropy_loss = jnp.sum(policy * jax.nn.log_softmax(logits))
    total = (
        jnp.sum(pg_terms)
        + config["baseline_cost"] * baseline_loss
        + config["entropy_cost"] * entropy_loss
    )
    scale = (
        jnp.sum(jnp.abs(pg_terms))
        + config["baseline_cost"] * baseline_loss
        + config["entropy_cost"] * jnp.abs(entropy_loss)
    )
    return total, scale


def forward(params, batch, core_state, config):
    """(policy_logits [T, B, A], baseline [T, B], new state, aux loss:
    none in this model). `core_state` has an item for each layer run
    that carries something, in order: (s [N, B, D], tail [K - 1, B, D])
    for a Mamba layer, (k, v [M, B, Hkv, hd], valid [M, B]) for a layer
    that attends over its own keys."""
    p = params["params"]
    eps = config["layer_norm_eps"]
    Hq, Hkv = config["num_attention_heads"], config["num_key_value_heads"]
    hd = config["hidden_size"] // Hq
    frame = batch["frame"]
    steps, rows = frame.shape[:2]
    # DEPARTURE: a linear projection of the frame (scaled to [-1, 1]),
    # plus one of the clipped reward and the last action, in place of the
    # token embedding.
    x = 2.0 * frame.reshape(steps * rows, -1).astype(jnp.float32) / 255.0 - 1.0
    x = x @ p["Dense_0"]["kernel"] + p["Dense_0"]["bias"]
    reward = jnp.clip(batch["reward"].astype(jnp.float32), -1, 1)
    extras = jnp.concatenate([
        reward.reshape(-1, 1),
        jax.nn.one_hot(
            batch["last_action"].reshape(-1), config["num_actions"]
        ),
    ], axis=-1)
    x = x + extras @ p["extras"]["kernel"] + p["extras"]["bias"]
    x = x.reshape(steps, rows, -1).transpose(1, 0, 2)  # [B, T, d]

    done = batch["done"]
    ends = jnp.cumsum(done.astype(jnp.int32), axis=0).T  # [B, T]
    kinds = layers_run(config)
    assert len(kinds) == config["num_hidden_layers"]
    carried = iter(core_state)
    new_state = []
    # What the later layers read of the earlier ones.
    memory = keys_values = None
    boundary = config["published_num_hidden_layers"] // 2
    for layer, (index, kind) in enumerate(kinds):
        block = p[f"block_{layer}"]
        u = _layer_norm(x, block["mixer_norm"], eps)
        if kind == MAMBA:
            s, tail = next(carried)
            out, m, s, tail = _mamba(
                u.transpose(1, 0, 2), done, block, s.transpose(1, 2, 0),
                tail, config,
            )
            x = x + out.transpose(1, 0, 2)
            new_state.append((s.transpose(2, 0, 1), tail))
            if index == boundary:
                # ASSUMED: the scan's output with the skip, before the
                # gate, is what the memory units read.
                memory = m.transpose(1, 0, 2)
        elif kind in (SLIDING, FULL):
            k_cache, v_cache, valid = next(carried)
            # DEPARTURE: a rolling cache of M keys and values, cut at
            # episode ends, in place of 262,144 positions; a sliding
            # layer's is its window less the query's own step.
            M = k_cache.shape[0]
            assert M == (
                config["memory_len"] if kind == FULL
                else min(config["memory_len"], config["sliding_window"] - 1)
            )
            allowed = _may_attend(done, valid, M)
            joined = u @ block["Wqkv"]["kernel"] + block["Wqkv"]["bias"]
            q, k, v = (
                part.reshape(rows, steps, -1, hd) for part in jnp.split(
                    joined, [Hq * hd, (Hq + Hkv) * hd], axis=-1
                )
            )
            k_all = jnp.concatenate([k_cache.transpose(1, 0, 2, 3), k], axis=1)
            v_all = jnp.concatenate([v_cache.transpose(1, 0, 2, 3), v], axis=1)
            x = x + _difference(q, k_all, v_all, allowed, block, index, config)
            if kind == FULL:
                keys_values = (k_all, v_all, allowed)
            # The cache the actor would carry on: the last M of [cache;
            # this unroll], of which only the last episode's stay valid.
            last = ends[:, -1:]
            kept = jnp.concatenate([
                (valid.T > 0) & (last == 0), ends == last,
            ], axis=1)
            new_state.append((
                k_all[:, -M:].transpose(1, 0, 2, 3),
                v_all[:, -M:].transpose(1, 0, 2, 3),
                kept[:, -M:].astype(jnp.float32).T,
            ))
        elif kind == MEMORY:
            x = x + (
                memory * jax.nn.silu(u @ block["in_proj"]["kernel"])
            ) @ block["out_proj"]["kernel"]
        else:
            assert kind == CROSS
            q = (u @ block["Wq"]["kernel"] + block["Wq"]["bias"]).reshape(
                rows, steps, Hq, hd
            )
            x = x + _difference(q, *keys_values, block, index, config)
        u = _layer_norm(x, block["mlp_norm"], eps)
        x = x + (
            jax.nn.silu(u @ block["gate_proj"]["kernel"])
            * (u @ block["up_proj"]["kernel"])
        ) @ block["down_proj"]["kernel"]
    x = _layer_norm(x, p["final_norm"], eps)
    x = x.transpose(1, 0, 2).reshape(steps * rows, -1)
    # DEPARTURE: policy and baseline heads in place of the LM head.
    head = p["head"]
    logits = x @ head["policy"]["kernel"] + head["policy"]["bias"]
    baseline = x @ head["baseline"]["kernel"] + head["baseline"]["bias"]
    return (
        logits.reshape(steps, rows, -1), baseline.reshape(steps, rows),
        tuple(new_state), 0.0,
    )


def loss_and_scale(params, batch, core_state, config):
    """The total loss of one [T+1, B] batch, and the sum of the
    magnitudes of its terms: the total is a sum of signed terms and can
    come out near zero, so a difference between two computations of it
    is judged against the scale, which cannot."""
    with jax.default_matmul_precision("highest"):
        logits, baseline, _, _ = forward(params, batch, core_state, config)
        return _impala_loss(logits, baseline, batch, config)


def loss(params, batch, core_state, config):
    """The total loss of one [T+1, B] batch."""
    return loss_and_scale(params, batch, core_state, config)[0]
