"""Plain reference of the OLMoE-block policy and its IMPALA loss.

Written from the model's description, not from the program: the block of
OLMoE-1B-7B-0125-Instruct (config.json, `model_type` olmoe; Muennighoff
et al. 2024, arXiv:2409.02060) and the V-trace actor-critic loss of
Espeholt et al. 2018 (arXiv:1802.01561, section 4), in straightforward
`jax.numpy` and float32 at the highest matmul precision. No sort, no
grouped matmul, no cache tricks: every expert runs on every token under a
0/1 mask of the token's top experts; attention is one masked matrix over
the cached and the unrolled steps; V-trace is the sequential recursion.
It reads the program's parameter tree (flax names) so that both can be
given the same weights, and imports nothing from the program.

Departures from the published model, each marked `# DEPARTURE` below:
an observation projection replaces the token embedding; policy and
baseline heads replace the LM head; attention is over a window of
`memory_len` past steps carried in a rolling cache, cut at episode ends,
not over 4,096 positions; RoPE positions are relative to the unroll's
first step. What the config does not spell out, each marked `# ASSUMED`:
`intermediate_size` is the width of one expert; q and k are RMS-normed
over the whole projected width before the split into heads; the
load-balance weight is 0.01 and there is no router z-loss.
"""

import jax
import jax.numpy as jnp


def _rmsnorm(x, p, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (
        p["scale"]
    )


def _rope(x, positions, theta):
    """Rotate-half RoPE over the last axis. x [..., S, H, D]."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, axis=-1)[:, None, :]
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rotated * sin


def _may_attend(done, valid, memory_len):
    """[B, T, M+T] 0/1: query step t of row b may attend key j.

    Keys are the M cache slots (slot m is time m - M) then the T unroll
    steps (step j is time j). A key at time s is visible from t when
    t - M <= s <= t and no episode ended in between: `done[u]` marks the
    FIRST step of a new episode, so none of steps s+1..t may carry it. A
    cache slot must also hold something (`valid`), and steps 0..t must
    all be free of `done`.
    """
    steps, rows = done.shape
    M = memory_len
    # ends[t]: how many episode starts lie in steps 0..t.
    ends = jnp.cumsum(done.astype(jnp.int32), axis=0).T  # [B, T]
    q_time = jnp.arange(steps)
    cache_time = jnp.arange(M) - M
    in_window_cache = (q_time[:, None] - cache_time[None, :]) <= M  # [T, M]
    cache = (
        in_window_cache[None]
        & (valid.T[:, None, :] > 0)
        & (ends[:, :, None] == 0)
    )
    gap = q_time[:, None] - q_time[None, :]  # [T, T]
    in_window_unroll = (gap >= 0) & (gap <= M)
    unroll = in_window_unroll[None] & (ends[:, :, None] == ends[:, None, :])
    return jnp.concatenate([cache, unroll], axis=-1)


def _attention(h, p, cache, allowed, config):
    """h [B, T, d] (already normed) -> (attended [B, T, d], k, v)."""
    rows, steps, width = h.shape
    heads = config["num_attention_heads"]
    head_dim = width // heads
    eps, theta = config["rms_norm_eps"], config["rope_theta"]
    M = config["memory_len"]
    # ASSUMED: q/k norm over the whole projected width, before the split.
    q = _rmsnorm(h @ p["q"]["kernel"], p["q_norm"], eps)
    k = _rmsnorm(h @ p["k"]["kernel"], p["k_norm"], eps)
    v = h @ p["v"]["kernel"]
    q, k, v = (a.reshape(rows, steps, heads, head_dim) for a in (q, k, v))
    k_all = jnp.concatenate([cache[0], k], axis=1)  # [B, M+T, H, D]
    v_all = jnp.concatenate([cache[1], v], axis=1)
    # DEPARTURE: positions relative to the unroll's first step; the cache
    # keeps keys un-rotated.
    key_time = jnp.concatenate([jnp.arange(M) - M, jnp.arange(steps)])
    q_rot = _rope(q, jnp.arange(steps), theta)
    k_rot = _rope(k_all, key_time, theta)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q_rot, k_rot) * head_dim ** -0.5
    scores = jnp.where(allowed[:, None], scores, -1e30)
    weights = jax.nn.softmax(scores, axis=-1)
    attended = jnp.einsum("bhqk,bkhd->bqhd", weights, v_all)
    return attended.reshape(rows, steps, width) @ p["o"]["kernel"], k, v


def _experts(h, p, config):
    """h [t, d] -> (y [t, d], load-balance term)."""
    E, K = config["num_experts"], config["num_experts_per_tok"]
    probs = jax.nn.softmax(h @ p["router"]["kernel"], axis=-1)  # [t, E]
    _, chosen = jax.lax.top_k(probs, K)
    mask = jax.nn.one_hot(chosen, E, dtype=jnp.float32).sum(axis=1)  # [t, E]
    # norm_topk_prob false: the gates are the chosen probabilities as
    # they are, not renormalised to sum to one.
    gates = probs * mask
    y = jnp.zeros_like(h)
    for e in range(E):
        # ASSUMED: intermediate_size is the width of one expert.
        hidden = jax.nn.silu(h @ p["w_gate"][e]) * (h @ p["w_up"][e])
        y = y + gates[:, e : e + 1] * (hidden @ p["w_down"][e])
    # ASSUMED: weight 0.01, no z-loss. E x sum_e (share of the K*t
    # assignments that went to e) x (mean router probability of e).
    share = mask.sum(axis=0) / (K * h.shape[0])
    balance = E * jnp.sum(share * probs.mean(axis=0))
    return y, config["load_balance_weight"] * balance


def forward(params, batch, core_state, config):
    """(policy_logits [T, B, A], baseline [T, B], new cache, aux loss)."""
    p = params["params"]
    eps = config["rms_norm_eps"]
    M = config["memory_len"]
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
    aux = 0.0
    new_state = []
    for layer in range(config["num_hidden_layers"]):
        block = p[f"block_{layer}"]
        k_cache, v_cache, valid = core_state[layer]  # [M, B, H, D], [M, B]
        # DEPARTURE: a window of M steps over a rolling cache, cut at
        # episode ends, in place of 4,096 positions of causal attention.
        allowed = _may_attend(done, valid, M)
        cache = (k_cache.transpose(1, 0, 2, 3), v_cache.transpose(1, 0, 2, 3))
        attended, k, v = _attention(
            _rmsnorm(x, block["attn_norm"], eps), block, cache, allowed,
            config,
        )
        x = x + attended
        h = _rmsnorm(x, block["moe_norm"], eps)
        y, balance = _experts(h.reshape(rows * steps, -1), block["moe"], config)
        x = x + y.reshape(rows, steps, -1)
        aux = aux + balance
        # The cache the actor would carry on: the last M of [cache; this
        # unroll], of which only the last episode's steps stay valid.
        last = ends[:, -1:]
        kept = jnp.concatenate([
            (valid.T > 0) & (last == 0), ends == last,
        ], axis=1)
        new_state.append((
            jnp.concatenate([cache[0], k], axis=1)[:, -M:].transpose(1, 0, 2, 3),
            jnp.concatenate([cache[1], v], axis=1)[:, -M:].transpose(1, 0, 2, 3),
            kept[:, -M:].astype(jnp.float32).T,
        ))
    x = _rmsnorm(x, p["final_norm"], eps)
    x = x.transpose(1, 0, 2).reshape(steps * rows, -1)
    # DEPARTURE: policy and baseline heads in place of the LM head.
    head = p["head"]
    logits = x @ head["policy"]["kernel"] + head["policy"]["bias"]
    baseline = x @ head["baseline"]["kernel"] + head["baseline"]["bias"]
    return (
        logits.reshape(steps, rows, -1), baseline.reshape(steps, rows),
        tuple(new_state), aux,
    )


def vtrace(log_rhos, discounts, rewards, values, bootstrap_value):
    """(vs, pg_advantages) by the backward recursion of the paper's
    remark 1, with rho-bar = c-bar = 1."""
    rhos = jnp.exp(log_rhos)
    clipped_rhos = jnp.minimum(rhos, 1.0)
    cs = jnp.minimum(rhos, 1.0)
    next_values = jnp.concatenate([values[1:], bootstrap_value[None]])
    deltas = clipped_rhos * (rewards + discounts * next_values - values)
    acc = jnp.zeros_like(bootstrap_value)
    corrections = []
    for t in reversed(range(values.shape[0])):
        acc = deltas[t] + discounts[t] * cs[t] * acc
        corrections.append(acc)
    vs = values + jnp.stack(corrections[::-1])
    next_vs = jnp.concatenate([vs[1:], bootstrap_value[None]])
    advantages = clipped_rhos * (rewards + discounts * next_vs - values)
    return vs, advantages


def loss_and_scale(params, batch, core_state, config):
    """The total loss of one [T+1, B] batch (IMPALA's three terms, as the
    reference implementation sums them, plus the load-balance term), and
    the sum of the magnitudes of its terms: the total is a sum of signed
    terms and can come out near zero, so a difference between two
    computations of it is judged against the scale, which cannot."""
    with jax.default_matmul_precision("highest"):
        logits, baseline, _, aux = forward(params, batch, core_state, config)
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
        # The targets are constants of the loss: no gradient flows
        # through them (section 4.2 of the IMPALA paper).
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
            + aux
        )
        scale = (
            jnp.sum(jnp.abs(pg_terms))
            + config["baseline_cost"] * baseline_loss
            + config["entropy_cost"] * jnp.abs(entropy_loss)
            + aux
        )
        return total, scale


def loss(params, batch, core_state, config):
    """The total loss of one [T+1, B] batch."""
    return loss_and_scale(params, batch, core_state, config)[0]
