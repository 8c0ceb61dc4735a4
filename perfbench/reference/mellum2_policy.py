"""Plain reference of the Mellum2-block policy and its IMPALA loss.

Written from the model's description, not from the program: the block of
Mellum2-12B-A2.5B-Instruct (JetBrains; config.json, `model_type` mellum)
and the V-trace actor-critic loss of Espeholt et al. 2018
(arXiv:1802.01561, section 4; the recursion is `olmoe_policy.vtrace`), in
straightforward `jax.numpy` and float32 at the highest matmul precision.
No sort, no grouped matmul, no cache roll, no grouped-query contraction:
keys and values are repeated for the eight query heads that share them,
each expert held runs on every token under the token's gate for it (zero
where the token did not choose it), attention is one masked matrix over
the cached and the unrolled steps, one row of the batch at a time so
that it fits beside the timed program. It reads the program's parameter
tree (flax names) so that both can be given the same weights, and
imports nothing from the program.

Per layer `l`, of kind `layer_types[l]`:

    h = rmsnorm(x)
    q = Wq h -> [32, 128];  k = Wk h -> [4, 128];  v = Wv h -> [4, 128]
    q, k = rmsnorm_128(q), rmsnorm_128(k)
    query head j reads key/value head j // 8; scores times 128^-0.5
    sliding: keys at most sliding_window - 1 steps back, RoPE theta 500000
    full:    every key its cache holds, RoPE with YaRN's frequencies,
             cos and sin times attention_factor
    x = x + Wo attend(rope(q), rope(k), v)
    u = rmsnorm(x);  p = softmax(Wr u) over 64;  top 8;  g = p / sum(p top 8)
    x = x + sum over the experts HELD of g_e * Wdown_e(silu(Wgate_e u) * Wup_e u)

The share: the configuration's `num_experts` is how many experts this
chip holds, `expert_share` [i, n] which ones (i * num_experts ..), and
`published_num_experts` what the router routes over. What the experts on
the other chips would add is left out here as in the program, and the
partial sum goes on to the next layer.

Departures from the published model, each marked `# DEPARTURE` below:
an observation projection replaces the token embedding; policy and
baseline heads replace the LM head (and the MTP head); a layer attends
over a rolling cache of its own length, cut at episode ends, not over
131,072 positions; RoPE positions are relative to the unroll's first
step. What the config does not spell out, each marked `# ASSUMED`: q and
k are RMS-normed per head over the head's 128 with one learned scale;
the load-balance weight is 0.001.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.reference.olmoe_policy import vtrace

FULL = "full_attention"


def _rmsnorm(x, p, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (
        p["scale"]
    )


def inv_freq_and_factor(rope, dim):
    """One kind's `rope_parameters` -> (inv_freq [dim/2], what cos and
    sin are multiplied by)."""
    theta = rope["rope_theta"]
    index = np.arange(0, dim, 2, dtype=np.float64)
    wavelength = theta ** (index / dim)  # f_i
    if rope["rope_type"] == "default":
        return 1.0 / wavelength, 1.0
    assert rope["rope_type"] == "yarn", rope
    original = rope["original_max_position_embeddings"]

    def correction(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) / (
            2 * math.log(theta)
        )

    low = max(math.floor(correction(rope["beta_fast"])), 0)
    high = min(math.ceil(correction(rope["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    inv_freq = (
        ramp / (rope["factor"] * wavelength) + (1 - ramp) / wavelength
    )
    return inv_freq, rope["attention_factor"]


def _rope(x, positions, inv_freq, factor):
    """Rotate-half RoPE over the last axis. x [S, H, D]."""
    half = x.shape[-1] // 2
    angles = positions.astype(jnp.float32)[:, None] * jnp.asarray(
        inv_freq, jnp.float32
    )[None, :]
    cos = factor * jnp.concatenate([jnp.cos(angles)] * 2, axis=-1)[:, None, :]
    sin = factor * jnp.concatenate([jnp.sin(angles)] * 2, axis=-1)[:, None, :]
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rotated * sin


def cache_len(kind, config):
    """Slots a layer of this kind carries: a full layer the family's
    `memory_len`, a sliding layer the window less the query's own step
    (or `memory_len`, if that is shorter)."""
    if kind == FULL:
        return config["memory_len"]
    return min(config["memory_len"], config["sliding_window"] - 1)


def _may_attend(done, valid, slots):
    """[B, T, M+T] 0/1: query step t of row b may attend key j.

    Keys are the M cache slots (slot m is time m - M) then the T unroll
    steps (step j is time j). A key at time s is visible from t when
    t - M <= s <= t and no episode ended in between: `done[u]` marks the
    FIRST step of a new episode, so none of steps s+1..t may carry it. A
    cache slot must also hold something (`valid`), and steps 0..t must
    all be free of `done`.
    """
    steps, _ = done.shape
    M = slots
    ends = jnp.cumsum(done.astype(jnp.int32), axis=0).T  # [B, T]
    q_time = jnp.arange(steps)
    cache_time = jnp.arange(M) - M
    cache = (
        ((q_time[:, None] - cache_time[None, :]) <= M)[None]
        & (valid.T[:, None, :] > 0)
        & (ends[:, :, None] == 0)
    )
    gap = q_time[:, None] - q_time[None, :]  # [T, T]
    unroll = ((gap >= 0) & (gap <= M))[None] & (
        ends[:, :, None] == ends[:, None, :]
    )
    return jnp.concatenate([cache, unroll], axis=-1)


def _attention(h, p, cache, allowed, kind, config):
    """h [B, T, d] (already normed) -> (attended [B, T, d], k, v)."""
    rows, steps, _ = h.shape
    heads, kv_heads = (
        config["num_attention_heads"], config["num_key_value_heads"]
    )
    head_dim, eps = config["head_dim"], config["rms_norm_eps"]
    M = cache[0].shape[1]
    q = (h @ p["q"]["kernel"]).reshape(rows, steps, heads, head_dim)
    k = (h @ p["k"]["kernel"]).reshape(rows, steps, kv_heads, head_dim)
    v = (h @ p["v"]["kernel"]).reshape(rows, steps, kv_heads, head_dim)
    # ASSUMED: q/k norm per head over the head's 128, one learned scale.
    q, k = _rmsnorm(q, p["q_norm"], eps), _rmsnorm(k, p["k_norm"], eps)
    k_all = jnp.concatenate([cache[0], k], axis=1)  # [B, M+T, 4, D]
    v_all = jnp.concatenate([cache[1], v], axis=1)
    # DEPARTURE: positions relative to the unroll's first step; the cache
    # keeps keys un-rotated.
    key_time = jnp.concatenate([jnp.arange(M) - M, jnp.arange(steps)])
    rope = config["rope_parameters"][kind]
    inv_freq, factor = inv_freq_and_factor(rope, head_dim)

    def one_row(args):
        q, k_all, v_all, allowed = args
        # Query head j reads key/value head j // 8: each repeated eight
        # times, side by side.
        k_rep = jnp.repeat(k_all, heads // kv_heads, axis=1)  # [M+T, 32, D]
        v_rep = jnp.repeat(v_all, heads // kv_heads, axis=1)
        q_rot = _rope(q, jnp.arange(steps), inv_freq, factor)
        k_rot = _rope(k_rep, key_time, inv_freq, factor)
        scores = jnp.einsum("qhd,khd->hqk", q_rot, k_rot) * head_dim ** -0.5
        scores = jnp.where(allowed[None], scores, -1e30)
        weights = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("hqk,khd->qhd", weights, v_rep)

    attended = jax.lax.map(one_row, (q, k_all, v_all, allowed))
    return (
        attended.reshape(rows, steps, heads * head_dim) @ p["o"]["kernel"],
        k, v,
    )


def _experts(h, p, config):
    """h [t, d] -> (the held experts' part of y [t, d], load-balance
    term over all the experts)."""
    E, K = config["published_num_experts"], config["num_experts_per_tok"]
    held = config["num_experts"]
    first = config["expert_share"][0] * held
    probs = jax.nn.softmax(h @ p["router"]["kernel"], axis=-1)  # [t, E]
    _, chosen = jax.lax.top_k(probs, K)
    mask = jax.nn.one_hot(chosen, E, dtype=jnp.float32).sum(axis=1)  # [t, E]
    gates = probs * mask
    if config["norm_topk_prob"]:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    y = jnp.zeros_like(h)
    for e in range(held):
        hidden = jax.nn.silu(h @ p["w_gate"][e]) * (h @ p["w_up"][e])
        y = y + gates[:, first + e : first + e + 1] * (hidden @ p["w_down"][e])
    # ASSUMED: weight 0.001. E x sum_e (share of the K*t assignments that
    # went to e) x (mean router probability of e), over all E: the term
    # needs the other chips' counts, not their weights.
    share = mask.sum(axis=0) / (K * h.shape[0])
    balance = E * jnp.sum(share * probs.mean(axis=0))
    return y, config["load_balance_weight"] * balance


def forward(params, batch, core_state, config):
    """(policy_logits [T, B, A], baseline [T, B], new caches, aux loss)."""
    p = params["params"]
    eps = config["rms_norm_eps"]
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
        kind = config["layer_types"][layer]
        block = p[f"block_{layer}"]
        k_cache, v_cache, valid = core_state[layer]  # [M, B, 4, D], [M, B]
        # DEPARTURE: a rolling cache of the kind's own length, cut at
        # episode ends, in place of 131,072 positions.
        M = cache_len(kind, config)
        assert k_cache.shape[0] == M, (layer, kind, k_cache.shape, M)
        allowed = _may_attend(done, valid, M)
        cache = (k_cache.transpose(1, 0, 2, 3), v_cache.transpose(1, 0, 2, 3))
        attended, k, v = _attention(
            _rmsnorm(x, block["attn_norm"], eps), block, cache, allowed,
            kind, config,
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
