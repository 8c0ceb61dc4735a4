"""Plain reference of the Ling-3.0-flash-VL policy cut and its IMPALA loss.

Written from the model's description, not from the program: the layers
of Ling-3.0-flash-VL (inclusionAI; config.json, `model_type`
bailing_hybrid: Kimi Delta Attention of the Kimi Linear report,
arXiv:2510.26692, beside the latent attention and the group-limited
sigmoid router of DeepSeek-V2/V3, arXiv:2405.04434 section 2.1 and
arXiv:2412.19437 section 2.1) and the V-trace actor-critic loss of
Espeholt et al. 2018 (arXiv:1802.01561, section 4; the recursion is
`olmoe_policy.vtrace`), in straightforward `jax.numpy` and float32 at
the highest matmul precision. The KDA mixer is the RECURRENCE, one step
at a time (`lax.scan` over the unroll's steps, the state and the
convolution's window zeroed at a step where `done` is set); the
convolution is four shifted adds over that window; latent attention is
DECOMPRESSED (`kv_b` applied to every cached latent as to every fresh
one, the one RoPE key repeated for the 32 heads, one masked matrix over
the cached and the unrolled steps, a row of the batch at a time); the
router chooses by a LOOP over the groups; each expert held runs on every
token under the token's gate for it (zero where the token did not
choose it). No chunks, no sub-blocks, no triangular system, no absorbed
product, no sort, no grouped matmul, no cache roll, no fused pass. It
reads the program's parameter tree (flax names) so that both can be
given the same weights, and imports nothing from the program.

`rmsnorm(x) = x / sqrt(mean(x^2) + 1e-6) * w`. Published layer l is `x =
x + mixer(rmsnorm(x)); x = x + ff(rmsnorm(x))`, no biases; the mixer is
latent attention where `(l + 1) % layer_group_size == 0`, else KDA; ff
is a SwiGLU of `intermediate_size` where l < `first_k_dense_replace`,
else the routed experts. `layers_run` names the published layers the
cut runs, in order (the program's blocks 2i and 2i + 1 are the i-th of
them).

  K  in_proj d -> [q | k | v | f], 4 x 32 x 128; in_proj_bg d -> [b 32
     | gate 32]; [q; k; v] = silu(conv4([q; k; v])), causal and
     depthwise, no bias; q, k = l2norm(q), l2norm(k) a head; q = q /
     sqrt(128); beta = sigmoid(b); a = f + dt_bias;
         g = kda_lower_bound x sigmoid(exp(A_log_h) a)    kda_safe_gate
         g = -exp(A_log_h) softplus(a)                    otherwise
         S' = exp(g_t) . S_{t-1} (a key channel each)
         u_t = beta_t (v_t - S'^T k_t);  S_t = S' + k_t u_t^T
         o_t = S_t^T q_t
     y = rmsnorm_128(o) * w * sigmoid(gate)_h; out_proj -> d
  M  q = Wq h -> [32, 192], q_nope (128) | q_rope (64); Wkva h -> c
     (512) | k_r (64); c = rmsnorm_512(c); Wkvb c -> [32, 256], k_nope
     (128) | v (128), for EVERY key; RoPE theta 6e6 on q_rope and k_r,
     pairs (2i, 2i+1); softmax((q_nope . k_nope + q_rope . k_r) /
     sqrt(192)) v; o(attended_h * sigmoid(W_g h)_h)
  ff s = sigmoid(W_r u) over 512; by group of 64: the sum of the two
     largest s + b; the `topk_group` best groups; the 8 largest s + b
     inside them; g = 2.5 s / (sum of the 8 chosen s + 1e-20); the sum
     over the experts HELD of g_e E_e(u), plus SwiGLU_768(u)

The share: the configuration's `num_experts` is what this chip HOLDS
(`expert_share` [i, n] says which part); `published_num_experts` is what
the router routes over. What the other chips' experts would add is left
out here as in the program, and the partial sum goes on.

`b` takes no gradient; `bias_step` below states how it moves.

Departures from the published model, each marked `# DEPARTURE` below:
an observation projection replaces the token embedding and the vision
tower; policy and baseline heads replace the LM head; a latent layer
attends over a rolling cache of latents and un-rotated rope keys cut at
episode ends, positions relative to the unroll's first step, not over
131,072 positions; a KDA layer's state and window are zeroed where an
episode ends; multi-token prediction is not run. What the config does
not spell out, each marked `# ASSUMED`.
"""

import jax
import jax.numpy as jnp

from perfbench.reference.kanana2_policy import _rope_pairs, _swiglu
from perfbench.reference.mellum2_policy import _may_attend, _rmsnorm
from perfbench.reference.olmoe_policy import vtrace


def _l2norm(x):
    # ASSUMED: eps 1e-6 inside the root (the reference implementation's).
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _log_decay(a, p, config):
    """a [B, H, D] (the projection) -> g [B, H, D], a channel each."""
    A = jnp.exp(p["A_log"])[None, :, None]
    a = a + p["dt_bias"].reshape(a.shape[1:])
    if config["kda_safe_gate"]:
        # ASSUMED: the bounded gate's form (the row has the two keys,
        # not the formula): lower_bound x sigmoid(A a), in (-5, 0).
        return config["kda_lower_bound"] * jax.nn.sigmoid(A * a)
    return -A * jax.nn.softplus(a)


def _kda(h, done, p, state, config):
    """h [T, B, d] (already normed), done [T, B] -> (out [T, B, d], the
    state and the window after the last step)."""
    H, D = config["num_attention_heads"], config["head_dim"]
    K = config["short_conv_kernel_size"]
    assert config["num_kv_heads_for_linear_attn"] == 0  # a key a head
    assert config["linear_silu"] and config["group_norm_size"] == 1
    inner = H * D
    carried, window = state  # [H, B, D, D], [K - 1, B, 3 H D]

    def step(carry, inputs):
        carried, window = carry
        h_t, done_t = inputs  # [B, d], [B]
        rows = h_t.shape[0]
        # DEPARTURE: an episode's first step starts from nothing.
        carried = jnp.where(done_t[None, :, None, None], 0.0, carried)
        window = jnp.where(done_t[None, :, None], 0.0, window)
        qkvf = h_t @ p["in_proj"]["kernel"]
        bg = h_t @ p["in_proj_bg"]["kernel"]
        joined, f = qkvf[:, : 3 * inner], qkvf[:, 3 * inner :]
        # Four shifted adds: the K - 1 steps before this one, and it.
        conv = p["conv_kernel"][K - 1] * joined
        for tap in range(K - 1):
            conv = conv + p["conv_kernel"][tap] * window[tap]
        window = jnp.concatenate([window[1:], joined[None]], axis=0)
        conv = jax.nn.silu(conv)
        q = _l2norm(conv[:, :inner].reshape(rows, H, D)) * D ** -0.5
        k = _l2norm(conv[:, inner : 2 * inner].reshape(rows, H, D))
        v = conv[:, 2 * inner :].reshape(rows, H, D)
        beta = jax.nn.sigmoid(bg[:, :H])  # [B, H]
        g = _log_decay(f.reshape(rows, H, D), p, config)  # [B, H, Dk]
        # Every key channel of the state decays by its own factor.
        carried = jnp.exp(g).transpose(1, 0, 2)[..., None] * carried
        read = jnp.einsum("hbkv,bhk->bhv", carried, k)
        u = beta[..., None] * (v - read)
        carried = carried + jnp.einsum("bhk,bhv->hbkv", k, u)
        o = jnp.einsum("hbkv,bhk->bhv", carried, q)
        # A head's own norm (one scale of 128 for every head), then one
        # gate a head.
        normed = o * jax.lax.rsqrt(
            jnp.mean(o * o, axis=-1, keepdims=True) + config["rms_norm_eps"]
        )
        y = normed * p["gate_norm"] * jax.nn.sigmoid(bg[:, H:])[..., None]
        return (carried, window), y.reshape(rows, -1) @ p["out_proj"]["kernel"]

    (carried, window), out = jax.lax.scan(step, (carried, window), (h, done))
    return out, (carried, window)


def _latent(h, p, cache, allowed, config):
    """h [B, T, d] (already normed) -> (attended [B, T, d], c, k_r)."""
    rows, steps, _ = h.shape
    heads, rank = config["num_attention_heads"], config["kv_lora_rank"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    value = config["v_head_dim"]
    assert config["q_lora_rank"] is None
    assert config["rotary_dim"] == rope
    assert config["gated_attention_proj_granularity_type"] == "head_wise"
    M = cache[0].shape[1]
    q = (h @ p["q"]["kernel"]).reshape(rows, steps, heads, nope + rope)
    compressed = h @ p["kv_a"]["kernel"]
    c = _rmsnorm(compressed[..., :rank], p["kv_a_norm"], config["rms_norm_eps"])
    k_r = compressed[..., rank:]
    gate = jax.nn.sigmoid(h @ p["head_gate"]["kernel"])  # [B, T, heads]
    c_all = jnp.concatenate([cache[0], c], axis=1)
    k_r_all = jnp.concatenate([cache[1], k_r], axis=1)
    # DEPARTURE: positions relative to the unroll's first step; the cache
    # keeps the rope keys un-rotated.
    key_time = jnp.concatenate([jnp.arange(M) - M, jnp.arange(steps)])
    theta = config["rope_theta"]

    def one_row(args):
        q, c_all, k_r_all, allowed = args
        # Every key, cached or fresh, decompressed.
        kv = (c_all @ p["kv_b"]).reshape(M + steps, heads, nope + value)
        k_nope, v = kv[..., :nope], kv[..., nope:]
        q_rot = _rope_pairs(q[..., nope:], jnp.arange(steps), theta)
        k_rot = _rope_pairs(k_r_all[:, None, :], key_time, theta)
        keys = jnp.concatenate(
            [k_nope, jnp.repeat(k_rot, heads, axis=1)], axis=-1
        )
        queries = jnp.concatenate([q[..., :nope], q_rot], axis=-1)
        scores = jnp.einsum("qhd,khd->hqk", queries, keys) * (
            (nope + rope) ** -0.5
        )
        scores = jnp.where(allowed[None], scores, -1e30)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)

    attended = jax.lax.map(one_row, (q, c_all, k_r_all, allowed))
    # One gate a head on what it attended, before `o`.
    gated = attended * gate[..., None]
    return (
        gated.reshape(rows, steps, heads * value) @ p["o"]["kernel"], c, k_r,
    )


def _route(h, p, config):
    """h [t, d] -> (gates [t, E], zero where not chosen; 0/1 mask)."""
    E, K = config["published_num_experts"], config["num_experts_per_tok"]
    groups, best = config["n_group"], config["topk_group"]
    assert config["score_function"] == "sigmoid"
    assert config["moe_router_enable_expert_bias"]
    scores = jax.nn.sigmoid(h @ p["router"]["kernel"])  # [t, E]
    # The bias chooses; it is no part of the gate.
    choice = scores + p["e_score_correction_bias"]
    size = E // groups
    # ASSUMED: a group's score is the sum of its two largest score +
    # bias (DeepSeek-V3's `noaux_tc`; the row names the counts alone).
    group_score = jnp.stack([
        jnp.sum(jax.lax.top_k(choice[:, g * size : (g + 1) * size], 2)[0], -1)
        for g in range(groups)
    ], axis=-1)  # [t, groups]
    _, best_groups = jax.lax.top_k(group_score, best)
    within = jnp.zeros_like(choice, dtype=bool)
    for g in range(groups):
        in_best = jnp.any(best_groups == g, axis=-1, keepdims=True)  # [t, 1]
        within = within.at[:, g * size : (g + 1) * size].set(
            jnp.broadcast_to(in_best, (choice.shape[0], size))
        )
    # ASSUMED: experts outside the chosen groups cannot be chosen at all
    # (-inf, not the 0.0 some implementations fill in, which a negative
    # score + bias would lose to).
    _, chosen = jax.lax.top_k(jnp.where(within, choice, -jnp.inf), K)
    mask = jax.nn.one_hot(chosen, E, dtype=jnp.float32).sum(axis=1)  # [t, E]
    gates = scores * mask
    if config["norm_topk_prob"]:
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
    return gates * config["routed_scaling_factor"], mask


def _experts(h, p, config):
    """h [t, d] -> the held experts' part of the routed sum plus the
    shared expert, [t, d]."""
    held = config["num_experts"]
    first = config["expert_share"][0] * held
    gates, _ = _route(h, p, config)
    y = jnp.zeros_like(h)
    for e in range(held):
        y = y + gates[:, first + e : first + e + 1] * _swiglu(
            h, p["w_gate"][e], p["w_up"][e], p["w_down"][e]
        )
    # `moe_shared_expert_intermediate_size` wide, every token's, unscaled.
    return y + _swiglu(
        h, p["shared_gate"]["kernel"], p["shared_up"]["kernel"],
        p["shared_down"]["kernel"],
    )


def bias_step(h, p, config):
    """What `e_score_correction_bias` moves by after an update whose
    router saw h [t, d]: u x sign(mean load - load), the load being the
    batch's assignments to each of the E experts (DeepSeek-V3, section
    2.1.2).
    # ASSUMED: the rule and u = `bias_update_rate`; config.json has no
    key for either."""
    _, mask = _route(h, p, config)
    load = mask.sum(axis=0)
    return config["bias_update_rate"] * jnp.sign(jnp.mean(load) - load)


def forward(params, batch, core_state, config, moe_inputs=None):
    """(policy_logits [T, B, A], baseline [T, B], new state, aux loss).
    `core_state` has an item a layer run: (S, window) for a KDA layer,
    (c, k_r, valid) for a latent layer. `moe_inputs`, a list, is given
    each MoE part's normed input [t, d] (what `bias_step` reads)."""
    p = params["params"]
    eps = config["rms_norm_eps"]
    M = config["memory_len"]
    frame = batch["frame"]
    steps, rows = frame.shape[:2]
    # DEPARTURE: a linear projection of the frame (scaled to [-1, 1]),
    # plus one of the clipped reward and the last action, in place of the
    # token embedding and the vision tower.
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
    assert len(config["layers_run"]) == config["num_hidden_layers"]
    new_state = []
    for i, layer in enumerate(config["layers_run"]):
        # The program keeps a layer's mixer and its feed-forward part in
        # two blocks of its tree, 2i and 2i + 1, each with its norm.
        block, ffn = p[f"block_{2 * i}"], p[f"block_{2 * i + 1}"]
        if (layer + 1) % config["layer_group_size"]:
            out, state = _kda(
                _rmsnorm(x, block["norm"], eps).transpose(1, 0, 2), done,
                block, core_state[i], config,
            )
            x = x + out.transpose(1, 0, 2)
            new_state.append(state)
        else:
            c_cache, k_r_cache, valid = core_state[i]
            # DEPARTURE: a rolling cache of M latents [M, B, 1, 512] and
            # rope keys [M, B, 1, 64], cut at episode ends, in place of
            # 131,072 positions.
            assert c_cache.shape[0] == k_r_cache.shape[0] == M
            allowed = _may_attend(done, valid, M)
            cache = (c_cache[:, :, 0].transpose(1, 0, 2),
                     k_r_cache[:, :, 0].transpose(1, 0, 2))
            attended, c, k_r = _latent(
                _rmsnorm(x, block["attn_norm"], eps), block, cache, allowed,
                config,
            )
            x = x + attended
            # The cache the actor would carry on: the last M of [cache;
            # this unroll], of which only the last episode's stay valid.
            last = ends[:, -1:]
            kept = jnp.concatenate([
                (valid.T > 0) & (last == 0), ends == last,
            ], axis=1)
            new_state.append((
                jnp.concatenate([cache[0], c], axis=1)[:, -M:].transpose(
                    1, 0, 2
                )[:, :, None],
                jnp.concatenate([cache[1], k_r], axis=1)[:, -M:].transpose(
                    1, 0, 2
                )[:, :, None],
                kept[:, -M:].astype(jnp.float32).T,
            ))
        h = _rmsnorm(x, ffn["norm"], eps)
        if layer < config["first_k_dense_replace"]:
            x = x + _swiglu(
                h, ffn["gate"]["kernel"], ffn["up"]["kernel"],
                ffn["down"]["kernel"],
            )
        else:
            tokens = h.reshape(rows * steps, -1)
            if moe_inputs is not None:
                moe_inputs.append(tokens)
            x = x + _experts(tokens, ffn["moe"], config).reshape(
                rows, steps, -1
            )
    # DEPARTURE: multi-token prediction is not run: there is no next
    # token to predict from.
    x = _rmsnorm(x, p["final_norm"], eps)
    x = x.transpose(1, 0, 2).reshape(steps * rows, -1)
    # DEPARTURE: policy and baseline heads in place of the LM head.
    head = p["head"]
    logits = x @ head["policy"]["kernel"] + head["policy"]["bias"]
    baseline = x @ head["baseline"]["kernel"] + head["baseline"]["bias"]
    # The router is balanced by its bias: no auxiliary loss.
    return (
        logits.reshape(steps, rows, -1), baseline.reshape(steps, rows),
        tuple(new_state), 0.0,
    )


def bias_steps(params, batch, core_state, config):
    """`bias_step` of every MoE part, in order, for this batch."""
    with jax.default_matmul_precision("highest"):
        inputs = []
        forward(params, batch, core_state, config, moe_inputs=inputs)
        routed = [
            i for i, layer in enumerate(config["layers_run"])
            if layer >= config["first_k_dense_replace"]
        ]
        return [
            bias_step(
                h, params["params"][f"block_{2 * i + 1}"]["moe"], config
            )
            for i, h in zip(routed, inputs)
        ]


def loss_and_scale(params, batch, core_state, config):
    """The total loss of one [T+1, B] batch (IMPALA's three terms, as the
    reference implementation sums them; this model has no auxiliary
    term), and the sum of the magnitudes of its terms: the total is a sum
    of signed terms and can come out near zero, so a difference between
    two computations of it is judged against the scale, which cannot."""
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
