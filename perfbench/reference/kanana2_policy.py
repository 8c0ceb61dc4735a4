"""Plain reference of the Kanana-2-block policy and its IMPALA loss.

Written from the model's description, not from the program: the block of
kanana-2-30b-a3b-instruct-2601 (kakaocorp; config.json, `model_type`
deepseek_v3: the layer of DeepSeek-V2/V3, arXiv:2405.04434 section 2.1
and arXiv:2412.19437 section 2.1) and the V-trace actor-critic loss of
Espeholt et al. 2018 (arXiv:1802.01561, section 4; the recursion is
`olmoe_policy.vtrace`), in straightforward `jax.numpy` and float32 at the
highest matmul precision. Attention is DECOMPRESSED: `kv_b` is applied
to every cached latent as to every fresh one, the one RoPE key is
repeated for the 32 heads, and one masked matrix runs over the cached
and the unrolled steps, a row of the batch at a time so that it fits
beside the timed program. No absorbed product, no two legs, no sort, no
grouped matmul, no cache roll: each expert held runs on every token
under the token's gate for it (zero where the token did not choose it).
It reads the program's parameter tree (flax names) so that both can be
given the same weights, and imports nothing from the program.

Per layer `l` (eps 1e-6, no biases):

    h = rmsnorm(x)
    q = Wq h -> [32, 192], split q_nope (128) | q_rope (64)
    Wkva h -> 576, split c (512) | k_r (64);  c = rmsnorm_512(c)
    Wkvb c -> [32, 256], split k_nope (128) | v (128), for EVERY key
    RoPE theta 1e6 on q_rope and k_r, pairs (2i, 2i+1) (rope_interleave)
    scores (q_nope . k_nope + q_rope . k_r) / sqrt(192), softmax, P v, Wo
    l < first_k_dense_replace:  x = x + SwiGLU_6144(rmsnorm(x))
    else: u = rmsnorm(x);  s = sigmoid(Wr u) over 128
          the 6 largest of s + b;  g = 2.448 s / (sum of the 6 s + 1e-20)
          x = x + sum over the experts HELD of g_e E_e(u) + SwiGLU_1536(u)

The share: the configuration's `n_routed_experts` is how many routed
experts this chip holds, `expert_share` [i, n] which ones (i *
n_routed_experts ..), and `published_n_routed_experts` what the router
routes over. What the experts on the other chips would add is left out
here as in the program, and the partial sum (with the shared expert,
whole on every chip) goes on to the next layer.

`b` takes no gradient; `bias_step` below states how it moves.

Departures from the published model, each marked `# DEPARTURE` below:
an observation projection replaces the token embedding; policy and
baseline heads replace the LM head; a layer attends over a rolling cache
of latents and rope keys, cut at episode ends, not over 32,768
positions; RoPE positions are relative to the unroll's first step. What
the config does not spell out, each marked `# ASSUMED`: the bias's rule
and its speed.
"""

import jax
import jax.numpy as jnp

from perfbench.reference.mellum2_policy import _may_attend, _rmsnorm
from perfbench.reference.olmoe_policy import vtrace


def _rope_pairs(x, positions, theta):
    """Interleaved RoPE over the last axis: the pair (x[2i], x[2i+1])
    turned by position x theta^(-2i/D). x [S, H, D]."""
    dim = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.zeros_like(x)
    out = out.at[..., 0::2].set(even * cos - odd * sin)
    return out.at[..., 1::2].set(odd * cos + even * sin)


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def _attention(h, p, cache, allowed, config):
    """h [B, T, d] (already normed) -> (attended [B, T, d], c, k_r)."""
    rows, steps, _ = h.shape
    heads, rank = config["num_attention_heads"], config["kv_lora_rank"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    value, eps = config["v_head_dim"], config["rms_norm_eps"]
    assert config["q_lora_rank"] is None and config["rope_scaling"] is None
    assert config["rope_interleave"] and nope + rope == config["qk_head_dim"]
    M = cache[0].shape[1]
    q = (h @ p["q"]["kernel"]).reshape(rows, steps, heads, nope + rope)
    compressed = h @ p["kv_a"]["kernel"]
    c = _rmsnorm(compressed[..., :rank], p["kv_a_norm"], eps)
    k_r = compressed[..., rank:]
    c_all = jnp.concatenate([cache[0], c], axis=1)  # [B, M+T, 512]
    k_r_all = jnp.concatenate([cache[1], k_r], axis=1)  # [B, M+T, 64]
    # DEPARTURE: positions relative to the unroll's first step; the cache
    # keeps the rope keys un-rotated.
    key_time = jnp.concatenate([jnp.arange(M) - M, jnp.arange(steps)])

    def one_row(args):
        q, c_all, k_r_all, allowed = args
        # Every key, cached or fresh, decompressed.
        kv = (c_all @ p["kv_b"]).reshape(M + steps, heads, nope + value)
        k_nope, v = kv[..., :nope], kv[..., nope:]
        q_rot = _rope_pairs(q[..., nope:], jnp.arange(steps), config["rope_theta"])
        k_rot = _rope_pairs(k_r_all[:, None, :], key_time, config["rope_theta"])
        keys = jnp.concatenate(
            [k_nope, jnp.repeat(k_rot, heads, axis=1)], axis=-1
        )  # [M+T, 32, 192]
        queries = jnp.concatenate([q[..., :nope], q_rot], axis=-1)
        scores = jnp.einsum("qhd,khd->hqk", queries, keys) * (
            (nope + rope) ** -0.5
        )
        scores = jnp.where(allowed[None], scores, -1e30)
        weights = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("hqk,khd->qhd", weights, v)

    attended = jax.lax.map(one_row, (q, c_all, k_r_all, allowed))
    return (
        attended.reshape(rows, steps, heads * value) @ p["o"]["kernel"],
        c, k_r,
    )


def _route(h, p, config):
    """h [t, d] -> (gates [t, E], zero where not chosen; 0/1 mask)."""
    E, K = config["published_n_routed_experts"], config["num_experts_per_tok"]
    assert config["scoring_func"] == "sigmoid"
    assert config["topk_method"] == "noaux_tc"
    # Group-limited selection with one group is plain top-k.
    assert config["n_group"] == config["topk_group"] == 1
    scores = jax.nn.sigmoid(h @ p["router"]["kernel"])  # [t, E]
    # The bias chooses; it is no part of the gate.
    _, chosen = jax.lax.top_k(scores + p["e_score_correction_bias"], K)
    mask = jax.nn.one_hot(chosen, E, dtype=jnp.float32).sum(axis=1)  # [t, E]
    gates = scores * mask
    if config["norm_topk_prob"]:
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
    return gates * config["routed_scaling_factor"], mask


def _experts(h, p, config):
    """h [t, d] -> the held experts' part of the routed sum plus the
    shared expert, [t, d]."""
    held = config["n_routed_experts"]
    first = config["expert_share"][0] * held
    gates, _ = _route(h, p, config)
    y = jnp.zeros_like(h)
    for e in range(held):
        y = y + gates[:, first + e : first + e + 1] * _swiglu(
            h, p["w_gate"][e], p["w_up"][e], p["w_down"][e]
        )
    # n_shared_experts x moe_intermediate_size wide, every token's.
    return y + _swiglu(
        h, p["shared_gate"]["kernel"], p["shared_up"]["kernel"],
        p["shared_down"]["kernel"],
    )


def bias_step(h, p, config):
    """What `e_score_correction_bias` moves by after an update whose
    router saw h [t, d]: u x sign(mean load - load), the load being the
    batch's assignments to each of the E experts (DeepSeek-V3, section
    2.1.2: down by gamma where overloaded, up where underloaded).
    # ASSUMED: the rule and u = `bias_update_rate`; config.json has no
    key for either."""
    _, mask = _route(h, p, config)
    load = mask.sum(axis=0)
    return config["bias_update_rate"] * jnp.sign(jnp.mean(load) - load)


def forward(params, batch, core_state, config, moe_inputs=None):
    """(policy_logits [T, B, A], baseline [T, B], new caches, aux loss).
    `moe_inputs`, a list, is given each MoE layer's normed input [t, d]
    (what `bias_step` reads)."""
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
    new_state = []
    for layer in range(config["num_hidden_layers"]):
        block = p[f"block_{layer}"]
        c_cache, k_r_cache, valid = core_state[layer]
        # DEPARTURE: a rolling cache of M latents [M, B, 1, 512] and rope
        # keys [M, B, 1, 64], cut at episode ends, in place of 32,768
        # positions.
        assert c_cache.shape[0] == k_r_cache.shape[0] == M
        allowed = _may_attend(done, valid, M)
        cache = (c_cache[:, :, 0].transpose(1, 0, 2),
                 k_r_cache[:, :, 0].transpose(1, 0, 2))
        attended, c, k_r = _attention(
            _rmsnorm(x, block["attn_norm"], eps), block, cache, allowed,
            config,
        )
        x = x + attended
        h = _rmsnorm(x, block["mlp_norm"], eps)
        if layer < config["first_k_dense_replace"]:
            x = x + _swiglu(
                h, block["gate"]["kernel"], block["up"]["kernel"],
                block["down"]["kernel"],
            )
        else:
            tokens = h.reshape(rows * steps, -1)
            if moe_inputs is not None:
                moe_inputs.append(tokens)
            x = x + _experts(tokens, block["moe"], config).reshape(
                rows, steps, -1
            )
        # The cache the actor would carry on: the last M of [cache; this
        # unroll], of which only the last episode's steps stay valid.
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
    x = _rmsnorm(x, p["final_norm"], eps)
    x = x.transpose(1, 0, 2).reshape(steps * rows, -1)
    # DEPARTURE: policy and baseline heads in place of the LM head.
    head = p["head"]
    logits = x @ head["policy"]["kernel"] + head["policy"]["bias"]
    baseline = x @ head["baseline"]["kernel"] + head["baseline"]["bias"]
    # topk_method noaux_tc: no auxiliary loss.
    return (
        logits.reshape(steps, rows, -1), baseline.reshape(steps, rows),
        tuple(new_state), 0.0,
    )


def bias_steps(params, batch, core_state, config):
    """`bias_step` of every MoE layer, in order, for this batch."""
    with jax.default_matmul_precision("highest"):
        inputs = []
        forward(params, batch, core_state, config, moe_inputs=inputs)
        first = config["first_k_dense_replace"]
        return [
            bias_step(h, params["params"][f"block_{first + i}"]["moe"], config)
            for i, h in enumerate(inputs)
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
