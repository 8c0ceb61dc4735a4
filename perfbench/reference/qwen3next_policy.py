"""Plain reference of the Qwen3-Next-period policy and its IMPALA loss.

Written from the model's description, not from the program: the layers
of Qwen3-Next-80B-A3B-Instruct (config.json, `model_type` qwen3_next;
the Gated DeltaNet mixer of Yang, Kautz and Hatamizadeh,
arXiv:2412.06464) and the V-trace actor-critic loss of Espeholt et al.
2018 (arXiv:1802.01561, section 4; the recursion is `olmoe_policy.
vtrace`), in straightforward `jax.numpy` and float32 at the highest
matmul precision. The DeltaNet mixer is the RECURRENCE, one step at a
time (`lax.scan` over the unroll's steps, the state and the
convolution's window zeroed at a step where `done` is set); the
convolution is four shifted adds over that window; attention is one
masked matrix over the cached and the unrolled steps, a row of the
batch at a time, its keys rotated; each expert held runs on every token
under the token's gate for it (zero where the token did not choose it).
No chunks, no triangular system, no decay matrix, no sort, no grouped
matmul, no cache roll, no fused pass. It reads the program's parameter
tree (flax names) so that both can be given the same weights, and
imports nothing from the program.

`norm0(x) = x / sqrt(mean(x^2) + 1e-6) * (1 + w)`. A layer is `x = x +
mixer(norm0(x)); x = x + moe(norm0(x))`, no biases; layer l is attention
where `(l + 1) % full_attention_interval == 0`, else Gated DeltaNet:

  D  in_proj_qkvz d -> a key head's [q 128 | k 128 | v 2 x 128 | z 2 x
     128] side by side; in_proj_ba d -> a key head's [b 2 | a 2];
     [q; k; v] = silu(conv4([q; k; v])), causal and depthwise, no bias;
     beta = sigmoid(b); g = -exp(A_log) softplus(a + dt_bias);
     q, k = l2norm(q), l2norm(k); q = q / sqrt(128); value head h reads
     key head h // 2;
         S' = exp(g_t) S_{t-1};  u_t = beta_t (v_t - S'^T k_t)
         S_t = S' + k_t u_t^T;   o_t = S_t^T q_t
     y = rmsnorm_128(o) * w * silu(z) a value head; out_proj -> d
  A  q_proj d -> a head's [query 256 | gate 256]; k, v d -> [2, 256];
     q, k = norm0 over the head; RoPE (rotate-half, theta 1e7) on the
     first `partial_rotary_factor` x 256 = 64 columns; softmax(q k^T /
     sqrt(256)) v over [cache; unroll], causal; o_proj(attended *
     sigmoid(gate))
  moe  p = softmax(W_r u) over 512; the 10 largest; g = p / (sum of the
     chosen p); SwiGLU experts; the sum over the experts HELD of g_e
     E_e(u), plus sigmoid(w_g . u) SwiGLU_shared(u)

The share: the configuration's `num_experts` is what this chip HOLDS
(`expert_share` [i, n] says which part); `published_num_experts` is what
the router routes over. What the other chips' experts would add is left
out here as in the program, and the partial sum goes on.

Departures from the published model, each marked `# DEPARTURE` below:
an observation projection replaces the token embedding; policy and
baseline heads replace the LM head; an attention layer attends over a
rolling cache of un-rotated keys cut at episode ends, positions
relative to the unroll's first step, not over 262,144 positions; a
DeltaNet layer's state and window are zeroed where an episode ends;
multi-token prediction is not run.
"""

import jax
import jax.numpy as jnp

from perfbench.reference.mellum2_policy import _may_attend, _rope
from perfbench.reference.olmoe_policy import vtrace


def _norm0(x, p, eps):
    """The zero-centred RMSNorm: the learned scale is 1 + w."""
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps
    ) * (1.0 + p["scale"])


def _l2norm(x):
    # ASSUMED: eps 1e-6 inside the root (the reference implementation's).
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _deltanet(h, done, p, state, config):
    """h [T, B, d] (already normed), done [T, B] -> (out [T, B, d], the
    state and the window after the last step)."""
    Hk, Hv = config["linear_num_key_heads"], config["linear_num_value_heads"]
    Dk, Dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    K = config["linear_conv_kernel_dim"]
    per = Hv // Hk
    A = jnp.exp(p["A_log"])  # [Hv]
    carried, window = state  # [Hv, B, Dk, Dv], [K - 1, B, C]

    def step(carry, inputs):
        carried, window = carry
        h_t, done_t = inputs  # [B, d], [B]
        rows = h_t.shape[0]
        # DEPARTURE: an episode's first step starts from nothing.
        carried = jnp.where(done_t[None, :, None, None], 0.0, carried)
        window = jnp.where(done_t[None, :, None], 0.0, window)
        qkvz = (h_t @ p["in_proj_qkvz"]["kernel"]).reshape(rows, Hk, -1)
        ba = (h_t @ p["in_proj_ba"]["kernel"]).reshape(rows, Hk, 2 * per)
        q, k, v, z = jnp.split(
            qkvz, [Dk, 2 * Dk, 2 * Dk + per * Dv], axis=-1
        )
        z = z.reshape(rows, Hv, Dv)
        b, a = ba[..., :per].reshape(rows, Hv), ba[..., per:].reshape(rows, Hv)
        joined = jnp.concatenate(
            [part.reshape(rows, -1) for part in (q, k, v)], axis=-1
        )
        # Four shifted adds: the K - 1 steps before this one, and it.
        conv = p["conv_kernel"][K - 1] * joined
        for tap in range(K - 1):
            conv = conv + p["conv_kernel"][tap] * window[tap]
        window = jnp.concatenate([window[1:], joined[None]], axis=0)
        conv = jax.nn.silu(conv)
        q = _l2norm(conv[:, : Hk * Dk].reshape(rows, Hk, Dk)) * Dk ** -0.5
        k = _l2norm(conv[:, Hk * Dk : 2 * Hk * Dk].reshape(rows, Hk, Dk))
        v = conv[:, 2 * Hk * Dk :].reshape(rows, Hv, Dv)
        # Value heads 2j and 2j + 1 read key head j.
        q, k = jnp.repeat(q, per, axis=1), jnp.repeat(k, per, axis=1)
        beta = jax.nn.sigmoid(b)  # [B, Hv]
        g = -A * jax.nn.softplus(a + p["dt_bias"])
        carried = jnp.exp(g).T[:, :, None, None] * carried
        read = jnp.einsum("hbkv,bhk->bhv", carried, k)
        u = beta[..., None] * (v - read)
        carried = carried + jnp.einsum("bhk,bhv->hbkv", k, u)
        o = jnp.einsum("hbkv,bhk->bhv", carried, q)
        # The norm first (one scale of 128 for every head), then the gate.
        normed = o * jax.lax.rsqrt(
            jnp.mean(o * o, axis=-1, keepdims=True) + config["rms_norm_eps"]
        )
        y = normed * p["gate_norm"] * jax.nn.silu(z)
        return (carried, window), y.reshape(rows, -1) @ p["out_proj"]["kernel"]

    (carried, window), out = jax.lax.scan(step, (carried, window), (h, done))
    return out, (carried, window)


def _attention(h, p, cache, allowed, config):
    """h [B, T, d] (already normed) -> (attended [B, T, d], k, v)."""
    rows, steps, _ = h.shape
    Hq, Hkv = config["num_attention_heads"], config["num_key_value_heads"]
    hd, eps = config["head_dim"], config["rms_norm_eps"]
    M = cache[0].shape[1]
    rotary = int(hd * config["partial_rotary_factor"])
    q_gate = (h @ p["q"]["kernel"]).reshape(rows, steps, Hq, 2 * hd)
    q, gate = q_gate[..., :hd], q_gate[..., hd:]
    k = (h @ p["k"]["kernel"]).reshape(rows, steps, Hkv, hd)
    v = (h @ p["v"]["kernel"]).reshape(rows, steps, Hkv, hd)
    q, k = _norm0(q, p["q_norm"], eps), _norm0(k, p["k_norm"], eps)
    k_all = jnp.concatenate([cache[0], k], axis=1)  # [B, M+T, Hkv, hd]
    v_all = jnp.concatenate([cache[1], v], axis=1)
    # DEPARTURE: positions relative to the unroll's first step; the cache
    # keeps keys un-rotated.
    key_time = jnp.concatenate([jnp.arange(M) - M, jnp.arange(steps)])
    inv_freq = config["rope_theta"] ** (
        -jnp.arange(0, rotary, 2, dtype=jnp.float32) / rotary
    )

    def placed(x, times):
        """RoPE on the first `rotary` columns of a head alone."""
        return jnp.concatenate([
            _rope(x[..., :rotary], times, inv_freq, 1.0), x[..., rotary:],
        ], axis=-1)

    def one_row(args):
        q, k_all, v_all, allowed = args
        # Every query head with its key/value head, repeated.
        keys = jnp.repeat(placed(k_all, key_time), Hq // Hkv, axis=1)
        values = jnp.repeat(v_all, Hq // Hkv, axis=1)
        scores = jnp.einsum(
            "qhd,khd->hqk", placed(q, jnp.arange(steps)), keys
        ) * hd ** -0.5
        scores = jnp.where(allowed[None], scores, -1e30)
        return jnp.einsum(
            "hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), values
        )

    attended = jax.lax.map(one_row, (q, k_all, v_all, allowed))
    gated = attended * jax.nn.sigmoid(gate)
    return gated.reshape(rows, steps, Hq * hd) @ p["o"]["kernel"], k, v


def _experts(h, p, config):
    """h [t, d] -> (the held experts' part of the routed sum plus the
    gated shared expert [t, d], the load-balance term over all the
    experts)."""
    E, K = config["published_num_experts"], config["num_experts_per_tok"]
    held = config["num_experts"]
    first = config["expert_share"][0] * held
    assert config["hidden_act"] == "silu"
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
    shared = (
        jax.nn.silu(h @ p["shared_gate"]["kernel"])
        * (h @ p["shared_up"]["kernel"])
    ) @ p["shared_down"]["kernel"]
    # One gate a token for the shared expert, d -> 1.
    y = y + jax.nn.sigmoid(h @ p["shared_expert_gate"]["kernel"]) * shared
    # ASSUMED: `router_aux_loss_coef` 0.001 (the catalog's row dropped the
    # key). E x sum_e (share of the K*t assignments that went to e) x
    # (mean router probability of e), over all E.
    share = mask.sum(axis=0) / (K * h.shape[0])
    balance = E * jnp.sum(share * probs.mean(axis=0))
    return y, config["router_aux_loss_coef"] * balance


def forward(params, batch, core_state, config):
    """(policy_logits [T, B, A], baseline [T, B], new state, aux loss).
    `core_state` has an item a layer: (S, window) for a DeltaNet layer,
    (k, v, valid) for an attention layer."""
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
    assert config["decoder_sparse_step"] == 1 and not config["mlp_only_layers"]
    aux = 0.0
    new_state = []
    for layer in range(config["num_hidden_layers"]):
        # The program keeps a layer's mixer and its MoE part in two
        # blocks of its tree, 2l and 2l + 1, each with its input norm.
        block, ffn = p[f"block_{2 * layer}"], p[f"block_{2 * layer + 1}"]
        h = _norm0(x, block["norm"], eps)
        if (layer + 1) % config["full_attention_interval"]:
            out, state = _deltanet(
                h.transpose(1, 0, 2), done, block, core_state[layer], config
            )
            x = x + out.transpose(1, 0, 2)
            new_state.append(state)
        else:
            k_cache, v_cache, valid = core_state[layer]
            # DEPARTURE: a rolling cache of M keys and values, cut at
            # episode ends, in place of 262,144 positions.
            assert k_cache.shape[0] == M
            allowed = _may_attend(done, valid, M)
            cache = (
                k_cache.transpose(1, 0, 2, 3), v_cache.transpose(1, 0, 2, 3)
            )
            attended, k, v = _attention(h, block, cache, allowed, config)
            x = x + attended
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
        h = _norm0(x, ffn["norm"], eps)
        y, balance = _experts(h.reshape(rows * steps, -1), ffn["moe"], config)
        x = x + y.reshape(rows, steps, -1)
        aux = aux + balance
    # DEPARTURE: multi-token prediction is not run: there is no next
    # token to predict from.
    x = _norm0(x, p["final_norm"], eps)
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
