"""Plain reference of the Ouro looped-block policy and its IMPALA loss.

Written from the model's description, not from the program: the layer
and the loop of Ouro-2.6B (ByteDance; config.json, `model_type` ouro;
"Scaling Latent Reasoning via Looped Language Models", arXiv:2510.25741)
and the V-trace actor-critic loss of Espeholt et al. 2018
(arXiv:1802.01561, section 4; the recursion is `olmoe_policy.vtrace`), in
straightforward `jax.numpy` and float32 at the highest matmul precision.
The loop is written out: `total_ut_steps` x `num_hidden_layers` layer
calls, one after the other, layer l of pass u reading the weights
`block_l` and the cache `u * num_hidden_layers + l`. No scan, no
rematerialisation, no shared module, no cache roll; attention is one
masked matrix over the cached and the unrolled steps. It reads the
program's parameter tree (flax names) so that both can be given the same
weights, and imports nothing from the program.

    for pass u:
      for layer l:
        x = x + n2_l(Wo_l attend(rope(Wq_l n1_l(x)),
                                 rope([cache.k ; Wk_l n1_l(x)]),
                                 [cache.v ; Wv_l n1_l(x)]))
        x = x + n4_l(Wdown_l(silu(Wgate_l n3_l(x)) * Wup_l n3_l(x)))
      x = final_norm(x)
      lambda_u = sigmoid(w_exit . x + b_exit)
    heads(x after the last pass)

n1..n4 are `input_layernorm`, `input_layernorm_2`, `post_attention_
layernorm`, `post_attention_layernorm_2` of the model's public modeling
file: an RMSNorm before each branch and one on its output.

Departures from the published model, each marked `# DEPARTURE` below:
an observation projection replaces the token embedding; policy and
baseline heads replace the LM head; attention is over a window of
`memory_len` past steps carried in rolling caches, one for every
(pass, layer), cut at episode ends, not over 65,536 positions; RoPE
positions are relative to the unroll's first step; the exit gates
take no part in the loss (`early_exit_threshold` 1.0 skips no pass, and
Ouro's expected loss over the exits is a language-modelling loss).
What the config does not spell out, each marked `# ASSUMED`: the four
norms a layer, the norm after every pass, the gate.
"""

import jax
import jax.numpy as jnp

from perfbench.reference.olmoe_policy import (
    _may_attend,
    _rmsnorm,
    _rope,
    vtrace,
)


def _layer(x, p, cache, allowed, config):
    """One application of one layer. x [B, T, d]; cache (k, v)
    [B, M, H, D]. Returns (x, k, v), this unroll's un-rotated keys and
    values."""
    rows, steps, _ = x.shape
    heads, head_dim = config["num_attention_heads"], config["head_dim"]
    eps, theta = config["rms_norm_eps"], config["rope_theta"]
    M = cache[0].shape[1]
    # ASSUMED: four norms a layer, two of them on the branches' outputs.
    h = _rmsnorm(x, p["input_layernorm"], eps)
    q, k, v = (
        (h @ p[name]["kernel"]).reshape(rows, steps, heads, head_dim)
        for name in ("q", "k", "v")
    )
    k_all = jnp.concatenate([cache[0], k], axis=1)  # [B, M+T, H, D]
    v_all = jnp.concatenate([cache[1], v], axis=1)
    # DEPARTURE: positions relative to the unroll's first step; the cache
    # keeps keys un-rotated.
    key_time = jnp.concatenate([jnp.arange(M) - M, jnp.arange(steps)])
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk",
        _rope(q, jnp.arange(steps), theta), _rope(k_all, key_time, theta),
    ) * head_dim ** -0.5
    scores = jnp.where(allowed[:, None], scores, -1e30)
    attended = jnp.einsum(
        "bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v_all
    ).reshape(rows, steps, heads * head_dim)
    x = x + _rmsnorm(attended @ p["o"]["kernel"], p["input_layernorm_2"], eps)
    h = _rmsnorm(x, p["post_attention_layernorm"], eps)
    hidden = jax.nn.silu(h @ p["gate"]["kernel"]) * (h @ p["up"]["kernel"])
    x = x + _rmsnorm(
        hidden @ p["down"]["kernel"], p["post_attention_layernorm_2"], eps
    )
    return x, k, v


def forward(params, batch, core_state, config):
    """(policy_logits [T, B, A], baseline [T, B], new caches, exit gates
    [passes, B, T])."""
    p = params["params"]
    eps, M = config["rms_norm_eps"], config["memory_len"]
    layers, passes = config["num_hidden_layers"], config["total_ut_steps"]
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
    last = ends[:, -1:]
    assert len(core_state) == passes * layers, (len(core_state), passes, layers)
    new_state, gates = [], []
    for u in range(passes):
        for layer in range(layers):
            # DEPARTURE: a rolling cache of `memory_len` steps for this
            # (pass, layer), cut at episode ends, in place of 65,536
            # positions. Same weights every pass, a cache of its own.
            k_cache, v_cache, valid = core_state[u * layers + layer]
            assert k_cache.shape[0] == M, (k_cache.shape, M)
            cache = (
                k_cache.transpose(1, 0, 2, 3), v_cache.transpose(1, 0, 2, 3)
            )
            x, k, v = _layer(
                x, p[f"block_{layer}"], cache,
                _may_attend(done, valid, M), config,
            )
            # The cache the actor would carry on: the last M of [cache;
            # this unroll], of which only the last episode's steps stay
            # valid.
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
        # ASSUMED: one norm, applied after every pass; its output feeds
        # the next pass and the gate.
        x = _rmsnorm(x, p["final_norm"], eps)
        # ASSUMED: the exit gate, Linear(d, 1) and a sigmoid.
        gate = p["exit_gate"]
        gates.append(jax.nn.sigmoid(x @ gate["kernel"] + gate["bias"])[..., 0])
    x = x.transpose(1, 0, 2).reshape(steps * rows, -1)
    # DEPARTURE: policy and baseline heads in place of the LM head, on
    # the last pass's output (no pass is skipped).
    head = p["head"]
    logits = x @ head["policy"]["kernel"] + head["policy"]["bias"]
    baseline = x @ head["baseline"]["kernel"] + head["baseline"]["bias"]
    return (
        logits.reshape(steps, rows, -1), baseline.reshape(steps, rows),
        tuple(new_state), jnp.stack(gates),
    )


def exit_distribution(gates):
    """[passes, ...] gates lambda_u -> [passes, ...] p_u = lambda_u
    prod_{j<u} (1 - lambda_j), the last pass taking what is left."""
    left, out = jnp.ones_like(gates[0]), []
    for gate in gates[:-1]:
        out.append(gate * left)
        left = left * (1.0 - gate)
    return jnp.stack(out + [left])


def loss_and_scale(params, batch, core_state, config):
    """The total loss of one [T+1, B] batch (IMPALA's three terms, as the
    reference implementation sums them), and the sum of the magnitudes of
    its terms: the total is a sum of signed terms and can come out near
    zero, so a difference between two computations of it is judged
    against the scale, which cannot."""
    with jax.default_matmul_precision("highest"):
        # DEPARTURE: the gates are not in the loss.
        logits, baseline, _, _ = forward(params, batch, core_state, config)
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
        )
        scale = (
            jnp.sum(jnp.abs(pg_terms))
            + config["baseline_cost"] * baseline_loss
            + config["entropy_cost"] * jnp.abs(entropy_loss)
        )
        return total, scale


def loss(params, batch, core_state, config):
    """The total loss of one [T+1, B] batch."""
    return loss_and_scale(params, batch, core_state, config)[0]
