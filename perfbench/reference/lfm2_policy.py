"""Plain reference of the LFM2-cut policy and its IMPALA loss.

Written from the model's description, not from the program: the layers
of LFM2-8B-A1B (Liquid AI; config.json, `model_type` lfm2_moe) and the
V-trace actor-critic loss of Espeholt et al. 2018 (arXiv:1802.01561,
section 4; the recursion is `olmoe_policy.vtrace`), in straightforward
`jax.numpy` and float32 at the highest matmul precision. The gated
short convolution is computed ONE STEP AT A TIME (`lax.scan` over the
unroll's steps) over an explicit tail of the two products before the
step, which is zeroed at a step where `done` is set; attention is one
masked matrix over the cached and the unrolled steps, a row of the
batch at a time, the keys and values of a group repeated for its query
heads, the keys rotated; each expert held runs on every token under the
token's gate for it (zero where the token did not choose it). No
shifted adds over the unroll, no comparison of counts of episode ends,
no sort, no grouped matmul, no cache roll, no fused pass, no batching
of anything. It reads the program's parameter tree (flax names) so that
both can be given the same weights, and imports nothing from the
program (which layers run is read as the counts read it: `perfbench/
flops_lfm2.py` `layers_run`).

`norm(x) = x / sqrt(mean(x^2) + norm_eps) * w`. A layer is `h = x +
operator(norm(x)); y = h + ffn(norm(h))`, no biases. `layers_run` lists
the published layers that are run, in order; published layer l has the
operator `layer_types[l]` and a dense SwiGLU where l < `num_dense_
layers`, else the experts:

  conv  [B | C | u] = in_proj(x), 3 d; p_t = B_t * u_t;
        c_t = w_0 p_{t-2} + w_1 p_{t-1} + w_2 p_t (causal, depthwise,
        `conv_L_cache` 3 taps, no bias); y = out_proj(C_t * c_t). No
        activation.
  full_attention  q, k, v = projections to 32 / 8 / 8 heads of 64;
        q, k = norm over the head's 64 with a learned scale; RoPE
        (rotate-half over the whole head, theta 1e6); softmax(q k^T /
        sqrt(64)) v over [cache; unroll], causal; o_proj
  dense  w2(silu(w1 x) * w3 x)
  moe   s = sigmoid(W_r x) over 32; the 4 largest of s + expert_bias;
        g = s / (sum of the chosen s + 1e-6), times `routed_scaling_
        factor`; the sum over the experts HELD of g_e E_e(x), E_e a
        SwiGLU

The share: the configuration's `num_experts` is what this chip HOLDS
(`expert_share` [i, n] says which part); `published_num_experts` is what
the router routes over. What the other chips' experts would add is left
out here as in the program, and the partial sum goes on.

Departures from the published model, each marked `# DEPARTURE` below:
an observation projection replaces the token embedding; policy and
baseline heads replace the LM head; an attention layer attends over a
rolling cache of un-rotated keys cut at episode ends, positions
relative to the unroll's first step, not over 128,000 positions; a conv
layer's tail is zeroed where an episode ends.
"""

import jax
import jax.numpy as jnp

from perfbench.flops_lfm2 import ATTENTION, CONV, layers_run
from perfbench.reference.mellum2_policy import _may_attend, _rope
from perfbench.reference.olmoe_policy import vtrace


def _norm(x, p, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps
    ) * p["scale"]


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def _conv_operator(h, done, p, tail, config):
    """h [T, B, d] (already normed), done [T, B], tail [K - 1, B, d] ->
    (out [T, B, d], the tail after the last step)."""
    K = config["conv_L_cache"]
    assert not config["conv_bias"]
    taps = p["conv_kernel"]  # [K, d], the last the step's own

    def step(tail, inputs):
        h_t, done_t = inputs  # [B, d], [B]
        # DEPARTURE: an episode's first step reads no product from
        # before it.
        tail = jnp.where(done_t[None, :, None], 0.0, tail)
        gate_in, gate_out, u = jnp.split(h_t @ p["in_proj"]["kernel"], 3, -1)
        product = gate_in * u
        conv = taps[K - 1] * product
        for tap in range(K - 1):
            conv = conv + taps[tap] * tail[tap]
        tail = jnp.concatenate([tail[1:], product[None]], axis=0)
        return tail, (gate_out * conv) @ p["out_proj"]["kernel"]

    tail, out = jax.lax.scan(step, tail, (h, done))
    return out, tail


def _attention(h, p, cache, allowed, config):
    """h [B, T, d] (already normed) -> (attended [B, T, d], k, v)."""
    rows, steps, d = h.shape
    Hq, Hkv = config["num_attention_heads"], config["num_key_value_heads"]
    hd, eps = d // Hq, config["norm_eps"]
    M = cache[0].shape[1]
    q = (h @ p["q"]["kernel"]).reshape(rows, steps, Hq, hd)
    k = (h @ p["k"]["kernel"]).reshape(rows, steps, Hkv, hd)
    v = (h @ p["v"]["kernel"]).reshape(rows, steps, Hkv, hd)
    # The norms BEFORE the rotation; the cache keeps normed keys.
    q, k = _norm(q, p["q_norm"], eps), _norm(k, p["k_norm"], eps)
    k_all = jnp.concatenate([cache[0], k], axis=1)  # [B, M+T, Hkv, hd]
    v_all = jnp.concatenate([cache[1], v], axis=1)
    # DEPARTURE: positions relative to the unroll's first step; the cache
    # keeps keys un-rotated.
    key_time = jnp.concatenate([jnp.arange(M) - M, jnp.arange(steps)])
    inv_freq = config["rope_theta"] ** (
        -jnp.arange(0, hd, 2, dtype=jnp.float32) / hd
    )

    def one_row(args):
        q, k_all, v_all, allowed = args
        # Every query head with its key/value head, repeated.
        keys = jnp.repeat(
            _rope(k_all, key_time, inv_freq, 1.0), Hq // Hkv, axis=1
        )
        values = jnp.repeat(v_all, Hq // Hkv, axis=1)
        scores = jnp.einsum(
            "qhd,khd->hqk", _rope(q, jnp.arange(steps), inv_freq, 1.0), keys
        ) * hd ** -0.5
        scores = jnp.where(allowed[None], scores, -1e30)
        return jnp.einsum(
            "hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), values
        )

    attended = jax.lax.map(one_row, (q, k_all, v_all, allowed))
    return attended.reshape(rows, steps, Hq * hd) @ p["o"]["kernel"], k, v


def _route(h, p, config):
    """(gates [t, E], zero where not chosen; the 0/1 choice [t, E])."""
    E, K = config["published_num_experts"], config["num_experts_per_tok"]
    scores = jax.nn.sigmoid(h @ p["router"]["kernel"])  # [t, E]
    selection = scores
    if config["use_expert_bias"]:
        # The bias chooses and is no part of the gate; no gradient
        # reaches it (the choice is an index).
        selection = scores + p["e_score_correction_bias"]
    _, chosen = jax.lax.top_k(selection, K)
    mask = jax.nn.one_hot(chosen, E, dtype=jnp.float32).sum(axis=1)
    gates = scores * mask
    if config["norm_topk_prob"]:
        # ASSUMED: the reference implementation's 1e-6 (no config key).
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-6)
    return gates * config["routed_scaling_factor"], mask


def _experts(h, p, config):
    """h [t, d] -> the held experts' part of the routed sum, [t, d]."""
    held = config["num_experts"]
    first = config["expert_share"][0] * held
    gates, _ = _route(h, p, config)
    y = jnp.zeros_like(h)
    for e in range(held):
        y = y + gates[:, first + e : first + e + 1] * _swiglu(
            h, p["w_gate"][e], p["w_up"][e], p["w_down"][e]
        )
    return y


def bias_step(h, p, config):
    """What the expert bias moves by after an update whose router saw h
    [t, d]: u x sign(mean load - load), the load being the batch's
    assignments to each of the E experts (DeepSeek-V3, arXiv:2412.19437,
    section 2.1.2).
    # ASSUMED: the rule and u = `bias_update_rate`; config.json says
    `use_expert_bias` and has no key for either."""
    _, mask = _route(h, p, config)
    load = mask.sum(axis=0)
    return config["bias_update_rate"] * jnp.sign(jnp.mean(load) - load)


def forward(params, batch, core_state, config, moe_inputs=None):
    """(policy_logits [T, B, A], baseline [T, B], new state, aux loss:
    none in this model). `core_state` has an item a layer run: (tail,)
    for a conv layer, (k, v, valid) for an attention layer. `moe_
    inputs`, a list, is given (the block's index, its MoE's normed
    input [t, d]) of each MoE layer: what `bias_step` reads."""
    p = params["params"]
    eps = config["norm_eps"]
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
    kinds = layers_run(config)
    assert len(kinds) == config["num_hidden_layers"]
    new_state = []
    for layer, (kind, dense) in enumerate(kinds):
        block = p[f"block_{layer}"]
        h = _norm(x, block["operator_norm"], eps)
        if kind == CONV:
            (tail,) = core_state[layer]
            out, tail = _conv_operator(
                h.transpose(1, 0, 2), done, block, tail, config
            )
            x = x + out.transpose(1, 0, 2)
            new_state.append((tail,))
        else:
            assert kind == ATTENTION
            k_cache, v_cache, valid = core_state[layer]
            # DEPARTURE: a rolling cache of M keys and values, cut at
            # episode ends, in place of 128,000 positions.
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
        h = _norm(x, block["ffn_norm"], eps).reshape(rows * steps, -1)
        if dense:
            y = _swiglu(
                h, block["w1"]["kernel"], block["w3"]["kernel"],
                block["w2"]["kernel"],
            )
        else:
            if moe_inputs is not None:
                moe_inputs.append((layer, h))
            y = _experts(h, block["moe"], config)
        x = x + y.reshape(rows, steps, -1)
    x = _norm(x, p["final_norm"], eps)
    x = x.transpose(1, 0, 2).reshape(steps * rows, -1)
    # DEPARTURE: policy and baseline heads in place of the LM head.
    head = p["head"]
    logits = x @ head["policy"]["kernel"] + head["policy"]["bias"]
    baseline = x @ head["baseline"]["kernel"] + head["baseline"]["bias"]
    return (
        logits.reshape(steps, rows, -1), baseline.reshape(steps, rows),
        tuple(new_state), 0.0,
    )


def bias_steps(params, batch, core_state, config):
    """`bias_step` of every MoE layer, in order, for this batch."""
    with jax.default_matmul_precision("highest"):
        inputs = []
        forward(params, batch, core_state, config, moe_inputs=inputs)
        return [
            bias_step(h, params["params"][f"block_{layer}"]["moe"], config)
            for layer, h in inputs
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
