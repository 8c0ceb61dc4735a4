"""Plain reference of the IMPALA deep ResNet + LSTM agent and its loss.

Written from the papers, not from the program: the network of Espeholt
et al. 2018 (arXiv:1802.01561, Figure 3 right) and the V-trace
actor-critic loss of its section 4, in straightforward `jax.numpy` and
float32 at the highest matmul precision, with no rematerialisation, no
fused losses and a sequential V-trace recursion. It reads the
program's parameter tree (flax names) so that both can be given the
same weights. Departures from the paper, all the reference
implementation's (torchbeast polybeast_learner.py) and shared by the
program: the clipped reward, not the last action, is appended to the
core input; sums, not means, reduce the losses; the baseline loss
carries a factor 0.5.
"""

import jax
import jax.numpy as jnp
from jax import lax


def _conv(x, p):
    y = lax.conv_general_dilated(
        x, p["kernel"], window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    return y + p["bias"]


def _max_pool(x):
    return lax.reduce_window(
        x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
        ((0, 0), (1, 1), (1, 1), (0, 0)),
    )


def _dense(x, p):
    return x @ p["kernel"] + p["bias"]


def forward(params, batch, core_state, config):
    """(policy_logits [T, B, A], baseline [T, B]) for a [T, B] batch."""
    p = params["params"]
    trunk, head = p["trunk"], p["head"]
    frame = batch["frame"]
    steps, rows = frame.shape[:2]
    x = frame.reshape((steps * rows,) + frame.shape[2:])
    x = x.astype(jnp.float32) / 255.0
    for stage in range(len(config["trunk_channels"])):
        x = _max_pool(_conv(x, trunk[f"feat_conv_{stage}"]))
        for block in range(2):
            skip = x
            x = _conv(jax.nn.relu(x), trunk[f"res_{stage}_{block}_conv1"])
            x = _conv(jax.nn.relu(x), trunk[f"res_{stage}_{block}_conv2"])
            x = x + skip
    x = jax.nn.relu(x).reshape(steps * rows, -1)
    x = jax.nn.relu(_dense(x, trunk["fc"]))
    reward = jnp.clip(batch["reward"].astype(jnp.float32), -1, 1)
    x = jnp.concatenate([x, reward.reshape(-1, 1)], axis=-1)

    if config["use_lstm"]:
        cell = head["core"]["Scan_StackedLSTMStep_0"]["layer_0"]
        x = x.reshape(steps, rows, -1)
        notdone = 1.0 - batch["done"].astype(jnp.float32)
        h, c = core_state[0][0], core_state[1][0]
        outputs = []
        for t in range(steps):
            h = h * notdone[t][:, None]
            c = c * notdone[t][:, None]
            gate = {
                g: x[t] @ cell["i" + g]["kernel"] + _dense(h, cell["h" + g])
                for g in "ifgo"
            }
            c = (
                jax.nn.sigmoid(gate["f"]) * c
                + jax.nn.sigmoid(gate["i"]) * jnp.tanh(gate["g"])
            )
            h = jax.nn.sigmoid(gate["o"]) * jnp.tanh(c)
            outputs.append(h)
        x = jnp.stack(outputs).reshape(steps * rows, -1)

    logits = _dense(x, head["policy"]).reshape(steps, rows, -1)
    baseline = _dense(x, head["baseline"]).reshape(steps, rows)
    return logits, baseline


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
    """The total IMPALA loss of one [T+1, B] batch, and the sum of the
    magnitudes of its terms. The total is a sum of signed terms and
    can come out near zero, so a difference between two computations
    of it is judged against the scale, which cannot."""
    with jax.default_matmul_precision("highest"):
        logits, baseline = forward(params, batch, core_state, config)
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
        vs, advantages = vtrace(
            log_rhos, discounts, rewards, values, bootstrap_value
        )
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
    """The total IMPALA loss of one [T+1, B] batch."""
    return loss_and_scale(params, batch, core_state, config)[0]
