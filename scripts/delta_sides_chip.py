"""What a chunk of the delta rule owes before its state enters it, ON
THE CHIP (ops/delta_rule.py's three cells), at the shapes of a
Qwen3-Next layer in `qwen3next_policy.learner`, the caller's three
passes:

- against float64: the op alone (U, Kd, A and every gradient under
  random cotangents) by the cells and by the `jax.numpy` form the model
  keeps for other shapes (the solve at the highest, the rest at
  `high`), each against the same form evaluated in float64 on the
  host's CPU, `--seeds` seeds: the largest error of each over the
  float64 result's largest entry;
- the time of the op, forward alone and forward + backward, by the
  cells and by XLA's form, and of each cell's call alone;
- with `--sweep`, the three calls at other counts of pairs a turn of
  the rolled loop (`_PAIRS_TOGETHER`) and pairs a cell (`_HEADS`).

    chiprun -- python3 scripts/delta_sides_chip.py --sweep \
        --out chiprun_out/pr69

Prints one JSON object and writes it to <out>/delta_sides_chip.json.
Exits 1 without a TPU: a CPU's times are nobody's.
"""

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from torchbeast_tpu.models import qwen3next  # noqa: E402
from torchbeast_tpu.models.nemotron3 import reaches  # noqa: E402
from torchbeast_tpu.ops import delta_rule  # noqa: E402

# Batch rows, chunks, key heads, value heads a key head: a learner
# step's layer in chunks of 64 at 128 x 128.
CELLS = {"qwen3next": (16, 4, 16, 2)}
Q, D, TERMS = 64, 128, 2
# (pairs a turn of the rolled loop, pairs a cell at most).
SWEEP = ((1, 8), (2, 8), (4, 8), (2, 4), (2, 16))


def case(cell, seed):
    """Operands like a layer's in a learner step (keys of unit length,
    beta a sigmoid's, log-decays whose sum over a chunk is a few units,
    an episode end at a tenth of the steps), heads before steps, and
    the results' cotangents: (q, k, v, beta, G), ends, (dU, dKd, dA)."""
    rows, chunks, Hk, per = CELLS[cell]
    rng = np.random.default_rng(seed)

    def unit(*shape):
        x = rng.standard_normal(shape)
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    lead = (rows, chunks, Hk)
    ends = np.cumsum(rng.random((rows, chunks, Q)) < 0.1, axis=-1)
    G = np.cumsum(-rng.uniform(0.0, 0.1, lead + (per, Q)), axis=-1)
    return (
        unit(*lead, Q, D) * D ** -0.5, unit(*lead, Q, D),
        rng.standard_normal(lead + (per, Q, D)),
        rng.uniform(0.05, 0.95, lead + (per, Q)), G,
    ), ends, (
        rng.standard_normal(lead + (per, Q, D)),
        rng.standard_normal(lead + (per, Q, D)),
        rng.standard_normal(lead + (per, Q, Q)),
    )


def in_cells(q, k, v, beta, G, ends):
    # v as the mixer leaves it, steps before heads.
    weights, values, keys_seen = delta_rule.sides_before_the_state(
        q, k, v.transpose(0, 1, 4, 2, 3, 5), beta, G, ends, TERMS
    )
    return values, keys_seen, weights


def in_numpy(q, k, v, beta, G, ends):
    """The op as `delta_scan` keeps it for other shapes, in the
    operands' dtype."""
    decay = jnp.exp(jnp.where(
        reaches(ends)[:, :, None, None],
        G[..., :, None] - G[..., None, :], -jnp.inf,
    ))
    from_start = jnp.where(ends[:, :, None, None] == 0, jnp.exp(G), 0.0)
    between_keys = jnp.einsum("bchid,bchjd->bchij", k, k)
    by_beta = qwen3next.unit_lower_inverse(jnp.where(
        np.tril(np.ones((Q, Q), bool), -1),
        beta[..., :, None] * between_keys[:, :, :, None] * decay, 0.0,
    )) * beta[..., None, :]
    return (
        jnp.einsum("bchpij,bchpjv->bchpiv", by_beta, v),
        jnp.einsum(
            "bchpij,bchjd->bchpid", by_beta * from_start[..., None, :], k
        ),
        jnp.einsum("bchid,bchjd->bchij", q, k)[:, :, :, None] * decay,
    )


def with_gradients(op):
    """Jitted (operands, ends, cotangents) -> the op's results and
    every operand's gradient, traced at the caller's `high`."""
    def run(operands, ends, cotangents):
        with jax.default_matmul_precision("high"):
            results, pull = jax.vjp(lambda *a: op(*a, ends), *operands)
        return tuple(results) + pull(tuple(cotangents))

    return jax.jit(run)


def forward_alone(op):
    def run(operands, ends):
        with jax.default_matmul_precision("high"):
            return op(*operands, ends)

    return jax.jit(run)


NAMES = ("U", "Kd", "A", "dq", "dk", "dv", "dbeta", "dG")


def against_float64(cell, seeds):
    """{name: (the cells' largest error, the `jax.numpy` form's)} over
    the seeds, each over the float64 result's largest entry."""
    worst = {name: [0.0, 0.0] for name in NAMES}
    sides = with_gradients(in_cells), with_gradients(in_numpy)
    for seed in seeds:
        operands, ends, cotangents = case(cell, seed)
        with jax.enable_x64(True), jax.default_device(jax.devices("cpu")[0]):
            exact = [np.asarray(x) for x in sides[1](
                tuple(jnp.asarray(a, jnp.float64) for a in operands),
                jnp.asarray(ends),
                tuple(jnp.asarray(a, jnp.float64) for a in cotangents),
            )]
        single = (
            tuple(jnp.asarray(a, jnp.float32) for a in operands),
            jnp.asarray(ends, jnp.int32),
            tuple(jnp.asarray(a, jnp.float32) for a in cotangents),
        )
        for side, run in enumerate(sides):
            for name, x, want in zip(NAMES, run(*single), exact):
                error = float(
                    np.max(np.abs(np.asarray(x, np.float64) - want))
                    / np.max(np.abs(want))
                )
                worst[name][side] = max(worst[name][side], error)
    return worst


def ms_a_call(fn, args, calls=40):
    jax.block_until_ready(fn(*args))
    start = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - start) / calls


def times(cell, seed):
    operands, ends, cotangents = case(cell, seed)
    ends = jnp.asarray(ends, jnp.int32)
    operands = tuple(jnp.asarray(a, jnp.float32) for a in operands)
    cotangents = tuple(jnp.asarray(a, jnp.float32) for a in cotangents)
    out = {
        "cells_forward_ms": ms_a_call(
            forward_alone(in_cells), (operands, ends)
        ),
        "cells_forward_backward_ms": ms_a_call(
            with_gradients(in_cells), (operands, ends, cotangents)
        ),
        "xla_forward_ms": ms_a_call(
            forward_alone(in_numpy), (operands, ends)
        ),
        "xla_forward_backward_ms": ms_a_call(
            with_gradients(in_numpy), (operands, ends, cotangents)
        ),
    }
    out.update(calls_alone(cell, operands, ends, cotangents))
    return out


def calls_alone(cell, operands, ends, cotangents):
    """Each cell's call alone, on operands already laid out."""
    rows, chunks, Hk, per = CELLS[cell]
    static = dict(terms=TERMS, interpret=False)
    q, k, v, beta, G = operands
    side = (rows, chunks, Hk, 2 * Q)
    laid = (
        q, k, v.transpose(0, 1, 4, 2, 3, 5).reshape(rows, chunks, Q, -1),
        jnp.stack([beta.reshape(side), G.reshape(side)], axis=3),
        jnp.tile(ends.astype(jnp.float32), (1, 1, 2))[:, :, None],
    )
    solved = delta_rule._solve(laid[1], *laid[3:], **static)
    return {
        "solve_ms": ms_a_call(
            lambda: delta_rule._solve(laid[1], *laid[3:], **static), ()
        ),
        "apply_ms": ms_a_call(
            lambda: delta_rule._apply(solved, *laid, **static), ()
        ),
        "backward_ms": ms_a_call(
            lambda: delta_rule._sides_backward(
                solved, laid, cotangents, **static
            ), (),
        ),
    }


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--cells", default="qwen3next")
    parser.add_argument("--seeds", type=int, default=3)
    parser.add_argument("--sweep", action="store_true")
    parser.add_argument("--out", default="chiprun_out/pr69")
    flags = parser.parse_args()
    if jax.default_backend() != "tpu":
        print(json.dumps({"ok": False, "why": "no TPU"}))
        return 1
    device = jax.devices()[0]
    report = {"device": device.device_kind, "cells": {}}
    for cell in flags.cells.split(","):
        entry = {"shape": CELLS[cell]}
        entry["against_float64"] = against_float64(
            cell, range(1000, 1000 + flags.seeds)
        )
        entry["ms"] = times(cell, 7)
        if flags.sweep:
            entry["sweep"] = {}
            kept = delta_rule._PAIRS_TOGETHER, delta_rule._HEADS
            operands, ends, cotangents = case(cell, 7)
            ends = jnp.asarray(ends, jnp.int32)
            operands = tuple(jnp.asarray(a, jnp.float32) for a in operands)
            cotangents = tuple(
                jnp.asarray(a, jnp.float32) for a in cotangents
            )
            for setting in SWEEP:
                delta_rule._PAIRS_TOGETHER, delta_rule._HEADS = setting
                jax.clear_caches()
                entry["sweep"]["x".join(map(str, setting))] = calls_alone(
                    cell, operands, ends, cotangents
                )
            delta_rule._PAIRS_TOGETHER, delta_rule._HEADS = kept
            jax.clear_caches()
        report["cells"][cell] = entry
        print(cell, json.dumps(entry), flush=True)
    os.makedirs(flags.out, exist_ok=True)
    with open(os.path.join(flags.out, "delta_sides_chip.json"), "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())
