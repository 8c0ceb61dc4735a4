"""Mamba-1's selective scan ON THE CHIP, at the Phi-4-mini-flash cell's
shapes (16 rows, 256 steps, 5,120 channels, 16 state columns, an
episode end at 10% of the steps): the two regimes of models/
phi4flash.py `selective_scan` side by side,

- `kernels`: ops/selective_scan.py's two Mosaic kernels (the state in
  VMEM), what the learner's unroll takes;
- `lax_scan`: the chunked, rematerialised `lax.scan` (the state through
  HBM every step), what acting and toy widths take;

parity of the output, the state handed on and the six gradients between
them (and of the kernels against a float64 recurrence on the host, the
first `--parity_rows` rows of `--parity_channels` channels), and the
time of a forward and of a forward + backward of each, alone.

    chiprun -- python3 scripts/selective_scan_chip.py \
        --out chiprun_out/pr55

Prints one JSON object and writes it to <out>/selective_scan_chip.json.
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

from torchbeast_tpu.models import phi4flash  # noqa: E402
from torchbeast_tpu.ops import selective_scan  # noqa: E402

NAMES = ("y", "last", "da", "ddt", "dA", "dB", "dC", "dstate")


def case(rows, steps, channels, columns, seed):
    """Operands like a learner step's: dt log-uniform in Mamba-1's
    [0.001, 0.1], A = -(1..N), a state an actor carried."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    dt = jnp.exp(jax.random.uniform(
        keys[1], (rows, steps, channels), minval=np.log(1e-3),
        maxval=np.log(1e-1),
    ))
    A = -jnp.broadcast_to(
        jnp.arange(1.0, columns + 1)[:, None], (columns, channels)
    )
    return (
        jax.random.normal(keys[0], (rows, steps, channels)), dt, A,
        jax.random.normal(keys[2], (rows, steps, columns)),
        jax.random.normal(keys[3], (rows, steps, columns)),
        jax.random.normal(keys[4], (rows, columns, channels)),
    ), jax.random.bernoulli(keys[5], 0.1, (rows, steps))


def by_host(operands, done, rows, channels):
    """(y, last) of the recurrence in float64 on the host."""
    a, dt, A, B_in, C_in, state = (
        np.asarray(x, np.float64) for x in operands
    )
    a, dt, state = a[:rows, :, :channels], dt[:rows, :, :channels], (
        state[:rows, :, :channels]
    )
    A, done = A[:, :channels], np.asarray(done)[:rows]
    ys = []
    for t in range(a.shape[1]):
        keep = 1.0 - done[:, t].astype(np.float64)
        state = (
            np.exp(dt[:, t, None, :] * A) * keep[:, None, None] * state
            + (dt[:, t] * a[:, t])[:, None, :] * B_in[:rows, t, :, None]
        )
        ys.append(np.einsum("bnd,bn->bd", state, C_in[:rows, t]))
    return np.stack(ys, axis=1), state


def timed(fn, operands, calls):
    jax.block_until_ready(fn(*operands))
    start = time.monotonic()
    for _ in range(calls):
        out = fn(*operands)
    jax.block_until_ready(out)
    return 1e3 * (time.monotonic() - start) / calls


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--rows", type=int, default=16)
    parser.add_argument("--steps", type=int, default=256)
    parser.add_argument("--channels", type=int, default=5120)
    parser.add_argument("--columns", type=int, default=16)
    parser.add_argument("--seed", type=int, default=7)
    parser.add_argument("--calls", type=int, default=10)
    parser.add_argument("--parity_rows", type=int, default=2)
    parser.add_argument("--parity_channels", type=int, default=256)
    parser.add_argument("--out", default=None)
    args = parser.parse_args(argv)
    if jax.default_backend() != "tpu":
        print("selective_scan_chip: no TPU", file=sys.stderr)
        return 1
    operands, done = case(
        args.rows, args.steps, args.channels, args.columns, args.seed
    )
    assert selective_scan.kernels_apply(
        args.steps, args.channels, args.columns
    )
    regimes = {
        "kernels": lambda *o: selective_scan.selective_scan_kernels(*o, done),
        # The `lax.scan` at the same shapes: the rule told not to apply.
        "lax_scan": lambda *o: _lax_scan(*o, done),
    }
    report = {
        "device": str(jax.devices()[0]), "shape": vars(args), "ms": {},
        "resets_per_row": float(jnp.mean(jnp.sum(done, axis=1))),
    }
    results = {}
    for name, scan in regimes.items():
        forward, both = programs(scan)
        report["ms"][name] = {
            "forward": timed(forward, operands, args.calls),
            "forward_and_backward": timed(both, operands, args.calls),
        }
        results[name] = tuple(forward(*operands)) + tuple(both(*operands)[1])
    report["kernels_against_lax_scan"] = {
        name: float(jnp.max(jnp.abs(got - want)))
        / (float(jnp.max(jnp.abs(want))) or 1.0)
        for name, got, want in zip(
            NAMES, results["kernels"], results["lax_scan"]
        )
    }
    want_y, want_last = by_host(
        operands, done, args.parity_rows, args.parity_channels
    )
    for name, got, want in (
        ("y", results["kernels"][0], want_y),
        ("last", results["kernels"][1], want_last),
    ):
        got = np.asarray(got, np.float64)[
            : args.parity_rows, ..., : args.parity_channels
        ]
        report.setdefault("kernels_against_float64", {})[name] = float(
            np.max(np.abs(got - want)) / np.max(np.abs(want))
        )
    print(json.dumps(report, indent=1))
    if args.out:
        os.makedirs(args.out, exist_ok=True)
        with open(os.path.join(args.out, "selective_scan_chip.json"), "w") as f:
            json.dump(report, f, indent=1)
    return 0


def programs(scan):
    """(forward, loss and its six gradients) of `scan`, jitted."""
    def loss(*operands):
        y, last = scan(*operands)
        return jnp.sum(jnp.sin(y)) + jnp.sum(last ** 2)

    return jax.jit(scan), jax.jit(
        jax.value_and_grad(loss, argnums=tuple(range(6)))
    )


def _lax_scan(a, dt, A, B_in, C_in, state, done):
    applies = phi4flash.kernels_apply
    phi4flash.kernels_apply = lambda *shape: False
    try:
        return phi4flash.selective_scan(
            a, dt, A, B_in, C_in, state, done
        )[:2]
    finally:
        phi4flash.kernels_apply = applies


if __name__ == "__main__":
    sys.exit(main())
