"""The Mamba-2 scan's kernels ON THE CHIP (ops/ssd_scan.py), at the
scan's shapes in the two cells that take them (`--cells`) and at 1, 2
or 3 bfloat16 terms an operand (`--terms`: what a caller at the
default, at `high`, at `highest` hands over):

- parity: y, the last state and every gradient of the kernels against
  the `jax.numpy` form of models/nemotron3.py `ssd_scan` traced at the
  precision that states the same terms (a batch with an episode end at
  a tenth of the steps and a nonzero entering state);
- the time of the forward and of the backward KERNEL each, alone (the
  calls `ssd_scan.scan` makes, on operands already laid out), and of
  XLA's form of the same scan, forward alone and forward + backward,
  with what the kernels owe by their shapes (`owed`: the products at
  the terms' passes, the bytes of x, y, B, C and the states) and the
  share of that roofline each reads;
- with `--sweep`, each kernel at other lane tiles a cell (two heads of
  64 a tile), the module's `_TILES` set here for the reading: where its
  value comes from.

    chiprun -- python3 scripts/ssd_scan_chip.py --sweep \
        --out chiprun_out/pr65

Prints one JSON object and writes it to <out>/ssd_scan_chip.json. Exits
1 without a TPU: a CPU's times are nobody's.
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

from torchbeast_tpu.models import nemotron3  # noqa: E402
from torchbeast_tpu.ops import ssd_scan  # noqa: E402

# Batch rows, steps, heads, head size, groups, state columns, chunk: a
# learner step's Mamba-2 layer (Nemotron-3's at the cell's quarter
# share of the heads).
CELLS = {
    "granite4": (8, 512, 64, 64, 1, 128, 256),
    "nemotron3": (16, 256, 32, 64, 2, 128, 128),
}
PRECISION = {1: "default", 2: "high", 3: "highest"}
NAMES = ("y", "last", "dx", "ddt", "dA", "dB", "dC", "dstate")
SWEEP = (1, 2, 4, 8, 16, 32)
# TPU v5e (Google Cloud documentation, "TPU v5e"): bf16 FLOP/s, HBM B/s.
PEAK_FLOPS, PEAK_BYTES = 197e12, 819e9


def case(shape, seed):
    """Operands like a mixer layer's in a learner step: dt in the
    published 0.001..0.1, an episode end at a tenth of the steps, a
    nonzero entering state, and the cotangents of y and the state."""
    B, T, H, P, G, N, _ = shape
    keys = jax.random.split(jax.random.PRNGKey(seed), 9)
    x = jax.random.normal(keys[0], (B, T, H, P))
    dt = jnp.exp(jax.random.uniform(
        keys[1], (B, T, H), minval=np.log(0.001), maxval=np.log(0.1)
    ))
    A = -jax.random.uniform(keys[2], (H,), minval=1.0, maxval=16.0)
    B_in = jax.random.normal(keys[3], (B, T, G, N))
    C_in = jax.random.normal(keys[4], (B, T, G, N))
    state = jax.random.normal(keys[5], (B, H, P, N))
    done = jax.random.uniform(keys[6], (B, T)) < 0.1
    dy = jax.random.normal(keys[7], (B, T, H, P))
    dlast = jax.random.normal(keys[8], (B, H, P, N))
    return (x, dt, A, B_in, C_in, state), done, (dy, dlast)


def in_xla(*args):
    """`ssd_scan` as it runs where the kernels do not apply."""
    saved = ssd_scan.kernels_apply
    ssd_scan.kernels_apply = lambda *shape: False
    try:
        return nemotron3.ssd_scan(*args)
    finally:
        ssd_scan.kernels_apply = saved


def forward_and_backward(scan, precision, done, chunk):
    def run(args, cotangents):
        with jax.default_matmul_precision(precision):
            results, pull = jax.vjp(lambda *a: scan(*a, done, chunk), *args)
        return results + pull(cotangents)

    return jax.jit(run)


def forward_alone(scan, precision, done, chunk):
    def run(args):
        with jax.default_matmul_precision(precision):
            return scan(*args, done, chunk)

    return jax.jit(run)


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


def seconds_a_call(fn, args, calls=10):
    jax.block_until_ready(fn(*args))
    start = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - start) / calls


def owed(shape, terms):
    """(forward, backward) seconds the kernels' work takes at the chip's
    peaks, each the larger of its products' and its bytes': the
    products the header of ops/ssd_scan.py names, a head's at its own
    64 columns (what the recurrence needs, not the 128 the MXU is fed),
    at `terms (terms + 1) / 2` passes; x, y, B, C, the scalars and the
    first and last state once, and for the backward the cotangents and
    gradients beside them and x, B of the chunks made again."""
    B, T, H, P, G, N, Q = shape
    c = -(-T // Q)
    cells, passes = B * c, terms * (terms + 1) // 2
    group = 2 * Q * Q * N  # C B^T
    intra = 2 * Q * Q * P  # (scores . L) x
    over_state = 2 * Q * P * N  # C S^T, or (e . x)^T B
    forward = cells * (G * group + H * (intra + 2 * over_state))
    backward = cells * (
        3 * G * group + H * (2 * intra + 5 * over_state)
    ) + B * (c - 1) * H * over_state
    x, bc, states = 4 * B * T * H * P, 4 * B * T * G * N, 4 * B * H * P * N
    scalars = 4 * B * c * Q * (2 * 8 * H + 3 * -(-H // 128) * 128)
    forward_bytes = 2 * x + 2 * bc + scalars + 2 * states
    backward_bytes = (
        3 * x + 4 * bc + 2 * scalars + 3 * states
        + (c - 1) * (x + bc) // c
    )
    return tuple(
        max(passes * flops / PEAK_FLOPS, moved / PEAK_BYTES)
        for flops, moved in (
            (forward, forward_bytes), (backward, backward_bytes)
        )
    ), {
        "forward_gflop_a_pass": forward / 1e9,
        "backward_gflop_a_pass": backward / 1e9,
        "forward_mb": forward_bytes / 1e6,
        "backward_mb": backward_bytes / 1e6,
    }


def parity(shape, terms, seed):
    args, done, cotangents = case(shape, seed)
    chunk = shape[6]
    got = forward_and_backward(
        nemotron3.ssd_scan, PRECISION[terms], done, chunk
    )(args, cotangents)
    want = forward_and_backward(in_xla, PRECISION[terms], done, chunk)(
        args, cotangents
    )
    return dict(zip(NAMES, map(rel, got, want)))


CHAINED = 16  # kernel calls a timed program: a host's dispatch of one
# call (~0.2 ms) is as long as a call


def kernel_ms(shape, terms, seed):
    """(forward ms, backward ms): the two kernel calls alone, as
    `ssd_scan.scan` makes them, `CHAINED` calls a program with each
    call's state (its cotangent) the next one's."""
    (x, dt, A, B_in, C_in, state), done, (dy, dlast) = case(shape, seed)
    B, T, H, P, G, N, Q = shape
    lay_out = jax.jit(lambda *xs: ssd_scan.operands(*xs, Q))
    laid_out = lay_out(x, dt, A, B_in, C_in, state, done)
    options = dict(terms=terms, P=P, N=N, interpret=False)

    @jax.jit
    def forward(operands):
        def call(_, carried):
            y, last = ssd_scan._forward(*operands[:-1], carried[1], **options)
            return y, last

        return jax.lax.fori_loop(
            0, CHAINED, call, (operands[0], operands[-1])
        )

    @jax.jit
    def backward(operands, dy, dlast):
        def call(_, carried):
            grads = ssd_scan._backward(
                *operands, dy, carried[-1], **options
            )
            return grads

        first = ssd_scan._backward(*operands, dy, dlast, **options)
        return jax.lax.fori_loop(0, CHAINED - 1, call, first)

    cotangents = (dy.reshape(B, T, H * P), dlast.reshape(B, H * P, N))
    return (
        1e3 * seconds_a_call(forward, (laid_out,)) / CHAINED,
        1e3 * seconds_a_call(backward, (laid_out,) + cotangents) / CHAINED,
    )


def scan_ms(shape, terms, seed):
    """The whole scan as the mixer calls it (the scalars' preparation
    and the kernels, or XLA's form): forward alone, forward + backward."""
    args, done, cotangents = case(shape, seed)
    chunk, precision = shape[6], PRECISION[terms]
    return {
        name: {
            "forward": 1e3 * seconds_a_call(
                forward_alone(scan, precision, done, chunk), (args,)
            ),
            "forward_and_backward": 1e3 * seconds_a_call(
                forward_and_backward(scan, precision, done, chunk),
                (args, cotangents),
            ),
        }
        for name, scan in (("kernels", nemotron3.ssd_scan), ("xla", in_xla))
    }


def main():
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--out", default="chiprun_out/pr65")
    parser.add_argument("--seed", type=int, default=65)
    parser.add_argument("--cells", default="granite4,nemotron3")
    parser.add_argument("--terms", default="2")
    parser.add_argument(
        "--sweep", action="store_true",
        help="time each kernel at other lane tiles a cell too",
    )
    parser.add_argument(
        "--rehearse", action="store_true",
        help="tiny shapes on whatever device there is: the control "
        "flow alone, its times mean nothing",
    )
    args = parser.parse_args()
    device = jax.devices()[0]
    if not args.rehearse and device.platform != "tpu":
        print(f"no TPU: {device.platform}", file=sys.stderr)
        return 1
    report = {
        "device": device.device_kind, "rehearsal": args.rehearse,
        "seed": args.seed, "cells": {},
    }
    for cell in args.cells.split(","):
        shape = CELLS[cell]
        if args.rehearse:
            shape = (2, 40, 4 * shape[4], 64, shape[4], 128, 16)
        for terms in map(int, args.terms.split(",")):
            entry = report["cells"].setdefault(cell, {})[str(terms)] = {
                "parity": parity(shape, terms, args.seed),
            }
            if args.rehearse:
                continue  # the kernel calls alone are compiled, not
                # interpreted: there is nothing to run them on here
            forward, backward = kernel_ms(shape, terms, args.seed)
            (owed_f, owed_b), counts = owed(shape, terms)
            entry["ms"] = {"forward": forward, "backward": backward}
            entry["owed"] = dict(
                counts, forward_ms=1e3 * owed_f, backward_ms=1e3 * owed_b,
                forward_roofline_pct=1e5 * owed_f / forward,
                backward_roofline_pct=1e5 * owed_b / backward,
            )
            entry["scan_ms"] = scan_ms(shape, terms, args.seed)
            if args.sweep:
                entry["sweep"] = sweep = {}
                of_group = shape[2] // shape[4] * shape[3] // 128
                chosen = ssd_scan._TILES
                for tiles in (n for n in SWEEP if of_group % n == 0):
                    # Read where the calls are traced: trace them again.
                    ssd_scan._TILES = tiles
                    jax.clear_caches()
                    try:
                        sweep[str(tiles)] = kernel_ms(shape, terms, args.seed)
                    except Exception as e:  # noqa: BLE001 — VMEM
                        sweep[str(tiles)] = repr(e)[:200]
                ssd_scan._TILES = chosen
                jax.clear_caches()
            print(json.dumps({cell: {terms: entry}}), flush=True)
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, "ssd_scan_chip.json"), "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())
