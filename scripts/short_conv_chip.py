"""The short convolution's kernels ON THE CHIP (ops/short_conv.py), at
the convolution's shapes in the five cells that take them (`--cells`):

- parity: the convolution, the new tail and every gradient of
  `conv_over_episodes` by the kernels against its `jax.numpy` form (a
  batch with an episode end at a tenth of the steps, at the first and
  at the last, and a nonzero tail);
- the time of the forward and of the backward KERNEL each, alone
  (chained calls, as scripts/ssd_scan_chip.py times its kernels: the
  timing body is that script's), and of XLA's form of the same
  function, forward alone and forward + backward, with the bytes a call
  owes (the array read and written once forward; dconv and the inputs
  read and dinputs written backward) and the share of the chip's
  bandwidth each reads;
- with `--sweep`, each kernel at other lane blocks a cell, the module's
  `_CELL_BYTES` set here for the reading: where its value comes from.

    chiprun -- python3 scripts/short_conv_chip.py --sweep \
        --out chiprun_out/pr67

Prints one JSON object and writes it to <out>/short_conv_chip.json.
Exits 1 without a TPU: a CPU's times are nobody's.
"""

import argparse
import json
import os
import sys

import jax

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from ssd_scan_chip import (  # noqa: E402
    CHAINED,
    PEAK_BYTES,
    rel,
    seconds_a_call,
)

from torchbeast_tpu.models import nemotron3  # noqa: E402
from torchbeast_tpu.ops import short_conv  # noqa: E402

# Batch rows, steps, channels, taps, whether a bias: a learner step's
# convolution (Qwen3-Next's q | k | v, Granite's and Nemotron-3's x | B |
# C, Phi-4-mini-flash's inner width, LFM2's B * u).
CELLS = {
    "qwen3next": (16, 256, 8192, 4, False),
    "granite4": (8, 512, 4352, 4, True),
    "nemotron3": (16, 256, 2560, 4, True),
    "phi4flash": (16, 256, 5120, 4, True),
    "lfm2": (16, 256, 2048, 3, False),
}
NAMES = ("conv", "new_tail", "dinputs", "dtail", "dtaps", "dbias")
SWEEP_MB = (0.25, 0.5, 1, 2, 4, 6, 8)


def case(shape, seed):
    """Operands like a layer's in a learner step, and the cotangents of
    the convolution and of the new tail."""
    B, T, C, K, with_bias = shape
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    bound = K ** -0.5
    inputs = jax.random.normal(keys[0], (B, T, C))
    tail = jax.random.normal(keys[1], (K - 1, B, C))
    done = jax.random.uniform(keys[2], (B, T)) < 0.1
    done = done.at[0, 0].set(True).at[1, T - 1].set(True)
    taps = jax.random.uniform(keys[3], (K, C), minval=-bound, maxval=bound)
    bias = jax.random.uniform(
        keys[4], (C,), minval=-bound, maxval=bound
    ) if with_bias else None
    cotangents = (
        jax.random.normal(keys[5], (B, T, C)),
        jax.random.normal(keys[6], (K - 1, B, C)),
    )
    return (inputs, tail, taps, bias), done, cotangents


def in_xla(*args):
    """`conv_over_episodes` as it runs where the kernels do not apply."""
    saved = short_conv.kernels_apply
    short_conv.kernels_apply = lambda *shape: False
    try:
        return nemotron3.conv_over_episodes(*args)
    finally:
        short_conv.kernels_apply = saved


def forward_and_backward(conv, done):
    def run(args, cotangents):
        results, pull = jax.vjp(
            lambda inputs, tail, taps, bias: conv(
                inputs, tail, done, taps, bias
            ), *args,
        )
        return results + tuple(g for g in pull(cotangents) if g is not None)

    return jax.jit(run)


def forward_alone(conv, done):
    return jax.jit(lambda args: conv(args[0], args[1], done, *args[2:]))


def parity(shape, seed):
    args, done, cotangents = case(shape, seed)
    got = forward_and_backward(nemotron3.conv_over_episodes, done)(
        args, cotangents
    )
    want = forward_and_backward(in_xla, done)(args, cotangents)
    return dict(zip(NAMES, map(rel, got, want)))


def kernel_ms(shape, seed):
    """(forward ms, backward ms): the two kernel calls alone, as
    `short_conv.short_conv` makes them, `CHAINED` calls a program, each
    call's last steps (the tail's gradient) the next one's tail: a call
    waits for the one before it and no array is copied between them (a
    loop that carries the ARRAY copies it every turn, 0.4 ms of
    Qwen3-Next's 0.84 as first measured)."""
    (inputs, tail, taps, bias), done, (dconv, _) = case(shape, seed)
    K = shape[3]
    inputs, tail, may, taps, bias = short_conv.operands(
        inputs, tail, short_conv.reach(done, K), taps, bias
    )

    @jax.jit
    def forward(x, tail):
        return jax.lax.fori_loop(
            0, CHAINED, lambda _, tail: short_conv._forward(
                x, tail, may, taps, bias, interpret=False
            )[:, 1 - K :], tail,
        )

    @jax.jit
    def backward(g, x, tail):
        return jax.lax.fori_loop(
            0, CHAINED, lambda _, tail: short_conv._backward(
                g, x, tail, may, taps, interpret=False
            )[2][:, 1 - K :], tail,
        )

    return (
        1e3 * seconds_a_call(forward, (inputs, tail)) / CHAINED,
        1e3 * seconds_a_call(backward, (dconv, inputs, tail)) / CHAINED,
    )


def conv_ms(shape, seed):
    """The whole function as a layer calls it (`reach`, the new tail and
    the kernels, or XLA's form): forward alone, forward + backward."""
    args, done, cotangents = case(shape, seed)
    return {
        name: {
            "forward": 1e3 * seconds_a_call(
                forward_alone(conv, done), (args,)
            ),
            "forward_and_backward": 1e3 * seconds_a_call(
                forward_and_backward(conv, done), (args, cotangents)
            ),
        }
        for name, conv in (
            ("kernels", nemotron3.conv_over_episodes), ("xla", in_xla)
        )
    }


def main():
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--out", default="chiprun_out/pr67")
    parser.add_argument("--seed", type=int, default=67)
    parser.add_argument("--cells", default=",".join(CELLS))
    parser.add_argument(
        "--sweep", action="store_true",
        help="time each kernel at other lane blocks a cell too",
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
            shape = (2, 16, 256) + shape[3:]
        entry = report["cells"][cell] = {"parity": parity(shape, args.seed)}
        if args.rehearse:
            continue  # the kernel calls alone are compiled, not
            # interpreted: there is nothing to run them on here
        forward, backward = kernel_ms(shape, args.seed)
        array_mb = 4 * shape[0] * shape[1] * shape[2] / 1e6
        entry["ms"] = {"forward": forward, "backward": backward}
        entry["owed"] = {
            "array_mb": array_mb,
            "forward_bandwidth_pct":
                100 * 2 * array_mb * 1e6 / PEAK_BYTES / (forward / 1e3),
            "backward_bandwidth_pct":
                100 * 3 * array_mb * 1e6 / PEAK_BYTES / (backward / 1e3),
        }
        entry["conv_ms"] = conv_ms(shape, args.seed)
        if args.sweep:
            entry["sweep"] = sweep = {}
            chosen = short_conv._CELL_BYTES
            for mb in SWEEP_MB:
                # Read where the calls are traced: trace them again.
                short_conv._CELL_BYTES = int(mb * 2 ** 20)
                tiles = short_conv._tiles_a_cell(shape[1], shape[2])
                if f"{tiles} tiles" in sweep:
                    continue  # this many bytes cut the row no other way
                jax.clear_caches()
                try:
                    sweep[f"{tiles} tiles"] = kernel_ms(shape, args.seed)
                except Exception as e:  # noqa: BLE001 — VMEM
                    sweep[f"{tiles} tiles"] = repr(e)[:200]
            short_conv._CELL_BYTES = chosen
            jax.clear_caches()
        print(json.dumps({cell: entry}), flush=True)
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, "short_conv_chip.json"), "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())
