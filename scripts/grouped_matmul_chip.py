"""The grouped matmuls that cut their operands in VMEM (ops/
grouped_matmul.py) ON THE CHIP, at a rung's shapes in the three cells
that trace under `high`: each of `gmm`, `gmm` on transposed weights and
`tgmm`, at the up and at the down projection's shape,

- against a float64 product of the same operands, at two terms a side
  and at three: within the precision's bound (2^-15, 2^-21 of the
  product of the magnitudes) and three terms four times closer than
  two: the tails are cut inside the kernel and not folded to zeros (a
  folded cast would read one pass's 2^-9 at any number of terms);
- timed against the arrangement it replaces (the shipped megablox
  kernel three times on terms cut by XLA in HBM, the results added),
  by row tile (`--rows`) and, with `--sweep`, by (contracted, column)
  tile.

    chiprun -- python3 scripts/grouped_matmul_chip.py --out chiprun_out/pr50

Prints one JSON object and writes it to <out>/grouped_matmul_chip.json.
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

from torchbeast_tpu.models import moe  # noqa: E402
from torchbeast_tpu.ops import grouped_matmul  # noqa: E402

# A rung's rows, the model's width, experts held, an expert's width.
CELLS = {
    "qwen3next": (5120, 2048, 32, 512),
    "kanana2": (4096, 2048, 16, 768),
    "nemotron3": (2816, 1024, 8, 2688),
}
BOUND = {2: 2.0**-15, 3: 2.0**-21}


def operands(cell, seed):
    """A rung as a step near an even load fills it: half its rows
    live, dealt to the held experts at random, the rest a last group
    that no expert visits."""
    rung, d, held, width = CELLS[cell]
    rng = np.random.default_rng(seed)
    mine = rng.multinomial(rung // 2, np.full(held, 1.0 / held))
    sizes = jnp.asarray(np.append(mine, rung - mine.sum()), jnp.int32)
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    return {
        "sizes": sizes,
        "x": jax.random.normal(keys[0], (rung, d)),
        "hidden": jax.random.normal(keys[1], (rung, width)),
        "w_up": jax.random.normal(keys[2], (held, d, width)) * d ** -0.5,
        "w_down": jax.random.normal(keys[3], (held, width, d))
        * width ** -0.5,
    }


def products(ops):
    """name -> (kernel, lhs, rhs, kwargs): the six products of a layer's
    forward and backward, by shape."""
    return {
        "gmm_up": ("gmm", ops["x"], ops["w_up"], {}),
        "gmm_down": ("gmm", ops["hidden"], ops["w_down"], {}),
        "gmm_t_up": (
            "gmm", ops["hidden"], ops["w_up"], {"transpose_rhs": True}
        ),
        "gmm_t_down": (
            "gmm", ops["x"], ops["w_down"], {"transpose_rhs": True}
        ),
        "tgmm_up": ("tgmm", ops["x"], ops["hidden"], {}),
        "tgmm_down": ("tgmm", ops["hidden"], ops["x"], {}),
    }


def by_hand(kernel, lhs, rhs, sizes, kwargs):
    """The product in float64 and its scale (the product of the
    magnitudes), on the host."""
    lhs, rhs = np.asarray(lhs, np.float64), np.asarray(rhs, np.float64)
    ends = np.cumsum(np.asarray(sizes))
    held = len(ends) - 1
    want, scale = [], []
    for group in range(held):
        rows = slice(ends[group - 1] if group else 0, ends[group])
        if kernel == "tgmm":
            a, b = lhs[rows].T, rhs[rows]
        else:
            a = lhs[rows]
            b = rhs[group].T if kwargs.get("transpose_rhs") else rhs[group]
        want.append(a @ b)
        scale.append(np.abs(a) @ np.abs(b))
    if kernel == "tgmm":
        return np.stack(want), np.stack(scale)
    tail = len(lhs) - ends[held - 1]
    zeros = np.zeros((tail, want[0].shape[1]))
    return np.concatenate(want + [zeros]), np.concatenate(scale + [zeros + 1])


def cut_in_vmem(kernel, terms, tm, tiling, held, kwargs):
    extra = {"num_actual_groups": held} if kernel == "tgmm" else {}
    return jax.jit(lambda lhs, rhs, sizes: getattr(grouped_matmul, kernel)(
        lhs, rhs, sizes, terms=terms, tm=tm, tiling=tiling,
        group_offset=jnp.int32(0), **extra, **kwargs,
    ))


def cut_in_hbm(kernel, terms, tm, held, kwargs):
    """What `moe._gmm_call` did before: the shipped kernel a pass."""
    shipped = getattr(moe._megablox, kernel)
    _, tk, tn = moe._GMM_TILING
    extra = {"num_actual_groups": held} if kernel == "tgmm" else {}

    def product(lhs, rhs, sizes):
        if kernel == "tgmm":
            lhs = lhs.swapaxes(0, 1)
        lhs, rhs = moe._bf16_terms(lhs, terms), moe._bf16_terms(rhs, terms)
        out = None
        for order in reversed(range(terms)):
            for i in range(order + 1):
                part = shipped(
                    lhs[i], rhs[order - i], sizes, jnp.float32,
                    (tm, tk, tn), group_offset=jnp.int32(0), **extra,
                    **kwargs,
                )
                out = part if out is None else out + part
        return out

    return jax.jit(product)


def timed(fn, *args, calls=20, repeats=3):
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / calls)
    return 1e3 * best


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--out", default="chiprun_out")
    parser.add_argument("--cells", nargs="+", default=sorted(CELLS))
    parser.add_argument("--rows", nargs="+", type=int, default=[128, 256])
    parser.add_argument("--seed", type=int, default=7)
    parser.add_argument("--sweep", action="store_true")
    parser.add_argument(
        "--shape", nargs=4, type=int, default=None,
        metavar=("ROWS", "WIDTH", "HELD", "EXPERT_WIDTH"),
        help="one more cell, of these shapes, under the name `shape`",
    )
    flags = parser.parse_args()
    if flags.shape:
        CELLS["shape"] = tuple(flags.shape)
        flags.cells = ["shape"]
    if jax.default_backend() != "tpu":
        print(json.dumps({"ok": False, "why": "no TPU"}))
        return 1
    report = {"device": jax.devices()[0].device_kind, "cells": {}}
    ok = True
    for cell in flags.cells:
        ops = operands(cell, flags.seed)
        held = CELLS[cell][2]
        entry = report["cells"][cell] = {
            "sizes": np.asarray(ops["sizes"]).tolist(), "products": {},
        }
        for name, (kernel, lhs, rhs, kwargs) in products(ops).items():
            want, scale = by_hand(kernel, lhs, rhs, ops["sizes"], kwargs)
            line = entry["products"][name] = {"worst": {}, "ms": {}}
            for terms in (2, 3):
                got = cut_in_vmem(kernel, terms, 256, None, held, kwargs)(
                    lhs, rhs, ops["sizes"]
                )
                worst = float(np.max(
                    np.abs(np.asarray(got, np.float64) - want) / scale
                ))
                line["worst"][terms] = worst
                ok = ok and worst <= BOUND[terms]
            # Three terms are worth their passes: the third is cut too.
            ok = ok and line["worst"][2] > 4 * line["worst"][3]
            k, n = lhs.shape[1], want.shape[-1]
            for tm in flags.rows:
                line["ms"][f"hbm_{tm}"] = timed(
                    cut_in_hbm(kernel, 2, tm, held, kwargs),
                    lhs, rhs, ops["sizes"],
                )
                chosen = grouped_matmul.tiles(
                    tm, k, n, 2, over_rows=kernel == "tgmm"
                )
                tilings = {chosen}
                if flags.sweep:
                    tilings |= {
                        (tk, tn) for tk in (256, 512, 1024)
                        for tn in (256, 512, 1024)
                        if k % tk == 0 and n % tn == 0
                    }
                for tk, tn in sorted(tilings):
                    key = f"vmem_{tm}_{tk}_{tn}" + (
                        "_chosen" if (tk, tn) == chosen else ""
                    )
                    try:
                        line["ms"][key] = timed(
                            cut_in_vmem(kernel, 2, tm, (tk, tn), held, kwargs),
                            lhs, rhs, ops["sizes"],
                        )
                    except Exception as e:  # noqa: BLE001 (VMEM overrun)
                        line["ms"][key] = f"refused: {str(e)[:120]}"
            print(cell, name, json.dumps(line), flush=True)
    report["ok"] = ok
    os.makedirs(flags.out, exist_ok=True)
    with open(os.path.join(flags.out, "grouped_matmul_chip.json"), "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
