"""The frame projection on integers (models/transformer.py
`frame_projection`) ON THE CHIP, alone, at the learner cells' shapes:
the [T, B, 84, 84, 4] uint8 frames against `Dense_0`'s [28224, d]
kernel, value and the kernel's gradient,

- against a float64 product of the same operands on the host, at one,
  two and three terms of the kernel: within the bound each count owes
  (2^-8, 2^-15, 2^-21 of the product of the magnitudes) and no further
  from it than the float expression it replaces at the matching
  precision (default, `high`, `highest`);
- timed against that expression (the frames cast to float32, scaled,
  merged time-major, the product transposed after) and against the
  least code that might have done (`--per_operand`: one `dot_general`
  on the bfloat16 integers and the float32 kernel at the per-operand
  precision (DEFAULT, HIGH)), beside the MXU's time for the passes.

    chiprun -- python3 scripts/frame_projection_chip.py --out chiprun_out/pr52

Prints one JSON object and writes it to <out>/frame_projection_chip.json.
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

from torchbeast_tpu.models import transformer  # noqa: E402

# Steps, rows, the model's width: the 4,096-row cells and a 2,592-row one.
CELLS = {
    "qwen3next": (256, 16, 2048),
    "nemotron3": (256, 16, 4096),
    "kanana2": (81, 32, 2048),
}
FRAME = (84, 84, 4)
PRECISION = {1: None, 2: "high", 3: "highest"}
BOUND = {1: 2.0**-8, 2: 2.0**-15, 3: 2.0**-21}
MXU_FLOPS = 197e12


def float_projection(frame, kernel, bias, frame_range):
    """The expression the integers replace (and the one every float
    frame still takes): [T, B, ...] -> [B, T, d]."""
    T, B = frame.shape[:2]
    x = transformer.scaled_frames(frame, frame_range, jnp.float32)
    return (x @ kernel + bias).reshape(T, B, -1).transpose(1, 0, 2)


def per_operand_projection(frame, kernel, bias, frame_range):
    """One dot on the bfloat16 integers at (DEFAULT, HIGH)."""
    operand = transformer.frame_integers(frame.swapaxes(0, 1), frame_range)
    scale, shift = transformer.integers_to_range(frame_range)
    y = jax.lax.dot_general(
        operand, kernel, (((2,), (0,)), ((), ())),
        precision=(jax.lax.Precision.DEFAULT, jax.lax.Precision.HIGH),
        preferred_element_type=jnp.float32,
    )
    return scale * y + bias + shift * jnp.sum(kernel, axis=0)


def timed(fn, *args, repeats=10):
    jax.block_until_ready(fn(*args))
    start = time.perf_counter()
    for _ in range(repeats):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - start) / repeats


def value_and_kernel_grad(projection, frame_range, precision):
    """Jitted (frame, kernel, bias, dy) -> (y, d kernel), traced under
    `precision` as a family traces its model."""
    def both(frame, kernel, bias, dy):
        with jax.default_matmul_precision(precision):
            y, pull = jax.vjp(
                lambda k, b: projection(frame, k, b, frame_range), kernel, bias
            )
            return y, pull(dy)[0]

    return jax.jit(both)


def forward_only(projection, frame_range, precision):
    def forward(frame, kernel, bias):
        with jax.default_matmul_precision(precision):
            return projection(frame, kernel, bias, frame_range)

    return jax.jit(forward)


def run_cell(cell, frame_range, seed, per_operand, check):
    T, B, d = CELLS[cell]
    rng = np.random.default_rng(seed)
    frame_host = rng.integers(0, 256, (T, B) + FRAME, dtype=np.uint8)
    F = int(np.prod(FRAME))
    keys = jax.random.split(jax.random.PRNGKey(seed % (2**31)), 3)
    kernel = jax.random.normal(keys[0], (F, d)) * F ** -0.5
    bias = 0.1 * jax.random.normal(keys[1], (d,))
    dy = jax.random.normal(keys[2], (B, T, d))
    frame = jnp.asarray(frame_host)

    low, high = frame_range
    if check:
        x64 = low + (high - low) * frame_host.reshape(T, B, F).astype(
            np.float64
        ) / 255.0
        k64, dy64 = np.asarray(kernel, np.float64), np.asarray(dy, np.float64)
        x64 = x64.transpose(1, 0, 2).reshape(B * T, F)
        exact_y = (x64 @ k64 + np.asarray(bias, np.float64)).reshape(B, T, d)
        scale_y = (np.abs(x64) @ np.abs(k64)).reshape(B, T, d)
        exact_dk = x64.T @ dy64.reshape(B * T, d)
        scale_dk = np.abs(x64).T @ np.abs(dy64.reshape(B * T, d))

    def errors(y, dk):
        if not check:
            return {}
        return {
            "value_error": float(np.max(
                np.abs(np.asarray(y, np.float64) - exact_y) / scale_y
            )),
            "grad_error": float(np.max(
                np.abs(np.asarray(dk, np.float64) - exact_dk) / scale_dk
            )),
        }

    product_flops = 2.0 * T * B * F * d
    report = {"steps": T, "rows": B, "width": d, "frame_range": frame_range}
    for terms, precision in PRECISION.items():
        integers = lambda f, k, b, r: transformer.frame_projection(  # noqa: E731
            f, k, b, r, terms
        )
        arrangements = {"float": float_projection, "integers": integers}
        if per_operand and terms == 2:
            arrangements["per_operand"] = per_operand_projection
        entry = {
            "bound": BOUND[terms],
            "mxu_ms_a_pass": 1e3 * product_flops / MXU_FLOPS,
        }
        for name, projection in arrangements.items():
            both = value_and_kernel_grad(projection, frame_range, precision)
            forward = forward_only(projection, frame_range, precision)
            y, dk = both(frame, kernel, bias, dy)
            entry[name] = {
                "forward_ms": timed(forward, frame, kernel, bias),
                "forward_and_grad_ms": timed(both, frame, kernel, bias, dy),
                **errors(y, dk),
            }
        report[f"terms_{terms}"] = entry
        if check:
            change, parent = entry["integers"], entry["float"]
            entry["ok"] = bool(
                change["value_error"] <= BOUND[terms]
                and change["grad_error"] <= BOUND[terms]
                and change["value_error"] <= 1.05 * parent["value_error"]
                and change["grad_error"] <= 1.05 * parent["grad_error"]
            )
    return report


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--out", default="chiprun_out/frame_projection")
    parser.add_argument("--seed", type=int, default=7)
    parser.add_argument("--cells", nargs="+", default=list(CELLS))
    parser.add_argument("--per_operand", action="store_true")
    parser.add_argument(
        "--check_cells", nargs="+", default=["qwen3next"],
        help="cells held to a float64 product on the host (minutes each)",
    )
    flags = parser.parse_args()
    if jax.default_backend() != "tpu":
        print(json.dumps({"ok": False, "error": "no TPU: nothing to time"}))
        return 1
    report = {"device": jax.devices()[0].device_kind, "seed": flags.seed}
    for cell in flags.cells:
        for frame_range in ((-1.0, 1.0), (0.0, 1.0)):
            check = cell in flags.check_cells
            if not check and frame_range != (-1.0, 1.0):
                continue
            report[f"{cell}{list(frame_range)}"] = run_cell(
                cell, frame_range, flags.seed, flags.per_operand, check
            )
    report["ok"] = all(
        entry.get("ok", True)
        for cell in report.values() if isinstance(cell, dict)
        for entry in cell.values() if isinstance(entry, dict)
    )
    os.makedirs(flags.out, exist_ok=True)
    with open(os.path.join(flags.out, "frame_projection_chip.json"), "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report))
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
