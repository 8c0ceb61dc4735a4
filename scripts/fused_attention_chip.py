"""The fused attention pass ON THE CHIP, at the attention shapes of the
four cells that take it (`--cells`) and at 1, 2 or 3 bfloat16 terms an
operand (`--terms`: what a caller at the default, at `high`, at
`highest` hands over):

- parity: the output and dq, dk, dv against a float64 product of the
  same operands (numpy, on the host, the first `--parity_rows` batch
  rows), beside XLA's dense body traced at the precision that states
  the same terms. At two or three terms the kernels cut their float32
  tiles in VMEM: a cast folded away would leave tails of zeros and one
  pass's error at any number of terms;
- the time of the forward and of the backward KERNEL each, alone (the
  calls `fused_attend` makes, on operands already laid out), at the
  blocks the pass chooses and, with `--sweep`, at forced blocks of
  keys (where `_CUT_FORWARD_KEYS` / `_CUT_BACKWARD_KEYS` come from);
- with `--rule`, at the Mellum2 widths, a forward + backward of the
  fused pass against the dense body's at several key counts (where the
  128 MiB rule of ops/attention.py `fused_pass_applies` comes from).

    chiprun -- python3 scripts/fused_attention_chip.py --sweep \
        --out chiprun_out/pr54

Prints one JSON object and writes it to <out>/fused_attention_chip.json.
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

from torchbeast_tpu.ops import attention  # noqa: E402
from torchbeast_tpu.ops import fused_attention  # noqa: E402
from torchbeast_tpu.ops.fused_attention import fused_attend  # noqa: E402

# Batch rows, steps, query heads, key/value heads, head size, cache
# slots: a learner step's attention layer (the layer that reads its
# whole cache in Mellum2's cell).
CELLS = {
    "lfm2": (16, 256, 32, 8, 64, 4095),
    "qwen3next": (16, 256, 16, 2, 256, 4095),
    "nemotron3": (16, 256, 8, 1, 128, 4095),
    "mellum2": (32, 81, 32, 4, 128, 4095),
}
PRECISION = {1: "default", 2: "high", 3: "highest"}
NAMES = ("out", "dq", "dk", "dv")
SWEEP = (256, 512, 768, 1024, 1152, 1536)


def case(shape, seed):
    """Operands like a learner step's: a cache of M slots of which each
    row holds a different number, the band within the unroll, an
    episode end in some rows."""
    B, T, H, HKV, D, M = shape
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(keys[0], (B, T, H, D))
    k = jax.random.normal(keys[1], (B, M + T, HKV, D))
    v = jax.random.normal(keys[2], (B, M + T, HKV, D))
    held = jax.random.randint(keys[3], (B,), 0, M + 1)
    cache_valid = jnp.arange(M)[None, :] >= (M - held)[:, None]
    cache_band, seq_band = attention.band_by_leg(T, M)
    end = jax.random.randint(keys[4], (B,), 1, 2 * T)  # >= T: no end
    segment = (jnp.arange(T)[None, :] >= end[:, None]).astype(jnp.int32)
    before_end = (segment == 0)[:, :, None]
    mask = jnp.concatenate(
        [
            cache_band[None] & cache_valid[:, None, :] & before_end,
            seq_band[None] & (segment[:, :, None] == segment[:, None, :]),
        ],
        axis=-1,
    )
    dout = jax.random.normal(keys[5], (B, T, H, D))
    return q, k, v, mask, dout


def dense(q, k, v, mask, precision="default", no_grad_keys=0):
    with jax.default_matmul_precision(precision):
        # The rule would send these shapes to the fused pass.
        saved = attention.FUSED_SCORE_BYTES
        attention.FUSED_SCORE_BYTES = float("inf")
        try:
            return attention.dense_transformer_attend(
                q, k, v, mask, None, None, no_grad_keys
            )
        finally:
            attention.FUSED_SCORE_BYTES = saved


def value_and_grads(fn):
    def run(q, k, v, mask, dout):
        out, pull = jax.vjp(lambda q, k, v: fn(q, k, v, mask), q, k, v)
        return (out,) + pull(dout)

    return jax.jit(run)


def exact(q, k, v, mask, dout, no_grad_keys):
    """The same in float64, a batch row at a time, by numpy (matmuls
    batched over the key/value heads: BLAS)."""
    q, k, v, dout = (np.asarray(x, np.float64) for x in (q, k, v, dout))
    mask = np.asarray(mask)
    B, T, H, D = q.shape
    hkv = k.shape[2]

    def rows(x):  # [T, H, D] -> [Hkv, G * T, D]
        return x.reshape(T, hkv, -1, D).transpose(1, 2, 0, 3).reshape(
            hkv, -1, D
        )

    def steps(x):  # and back
        return x.reshape(hkv, -1, T, D).transpose(2, 0, 1, 3).reshape(T, H, D)

    results = [[] for _ in NAMES]
    for b in range(B):
        qb, db = rows(q[b]), rows(dout[b])
        kb, vb = k[b].transpose(1, 0, 2), v[b].transpose(1, 0, 2)
        admitted = np.tile(mask[b], (H // hkv, 1))[None]
        s = np.where(admitted, qb @ kb.transpose(0, 2, 1) * D ** -0.5, -np.inf)
        p = np.exp(s - s.max(axis=-1, keepdims=True))
        p /= p.sum(axis=-1, keepdims=True)
        dp = db @ vb.transpose(0, 2, 1)
        ds = p * (dp - (dp * p).sum(axis=-1, keepdims=True)) * D ** -0.5
        dk = (ds.transpose(0, 2, 1) @ qb).transpose(1, 0, 2)
        dv = (p.transpose(0, 2, 1) @ db).transpose(1, 0, 2)
        dk[:no_grad_keys] = dv[:no_grad_keys] = 0
        for result, x in zip(results, (steps(p @ vb), steps(ds @ kb), dk, dv)):
            result.append(x)
    return [np.stack(x) for x in results]


def rel(a, b):
    a = np.asarray(a, np.float64)
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


def seconds_a_call(fn, args, calls=5):
    jax.block_until_ready(fn(*args))
    start = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - start) / calls


def parity(shape, terms, seed, rows):
    """Each of the fused pass and the dense body against float64."""
    M = shape[5]
    operands = tuple(x[:rows] for x in case(shape, seed))
    want = exact(*operands, M)
    got = value_and_grads(
        lambda q, k, v, mask: fused_attend(q, k, v, mask, M, terms=terms)
    )(*operands)
    plain = value_and_grads(
        lambda q, k, v, mask: dense(q, k, v, mask, PRECISION[terms], M)
    )(*operands)
    return {
        "fused_vs_f64": dict(zip(NAMES, map(rel, got, want))),
        "dense_vs_f64": dict(zip(NAMES, map(rel, plain, want))),
    }


def kernel_ms(shape, terms, seed, blocks=None):
    """(forward ms, backward ms, the blocks of keys): the two kernel
    calls alone, as `fused_attend` makes them; `blocks` (forward,
    backward) forces the keys a cell."""
    B, T, H, HKV, D, M = shape
    saved = fused_attention._key_blocks
    if blocks:
        fused_attention._key_blocks = lambda num_keys, terms: blocks
    try:
        q, k, v, mask, dout = case(shape, seed)
        narrow = -D % 128 if D < 128 else 0
        q, k, v, dout = (
            jnp.pad(x, ((0, 0),) * 3 + ((0, narrow),))
            for x in (q, k, v, dout)
        )
        block_f, block_b = fused_attention._key_blocks(M + T, terms)
        laid_out = jax.jit(
            lambda *xs: fused_attention._operands(*xs, True, terms)
        )
        operands = laid_out(q, k, v, mask)
        groups, scale = H // HKV, D ** -0.5
        forward = jax.jit(lambda *xs: fused_attention._forward_call(
            *xs, groups, False, terms, scale
        ))
        out, lse = forward(*operands)
        dout_rows = fused_attention._as_rows(
            dout, HKV, fused_attention.padded_steps(T)
        )
        backward = jax.jit(lambda *xs: fused_attention._backward_call(
            *xs, groups, M // block_b, False, terms, scale
        ))
        return (
            1e3 * seconds_a_call(forward, operands),
            1e3 * seconds_a_call(
                backward, operands + (out, lse, dout_rows)
            ),
            (block_f, block_b),
        )
    finally:
        fused_attention._key_blocks = saved


def rule_ms(seed):
    """Fused against dense, forward + backward, by key count at the
    Mellum2 widths."""
    B, T, H, HKV, D, _ = CELLS["mellum2"]
    report = {}
    for num_keys in (209, 336, 593, 1104, 4176):
        operands = case((B, T, H, HKV, D, num_keys - T), seed)
        report[str(num_keys)] = {
            "score_mib": B * H * T * num_keys * 4 / 2 ** 20,
            "fused": 1e3 * seconds_a_call(
                value_and_grads(fused_attend), operands
            ),
            "dense": 1e3 * seconds_a_call(value_and_grads(dense), operands),
        }
    return report


def main():
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--out", default="chiprun_out/pr54")
    parser.add_argument("--seed", type=int, default=54)
    parser.add_argument("--cells", default="lfm2,qwen3next,nemotron3")
    parser.add_argument("--terms", default="2")
    parser.add_argument("--parity_rows", type=int, default=2)
    parser.add_argument(
        "--sweep", action="store_true",
        help="time each kernel at forced blocks of keys too",
    )
    parser.add_argument("--rule", action="store_true")
    parser.add_argument(
        "--rehearse", action="store_true",
        help="tiny widths on whatever device there is: the control "
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
            shape = (2, 16, 4, 2, min(shape[4], 128), 300)
        for terms in map(int, args.terms.split(",")):
            entry = report["cells"].setdefault(cell, {})[str(terms)] = {
                "parity": parity(shape, terms, args.seed, args.parity_rows),
            }
            if args.rehearse:
                continue  # the kernel calls alone are compiled, not
                # interpreted: there is nothing to run them on here
            forward, backward, blocks = kernel_ms(shape, terms, args.seed)
            entry["ms"] = {
                "forward": forward, "backward": backward, "blocks": blocks,
            }
            if args.sweep:
                entry["sweep"] = sweep = {}
                for block in SWEEP:
                    try:
                        sweep[str(block)] = kernel_ms(
                            shape, terms, args.seed, (block, block)
                        )[:2]
                    except Exception as e:  # noqa: BLE001 — VMEM
                        sweep[str(block)] = repr(e)[:200]
            print(json.dumps({cell: {terms: entry}}), flush=True)
    if args.rule:
        report["rule_ms"] = rule_ms(args.seed)
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, "fused_attention_chip.json"), "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())
