"""The fused attention pass against the dense body ON THE CHIP, at the
Mellum2 cell's widths: parity of the output and of dq, dk, dv, and the
time of a forward + backward of each at several key counts (where the
128 MiB rule of ops/attention.py `fused_pass_applies` comes from).

    chiprun -- python3 scripts/fused_attention_chip.py --out chiprun_out/pr37

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

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from torchbeast_tpu.ops import attention  # noqa: E402
from torchbeast_tpu.ops.fused_attention import fused_attend  # noqa: E402

B, T, H, HKV, D = 32, 81, 32, 4, 128


def case(num_keys, seed):
    """Operands like a learner step's: a cache of num_keys - T slots of
    which each row holds a different number, the band within the unroll,
    an episode end in some rows."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    M = num_keys - T
    q = jax.random.normal(keys[0], (B, T, H, D))
    k = jax.random.normal(keys[1], (B, num_keys, HKV, D))
    v = jax.random.normal(keys[2], (B, num_keys, HKV, D))
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


def dense(q, k, v, mask, precision=None):
    with jax.default_matmul_precision(precision or "default"):
        # The rule would send these shapes to the fused pass.
        saved = attention.FUSED_SCORE_BYTES
        attention.FUSED_SCORE_BYTES = float("inf")
        try:
            return attention.dense_transformer_attend(
                q, k, v, mask, None, None
            )
        finally:
            attention.FUSED_SCORE_BYTES = saved


def value_and_grads(fn):
    def run(q, k, v, mask, dout):
        out, pull = jax.vjp(lambda q, k, v: fn(q, k, v, mask), q, k, v)
        return (out,) + pull(dout)

    return jax.jit(run)


def rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


def seconds_a_call(fn, args, calls=5):
    jax.block_until_ready(fn(*args))
    start = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - start) / calls


def main():
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--out", default="chiprun_out/pr37")
    parser.add_argument("--seed", type=int, default=37)
    parser.add_argument(
        "--rehearse", action="store_true",
        help="tiny widths on whatever device there is: the control "
        "flow alone, its times mean nothing",
    )
    args = parser.parse_args()
    device = jax.devices()[0]
    if args.rehearse:
        global B, T, H, HKV, D
        B, T, H, HKV, D = 2, 5, 4, 2, 8
    elif device.platform != "tpu":
        print(f"no TPU: {device.platform}", file=sys.stderr)
        return 1
    report = {
        "device": device.device_kind, "rehearsal": args.rehearse,
        "parity": {}, "ms": {},
    }
    names = ("out", "dq", "dk", "dv")
    for num_keys in (4176, 1104):
        operands = case(num_keys, args.seed)
        fused = value_and_grads(fused_attend)(*operands)
        plain = value_and_grads(dense)(*operands)
        # Both against the dense body with every matmul in f32: how far
        # each is from the mathematics, beside how far from each other.
        exact = value_and_grads(
            lambda q, k, v, mask: dense(q, k, v, mask, "highest")
        )(*operands)
        report["parity"][str(num_keys)] = {
            "fused_vs_dense": dict(zip(names, map(rel, fused, plain))),
            "fused_vs_f32": dict(zip(names, map(rel, fused, exact))),
            "dense_vs_f32": dict(zip(names, map(rel, plain, exact))),
        }
        del fused, plain, exact
    for num_keys in (209, 336, 593, 1104, 4176):
        operands = case(num_keys, args.seed)
        report["ms"][str(num_keys)] = {
            "score_mib": B * H * T * num_keys * 4 / 2 ** 20,
            "fused": 1e3 * seconds_a_call(
                value_and_grads(fused_attend), operands
            ),
            "dense": 1e3 * seconds_a_call(value_and_grads(dense), operands),
        }
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, "fused_attention_chip.json"), "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())
