"""Fused Pallas attention vs dense XLA: on-chip forward timing.

The kernel's reason to exist (ops/pallas_attention.py docstring) is
fusing score/bias/mask/softmax/weighted-sum per (b, h) cell in VMEM
instead of materializing [B, H, T, M+T] scores in HBM between XLA ops.
This measures that claim on the real chip at the flagship RL-unroll
shape and two longer-context shapes (still inside the kernel's VMEM
guard).

Method: marginal device time, same as vtrace_bench.py — chain `steps`
forwards in one dispatch (out feeds q, both [B, T, H, D]) at steps and
3*steps, and difference out the fixed per-dispatch floor (which would
otherwise swamp forwards that take microseconds).

Usage: python benchmarks/pallas_attn_bench.py [--steps 50]
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


def make_inputs(b, t, h, d, m, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((b, t, h, d)).astype(np.float32))
    k = jnp.asarray(
        rng.standard_normal((b, m + t, h, d)).astype(np.float32)
    )
    v = jnp.asarray(
        rng.standard_normal((b, m + t, h, d)).astype(np.float32)
    )
    done = rng.random((t, b)) < 0.1
    seg = jnp.asarray(np.cumsum(done, axis=0).T.astype(np.int32))
    cache_valid = jnp.asarray(
        (rng.random((b, m)) < 0.7).astype(np.float32)
    )
    no_done = jnp.asarray(np.cumsum(done, axis=0).T == 0)
    rel_bias = jnp.asarray(
        rng.standard_normal((h, m + 1)).astype(np.float32) * 0.1
    )
    return q, k, v, seg, cache_valid, no_done, rel_bias


def chained_ms(impl: str, shape, steps: int, interpret: bool) -> float:
    from torchbeast_tpu.ops.pallas_attention import (
        _reference,
        transformer_attention,
    )

    b, t, h, d, m = shape
    q, k, v, seg, valid, nodone, bias = make_inputs(b, t, h, d, m)

    if impl == "pallas":
        def one(qq):
            return transformer_attention(
                m, interpret, qq, k, v, seg, valid, nodone, bias
            )
    else:
        def one(qq):
            return _reference(qq, k, v, seg, valid, nodone, bias, m)

    @jax.jit
    def chained(qq):
        def body(_, acc):
            return one(acc)
        return jax.lax.fori_loop(0, steps, body, qq)

    jax.block_until_ready(chained(q))  # compile + warm
    t0 = time.perf_counter()
    jax.block_until_ready(chained(q))
    return (time.perf_counter() - t0) * 1e3


def marginal_ms(
    impl: str, shape, steps: int, interpret: bool
) -> tuple[float, bool]:
    from benchmarks._timing import marginal_from_totals

    lo = chained_ms(impl, shape, steps, interpret)
    hi = chained_ms(impl, shape, 3 * steps, interpret)
    return marginal_from_totals(lo, hi, steps)


def main() -> None:
    ap = argparse.ArgumentParser()
    # 200, not 50: at 50 the flagship shape's ~10 us marginal sits below
    # the differencing noise and produced a spurious 38x once (rejected
    # in benchmarks/artifacts/pallas_attn_chip.md).
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--interpret", action="store_true",
                    help="Pallas interpreter (CPU rehearsal)")
    args = ap.parse_args()

    platform = jax.devices()[0].platform
    shapes = [
        ("flagship B8 T20 M40", (8, 20, 4, 64, 40)),
        ("long B4 T128 M128", (4, 128, 4, 64, 128)),
        ("long B2 T256 M256", (2, 256, 4, 64, 256)),
    ]
    rows = []
    for name, shape in shapes:
        dense, d_floor = marginal_ms(
            "dense", shape, args.steps, args.interpret
        )
        pallas, p_floor = marginal_ms(
            "pallas", shape, args.steps, args.interpret
        )
        rows.append({
            "shape": name,
            "dense_ms": round(dense, 4),
            "pallas_ms": round(pallas, 4),
            "speedup": round(dense / pallas, 2) if pallas > 0 else None,
            # True when the two-point differencing degenerated and the
            # value is a floor-contaminated upper bound, not a marginal.
            "floor_contaminated": d_floor or p_floor,
        })
    print(json.dumps({
        "bench": "pallas_attention_fwd",
        "platform": platform,
        "mosaic": platform == "tpu" and not args.interpret,
        "steps": args.steps,
        "utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "rows": rows,
    }))


if __name__ == "__main__":
    main()
