"""Pallas kernels against their plain references, compiled or interpreted.

Every CPU test runs `ops/pallas_attention.py`, `ops/pallas_pool.py`,
`ops/pallas_vtrace.py` and `ops/pallas_opt.py` under the Pallas
INTERPRETER; what Mosaic refuses (block shapes, scoped VMEM, an op the
chip's VPU lacks) shows only when a kernel is compiled for a TPU. Each
case here runs one kernel with an EXPLICIT `interpret` argument — never
the kernels' own backend-based default — against its dense/XLA twin and
returns a verdict dict. `flagship_cases` is the set chip_smoke.py runs
compiled on the chip (and tests/test_chip_smoke.py runs interpreted at
a tiny size); this script's own CLI prints one JSON verdict line.

With `interpret=False` on the CPU backend every case fails cleanly
("Only interpret mode is supported on CPU backend") and the verdict
line still prints — what a Mosaic refusal looks like on the chip.

Usage: python benchmarks/pallas_smoke.py [--sizes test,chip] [--interpret]
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402


def attention_case(b, t, h, d, m, seed=0, interpret=False):
    from torchbeast_tpu.ops.pallas_attention import (
        _reference,
        transformer_attention,
    )

    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((b, t, h, d)).astype(np.float32))
    k = jnp.asarray(
        rng.standard_normal((b, m + t, h, d)).astype(np.float32)
    )
    v = jnp.asarray(
        rng.standard_normal((b, m + t, h, d)).astype(np.float32)
    )
    done = rng.random((t, b)) < 0.15
    seg = jnp.asarray(np.cumsum(done, axis=0).T.astype(np.int32))
    cache_valid = jnp.asarray((rng.random((b, m)) < 0.7).astype(np.float32))
    no_done = jnp.asarray(np.cumsum(done, axis=0).T == 0)
    rel_bias = jnp.asarray(
        rng.standard_normal((h, m + 1)).astype(np.float32) * 0.1
    )
    # Interpreted, both sides are exact f32. Compiled, f32 matmuls run
    # bf16 passes on the MXU at default precision — and XLA may compute
    # the reference's T=1 matrix-vector product exactly on the VPU — so
    # the two agree to bf16 rounding (2^-8), not to f32 rounding.
    rel_tol = 5e-4 if interpret else 1e-2

    cot = jnp.asarray(rng.standard_normal(q.shape).astype(np.float32))

    # Each side is ONE jitted program, forward and backward (eager,
    # every jnp op of the reference would compile on its own). The
    # backward is the kernel's custom VJP against autodiff of the
    # reference, same cotangent.
    def out_and_grads(attend):
        def loss(q, k, v, bias):
            out = attend(q, k, v, bias)
            return jnp.sum(cot * out), out

        return jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3), has_aux=True
        )(q, k, v, rel_bias)

    @jax.jit
    def kernel_side():
        return out_and_grads(lambda q, k, v, bias: transformer_attention(
            m, interpret, q, k, v, seg, cache_valid, no_done, bias
        ))

    @jax.jit
    def reference_side():
        return out_and_grads(lambda q, k, v, bias: _reference(
            q, k, v, seg, cache_valid, no_done, bias, m
        ))

    t0 = time.perf_counter()
    (_, ours), grads = jax.block_until_ready(kernel_side())
    compile_s = time.perf_counter() - t0
    (_, ref), ref_grads = reference_side()
    err = float(jnp.max(jnp.abs(ours - ref)))
    scale = float(jnp.max(jnp.abs(ref))) or 1.0
    grad_rel_err = max(
        float(jnp.max(jnp.abs(g - r)))
        / (float(jnp.max(jnp.abs(r))) or 1.0)
        for g, r in zip(grads, ref_grads)
    )
    return {
        "kernel": "transformer_attention",
        "shape": f"B{b} T{t} H{h} D{d} M{m}",
        "max_abs_err": err,
        "rel_err": err / scale,
        "grad_rel_err": grad_rel_err,
        "compile_s": round(compile_s, 2),
        "rel_tol": rel_tol,
        "ok": bool(err / scale < rel_tol and grad_rel_err < rel_tol),
    }


def vtrace_case(t, b, seed=0, interpret=False):
    """The fused V-trace targets kernel (ops/pallas_vtrace.py) vs the
    sequential-scan reference — vs AND pg_advantages from one kernel.
    The kernel is called directly so `interpret` is this case's to
    state; the prologue is ops/vtrace.from_importance_weights' own."""
    from torchbeast_tpu.ops import pallas_vtrace, vtrace

    rng = np.random.default_rng(seed)
    log_rhos = jnp.asarray(
        rng.uniform(-2.5, 2.5, (t, b)).astype(np.float32)
    )
    discounts = jnp.asarray(
        ((rng.random((t, b)) > 0.1) * 0.99).astype(np.float32)
    )
    rewards = jnp.asarray(rng.standard_normal((t, b)).astype(np.float32))
    values = jnp.asarray(
        (rng.standard_normal((t, b)) * 2).astype(np.float32)
    )
    bootstrap_value = jnp.asarray(
        (rng.standard_normal((b,)) * 2).astype(np.float32)
    )
    ref = vtrace.from_importance_weights(
        log_rhos=log_rhos, discounts=discounts, rewards=rewards,
        values=values, bootstrap_value=bootstrap_value,
        scan_impl="sequential",
    )
    rhos = jnp.exp(log_rhos)
    clipped = jnp.minimum(rhos, 1.0)  # rho-bar = c-bar = pg-rho-bar = 1
    values_tp1 = jnp.concatenate(
        [values[1:], bootstrap_value[None]], axis=0
    )
    deltas = clipped * (rewards + discounts * values_tp1 - values)
    t0 = time.perf_counter()
    vs, pg_advantages = pallas_vtrace.vtrace_targets(
        discounts * clipped, deltas, clipped, rewards, discounts,
        values, bootstrap_value, interpret=interpret,
    )
    jax.block_until_ready(vs)
    compile_s = time.perf_counter() - t0
    err = max(
        float(jnp.max(jnp.abs(vs - ref.vs))),
        float(jnp.max(jnp.abs(pg_advantages - ref.pg_advantages))),
    )
    scale = float(jnp.max(jnp.abs(ref.vs))) or 1.0
    return {
        "kernel": "vtrace_targets",
        "shape": f"T{t} B{b}",
        "max_abs_err": err,
        "rel_err": err / scale,
        "compile_s": round(compile_s, 2),
        "ok": bool(err / scale < 5e-5),
    }


def opt_case(shapes, seed=0, interpret=False, precision="bf16_train"):
    """The fused optimizer tail (ops/pallas_opt.py) vs the optax chain
    learner.make_optimizer composes — one update over a leaf tree of
    `shapes` (odd/1-D shapes included: the kernel runs leaves natively),
    momentum + clip active, bf16-resident master write exercised under
    bf16_train. The fused transform is built directly so `interpret` is
    explicit; at update 0 the reference's LR schedule reads
    hp.learning_rate, which is what the fused tail is given."""
    from torchbeast_tpu import learner as learner_lib
    from torchbeast_tpu.ops.pallas_opt import fused_rmsprop_tail

    rng = np.random.default_rng(seed)
    bf16 = precision == "bf16_train"
    dt = jnp.bfloat16 if bf16 else jnp.float32

    def draw():
        return jax.device_put({
            f"leaf{i}": rng.standard_normal(shape).astype(
                np.float32
            ).astype(dt)
            for i, shape in enumerate(shapes)
        })

    params, grads = draw(), draw()
    hp = learner_lib.HParams(
        grad_norm_clipping=0.5,  # small: the clip branch fires
        rmsprop_momentum=0.9,
        opt_state_dtype="bf16" if bf16 else "f32",
        param_dtype="bf16" if bf16 else "f32",
    )

    def run(opt):
        # One program per optimizer: init, update and apply for the
        # whole tree (a per-leaf eager loop would compile per shape).
        @jax.jit
        def one_update(params, grads):
            state = opt.init(params)
            updates, state = opt.update(grads, state, params)
            return learner_lib.apply_updates(params, updates, state)

        return one_update(params, grads)

    ref = run(learner_lib.make_optimizer(hp))
    t0 = time.perf_counter()
    ours = run(fused_rmsprop_tail(
        hp.learning_rate,
        decay=hp.rmsprop_alpha,
        eps=hp.rmsprop_eps,
        momentum=hp.rmsprop_momentum,
        max_norm=hp.grad_norm_clipping,
        param_dtype=hp.param_dtype,
        state_dtype=jnp.bfloat16 if bf16 else None,
        interpret=interpret,
    ))
    jax.block_until_ready(ours)
    compile_s = time.perf_counter() - t0

    @jax.jit
    def compare(ref, ours):
        f32 = [
            (a.astype(jnp.float32), b.astype(jnp.float32))
            for a, b in zip(
                jax.tree_util.tree_leaves(ref),
                jax.tree_util.tree_leaves(ours),
            )
        ]
        return (
            jnp.max(jnp.stack([jnp.max(jnp.abs(a - b)) for a, b in f32])),
            jnp.max(jnp.stack([jnp.max(jnp.abs(a)) for a, _ in f32])),
        )

    err, scale = (float(v) for v in compare(ref, ours))
    scale = scale or 1.0
    return {
        "kernel": "fused_opt_tail",
        "leaves": len(shapes),
        "precision": precision,
        "max_abs_err": err,
        "rel_err": err / scale,
        "compile_s": round(compile_s, 2),
        "ok": bool(err / scale < 5e-4),
    }


@jax.jit
def _unique_winner_positions(x, y):
    """Input positions where the kernel and SelectAndScatter must agree:
    those reached only by windows with exactly ONE maximal input. On a
    tie the kernel credits every tying position and SelectAndScatter the
    first; f32 normals tie rarely, but the flagship stage-1 operand has
    2.9e8 of them, so some windows do. Returns (mask, tied windows)."""
    from torchbeast_tpu.ops.pool import _place_on_input_grid

    ho, wo = y.shape[1], y.shape[2]
    xp = jnp.pad(
        x, ((0, 0), (1, 1), (1, 1), (0, 0)), constant_values=-jnp.inf
    )
    taps = [(kh, kw) for kh in range(3) for kw in range(3)]
    winners = sum(
        (xp[:, kh:kh + 2 * ho:2, kw:kw + 2 * wo:2, :] == y).astype(
            jnp.int8
        )
        for kh, kw in taps
    )
    tied = (winners > 1).astype(jnp.int8)
    reached_by_tie = sum(
        _place_on_input_grid(tied, x.shape, tap, (2, 2), (1, 1), 0)
        for tap in taps
    )
    return reached_by_tie == 0, jnp.sum(tied, dtype=jnp.int32)


def pool_case(shape, seed=0, interpret=False):
    """ops/pallas_pool.pool_bwd vs the autodiff (SelectAndScatter)
    gradient, compared wherever the two tie conventions agree. Inputs
    are drawn on the device: the flagship stage-1 operand is 1.2 GB."""
    from torchbeast_tpu.ops.pallas_pool import pool_bwd

    def fwd(x):
        return lax.reduce_window(
            x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
            ((0, 0), (1, 1), (1, 1), (0, 0)),
        )

    @jax.jit
    def reference(key):
        kx, kg = jax.random.split(key)
        x = jax.random.normal(kx, shape, jnp.float32)
        y, vjp = jax.vjp(fwd, x)
        g = jax.random.normal(kg, y.shape, jnp.float32)
        return x, y, g, vjp(g)[0]

    @jax.jit
    def compare(x, y, gx, gx_ref):
        comparable, tied_windows = _unique_winner_positions(x, y)
        return (
            jnp.max(jnp.where(comparable, jnp.abs(gx - gx_ref), 0)),
            jnp.mean(comparable, dtype=jnp.float32),
            tied_windows,
        )

    x, y, g, gx_ref = reference(jax.random.PRNGKey(seed))
    t0 = time.perf_counter()
    gx = pool_bwd(x, y, g, interpret=interpret)
    jax.block_until_ready(gx)
    compile_s = time.perf_counter() - t0
    err, compared, tied_windows = (
        float(v) for v in compare(x, y, gx, gx_ref)
    )
    return {
        "kernel": "pool_bwd",
        "shape": "x".join(map(str, shape)),
        "max_abs_err": err,
        "tied_windows": int(tied_windows),
        "compared_fraction": compared,
        "compile_s": round(compile_s, 2),
        "ok": bool(err < 1e-5 and compared > 0.99),
    }


# (H, W, C) of the pool input at the deep trunk's three stages
# (models/resnet.py: 16/32/32 channels on 84x84 frames).
FLAGSHIP_POOL_STAGES = ((84, 84, 16), (42, 42, 32), (21, 21, 32))
# (B, T, H, D, M): the transformer policy's learner unroll and its
# stepwise acting call (models/transformer.py defaults).
FLAGSHIP_ATTENTION_SHAPES = ((8, 20, 4, 64, 40), (1, 1, 4, 64, 40))


def flagship_cases(interpret, t=80, b=32,
                   attention_shapes=FLAGSHIP_ATTENTION_SHAPES):
    """[(name, thunk)]: every Pallas kernel a driver can select, at the
    flagship learner's shapes — unroll `t`, batch `b`, so the trunk
    pools see N = (t + 1) * b rows."""
    cases = [(
        f"vtrace-T{t}-B{b}",
        lambda: vtrace_case(t, b, interpret=interpret),
    )]
    import __graft_entry__

    shapes = [
        leaf.shape for leaf in jax.tree_util.tree_leaves(
            __graft_entry__._flagship_param_structs()[1]
        )
    ]
    for precision in ("f32", "bf16_train"):
        cases.append((
            f"opt-{precision}",
            lambda precision=precision: opt_case(
                shapes, interpret=interpret, precision=precision
            ),
        ))
    for shape in attention_shapes:
        cases.append((
            "attn-" + "x".join(map(str, shape)),
            lambda shape=shape: attention_case(
                *shape, interpret=interpret
            ),
        ))
    n = (t + 1) * b
    for h, w, c in FLAGSHIP_POOL_STAGES:
        cases.append((
            f"pool-{n}x{h}x{w}x{c}",
            lambda shape=(n, h, w, c): pool_case(
                shape, interpret=interpret
            ),
        ))
    return cases


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--sizes", default="test,chip",
        help="comma set: 'test' = unit-test shapes, 'chip' = "
        "flagship_cases (the learner's T=80, B=32 shapes)",
    )
    ap.add_argument(
        "--interpret", action="store_true",
        help="run under the Pallas interpreter (the CPU rehearsal: "
        "numerics, not Mosaic)",
    )
    args = ap.parse_args()
    sizes = set(args.sizes.split(","))
    itp = args.interpret

    backend = jax.default_backend()
    cases = []
    if "test" in sizes:
        cases.append(
            ("attn-test",
             lambda: attention_case(2, 12, 4, 16, 8, interpret=itp))
        )
        cases.append(
            ("pool-test",
             lambda: pool_case((2, 21, 21, 32), interpret=itp))
        )
        cases.append(
            ("vtrace-test",
             lambda: vtrace_case(13, 8, interpret=itp))
        )
        cases.append(
            ("opt-test",
             lambda: opt_case(
                 [(7,), (16, 128), (13, 37)], interpret=itp
             ))
        )
    if "chip" in sizes:
        cases += flagship_cases(itp)

    results, failures = [], []
    for name, fn in cases:
        try:
            r = fn()
            r["case"] = name
            results.append(r)
            if not r["ok"]:
                failures.append(name)
        except Exception as e:  # noqa: BLE001 — verdict must always print
            results.append({
                "case": name,
                "ok": False,
                "error": f"{type(e).__name__}: {e}",
                "traceback": traceback.format_exc()[-1500:],
            })
            failures.append(name)

    print(json.dumps({
        "bench": "pallas_smoke",
        "backend": backend,
        "interpret": args.interpret,
        "mosaic": backend == "tpu" and not args.interpret,
        "utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "ok": not failures,
        "failures": failures,
        "cases": results,
    }))
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
