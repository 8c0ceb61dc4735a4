"""Sequential vs associative V-trace timing (VERDICT r4 item 4).

`--vtrace_impl associative` exists for O(log T) depth at long T
(ops/vtrace.py:103-112; reference recursion:
/root/reference/torchbeast/core/vtrace.py:116-122). This measures the
claim: jitted solve time for both impls at T in {80, 1000, 4000}.

Interpretation caveat (recorded in the output): on a 1-core CPU host
the associative variant does MORE total work (O(T log T) element ops
vs O(T)) and has no parallel lanes to spend depth on, so CPU numbers
bound the overhead, not the chip win. The chip row is what decides
whether the flag's help text keeps its promise.

Usage: python benchmarks/vtrace_bench.py [--steps 30] [--batch 32]
Emits one JSON object; `--out` appends a markdown table row set to
benchmarks/artifacts/vtrace_scan_bench.md.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from torchbeast_tpu.ops import vtrace  # noqa: E402


def time_impl(impl: str, t: int, b: int, steps: int) -> float:
    """ms per V-trace solve, measured as ONE device dispatch that chains
    `steps` solves with a data dependence (each iteration's vs feeds the
    next solve's values).

    Why not a host loop of calls: each would pay the per-dispatch
    floor, and the chain is what a learner step sees anyway — the solve
    sits inside a bigger jitted program.
    """
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 5)
    log_rhos = jax.random.normal(ks[0], (t, b)) * 0.1
    discounts = jnp.full((t, b), 0.99)
    rewards = jax.random.normal(ks[1], (t, b))
    values = jax.random.normal(ks[2], (t, b))
    bootstrap = jax.random.normal(ks[3], (b,))

    @jax.jit
    def chained(values):
        def body(_, vals):
            out = vtrace.from_importance_weights(
                log_rhos, discounts, rewards, vals, bootstrap,
                scan_impl=impl,
            )
            return out.vs
        return jax.lax.fori_loop(0, steps, body, values)

    jax.block_until_ready(chained(values))  # compile + warm
    t0 = time.perf_counter()
    jax.block_until_ready(chained(values))
    return (time.perf_counter() - t0) / steps * 1e3


def marginal_ms(impl: str, t: int, b: int, steps: int) -> float:
    """Per-solve ms with the fixed per-dispatch floor eliminated.

    Even the chained instrument carries a constant per-call cost
    (program launch + the host's wait), which swamps a T=80 solve.
    Two-point elimination: total(3s) - total(s) contains no
    fixed cost, so dividing by 2s gives the marginal device time per
    solve — the number a learner step actually pays when the solve sits
    inside a bigger jitted program.
    """
    from benchmarks._timing import marginal_from_totals

    lo = time_impl(impl, t, b, steps) * steps
    hi = time_impl(impl, t, b, 3 * steps) * 3 * steps
    # On noisy hosts with tiny T the fallback (floor-contaminated
    # amortized upper bound) keeps the bench contract (positive rows).
    ms, _contaminated = marginal_from_totals(lo, hi, steps)
    return ms


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument(
        "--out", default="benchmarks/artifacts/vtrace_scan_bench.md"
    )
    ap.add_argument("--no_artifact", action="store_true")
    args = ap.parse_args()

    platform = jax.devices()[0].platform
    rows = []
    for t in (80, 1000, 4000):
        seq = marginal_ms("sequential", t, args.batch, args.steps)
        aso = marginal_ms("associative", t, args.batch, args.steps)
        rows.append({
            "T": t,
            "sequential_ms": round(seq, 3),
            "associative_ms": round(aso, 3),
            "assoc_speedup": round(seq / aso, 2) if aso > 0 else None,
        })
    result = {
        "bench": "vtrace_scan",
        "platform": platform,
        "batch": args.batch,
        "steps": args.steps,
        "utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "rows": rows,
        "caveat": (
            "cpu rows bound overhead only (O(T log T) work, no parallel "
            "lanes); the chip row decides the O(log T) depth claim"
        ) if platform == "cpu" else None,
    }
    print(json.dumps(result))

    if not args.no_artifact:
        out = Path(args.out)
        out.parent.mkdir(parents=True, exist_ok=True)
        lines = [
            f"\n## {platform} — {result['utc']} "
            f"(B={args.batch}, {args.steps} steps/point)\n",
            "| T | sequential ms | associative ms | assoc speedup |",
            "|---|---|---|---|",
        ]
        for r in rows:
            lines.append(
                f"| {r['T']} | {r['sequential_ms']} | "
                f"{r['associative_ms']} | {r['assoc_speedup']}x |"
            )
        if result["caveat"]:
            lines.append(f"\n_{result['caveat']}_")
        with out.open("a") as f:
            f.write("\n".join(lines) + "\n")


if __name__ == "__main__":
    main()
