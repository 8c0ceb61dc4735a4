"""Profile the flagship learner step and print an op-level summary.

Runs a few update steps under `jax.profiler.trace`, parses the captured
XSpace with `jax.profiler.ProfileData` (no tensorboard round-trip), and
prints:
  - top-10 device ops by total self time (name, ms, share),
  - device busy time vs wall time per step (idle %),
  - the XLA cost-analysis HBM roofline fields (bytes/step, achieved
    GB/s vs the chip peak) that bench.py also emits.

This is the evidence VERDICT round 2 asked for behind the "the step is
bandwidth-bound" claim: if the top ops are conv backprops and the
achieved HBM GB/s sits near the chip peak while MXU-visible time is a
sliver, the claim stands measured, not argued.

Usage: python benchmarks/profile_step.py [--dtype bf16|f32] [--steps 10]
Ambient backend (TPU under the driver; CPU with JAX_PLATFORMS=cpu).
Output: one JSON line + a human table on stderr; trace kept under
--out (default /tmp/tbt_profile) for later tensorboard inspection.
"""

import argparse
import glob
import json
import os
import sys
import time

import numpy as np


def find_xplane(out_dir):
    hits = glob.glob(
        os.path.join(out_dir, "**", "*.xplane.pb"), recursive=True
    )
    return max(hits, key=os.path.getmtime) if hits else None


def summarize_xplane(path, wall_s, steps):
    """(top_ops, busy_ms_per_step, track_name) from the densest single
    track of the densest device plane (TPU: the '/device:TPU:0' XLA-ops
    line). Aggregating ONE track avoids double-counting nested host
    frames and parallel-track overlap."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    best = None
    for plane in data.planes:
        is_device = plane.name.startswith("/device:")
        for line in plane.lines:
            totals = {}
            for ev in line.events:
                ns = ev.duration_ns
                if ns <= 0:
                    continue
                totals[ev.name] = totals.get(ev.name, 0) + ns
            if not totals:
                continue
            busy_ns = sum(totals.values())
            score = (is_device, busy_ns)
            if best is None or score > best[0]:
                best = (
                    score, f"{plane.name} :: {line.name}", totals
                )
    if best is None:
        return None
    _, track_name, totals = best
    busy_ns = sum(totals.values())
    top = sorted(totals.items(), key=lambda kv: -kv[1])[:10]
    return (
        [
            {
                "op": name[:100],
                "ms_per_step": round(ns / 1e6 / steps, 3),
                "share": round(ns / busy_ns, 3),
            }
            for name, ns in top
        ],
        busy_ns / 1e6 / steps,
        track_name,
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dtype", default="bf16", choices=["bf16", "f32"])
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--out", default="/tmp/tbt_profile")
    args = ap.parse_args()

    import jax

    import jax.numpy as jnp

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    )))
    import __graft_entry__
    import bench as bench_lib
    from torchbeast_tpu import learner as learner_lib
    from torchbeast_tpu.utils.xla_cache import use_compile_cache

    use_compile_cache()
    device = jax.devices()[0]
    dtype = jnp.bfloat16 if args.dtype == "bf16" else jnp.float32

    T, B = bench_lib.T, bench_lib.B
    model, params, batch, state = __graft_entry__._flagship(
        batch_size=B, t=T, dtype=dtype
    )
    hp = learner_lib.HParams(batch_size=B, unroll_length=T)
    optimizer = learner_lib.make_optimizer(hp)
    opt_state = optimizer.init(params)
    update_step = learner_lib.make_update_step(model, optimizer, hp)
    batch_d = jax.device_put(batch)
    state_d = jax.device_put(state)

    flops, hbm_bytes = bench_lib._cost_analysis(
        update_step, params, opt_state, batch_d, state_d
    )

    # Warm (compile outside the trace).
    for _ in range(2):
        params, opt_state, stats = update_step(
            params, opt_state, batch_d, state_d
        )
    float(stats["total_loss"])

    os.makedirs(args.out, exist_ok=True)
    t0 = time.perf_counter()
    with jax.profiler.trace(args.out):
        for _ in range(args.steps):
            params, opt_state, stats = update_step(
                params, opt_state, batch_d, state_d
            )
        float(stats["total_loss"])  # host fetch: honest sync
    wall = time.perf_counter() - t0
    step_ms = 1000 * wall / args.steps

    # A roofline share needs the peak of the chip it ran on; the CPU
    # rehearsal has none (and _peak_for raises on an unknown kind).
    peak_hbm = (
        bench_lib._peak_for(device.device_kind, bench_lib.PEAK_HBM_GBPS)
        if device.platform != "cpu" else None
    )
    hbm_gbps = (
        hbm_bytes / (step_ms / 1000) / 1e9 if hbm_bytes else None
    )

    xplane = find_xplane(args.out)
    top_ops = busy_ms = plane = None
    if xplane:
        parsed = summarize_xplane(xplane, wall, args.steps)
        if parsed:
            top_ops, busy_ms, plane = parsed

    result = {
        "dtype": args.dtype,
        "platform": device.platform,
        "device_kind": device.device_kind,
        "steps": args.steps,
        "step_ms": round(step_ms, 2),
        "hbm_bytes_per_step": hbm_bytes,
        "achieved_hbm_gbps": round(hbm_gbps, 1) if hbm_gbps else None,
        "peak_hbm_gbps": peak_hbm,
        "hbm_roofline_util": (
            round(hbm_gbps / peak_hbm, 4) if hbm_gbps and peak_hbm else None
        ),
        "flops_per_step": flops,
        "device_busy_ms_per_step": (
            round(busy_ms, 2) if busy_ms else None
        ),
        "device_idle_frac": (
            round(1 - busy_ms / step_ms, 4)
            if busy_ms and busy_ms < step_ms
            else None
        ),
        "plane": plane,
        "trace_dir": args.out,
        "top_ops": top_ops,
    }
    print(json.dumps(result))
    if top_ops:
        for o in top_ops:
            sys.stderr.write(
                f"{o['ms_per_step']:>9.3f} ms {o['share']:>6.1%}  "
                f"{o['op']}\n"
            )


if __name__ == "__main__":
    main()
