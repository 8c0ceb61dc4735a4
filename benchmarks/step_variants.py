"""Sweep flagship learner-step variants on the ambient accelerator.

Measures step time for remat strategy x trunk dtype x pool-backward
implementation at the reference's T=80 B=32 flagship shape, reporting
ms/step, frames/s, and which variants OOM. Used to pick the defaults that
bench.py and the drivers ship with (the fastest configuration with a
confirmed HBM fit wins).

Run on the TPU host:   python benchmarks/step_variants.py
Quick CPU sanity run:  JAX_PLATFORMS=cpu python benchmarks/step_variants.py --tiny

Timing ends on a host fetch of the last loss, which depends on the
whole chain of steps.
"""

import argparse
import json
import os
import sys
import time

# Must be set before jax initializes anything pool.py traces later.
_POOL_ENV = "TBT_POOL_PALLAS"


def measure(remat, dtype_name, pallas_pool, t, b, steps):
    import jax
    import jax.numpy as jnp

    from torchbeast_tpu import learner as learner_lib
    from torchbeast_tpu.models import create_model

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    import __graft_entry__

    os.environ[_POOL_ENV] = "1" if pallas_pool else "0"
    dtype = {"f32": jnp.float32, "bf16": jnp.bfloat16}[dtype_name]
    model = create_model(
        "deep", num_actions=6, use_lstm=True, dtype=dtype, remat=remat
    )
    batch = __graft_entry__._make_batch(t, b, 6)
    state = model.initial_state(b)
    params = model.init(
        {"params": jax.random.PRNGKey(0), "action": jax.random.PRNGKey(1)},
        batch, state,
    )
    hp = learner_lib.HParams(batch_size=b, unroll_length=t)
    optimizer = learner_lib.make_optimizer(hp)
    opt_state = optimizer.init(params)
    step = learner_lib.make_update_step(model, optimizer, hp)
    batch = jax.device_put(batch)
    state = jax.device_put(state)

    params, opt_state, stats = step(params, opt_state, batch, state)
    float(stats["total_loss"])  # compile + sync
    t0 = time.perf_counter()
    for _ in range(steps):
        params, opt_state, stats = step(params, opt_state, batch, state)
    float(stats["total_loss"])
    ms = (time.perf_counter() - t0) / steps * 1000
    return ms


def main():
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--tiny", action="store_true",
                        help="T=8 B=4 CPU sanity mode")
    parser.add_argument("--steps", type=int, default=10)
    args = parser.parse_args()

    t, b = (8, 4) if args.tiny else (80, 32)
    variants = []
    for remat in (
        True,
        (True, False, False),
        ("front", False, False),
        ("front", "front", "front"),
    ):
        for dtype_name in ("f32", "bf16"):
            for pallas_pool in (False, True):
                variants.append((remat, dtype_name, pallas_pool))

    results = []
    for remat, dtype_name, pallas_pool in variants:
        tag = f"remat={remat!r} dtype={dtype_name} pallas_pool={pallas_pool}"
        # Each variant in a fresh subprocess: isolates OOMs/compile faults
        # and resets the TBT_POOL_PALLAS trace-time switch.
        code = (
            "import json, sys; sys.path.insert(0, {root!r});\n"
            "from benchmarks.step_variants import measure\n"
            "ms = measure({remat!r}, {dtype!r}, {pp!r}, {t}, {b}, {steps})\n"
            "print('RESULT', json.dumps(ms))\n"
        ).format(
            root=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            remat=remat, dtype=dtype_name, pp=pallas_pool,
            t=t, b=b, steps=args.steps,
        )
        import subprocess

        env = dict(os.environ)
        env[_POOL_ENV] = "1" if pallas_pool else "0"
        try:
            out = subprocess.run(
                [sys.executable, "-c", code], capture_output=True,
                text=True, timeout=1800, env=env,
            )
        except subprocess.TimeoutExpired:
            results.append({"variant": tag, "error": "timeout"})
            print(f"{tag}: TIMEOUT", flush=True)
            continue
        ms = None
        for line in out.stdout.splitlines():
            if line.startswith("RESULT "):
                ms = json.loads(line[len("RESULT "):])
        if ms is None:
            err = out.stderr.strip().splitlines()
            tail = err[-1][:200] if err else f"rc={out.returncode}"
            results.append({"variant": tag, "error": tail})
            print(f"{tag}: FAILED {tail}", flush=True)
        else:
            results.append({
                "variant": tag, "ms_per_step": round(ms, 2),
                "frames_per_sec": round(t * b / ms * 1000, 1),
            })
            print(f"{tag}: {ms:.2f} ms/step", flush=True)
    print(json.dumps(results, indent=2))


if __name__ == "__main__":
    main()
