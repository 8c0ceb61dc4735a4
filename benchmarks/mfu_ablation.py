"""Where does the flagship learner step's time go, and is the low MFU the
model's fault or the program's?

Round-2/3 VERDICTs flagged that two claims rested on prose, not records:
(a) ">95% of the step is the conv trunk backward" (the model-bound story
behind MFU 12.3%), and (b) "the 16/32/32-channel trunk cannot fill the
MXU" (a v5e tile contracts 128x128; a 16-channel conv's im2col matmul
fills 16 of 128 output lanes). This script measures both:

  1. decompose — jit the full update step (fwd+bwd+V-trace+optimizer)
     and the trunk alone (fwd, and fwd+bwd with the same remat config
     training uses) at the same T/B; report the trunk's share of the
     step and the trunk backward's share of the trunk.
  2. channels — step the full learner at trunk widths 16/32/32 (the
     reference's, polybeast_learner.py:140-147), 32/64/64, and
     64/128/128 (the opt-in --trunk_channels variants); report step_ms
     against XLA cost-analysis FLOPs. If time grows far slower than
     FLOPs, the MXU had idle lanes — capacity is nearly free and the
     low MFU is the small model, measured; if time tracks FLOPs, the
     step is genuinely saturated and the MFU story needs the HBM
     roofline instead.
  3. batch — step_ms across a batch sweep at fixed width. Same logic on
     the batch axis: sublinear time growth = idle hardware at B=32.

Defaults are CPU-sized (T=16, B=4, 3 steps) so the decomposition runs
anywhere; `--full` selects the chip shapes (T=80, B=32, the bench
config) for a run on the real TPU.
Output: one JSON line on stdout; human summary on stderr.
"""

import argparse
import json
import os
import sys
import time

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="Chip shapes: T=80 B=32 steps=10 and the full "
                         "channel/batch sweeps (several compiles).")
    ap.add_argument("--t", type=int, default=None)
    ap.add_argument("--b", type=int, default=None)
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--budget_s", type=float, default=1200.0,
                    help="Soft wall-clock budget: later sweep points are "
                         "skipped (and listed) once exceeded.")
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
    from torchbeast_tpu.models import create_model
    from torchbeast_tpu.models.resnet import ResNetBase

    use_compile_cache()
    device = jax.devices()[0]
    on_accel = device.platform != "cpu"

    T = args.t or (80 if args.full else 16)
    B = args.b or (32 if args.full else 4)
    steps = args.steps or (10 if args.full else 3)
    deadline = time.monotonic() + args.budget_s
    skipped = []

    def over_budget(tag):
        if time.monotonic() > deadline:
            skipped.append(tag)
            sys.stderr.write(f"mfu_ablation: budget exhausted, "
                             f"skipping {tag}\n")
            return True
        return False

    def timeit(fn, sync, n=steps, warmup=1):
        for _ in range(warmup):
            out = fn()
        sync(out)
        t0 = time.perf_counter()
        for _ in range(n):
            out = fn()
        sync(out)  # host fetch of a dependent scalar: honest sync
        return 1000 * (time.perf_counter() - t0) / n

    def step_runner(step, p, o, *rest):
        """Chain a DONATING update step: params/opt_state rebind every
        call (the default donate=True invalidates the argument buffers —
        reusing the originals would poison the second call)."""
        stash = {"p": p, "o": o}

        def run():
            stash["p"], stash["o"], stats = step(
                stash["p"], stash["o"], *rest
            )
            return stats

        return run

    # ---- 1. decompose: full step vs trunk alone ----
    model, params, batch, state = __graft_entry__._flagship(
        batch_size=B, t=T
    )
    hp = learner_lib.HParams(batch_size=B, unroll_length=T)
    optimizer = learner_lib.make_optimizer(hp)
    opt_state = optimizer.init(params)
    update_step = learner_lib.make_update_step(model, optimizer, hp)
    batch_d = jax.device_put(batch)
    state_d = jax.device_put(state)

    full_flops, full_bytes = bench_lib._cost_analysis(
        update_step, params, opt_state, batch_d, state_d
    )

    full_ms = timeit(
        step_runner(update_step, params, opt_state, batch_d, state_d),
        lambda stats: float(stats["total_loss"]),
    )

    # Trunk alone, same remat config the training step uses (remat=True:
    # its backward RECOMPUTES the forward, so trunk_fwd_bwd_ms already
    # contains the recompute cost exactly as it occurs inside the step).
    frames = batch_d["frame"]
    trunk = ResNetBase(dtype=jnp.float32, remat=True)
    trunk_params = trunk.init(jax.random.PRNGKey(0), frames)

    trunk_fwd = jax.jit(lambda p: trunk.apply(p, frames).sum())
    trunk_grad = jax.jit(
        jax.grad(lambda p: trunk.apply(p, frames).sum())
    )
    trunk_flops, _ = bench_lib._cost_analysis(trunk_grad, trunk_params)

    fwd_ms = timeit(
        lambda: trunk_fwd(trunk_params), lambda o: float(o)
    )
    fwdbwd_ms = timeit(
        lambda: trunk_grad(trunk_params),
        lambda o: float(
            jax.tree_util.tree_leaves(o)[0].ravel()[0]
        ),
    )

    # Incremental emission: each phase prints the cumulative result as a
    # JSON line (keyed "partial") the moment it lands, so a hard outer
    # timeout can never discard already-measured phases. Readers take
    # the LAST line; "partial": false marks the complete run.
    result = {
        "platform": device.platform,
        "device_kind": device.device_kind,
        "t": T,
        "b": B,
        "steps": steps,
        "partial": True,
    }

    def emit():
        print(json.dumps(result))
        sys.stdout.flush()

    decompose = {
        "full_step_ms": round(full_ms, 2),
        "trunk_fwd_ms": round(fwd_ms, 2),
        "trunk_fwd_bwd_ms": round(fwdbwd_ms, 2),
        "trunk_bwd_ms": round(fwdbwd_ms - fwd_ms, 2),
        "trunk_share_of_step": round(fwdbwd_ms / full_ms, 3),
        "trunk_bwd_share_of_step": round(
            (fwdbwd_ms - fwd_ms) / full_ms, 3
        ),
        "full_step_flops": full_flops,
        "trunk_fwd_bwd_flops": trunk_flops,
    }
    result["decompose"] = decompose
    emit()

    # ---- 2. channels sweep: the MXU-lane experiment ----
    widths = [(16, 32, 32), (32, 64, 64), (64, 128, 128)]
    if not (args.full or on_accel):
        widths = widths[:2]  # CPU smoke: the scaling point, not the tail

    def step_at(trunk_channels):
        m = create_model(
            "deep", num_actions=6, use_lstm=True,
            trunk_channels=trunk_channels,
        )
        p = m.init(
            {"params": jax.random.PRNGKey(0),
             "action": jax.random.PRNGKey(1)},
            batch, state,
        )
        opt = learner_lib.make_optimizer(hp)
        os_ = opt.init(p)
        step = learner_lib.make_update_step(m, opt, hp)
        fl, _ = bench_lib._cost_analysis(step, p, os_, batch_d, state_d)
        ms = timeit(
            step_runner(step, p, os_, batch_d, state_d),
            lambda stats: float(stats["total_loss"]),
        )
        return ms, fl

    channels = []
    base_ms = base_fl = base_w = None
    for w in widths:
        tag = "channels " + "/".join(map(str, w))
        if over_budget(tag):
            continue
        ms, fl = step_at(w)
        if base_ms is None:
            # Ratios baseline to the first width that RAN, which is not
            # necessarily widths[0] (earlier points can be skipped by
            # the budget check) — so every entry records its baseline
            # width and the ratios stay self-describing.
            base_ms, base_fl, base_w = ms, fl, list(w)
        channels.append({
            "trunk_channels": list(w),
            "step_ms": round(ms, 2),
            "flops": fl,
            "baseline_channels": base_w,
            "time_x": round(ms / base_ms, 2),
            "flops_x": round(fl / base_fl, 2) if fl and base_fl else None,
        })
        result["channels"] = channels
        emit()

    # ---- 3. batch sweep ----
    batches = [32, 64, 128] if (args.full or on_accel) else [B, 2 * B]
    batch_sweep = []
    b0 = None
    for bsz in batches:
        tag = f"batch {bsz}"
        if over_budget(tag):
            continue
        m2, p2, batch2, state2 = __graft_entry__._flagship(
            batch_size=bsz, t=T
        )
        hp2 = learner_lib.HParams(batch_size=bsz, unroll_length=T)
        opt2 = learner_lib.make_optimizer(hp2)
        os2 = opt2.init(p2)
        step2 = learner_lib.make_update_step(m2, opt2, hp2)
        b2d = jax.device_put(batch2)
        s2d = jax.device_put(state2)
        ms = timeit(
            step_runner(step2, p2, os2, b2d, s2d),
            lambda stats: float(stats["total_loss"]),
        )
        fps = T * bsz / (ms / 1000)
        if b0 is None:
            b0 = fps
        batch_sweep.append({
            "batch": bsz,
            "step_ms": round(ms, 2),
            "frames_per_sec": round(fps, 1),
            "fps_x": round(fps / b0, 2),
        })
        result["batch_sweep"] = batch_sweep
        emit()

    result["skipped"] = skipped
    result["partial"] = False
    print(json.dumps(result))
    sys.stderr.write(
        f"trunk share of step: {decompose['trunk_share_of_step']:.1%} "
        f"(bwd alone {decompose['trunk_bwd_share_of_step']:.1%})\n"
    )
    for c in channels:
        sys.stderr.write(
            f"channels {c['trunk_channels']}: {c['step_ms']} ms "
            f"({c['time_x']}x time, {c['flops_x']}x flops)\n"
        )
    for br in batch_sweep:
        sys.stderr.write(
            f"batch {br['batch']}: {br['step_ms']} ms, "
            f"{br['frames_per_sec']} fps ({br['fps_x']}x)\n"
        )


if __name__ == "__main__":
    main()
