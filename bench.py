"""Benchmark: learner-update throughput in env frames/sec/chip.

Measures the flagship IMPALA learner step (deep ResNet + LSTM, unroll T=80,
batch B=32 — the reference's beefy-machine unroll with its canonical
large-scale batch, BASELINE.md) as a single jitted XLA program with donated
state, on whatever accelerator the ambient JAX sees (the real TPU chip under
the driver).

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "frames/sec/chip", "vs_baseline": N}
where `value`/`vs_baseline` are the f32 learner step (apples-to-apples with
the f32 torch baseline), plus diagnostic fields: platform/device, step_ms,
bf16_value + bf16_vs_baseline (accelerator only — the mixed-precision
number, reported separately precisely because it is NOT numerics-identical
to the baseline), per-dtype achieved TFLOP/s from XLA's own cost analysis,
mfu (bf16 achieved vs the chip's bf16 peak), HBM roofline fields
(f32/bf16_hbm_gbps, hbm_roofline_util — the meaningful ceiling metric for
this bandwidth-bound model), inference_steps_per_sec (largest act bucket),
and anakin_sps (the fully-on-device Podracer trainer on Catch).

vs_baseline compares against the torch-CPU reference-equivalent learner step
measured by benchmarks/torch_baseline.py on this machine (stored in
BASELINE_measured.json). The reference repo publishes no numbers
(BASELINE.md), so the baseline is measured, not copied.

One process, one device: the process that measures is the one that
holds the chip. With no accelerator the script fails — it prints no
metric line and exits non-zero; it never falls back to the CPU and never
repeats an old record. `BENCH_FORCE_CPU=1` is the explicit CPU
rehearsal of the harness (control flow and schema, not speed); its line
says `platform: "cpu"`. The compile cache is the checkout's one
(torchbeast_tpu/utils/xla_cache.py).
"""

import json
import os
import sys
import time

import numpy as np

T = 80
B = 32
STEPS = 10
WARMUP = 2

_REPO = os.path.dirname(os.path.abspath(__file__))
# Peak bf16 TFLOP/s per chip by device kind (public figures). A kind
# that is not in the tables is an error (_peak_for), never a default.
PEAK_BF16_TFLOPS = {
    "v2": 45.0,
    "v3": 123.0,
    "v4": 275.0,
    "v5 lite": 197.0,
    "v5e": 197.0,
    "v5p": 459.0,
    "v6 lite": 918.0,
    "v6e": 918.0,
}

# Peak HBM bandwidth GB/s per chip (public figures). The IMPALA trunk's
# 16/32-channel convs are ~28 FLOP/byte — far below the ~240 FLOP/byte a
# v5e needs to saturate the MXU from HBM — so the step is bandwidth-bound
# and HBM roofline utilization, not MFU, is the number that says whether
# the program is near the hardware ceiling.
PEAK_HBM_GBPS = {
    "v2": 700.0,
    "v3": 900.0,
    "v4": 1228.0,
    "v5 lite": 819.0,
    "v5e": 819.0,
    "v5p": 2765.0,
    "v6 lite": 1640.0,
    "v6e": 1640.0,
}


def _base_result(**extra):
    """The metric-line skeleton every emit site shares (preliminary and
    final) — one definition so the schema cannot drift between them."""
    result = {
        "metric": (
            "IMPALA learner update throughput "
            f"(deep ResNet+LSTM, T={T}, B={B})"
        ),
        "value": None,
        "unit": "frames/sec/chip",
        "vs_baseline": None,
    }
    result.update(extra)
    return result


def _peak_for(kind: str, table):
    """Chip-kind -> peak figure by substring match of the lowercased
    `device_kind`. An unknown kind raises: a utilization against an
    assumed peak is a number about no chip in particular."""
    kind = kind.lower()
    for name, peak in table.items():
        if name in kind:
            return peak
    raise ValueError(
        f"device_kind {kind!r} is not in bench.py's peaks tables "
        f"({sorted(table)}); add its published figure with a source"
    )


def _cost_analysis(jitted, *args):
    """(flops, bytes_accessed) per call from XLA's own cost analysis of
    the optimized HLO (bytes are a post-fusion proxy for HBM traffic)."""
    analysis = jitted.lower(*args).compile().cost_analysis()
    if isinstance(analysis, (list, tuple)):
        analysis = analysis[0]
    return float(analysis["flops"]), float(analysis["bytes accessed"])


def run_bench(device):
    """Measure on `device` (jax.devices()[0]) and print the metric
    lines. Any phase that fails raises: no number is better than a
    line with a hole nobody notices."""
    import jax
    import jax.numpy as jnp

    from torchbeast_tpu import learner as learner_lib

    platform = device.platform
    on_accel = platform != "cpu"
    steps, warmup = (STEPS, WARMUP) if on_accel else (3, 1)

    baseline = None
    baseline_path = os.path.join(_REPO, "BASELINE_measured.json")
    if os.path.exists(baseline_path):
        with open(baseline_path) as f:
            baseline = json.load(f).get("torch_cpu_frames_per_sec")

    # Same flagship construction the driver compile-checks (one source of
    # truth for the model/batch schema).
    import __graft_entry__

    def measure(dtype):
        model, params, batch, state = __graft_entry__._flagship(
            batch_size=B, t=T, dtype=dtype
        )
        hp = learner_lib.HParams(batch_size=B, unroll_length=T)
        optimizer = learner_lib.make_optimizer(hp)
        opt_state = optimizer.init(params)
        update_step = learner_lib.make_update_step(model, optimizer, hp)

        batch_d = jax.device_put(batch)
        state_d = jax.device_put(state)

        flops, hbm_bytes = _cost_analysis(
            update_step, params, opt_state, batch_d, state_d
        )

        for _ in range(warmup):
            params, opt_state, stats = update_step(
                params, opt_state, batch_d, state_d
            )
        jax.block_until_ready(stats)

        t0 = time.perf_counter()
        for _ in range(steps):
            params, opt_state, stats = update_step(
                params, opt_state, batch_d, state_d
            )
        jax.block_until_ready((params, stats))
        elapsed = time.perf_counter() - t0
        return (T * B * steps / elapsed, 1000 * elapsed / steps, flops,
                hbm_bytes)

    frames_per_sec, step_ms, flops, hbm_bytes = measure(jnp.float32)
    # The headline number is in hand: emit a preliminary line so a
    # failure in a later phase cannot take it along.
    print(json.dumps(_base_result(
        value=round(frames_per_sec, 1),
        vs_baseline=(
            round(frames_per_sec / baseline, 2) if baseline else None
        ),
        platform=platform,
        device_kind=device.device_kind,
        step_ms=round(step_ms, 2),
        note="preliminary (f32 only; later phases pending)",
    )))
    sys.stdout.flush()
    # bf16 trunk variant: only worth the extra compile on an accelerator.
    bf16_frames_per_sec = bf16_step_ms = bf16_flops = bf16_hbm_bytes = None
    if on_accel:
        (bf16_frames_per_sec, bf16_step_ms, bf16_flops,
         bf16_hbm_bytes) = measure(jnp.bfloat16)

    # Per-dtype achieved TFLOP/s; MFU only for the bf16 run against the
    # chip's bf16 peak (comparing an f32 run to a bf16 peak would
    # understate utilization ~2x).
    def tflops(ms, fl):
        return fl / (ms / 1000) / 1e12 if ms else None

    f32_tflops = tflops(step_ms, flops)
    bf16_tflops = tflops(bf16_step_ms, bf16_flops)
    mfu = None
    if bf16_tflops:
        mfu = bf16_tflops / _peak_for(device.device_kind, PEAK_BF16_TFLOPS)

    # HBM roofline: the trunk's arithmetic intensity (~28 FLOP/byte) is
    # far under the chip's balance point, so bandwidth utilization is the
    # meaningful ceiling metric for this model — MFU cannot approach 1
    # no matter how good the program is.
    def hbm_gbps(ms, nbytes):
        return nbytes / (ms / 1000) / 1e9 if ms else None

    f32_hbm_gbps = hbm_gbps(step_ms, hbm_bytes)
    bf16_hbm_gbps = hbm_gbps(bf16_step_ms, bf16_hbm_bytes)
    hbm_util = None
    if bf16_hbm_gbps:
        hbm_util = bf16_hbm_gbps / _peak_for(
            device.device_kind, PEAK_HBM_GBPS
        )

    # Inference throughput at the largest bucket (the actor-side hot path).
    def measure_inference(batch_size=64, n=20):
        model, params, batch, _ = __graft_entry__._flagship(
            batch_size=batch_size, t=0
        )
        act_step = learner_lib.make_act_step(model)
        env_output = {
            k: jax.device_put(batch[k][0])
            for k in ("frame", "reward", "done", "last_action")
        }
        state = jax.device_put(model.initial_state(batch_size))
        key = jax.random.PRNGKey(0)
        out, state = act_step(params, key, env_output, state)  # compile
        np.asarray(out.action)
        t0 = time.perf_counter()
        for _ in range(n):
            out, state = act_step(params, key, env_output, state)
            # The act path's real contract is actions-on-host every call
            # (the DynamicBatcher replies to blocked actors), so the
            # per-call fetch IS the workload, not measurement overhead.
            np.asarray(out.action)
        return batch_size * n / (time.perf_counter() - t0)

    inference_sps = measure_inference(n=20 if on_accel else 3)

    # Anakin (fully-on-device Podracer, Catch): the purest chip-utilization
    # story — env, policy, and update all inside one XLA program.
    def measure_anakin(batch_size=256, unroll=16, n=20):
        from torchbeast_tpu.anakin import initial_carry, make_train_step
        from torchbeast_tpu.envs.jax_env import create_jax_env
        from torchbeast_tpu.models import create_model

        env = create_jax_env("Catch")
        hp = learner_lib.HParams(batch_size=batch_size, unroll_length=unroll)
        model = create_model(
            "mlp", num_actions=env.num_actions, use_lstm=False
        )
        optimizer = learner_lib.make_optimizer(hp)
        params, carry = initial_carry(
            env, model, batch_size, jax.random.PRNGKey(0)
        )
        opt_state = optimizer.init(params)
        train_step = make_train_step(env, model, optimizer, hp)
        params, opt_state, carry, stats = train_step(
            params, opt_state, carry
        )  # compile
        jax.block_until_ready(stats)
        t0 = time.perf_counter()
        for _ in range(n):
            params, opt_state, carry, stats = train_step(
                params, opt_state, carry
            )
        jax.block_until_ready((params, stats))
        return batch_size * unroll * n / (time.perf_counter() - t0)

    anakin_sps = measure_anakin(n=50 if on_accel else 10)

    # benchmarks/learner_bench.py is loaded by path (the benchmarks dir
    # is not a package) and memoized: three measurement phases below
    # share ONE module execution.
    _lb_cache = []

    def _load_learner_bench():
        if not _lb_cache:
            import importlib.util

            spec = importlib.util.spec_from_file_location(
                "learner_bench",
                os.path.join(_REPO, "benchmarks", "learner_bench.py"),
            )
            lb = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(lb)
            _lb_cache.append(lb)
        return _lb_cache[0]

    # Learner superstep throughput (ISSUE 4): the small-MLP K=8 fused
    # dispatch — the dispatch-amortization metric the superstep work
    # moves. ONE measurement implementation, shared with the committed
    # artifact.
    def measure_learner_superstep(k=8, n_updates=32):
        lb = _load_learner_bench()
        hp, model, optimizer, params, lrng = lb.build_config(
            use_lstm=False
        )
        row = lb.measure_updates_per_sec(
            hp, model, optimizer, params, lrng, k, n_updates
        )
        return row["updates_per_sec"]

    learner_updates_sps = measure_learner_superstep(
        n_updates=32 if on_accel else 16
    )

    # Learner bytes-moved accounting (ISSUE 8): XLA-reported bytes
    # accessed per update, f32 vs --precision bf16_train, from the
    # dtype-faithful lowered HLO (lowering-only — no compile, cheap on
    # any host; methodology in benchmarks/learner_bench.py). ONE
    # measurement implementation, shared with the committed artifact.
    def measure_learner_bytes():
        lb = _load_learner_bench()
        rows, _ = lb.measure_bytes(
            "mlp", ks=[1], t=lb.BYTES_T, b=lb.BYTES_B
        )
        by_prec = {
            r["precision"]: r["bytes_accessed"]
            for r in rows
            if r["k"] == 1 and r["bytes_accessed"]
        }
        f32_b = by_prec.get("f32")
        bf16_b = by_prec.get("bf16_train")
        reduction = f32_b / bf16_b if f32_b and bf16_b else None
        return f32_b, bf16_b, reduction

    hbm_f32, hbm_bf16, hbm_reduction = measure_learner_bytes()

    # Fused optimizer tail (ISSUE 13): xla-vs-pallas full-update bytes
    # on the flagship LSTM under bf16_train (the shape whose tail is
    # large enough to carry the 1.15x acceptance), same lowered-HLO
    # accounting and same _prev/_delta convention as the hbm keys. ONE
    # measurement implementation, shared with the committed artifact.
    def measure_opt_tail_reduction():
        lb = _load_learner_bench()
        rows = lb.measure_opt_tail("lstm", lb.BYTES_T, lb.BYTES_B)
        by_impl = {
            r["opt_impl"]: r["bytes_accessed"]
            for r in rows
            if r["precision"] == "bf16_train" and r["bytes_accessed"]
        }
        x, p = by_impl.get("xla"), by_impl.get("pallas")
        return x / p if x and p else None

    opt_tail_reduction = measure_opt_tail_reduction()

    result = _base_result()
    result.update({
        "value": round(frames_per_sec, 1),
        "vs_baseline": (
            round(frames_per_sec / baseline, 2) if baseline else None
        ),
        "platform": platform,
        "device_kind": device.device_kind,
        "step_ms": round(step_ms, 2),
        "bf16_value": (
            round(bf16_frames_per_sec, 1) if bf16_frames_per_sec else None
        ),
        "bf16_step_ms": round(bf16_step_ms, 2) if bf16_step_ms else None,
        "bf16_vs_baseline": (
            round(bf16_frames_per_sec / baseline, 2)
            if bf16_frames_per_sec and baseline
            else None
        ),
        "f32_achieved_tflops": round(f32_tflops, 2) if f32_tflops else None,
        "bf16_achieved_tflops": (
            round(bf16_tflops, 2) if bf16_tflops else None
        ),
        "mfu": round(mfu, 4) if mfu else None,
        "f32_hbm_gbps": round(f32_hbm_gbps, 1) if f32_hbm_gbps else None,
        "bf16_hbm_gbps": (
            round(bf16_hbm_gbps, 1) if bf16_hbm_gbps else None
        ),
        "hbm_roofline_util": round(hbm_util, 4) if hbm_util else None,
        "inference_steps_per_sec": (
            round(inference_sps, 1) if inference_sps else None
        ),
        "anakin_sps": round(anakin_sps, 1) if anakin_sps else None,
    })
    # Learner superstep regression visibility (ISSUE 4), mirroring the
    # inference convention: delta vs the committed learner_bench
    # artifact's small-MLP K=8 number — but only when the platforms
    # match (the committed artifact records where it was measured;
    # CPU-vs-TPU deltas are meaningless).
    result["learner_updates_per_sec"] = (
        round(learner_updates_sps, 1) if learner_updates_sps else None
    )
    with open(os.path.join(
        _REPO, "benchmarks", "artifacts", "learner_bench.json"
    )) as f:
        lb_art = json.load(f)
    accepted = lb_art.get("acceptance", {})
    prev_learner = accepted.get("mlp_updates_per_sec_ktop")
    prev_learner_platform = lb_art.get("platform")
    result["learner_updates_per_sec_prev"] = (
        round(prev_learner, 1) if prev_learner else None
    )
    result["learner_updates_per_sec_delta_pct"] = (
        round(
            100.0 * (learner_updates_sps - prev_learner) / prev_learner,
            1,
        )
        if learner_updates_sps and prev_learner
        and prev_learner_platform == platform
        else None
    )
    # Bytes-moved regression visibility (ISSUE 8), same _prev/_delta
    # convention against the committed learner_bench artifact's
    # small-MLP K=1 reduction. The lowered-HLO figure is platform-
    # neutral (no platform match required): a delta here means the
    # learner's byte diet itself changed, not the machine.
    result["learner_hbm_bytes_per_update"] = hbm_f32
    result["learner_hbm_bytes_per_update_bf16"] = hbm_bf16
    result["learner_hbm_bytes_reduction"] = (
        round(hbm_reduction, 3) if hbm_reduction else None
    )
    prev_hbm = accepted.get("bytes", {}).get("mlp_update_reduction_k1")
    result["learner_hbm_bytes_reduction_prev"] = (
        round(prev_hbm, 3) if prev_hbm else None
    )
    result["learner_hbm_bytes_reduction_delta_pct"] = (
        round(100.0 * (hbm_reduction - prev_hbm) / prev_hbm, 1)
        if hbm_reduction and prev_hbm
        else None
    )
    # Fused-tail regression visibility (ISSUE 13), platform-neutral
    # like the hbm reduction: flagship-LSTM bf16_train xla/pallas
    # full-update bytes vs the committed learner_bench artifact.
    result["learner_opt_tail_bytes_reduction"] = (
        round(opt_tail_reduction, 3) if opt_tail_reduction else None
    )
    prev_tail = accepted.get("opt_tail", {}).get(
        "lstm_update_reduction_bf16"
    )
    result["learner_opt_tail_bytes_reduction_prev"] = (
        round(prev_tail, 3) if prev_tail else None
    )
    result["learner_opt_tail_bytes_reduction_delta_pct"] = (
        round(
            100.0 * (opt_tail_reduction - prev_tail) / prev_tail, 1
        )
        if opt_tail_reduction and prev_tail
        else None
    )
    if not on_accel:
        result["note"] = (
            "BENCH_FORCE_CPU rehearsal: exercises the harness, says "
            "nothing about the accelerator"
        )
    print(json.dumps(result))
    sys.stdout.flush()


def main() -> int:
    force_cpu = os.environ.get("BENCH_FORCE_CPU") == "1"
    if force_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"  # before jax is imported
    import jax

    from torchbeast_tpu.utils.xla_cache import use_compile_cache

    use_compile_cache()
    device = jax.devices()[0]
    if device.platform == "cpu" and not force_cpu:
        sys.stderr.write(
            "bench: JAX found no accelerator (jax.devices()[0] is "
            f"{device.device_kind!r}); nothing measured. Run it where "
            "the chip is, or set BENCH_FORCE_CPU=1 to rehearse the "
            "harness on the CPU.\n"
        )
        return 1
    sys.stderr.write(
        f"bench: running on {device.platform} ({device.device_kind})\n"
    )
    run_bench(device)
    return 0


if __name__ == "__main__":
    sys.exit(main())
