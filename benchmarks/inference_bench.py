"""Actor-side inference hot path under load: DynamicBatcher ->
bucket-padded jitted act, driven by many concurrent fake actors.

Measures what an env-server actor actually experiences: the latency of
`batcher.compute()` (enqueue -> batched forward -> row slice back), p50
and p99, plus aggregate steps/s — for each combination of
{python, native} batcher x {global inference lock, no lock}.

Purpose: decide whether the reference-style global inference lock
(reference polybeast_learner.py:269, 281-283) costs throughput on this
runtime, where act_fn is a pure jitted function and params access is
internally synchronized — the lock's only remaining effect is
serializing host-side pad/dispatch/device-sync work across inference
threads.

A second section ("acting_path") benchmarks the collector-side acting
schedules on the LSTM model at B=32: the pre-PR synchronous path (block
on host materialization of the full AgentOutput AND the recurrent state
every env step — the legacy request/reply framing's semantics) against
the lag-1 pipelined path (state device-resident, action-only per-step
fetch, everything else retrieved one tick behind). Reports acting
steps/sec for each, the speedup, and the per-step host<->device byte
traffic both ways; the result is recorded in
benchmarks/artifacts/acting_path_bench.json either way.

Run:  python benchmarks/inference_bench.py [--actors 32] [--seconds 5]
      [--skip_hot_path] [--skip_acting]
Emits one JSON line per configuration.
"""

import argparse
import json
import os
import sys
import threading
import time

# Runnable as `python benchmarks/inference_bench.py` (same repo-root
# insert as the sibling benches; otherwise torchbeast_tpu only resolves
# when the caller exports PYTHONPATH).
sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

_ARTIFACT = os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    "artifacts",
    "acting_path_bench.json",
)


def _nest_bytes(tree) -> int:
    import numpy as np

    import jax

    return sum(
        int(np.asarray(leaf).nbytes)
        for leaf in jax.tree_util.tree_leaves(tree)
    )


def acting_path_bench(args):
    """Sync vs lag-1 acting throughput through the REAL collectors
    (rollout.py) over a Mock env pool — the monobeast acting hot path,
    minus the learner."""
    import jax
    import numpy as np

    from torchbeast_tpu import learner as learner_lib
    from torchbeast_tpu.envs.mock import MockEnv
    from torchbeast_tpu.envs.vec import ProcessEnvPool, SerialEnvPool
    from torchbeast_tpu.models import create_model
    from torchbeast_tpu.rollout import (
        PipelinedRolloutCollector,
        RolloutCollector,
    )

    B, T, A = args.acting_batch, args.acting_unroll, 6
    model = create_model(args.model, num_actions=A, use_lstm=True)
    dummy = {
        "frame": np.zeros((1, 1, 84, 84, 4), np.uint8),
        "reward": np.zeros((1, 1), np.float32),
        "done": np.zeros((1, 1), bool),
        "last_action": np.zeros((1, 1), np.int32),
    }
    params = model.init(
        {"params": jax.random.PRNGKey(0), "action": jax.random.PRNGKey(1)},
        dummy,
        model.initial_state(1),
    )
    act_step = learner_lib.make_act_step(model)
    rng_cell = [jax.random.PRNGKey(0)]

    def forward(env_output, agent_state):
        rng_cell[0], key = jax.random.split(rng_cell[0])
        inputs = {
            k: env_output[k]
            for k in ("frame", "reward", "done", "last_action")
        }
        return act_step(params, key, inputs, agent_state)

    def host_policy(env_output, agent_state):
        # Pre-PR synchronous framing: the full AgentOutput AND the
        # recurrent state materialize to host every step, and numpy
        # state re-enters the device next step.
        out, new_state = forward(env_output, agent_state)
        return (
            jax.device_get(out),
            jax.tree_util.tree_map(np.asarray, new_state),
        )

    def device_policy(env_output, agent_state):
        # Device-resident: state flows device -> device; the lag-1
        # collector fetches the action (and, one tick behind, the rest).
        return forward(env_output, agent_state)

    # ProcessEnvPool (monobeast's default) gives the lag-1 schedule a
    # real overlap window: workers step envs while the host materializes
    # the previous tick. SerialEnvPool isolates the pure framing cost.
    pool_cls = (
        ProcessEnvPool if args.acting_pool == "process" else SerialEnvPool
    )

    def make_pool():
        # functools.partial, not a lambda: ProcessEnvPool pickles env_fns
        # into its workers.
        import functools

        return pool_cls(
            [functools.partial(MockEnv, num_actions=A) for _ in range(B)]
        )

    from torchbeast_tpu import telemetry

    snap_before = telemetry.snapshot()
    reg = telemetry.get_registry()

    def measure(collector, pool, label):
        h_collect = reg.histogram(f"acting.{label}.collect_s")
        try:
            for _ in range(args.acting_warmup):
                collector.collect()  # compile + steady-state the pipeline
            t0 = time.perf_counter()
            for _ in range(args.acting_collects):
                tc = time.perf_counter()
                collector.collect()
                h_collect.observe(time.perf_counter() - tc)
            return (
                T * B * args.acting_collects / (time.perf_counter() - t0)
            )
        finally:
            pool.close()

    pool = make_pool()
    sync_sps = measure(
        RolloutCollector(pool, host_policy, model.initial_state(B), T),
        pool,
        "sync",
    )
    pool = make_pool()
    lag1_sps = measure(
        PipelinedRolloutCollector(
            pool,
            device_policy,
            jax.device_put(model.initial_state(B)),
            T,
        ),
        pool,
        "pipelined",
    )

    # Per-env-step host<->device traffic (whole batch, both directions).
    env_up = _nest_bytes(
        {
            "frame": np.zeros((B, 84, 84, 4), np.uint8),
            "reward": np.zeros(B, np.float32),
            "done": np.zeros(B, bool),
            "last_action": np.zeros(B, np.int32),
        }
    )
    out_down = _nest_bytes(
        {
            "action": np.zeros(B, np.int32),
            "policy_logits": np.zeros((B, A), np.float32),
            "baseline": np.zeros(B, np.float32),
        }
    )
    state_bytes = _nest_bytes(model.initial_state(B))
    result = {
        "bench": "acting_path",
        "model": args.model,
        "use_lstm": True,
        "batch": B,
        "unroll": T,
        "pool": args.acting_pool,
        "sync_steps_per_sec": round(sync_sps, 1),
        "pipelined_steps_per_sec": round(lag1_sps, 1),
        "speedup": round(lag1_sps / sync_sps, 3),
        "bytes_per_step": {
            "sync_up": env_up + state_bytes,
            "sync_down": out_down + state_bytes,
            "pipelined_up": env_up,
            "pipelined_down": out_down,
            "agent_state": state_bytes,
        },
        "platform": jax.devices()[0].platform,
        "measured_at": time.strftime("%Y-%m-%d %H:%M:%S"),
        # Interval telemetry for THIS section (per-collect latency
        # distributions under acting.{sync,pipelined}.collect_s) — run
        # variance is attributable from the artifact alone.
        "telemetry": telemetry.telemetry_block(prev=snap_before),
    }
    print(json.dumps(result), flush=True)
    try:
        os.makedirs(os.path.dirname(_ARTIFACT), exist_ok=True)
        with open(_ARTIFACT, "w") as f:
            json.dump(result, f, indent=2)
            f.write("\n")
    except OSError as e:
        sys.stderr.write(f"could not write acting-path artifact: {e}\n")
    return result


def main():
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--actors", type=int, default=32)
    parser.add_argument("--seconds", type=float, default=5.0)
    parser.add_argument("--num_inference_threads", type=int, default=1)
    parser.add_argument("--max_batch_size", type=int, default=64)
    parser.add_argument("--model", default="shallow")
    parser.add_argument("--skip_hot_path", action="store_true",
                        help="Skip the DynamicBatcher hot-path section.")
    parser.add_argument("--skip_acting", action="store_true",
                        help="Skip the collector acting-path section.")
    parser.add_argument("--acting_batch", type=int, default=32)
    parser.add_argument("--acting_unroll", type=int, default=20)
    parser.add_argument("--acting_collects", type=int, default=8)
    parser.add_argument("--acting_warmup", type=int, default=2)
    parser.add_argument("--acting_pool", choices=("process", "serial"),
                        default="process",
                        help="Env pool for the acting section: process "
                             "(monobeast default; real overlap window) "
                             "or serial (pure framing-cost isolation).")
    parser.add_argument("--no_telemetry", action="store_true",
                        help="Disable instrumentation (the acceptance "
                             "overhead measurement runs the bench with "
                             "and without and compares SPS).")
    args = parser.parse_args()

    if os.environ.get("JAX_PLATFORMS"):
        import jax

        jax.config.update("jax_platforms", os.environ["JAX_PLATFORMS"])
    import jax
    import numpy as np

    from torchbeast_tpu import telemetry

    telemetry.set_enabled(not args.no_telemetry)

    from torchbeast_tpu import learner as learner_lib
    from torchbeast_tpu.models import create_model
    from torchbeast_tpu.runtime.inference import inference_loop
    from torchbeast_tpu.runtime.native import import_native
    import torchbeast_tpu.runtime as py_runtime

    A = 6
    model = create_model(args.model, num_actions=A, use_lstm=False)
    frame = np.zeros((1, 1, 84, 84, 4), np.uint8)
    dummy = {
        "frame": frame,
        "reward": np.zeros((1, 1), np.float32),
        "done": np.zeros((1, 1), bool),
        "last_action": np.zeros((1, 1), np.int32),
    }
    state0 = model.initial_state(1)
    params = model.init(
        {"params": jax.random.PRNGKey(0), "action": jax.random.PRNGKey(1)},
        dummy,
        state0,
    )
    act_step = learner_lib.make_act_step(model)

    rng_cell = [jax.random.PRNGKey(0)]
    rng_lock = threading.Lock()

    def act_fn(env_outputs, agent_state, batch_size):
        with rng_lock:
            rng_cell[0], key = jax.random.split(rng_cell[0])
        model_inputs = {
            k: env_outputs[k][0]
            for k in ("frame", "reward", "done", "last_action")
        }
        out, new_state = act_step(params, key, model_inputs, agent_state)
        return (
            {
                "action": np.asarray(out.action)[None],
                "policy_logits": np.asarray(out.policy_logits)[None],
                "baseline": np.asarray(out.baseline)[None],
            },
            new_state,
        )

    def run_config(runtime_name, queue_mod, with_lock):
        # telemetry_name is Python-runtime-only (the C++ batcher doesn't
        # take the kwarg; its batch sizes come from inference_loop's own
        # instruments).
        batcher_tm = (
            {"telemetry_name": "inference"}
            if runtime_name == "python" else {}
        )
        batcher = queue_mod.DynamicBatcher(
            batch_dim=1,
            minimum_batch_size=1,
            maximum_batch_size=args.max_batch_size,
            timeout_ms=20,
            **batcher_tm,
        )
        lock = threading.Lock() if with_lock else None
        servers = [
            threading.Thread(
                target=inference_loop,
                args=(batcher, act_fn, args.max_batch_size),
                kwargs={"lock": lock},
                daemon=True,
            )
            for _ in range(args.num_inference_threads)
        ]
        for t in servers:
            t.start()

        latencies = []
        lat_lock = threading.Lock()
        stop = threading.Event()

        def actor(idx):
            rng = np.random.default_rng(idx)
            env = {
                "frame": rng.integers(
                    0, 256, (1, 1, 84, 84, 4), dtype=np.uint8
                ),
                "reward": np.zeros((1, 1), np.float32),
                "done": np.zeros((1, 1), bool),
                "last_action": np.zeros((1, 1), np.int32),
            }
            state = model.initial_state(1)
            mine = []
            while not stop.is_set():
                t0 = time.perf_counter()
                result = batcher.compute({"env": env, "agent_state": state})
                mine.append(time.perf_counter() - t0)
                state = result["agent_state"]
            with lat_lock:
                latencies.extend(mine)

        actors = [
            threading.Thread(target=actor, args=(i,), daemon=True)
            for i in range(args.actors)
        ]
        warm_deadline = time.time() + 2.0  # compile the buckets first
        for t in actors:
            t.start()
        while time.time() < warm_deadline:
            time.sleep(0.1)
        with lat_lock:
            latencies.clear()  # drop compile-tainted samples
        # Snapshot AFTER warmup so the embedded telemetry delta covers
        # the same steady-state window as the latency numbers.
        snap_before = telemetry.snapshot()
        time.sleep(args.seconds)
        stop.set()
        for t in actors:
            t.join(timeout=10)
        try:
            batcher.close()
        except RuntimeError:
            pass
        for t in servers:
            t.join(timeout=10)

        lat = np.sort(np.asarray(latencies))
        # Legacy request/reply framing: agent state rides both ways on
        # every step (zero for this stateless model — the acting_path
        # section below measures the recurrent case).
        state_bytes = _nest_bytes(model.initial_state(1))
        req_bytes = _nest_bytes(dummy) + state_bytes
        result = {
            "bench": "inference_hot_path",
            "runtime": runtime_name,
            "lock": with_lock,
            "actors": args.actors,
            "inference_threads": args.num_inference_threads,
            "steps_per_sec": round(len(lat) / args.seconds, 1),
            "p50_ms": round(1000 * float(lat[len(lat) // 2]), 2),
            "p99_ms": round(1000 * float(lat[int(len(lat) * 0.99)]), 2),
            "bytes_per_step_up": req_bytes,
            "bytes_per_step_down": 4 + 4 * A + 4 + state_bytes,
            "platform": jax.devices()[0].platform,
            # Interval telemetry for THIS configuration (batch-size
            # distribution, queue/dispatch/reply latency p50/p95/p99).
            "telemetry": telemetry.telemetry_block(prev=snap_before),
        }
        print(json.dumps(result), flush=True)
        return result

    results = []
    if not args.skip_hot_path:
        configs = [("python", py_runtime)]
        native = import_native()
        if native is not None:
            configs.append(("native", native))
        else:
            sys.stderr.write("native runtime not built; python only\n")

        for runtime_name, queue_mod in configs:
            for with_lock in (True, False):
                results.append(
                    run_config(runtime_name, queue_mod, with_lock)
                )
    if not args.skip_acting:
        results.append(acting_path_bench(args))
    return results


if __name__ == "__main__":
    main()
