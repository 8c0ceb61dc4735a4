"""End-to-end async-runtime smoke: polybeast trains on Mock env servers over
unix sockets with the real model, inference bucketing, and the learner
thread; checkpoint written; steps advance."""

import numpy as np
import pytest

from torchbeast_tpu import polybeast



def make_flags(tmp_path, **overrides):
    argv = [
        "--env", "Mock",
        "--num_servers", "2",
        "--batch_size", "2",
        "--unroll_length", "5",
        "--total_steps", "60",
        "--savedir", str(tmp_path),
        "--xpid", "poly-smoke",
        "--model", "shallow",
        "--pipes_basename", f"unix:{tmp_path}/pipes",
        "--num_inference_threads", "1",
        "--max_inference_batch_size", "4",
        "--checkpoint_interval_s", "100000",
    ]
    for k, v in overrides.items():
        argv += [f"--{k}"] if v is True else [f"--{k}", str(v)]
    return polybeast.make_parser().parse_args(argv)


def test_polybeast_train_smoke(tmp_path):
    flags = make_flags(tmp_path)
    stats = polybeast.train(flags)
    assert stats["step"] >= 60
    assert np.isfinite(stats["total_loss"])
    assert (tmp_path / "poly-smoke" / "model.ckpt").exists()
    assert (tmp_path / "poly-smoke" / "logs.csv").exists()


@pytest.mark.slow
def test_polybeast_train_lstm(tmp_path):
    flags = make_flags(tmp_path, xpid="poly-lstm", use_lstm=True)
    stats = polybeast.train(flags)
    assert stats["step"] >= 60
    assert np.isfinite(stats["total_loss"])


@pytest.mark.slow
def test_polybeast_train_native_runtime(tmp_path):
    from torchbeast_tpu.runtime.native import available

    if not available():
        import pytest

        pytest.skip("_tbt_core not built")
    flags = make_flags(tmp_path, xpid="poly-native", native_runtime=True,
                       use_lstm=True)
    stats = polybeast.train(flags)
    assert stats["step"] >= 60
    assert np.isfinite(stats["total_loss"])


@pytest.mark.slow
def test_polybeast_replica_serving(tmp_path):
    """Replica serving end to end through the driver (ISSUE 14): the
    learner publishes versioned snapshots, replica threads answer
    acting requests with policy_lag recorded into the rollout, and the
    run trains to completion with requests actually served from the
    replica path."""
    from torchbeast_tpu import telemetry

    reg = telemetry.get_registry()
    before = {
        name: int(reg.counter(name).value())
        for name in (
            "serving.replica_requests",
            "serving.snapshots_published",
        )
    }
    flags = make_flags(
        tmp_path, xpid="poly-replica", use_lstm=True,
        no_native_runtime=True, replica_refresh_updates="2",
        max_policy_lag="50",
    )
    stats = polybeast.train(flags)
    assert stats["step"] >= 60
    assert np.isfinite(stats["total_loss"])
    published = (
        int(reg.counter("serving.snapshots_published").value())
        - before["serving.snapshots_published"]
    )
    replica_served = (
        int(reg.counter("serving.replica_requests").value())
        - before["serving.replica_requests"]
    )
    assert published >= 2  # v0 + at least one refresh
    assert replica_served > 0  # requests really went to the replica
    # The recorded lag histogram saw real observations (0-lag counts).
    assert reg.histogram("serving.policy_lag").count > 0


@pytest.mark.slow
def test_polybeast_test_mode(tmp_path):
    # Train a checkpoint, then greedy-evaluate it via the poly CLI (the
    # reference's poly test() raises NotImplementedError).
    flags = make_flags(tmp_path)
    polybeast.train(flags)
    tflags = make_flags(tmp_path, mode="test", num_test_episodes="1")
    returns = polybeast.main(tflags)
    assert len(returns) == 1
    assert returns[0] == 200.0  # Mock: 200 steps x reward 1.0


@pytest.mark.slow
def test_polybeast_bf16_trunk(tmp_path):
    flags = make_flags(tmp_path, xpid="poly-bf16", model_dtype="bfloat16")
    stats = polybeast.train(flags)
    assert stats["step"] >= 60
    assert np.isfinite(stats["total_loss"])


@pytest.mark.slow
def test_polybeast_train_data_parallel(tmp_path):
    # 4-way DP learner over the virtual CPU mesh inside the async driver.
    flags = make_flags(
        tmp_path, xpid="poly-dp", num_learner_devices="4", batch_size="4",
        num_servers="4",
    )
    stats = polybeast.train(flags)
    assert stats["step"] >= 60
    assert np.isfinite(stats["total_loss"])


@pytest.mark.slow
def test_polybeast_train_native_feedforward(tmp_path):
    # The default (no-LSTM) path carries an EMPTY agent-state nest through
    # the whole C++ pipeline — distinct empty-nest round-trip coverage.
    from torchbeast_tpu.runtime.native import available

    if not available():
        import pytest

        pytest.skip("_tbt_core not built")
    flags = make_flags(tmp_path, xpid="poly-native-ff", native_runtime=True)
    stats = polybeast.train(flags)
    assert stats["step"] >= 60
    assert np.isfinite(stats["total_loss"])


@pytest.mark.slow
def test_poly_transformer_sequence_parallel(tmp_path):
    """The async driver trains the transformer with ring attention over a
    4-way seq mesh (unroll+1 = 8 divisible by 4; the T=1 inference path
    falls back to dense with the same params)."""
    from torchbeast_tpu import polybeast

    flags = polybeast.make_parser().parse_args([
        "--env", "Mock",
        "--xpid", "seqpar",
        "--num_servers", "2",
        "--batch_size", "2",
        "--unroll_length", "7",
        "--total_steps", "56",
        "--model", "transformer",
        "--sequence_parallel", "4",
        "--savedir", str(tmp_path),
        "--pipes_basename", f"unix:{tmp_path}/pipes",
        "--checkpoint_interval_s", "100000",
    ])
    stats = polybeast.train(flags)
    assert stats["step"] >= 56
    assert np.isfinite(stats["total_loss"])


@pytest.mark.slow
def test_prewarm_inference(tmp_path, caplog):
    """--prewarm_inference compiles every bucket before actors connect
    and the run proceeds normally (the log record proves the prewarm
    actually ran — a no-op would still reach total_steps)."""
    import logging

    flags = make_flags(tmp_path, xpid="prewarm", prewarm_inference=True)
    with caplog.at_level(logging.INFO):
        stats = polybeast.train(flags)
    assert stats["step"] >= flags.total_steps
    assert any(
        "Prewarmed 3 inference buckets" in r.message for r in caplog.records
    ), [r.message for r in caplog.records][:20]


@pytest.mark.slow
def test_poly_lstm_solves_memory_env(tmp_path):
    """The async stack's agent-state path (per-actor state through the
    DynamicBatcher + rollout re-pairing) must carry memory end-to-end:
    the Memory probe is unsolvable without it (see MemoryChainEnv and
    benchmarks/artifacts/lstm_learning.md §2b; pilot hit +0.99 by ~19k
    steps, sustained 1.0 to 150k)."""
    flags = make_flags(
        tmp_path, xpid="poly-mem-lstm", env="Memory", model="mlp",
        use_lstm=True, num_servers="8", num_actors="16",
        batch_size="16", unroll_length="20", total_steps="80000",
        learning_rate="1e-3", entropy_cost="0.01",
        max_inference_batch_size="16", env_seed="1",
    )
    stats = polybeast.train(flags)
    assert stats.get("mean_episode_return", -1.0) > 0.6


@pytest.mark.slow
def test_poly_transformer_solves_memory_env(tmp_path):
    """Attention-as-memory through the ASYNC stack: the transformer's
    incremental KV cache rides per-actor through the DynamicBatcher
    into jitted inference and back (the same route the LSTM state takes
    in test_poly_lstm_solves_memory_env), and must deliver the t=0 cue,
    segment-masked, to the query step. Hyperparameters are the
    saturation-safe pair from the mono twin (lr 5e-4, entropy 0.02 —
    see tests/test_monobeast.py::test_transformer_solves_memory_env);
    pilot sustained 1.0 through 150k at ~960 SPS
    (benchmarks/artifacts/lstm_learning.md §4)."""
    flags = make_flags(
        tmp_path, xpid="poly-mem-transformer", env="Memory",
        model="transformer", num_servers="8", num_actors="16",
        batch_size="16", unroll_length="20", total_steps="150000",
        learning_rate="5e-4", entropy_cost="0.02",
        max_inference_batch_size="16", env_seed="1",
        # env_seed pins each stream's cue sequence (assignment order
        # still follows connections, so poly is variance-reduced, not
        # bit-deterministic like the mono twin).
    )
    stats = polybeast.train(flags)
    assert stats.get("mean_episode_return", -1.0) > 0.6


@pytest.mark.slow
def test_server_supervisor_restarts_dead_server(tmp_path):
    """Chaos: SIGKILL one env server mid-train. The supervisor must
    respawn it on the same address, the affected actors must bridge the
    gap through their reconnect budget, and training must reach
    total_steps. The reference's env driver only LOGS a death — a dead
    gRPC server takes its slot down for good."""
    import multiprocessing as mp
    import threading
    import time as time_lib

    flags = make_flags(
        tmp_path, xpid="supervised", env="Mock", model="mlp",
        num_servers="2", num_actors="4", batch_size="4",
        unroll_length="10", total_steps="40000",
        max_actor_reconnects="10",
    )
    before = {p.pid for p in mp.active_children()}
    killed = {}
    train_done = threading.Event()

    def killer():
        deadline = time_lib.monotonic() + 30
        while time_lib.monotonic() < deadline and not killed:
            victims = [
                p for p in mp.active_children() if p.pid not in before
            ]
            if victims:
                time_lib.sleep(3)  # let training get underway first
                if train_done.is_set():
                    return  # too late — a no-op kill must not count
                victim = victims[0]
                victim.kill()
                killed["pid"] = victim.pid
                # Direct evidence of supervision: a NEW child pid
                # (neither pre-existing nor the victim) must appear
                # while training continues — this is the respawn, and
                # observing it here removes the end-of-run race where a
                # kill lands correctly but train finishes before the
                # supervisor's next poll.
                respawn_deadline = time_lib.monotonic() + 30
                while time_lib.monotonic() < respawn_deadline:
                    fresh = [
                        p for p in mp.active_children()
                        if p.pid not in before and p.pid != victim.pid
                        and p.is_alive()
                    ]
                    if len(fresh) >= flags.num_servers:
                        killed["respawned"] = True
                        return
                    time_lib.sleep(0.2)
                return
            time_lib.sleep(0.2)

    t = threading.Thread(target=killer)
    t.start()
    stats = polybeast.train(flags)
    train_done.set()
    t.join()
    assert killed, (
        "killer never landed mid-train (train finished first or no "
        "server appeared); raise total_steps if machines got faster"
    )
    assert killed.get("respawned"), "no respawned server observed"
    assert stats["step"] >= 40000
    assert stats.get("server_restarts", 0) >= 1


def test_failed_validation_reaps_servers(tmp_path):
    """A post-spawn failure (here: a flag-validation raise) must reap
    the just-spawned env-server group — terminate-without-join used to
    strand spawn-context children as orphans (ppid 1) after every
    validation-failure run."""
    import multiprocessing as mp

    before = {p.pid for p in mp.active_children()}
    flags = make_flags(tmp_path, xpid="leak-check", tensor_parallel="2")
    with pytest.raises(ValueError, match="tensor_parallel"):
        polybeast.train(flags)
    # Order-independent: only processes spawned BY this train call count.
    leftovers = [
        p for p in mp.active_children() if p.pid not in before
    ]
    assert not leftovers, [p.pid for p in leftovers]


def test_polybeast_superstep_smoke(tmp_path):
    """--superstep_k 2: the learner drains rollouts through the K-batch
    arena and dispatches scanned supersteps; steps land on whole
    supersteps (K*T*B per dispatch) and the telemetry accounting shows
    K updates per dispatch with host syncs amortized K-fold."""
    import json

    from torchbeast_tpu import telemetry

    flags = make_flags(
        tmp_path, xpid="poly-ss", superstep_k="2", model="mlp",
        use_lstm=True, total_steps="80",
    )
    # The registry is process-global (other tests' driver runs tick the
    # same counters), so diff snapshots around THIS run.
    before = telemetry.snapshot()
    stats = polybeast.train(flags)
    run = telemetry.delta(telemetry.snapshot(), before)
    assert stats["step"] >= 80
    assert stats["step"] % (2 * 5 * 2) == 0  # K * T * batch_size
    assert np.isfinite(stats["total_loss"])
    # K-fold amortization: updates = K * dispatches, host_syncs =
    # dispatches (every dispatch's stats flushed exactly once).
    updates = run["counters"]["learner.updates"]
    syncs = run["counters"]["learner.host_syncs"]
    dispatches = run["histograms"]["learner.updates_per_dispatch"][
        "count"
    ]
    assert dispatches > 0
    assert updates == 2 * dispatches
    assert syncs == dispatches
    # The snapshot file carries the gauge for post-hoc reads.
    lines = [
        json.loads(ln)
        for ln in (tmp_path / "poly-ss" / "telemetry.jsonl")
        .read_text().splitlines()
    ]
    assert lines[-1]["gauges"]["learner.superstep_k"] == 2


def test_polybeast_superstep_native_smoke(tmp_path):
    """--superstep_k 2 on the NATIVE runtime (ISSUE 9: the C++ queue's
    raw-item intake feeds the same host arena): K-vs-1 accounting holds
    — K updates per dispatch, host syncs amortized K-fold, steps landing
    on whole supersteps — and the native telemetry fold emits the wire/
    step series on the same snapshot."""
    import json

    from torchbeast_tpu import telemetry
    from torchbeast_tpu.runtime.native import available

    if not available():
        pytest.skip("_tbt_core not built")
    flags = make_flags(
        tmp_path, xpid="poly-ss-native", superstep_k="2", model="mlp",
        use_lstm=True, total_steps="80", native_runtime=True,
    )
    before = telemetry.snapshot()
    stats = polybeast.train(flags)
    run = telemetry.delta(telemetry.snapshot(), before)
    assert stats["step"] >= 80
    assert stats["step"] % (2 * 5 * 2) == 0  # K * T * batch_size
    assert np.isfinite(stats["total_loss"])
    updates = run["counters"]["learner.updates"]
    syncs = run["counters"]["learner.host_syncs"]
    dispatches = run["histograms"]["learner.updates_per_dispatch"]["count"]
    assert dispatches > 0
    assert updates == 2 * dispatches
    assert syncs == dispatches
    lines = [
        json.loads(ln)
        for ln in (tmp_path / "poly-ss-native" / "telemetry.jsonl")
        .read_text().splitlines()
    ]
    last = lines[-1]
    assert last["gauges"]["learner.superstep_k"] == 2
    # The native fold's series (C++ pool/batcher/queue stamps).
    assert run["counters"]["wire.bytes_up"] > 0
    assert run["counters"]["actor.env_steps"] > 0
    assert run["histograms"]["actor.request_rtt_s"]["count"] > 0
    assert run["histograms"]["inference.request_wait_s"]["count"] > 0


def test_polybeast_chaos_native_accepted(tmp_path):
    """--chaos_plan with --native_runtime is SUPPORTED since ISSUE 12:
    the controller drives the C++ pool's FaultHooks instead of the
    Python transport wrap (the capability gate this test used to pin is
    gone). An armed-but-empty plan must run to completion and carry the
    chaos summary in the final stats."""
    from torchbeast_tpu.runtime.native import available

    if not available():
        pytest.skip("_tbt_core not built")
    plan_path = tmp_path / "empty_plan.json"
    plan_path.write_text('{"seed": 1, "faults": []}')
    flags = make_flags(
        tmp_path, xpid="poly-chaos-native", native_runtime=True,
        chaos_plan=str(plan_path),
    )
    stats = polybeast.train(flags)
    assert stats["step"] >= 60
    assert stats["chaos"] == {
        "seed": 1, "injected": {}, "abandoned": [], "pending": [],
    }
