"""End-to-end smoke: the sync trainer runs on the Mock env, steps advance,
checkpoint round-trips, logs written, test mode evaluates."""

import os

import numpy as np
import pytest

from torchbeast_tpu import monobeast


def make_flags(tmp_path, **overrides):
    argv = [
        "--env", "Mock",
        "--num_actors", "2",
        "--batch_size", "2",
        "--unroll_length", "5",
        "--total_steps", "40",
        "--savedir", str(tmp_path),
        "--xpid", "smoke",
        "--serial_envs",
        "--checkpoint_interval_s", "100000",
    ]
    for k, v in overrides.items():
        argv += [f"--{k}"] if v is True else [f"--{k}", str(v)]
    return monobeast.make_parser().parse_args(argv)


def test_train_smoke_and_resume(tmp_path):
    flags = make_flags(tmp_path)
    stats = monobeast.train(flags)
    assert stats["step"] >= 40

    xpdir = tmp_path / "smoke"
    assert (xpdir / "model.ckpt").exists()
    assert (xpdir / "logs.csv").exists()
    assert (xpdir / "meta.json").exists()

    # Resume: starts from the saved step counter and continues further.
    flags2 = make_flags(tmp_path, total_steps=80)
    stats2 = monobeast.train(flags2)
    assert stats2["step"] >= 80


def test_train_with_lstm(tmp_path):
    flags = make_flags(tmp_path, xpid="smoke-lstm", use_lstm=True)
    stats = monobeast.train(flags)
    assert stats["step"] >= 40
    assert np.isfinite(stats["total_loss"])


def test_test_mode(tmp_path):
    flags = make_flags(tmp_path)
    monobeast.train(flags)
    tflags = make_flags(tmp_path, mode="test", num_test_episodes="2")
    # Mock episodes are 200 steps of reward 1.0.
    returns = monobeast.test(tflags)
    assert len(returns) == 2
    assert all(r == 200.0 for r in returns)


def test_bf16_train_learns_catch(tmp_path):
    """--precision bf16_train LEARNING smoke, tier-1 by design (ISSUE
    8): bf16-resident params + bf16 staged batch + bf16 second moment
    must still solve Catch (return 1.0 measured in the calibration run;
    gated at 0.5 — well above the ~-0.3 chance floor — to absorb
    CPU-container seed noise). The f32 twin of this config is the slow
    test_mono_learns_catch; this is the one end-to-end proof that the
    precision policy changes bytes, not the algorithm."""
    flags = monobeast.make_parser().parse_args([
        "--env", "Catch",
        "--model", "mlp",
        "--num_actors", "16",
        "--batch_size", "16",
        "--unroll_length", "9",
        "--total_steps", "60000",
        "--serial_envs",
        "--learning_rate", "2e-3",
        "--entropy_cost", "0.01",
        "--savedir", str(tmp_path),
        "--xpid", "catch-bf16",
        "--checkpoint_interval_s", "100000",
        "--precision", "bf16_train",
    ])
    stats = monobeast.train(flags)
    assert stats.get("mean_episode_return", -1.0) > 0.5


@pytest.mark.slow
def test_mono_learns_catch(tmp_path):
    """End-to-end learning check on a real task: the sync driver must
    learn Catch well above chance (~-0.3) within a small frame budget."""
    flags = monobeast.make_parser().parse_args([
        "--env", "Catch",
        "--model", "mlp",
        "--num_actors", "16",
        "--batch_size", "16",
        "--unroll_length", "9",
        "--total_steps", "80000",
        "--serial_envs",
        "--learning_rate", "2e-3",
        "--entropy_cost", "0.01",
        "--savedir", str(tmp_path),
        "--xpid", "catch-learn",
        "--checkpoint_interval_s", "100000",
    ])
    stats = monobeast.train(flags)
    assert stats.get("mean_episode_return", -1.0) > 0.5


@pytest.mark.slow
def test_mono_learns_catch_with_lstm(tmp_path):
    """BASELINE config 3's shape (--use_lstm): the recurrent core must
    LEARN, not just run — state carry/reset through the unroll is the
    trickiest on-policy machinery (reference monobeast.py:599-611,
    core_agent_state_test.py). Pilot run solved Catch (return 1.0) by
    ~38k steps with these hyperparameters
    (benchmarks/artifacts/lstm_learning.md)."""
    flags = monobeast.make_parser().parse_args([
        "--env", "Catch",
        "--model", "mlp",
        "--use_lstm",
        "--num_actors", "16",
        "--batch_size", "16",
        "--unroll_length", "20",
        "--total_steps", "60000",
        "--serial_envs",
        "--learning_rate", "2e-3",
        "--entropy_cost", "0.01",
        "--savedir", str(tmp_path),
        "--xpid", "catch-lstm",
        "--checkpoint_interval_s", "100000",
    ])
    stats = monobeast.train(flags)
    assert stats.get("mean_episode_return", -1.0) > 0.5


@pytest.mark.slow
def test_lstm_solves_memory_env(tmp_path):
    """The FF-vs-LSTM differential on the Memory probe (MemoryChainEnv):
    nothing observable at the decision step correlates with the cue and
    the forward-penalty breaks the last-action relay, so feed-forward
    caps at ~0 while a working recurrent core reaches +1. Pilot curves:
    LSTM sustained 1.0 from ~37k steps; FF oscillated in [-0.35, +0.3]
    for 150k (benchmarks/artifacts/lstm_learning.md)."""

    def run(use_lstm, xpid):
        argv = [
            "--env", "Memory",
            "--model", "mlp",
            "--num_actors", "16",
            "--batch_size", "16",
            "--unroll_length", "20",
            "--total_steps", "80000",
            "--serial_envs",
            "--learning_rate", "1e-3",
            "--entropy_cost", "0.01",
            # Pinned cue stream (verified good for BOTH arms): with
            # serial envs + the fixed model seed the whole run is
            # deterministic, so this test cannot flake.
            "--env_seed", "1",
            "--savedir", str(tmp_path),
            "--xpid", xpid,
            "--checkpoint_interval_s", "100000",
        ] + (["--use_lstm"] if use_lstm else [])
        return monobeast.train(monobeast.make_parser().parse_args(argv))

    lstm_stats = run(True, "mem-lstm")
    assert lstm_stats.get("mean_episode_return", -1.0) > 0.6
    ff_stats = run(False, "mem-ff")
    assert ff_stats.get("mean_episode_return", 1.0) < 0.5


@pytest.mark.slow
def test_transformer_solves_memory_env(tmp_path):
    """Attention-as-memory: the transformer policy (no LSTM) solves the
    Memory probe because its segment-masked attention over the KV cache
    retrieves the cue frame at the query step — the same differential
    the LSTM test pins, carried by the OTHER memory mechanism. This
    functionally exercises the acting-path cache (the cue enters the
    cache at t=0 and must survive, segment-masked, to t=length-1) and
    the learner's full-attention replay.

    Hyperparameters matter here: at lr 1e-3 roughly 1 run in 3 locks
    into a query-compliance collapse — the corridor penalty's
    "always forward" habit generalizes to the query frame, the
    deterministic −1 there is predicted exactly by the value head, and
    the zeroed advantage freezes the policy (checkpoint rollouts show
    query_action=2 every episode; lstm_learning.md §4 has the
    corrected analysis). lr 5e-4 + entropy 0.02 escaped in 8/8 pilot
    reps by 150k steps; --env_seed 1 (verified passing) + serial envs
    + the fixed model seed make this run deterministic, so the
    residual trap odds cannot flake the test."""
    flags = monobeast.make_parser().parse_args([
        "--env", "Memory",
        "--model", "transformer",
        "--num_actors", "16",
        "--batch_size", "16",
        "--unroll_length", "20",
        "--total_steps", "150000",
        "--serial_envs",
        "--learning_rate", "5e-4",
        "--entropy_cost", "0.02",
        "--env_seed", "1",
        "--savedir", str(tmp_path),
        "--xpid", "mem-transformer",
        "--checkpoint_interval_s", "100000",
    ])
    stats = monobeast.train(flags)
    assert stats.get("mean_episode_return", -1.0) > 0.6


@pytest.mark.slow
@pytest.mark.parametrize("sp_strategy", ["ring", "ulysses"])
def test_sequence_parallel_solves_memory_env(tmp_path, sp_strategy):
    """Memory under sequence-parallel attention on a 4-way `seq` mesh:
    the learner shards the 19-step unroll over time, so cue-to-query
    attention routinely crosses shard boundaries — through the ppermute
    ring, or through ulysses' head-sharding all-to-alls — a LEARNING
    proof for the sequence-parallel path, beyond its existing
    gradient-parity pins. Pilot: 1.0 by <48k steps for both."""
    flags = monobeast.make_parser().parse_args([
        "--env", "Memory",
        "--model", "transformer",
        "--sequence_parallel", "4",
        "--sp_strategy", sp_strategy,
        "--num_actors", "16",
        "--batch_size", "16",
        "--unroll_length", "19",  # T+1 = 20 divisible by the seq axis
        "--total_steps", "60000",
        "--serial_envs",
        "--learning_rate", "5e-4",
        "--entropy_cost", "0.02",
        "--env_seed", "1",
        "--savedir", str(tmp_path),
        "--xpid", f"mem-sp-{sp_strategy}",
        "--checkpoint_interval_s", "100000",
    ])
    stats = monobeast.train(flags)
    assert stats.get("mean_episode_return", -1.0) > 0.6


@pytest.mark.slow
def test_entropy_anneal_cracks_long_corridor(tmp_path):
    """--entropy_cost_final turns the L41 Memory corridor from
    unsolvable (0/6 constant-entropy configs, lstm_learning.md §4b)
    into solved (3/3 pilot seeds, first crossing ~479k steps): early
    high entropy keeps answer actions sampled at the query until the
    +2 advantage takes hold, and the anneal removes the tax before
    convergence. Deterministic via env_seed + serial envs."""
    flags = monobeast.make_parser().parse_args([
        "--env", "Memory-L41",
        "--model", "transformer",
        "--num_actors", "16",
        "--batch_size", "16",
        "--unroll_length", "47",
        "--total_steps", "1000000",
        "--serial_envs",
        "--learning_rate", "5e-4",
        "--entropy_cost", "0.2",
        "--entropy_cost_final", "0.01",
        "--env_seed", "1",
        "--savedir", str(tmp_path),
        "--xpid", "anneal41",
        "--checkpoint_interval_s", "100000",
    ])
    stats = monobeast.train(flags)
    assert stats.get("mean_episode_return", -1.0) > 0.6


@pytest.mark.slow
def test_env_seed_makes_runs_reproducible(tmp_path):
    """--env_seed + --serial_envs + fixed --seed = bit-reproducible
    training: the only OS entropy in the sync driver is the env draw
    stream, which env_seed pins (env i draws from env_seed+i, keeping
    actors decorrelated). Compare full return curves, not just the
    final value; a third run with a different env_seed must diverge
    (else the flag is silently ignored)."""
    import csv

    def returns(xpid, env_seed):
        flags = make_flags(
            tmp_path, xpid=xpid, env="Catch", model="mlp",
            num_actors="4", batch_size="4", unroll_length="10",
            total_steps="4000", learning_rate="2e-3",
            entropy_cost="0.01", env_seed=str(env_seed),
        )
        monobeast.train(flags)
        with open(tmp_path / xpid / "logs.csv") as f:
            return [
                row["mean_episode_return"] for row in csv.DictReader(f)
            ]

    a = returns("det-a", 7)
    b = returns("det-b", 7)
    c = returns("det-c", 8)
    assert a == b
    assert len(a) > 3
    assert a != c


def test_trunk_channels_validation(tmp_path):
    with pytest.raises(ValueError, match="deep only"):
        monobeast.train(
            make_flags(tmp_path, trunk_channels="32,64,64")
        )  # default model is shallow
    with pytest.raises(ValueError, match="three positive"):
        monobeast.train(
            make_flags(tmp_path, model="deep", trunk_channels="32,64")
        )


def test_unaligned_actors_rejected(tmp_path):
    flags = make_flags(tmp_path, num_actors="3")
    try:
        monobeast.train(flags)
        raised = False
    except ValueError:
        raised = True
    assert raised


@pytest.mark.slow
def test_train_transformer_sequence_parallel(tmp_path):
    """The transformer trains with its unroll attention running as ring
    attention over a 4-way `seq` mesh (T+1 = 8 divisible by 4; acting at
    T=1 falls back to dense with the same params)."""
    flags = make_flags(
        tmp_path,
        xpid="smoke-seqpar",
        model="transformer",
        sequence_parallel=4,
        unroll_length=7,
        env="Catch",
        total_steps=56,
    )
    stats = monobeast.train(flags)
    assert stats["step"] >= 56
    assert np.isfinite(stats["total_loss"])


@pytest.mark.slow
def test_train_transformer_zigzag_sequence_parallel(tmp_path):
    """Sequence-parallel training with the zig-zag ring schedule
    (T+1 = 16 divisible by 2N = 8 on a 4-way seq mesh)."""
    flags = make_flags(
        tmp_path,
        xpid="smoke-zigzag",
        model="transformer",
        sequence_parallel=4,
        ring_schedule="zigzag",
        unroll_length=15,
        env="Catch",
        total_steps=64,
    )
    stats = monobeast.train(flags)
    assert stats["step"] >= 64
    assert np.isfinite(stats["total_loss"])


def test_train_overlap_collect(tmp_path):
    """--overlap_collect (policy lag 1): trains, checkpoints, resumes."""
    flags = make_flags(tmp_path, xpid="smoke-ovl", overlap_collect=True)
    stats = monobeast.train(flags)
    assert stats["step"] >= 40
    assert np.isfinite(stats["total_loss"])
    flags2 = make_flags(
        tmp_path, xpid="smoke-ovl", overlap_collect=True, total_steps=80
    )
    stats2 = monobeast.train(flags2)
    assert stats2["step"] >= 80


@pytest.mark.slow
def test_overlap_collect_learns_catch(tmp_path):
    """Lag-1 acting must not break learning: Catch is solved (or close)
    within the same budget the zero-lag test uses."""
    flags = make_flags(
        tmp_path, xpid="ovl-catch", overlap_collect=True, env="Catch",
        model="mlp", num_actors="16", batch_size="8", unroll_length="20",
        total_steps="60000", learning_rate="2e-3", entropy_cost="0.01",
    )
    stats = monobeast.train(flags)
    assert stats["mean_episode_return"] > 0.8


@pytest.mark.slow
def test_train_sp_x_ep_composite_flags(tmp_path):
    """--sequence_parallel + --expert_parallel through the real flag
    path: one composite (data=1, model=1, seq, expert) mesh shared by
    the attention shard_maps and the MoE constraints (a regression here
    is an XLA 'incompatible devices' compile error)."""
    flags = make_flags(
        tmp_path, xpid="spep", model="transformer",
        sequence_parallel="2", num_experts="4", expert_parallel="2",
        unroll_length="7", total_steps="28",
    )
    stats = monobeast.train(flags)
    assert stats["step"] >= 28
    assert np.isfinite(stats["total_loss"])
    assert stats["aux_loss"] > 0.0


@pytest.mark.slow
def test_train_mono_data_parallel(tmp_path):
    """--num_learner_devices: sync trainer DP over 4 virtual devices,
    incl. checkpoint/resume and composition with --overlap_collect."""
    flags = make_flags(
        tmp_path, xpid="mono-dp", num_learner_devices="4", batch_size="4",
        num_actors="4",
    )
    stats = monobeast.train(flags)
    assert stats["step"] >= 40
    assert np.isfinite(stats["total_loss"])
    flags2 = make_flags(
        tmp_path, xpid="mono-dp", num_learner_devices="4", batch_size="4",
        num_actors="4", total_steps=80, overlap_collect=True,
    )
    stats2 = monobeast.train(flags2)
    assert stats2["step"] >= 80
    # Pin the RESUME (not a silent restart): the appended log's step
    # column must increase monotonically across both runs — a restart
    # would drop back below run 1's final step.
    import csv

    with open(tmp_path / "mono-dp" / "logs.csv") as f:
        steps = [int(r["step"]) for r in csv.DictReader(f)]
    assert steps == sorted(steps) and steps[-1] >= 80, steps


def test_mono_dp_rejects_bad_combos(tmp_path):
    flags = make_flags(
        tmp_path, xpid="mono-dp-bad", num_learner_devices="3",
    )
    with pytest.raises(ValueError, match="not divisible"):
        monobeast.train(flags)
    flags = make_flags(
        tmp_path, xpid="mono-dp-bad2", num_learner_devices="2",
        model="transformer", sequence_parallel="2", unroll_length="7",
    )
    with pytest.raises(ValueError, match="composite meshes"):
        monobeast.train(flags)


def test_superstep_train_bit_identical_to_sequential(tmp_path):
    """--superstep_k 2 must train BIT-identically to --superstep_k 1 on
    the same seeds: the K-scan applies the same updates in the same
    order (schedules tick per-update), acting only sees params between
    collects, and the Mock env + fixed seeds make the whole run
    deterministic. Compared via the serialized checkpoint params/opt
    bytes — any numeric drift anywhere in the superstep path fails.

    MLP+LSTM model: the conv families are NOT bit-stable under a scan
    (XLA fuses the conv differently inside the scan body, ~1e-8 ulp
    drift — same training distribution, different bits), which is why
    the bit-identity contract is pinned on the MLP families."""
    import flax.serialization

    def run(xpid, k):
        flags = make_flags(
            tmp_path, xpid=xpid, superstep_k=str(k),
            num_actors="4", batch_size="2", total_steps="80",
            model="mlp", use_lstm=True,
        )
        stats = monobeast.train(flags)
        with open(tmp_path / xpid / "model.ckpt", "rb") as f:
            payload = flax.serialization.msgpack_restore(f.read())
        return stats, payload

    stats1, ck1 = run("ss-k1", 1)
    stats2, ck2 = run("ss-k2", 2)
    assert ck1["step"] == ck2["step"]
    assert ck1["params"] == ck2["params"]
    assert ck1["opt_state"] == ck2["opt_state"]
    assert stats1["total_loss"] == stats2["total_loss"]


def test_superstep_step_accounting(tmp_path):
    """A K=2 dispatch consumes K*T*batch_size frames: the reported step
    counter must land on a whole number of supersteps, not undercount
    by /K."""
    flags = make_flags(
        tmp_path, xpid="ss-acct", superstep_k="2",
        num_actors="4", batch_size="2", total_steps="40",
    )
    stats = monobeast.train(flags)
    assert stats["step"] >= 40
    assert stats["step"] % (2 * 5 * 2) == 0  # K * T * batch_size


def test_superstep_divisibility_rejected(tmp_path):
    """K must divide the sub-batches per collect (a fixed-K scan cannot
    take a partial group, and spilling across collects would change
    policy lag)."""
    flags = make_flags(
        tmp_path, xpid="ss-bad", superstep_k="3",
        num_actors="4", batch_size="2",
    )
    with pytest.raises(ValueError, match="superstep_k"):
        monobeast.train(flags)
