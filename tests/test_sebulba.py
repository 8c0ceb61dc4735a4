"""Sebulba device split (ISSUE 15): placement parsing/hashing, per-slice
table pinning under jax.transfer_guard, static hash-by-connection
routing stability, DP-sharded superstep accounting on a 2-device learner
mesh, device-to-device snapshot publication parity, and the async driver
end to end with `--device_split`.

Multi-device cases run on the conftest's 8 forced host CPU devices and
SKIP visibly (tests/jax_caps.has_multi_device_cpu) where the
`--xla_force_host_platform_device_count` flag is unsupported.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tests import jax_caps
from tests import family_scaffold as scaffold
from torchbeast_tpu.runtime.placement import (
    DeviceSplit,
    parse_device_split,
    resolve_device_split,
)

multi_device = pytest.mark.skipif(
    not jax_caps.has_multi_device_cpu(2),
    reason="needs >= 2 jax devices "
           "(xla_force_host_platform_device_count unsupported here)",
)


class _FakeDevice:
    def __init__(self, i):
        self.id = i

    def __repr__(self):
        return f"dev{self.id}"


def _fake_devices(n):
    return [_FakeDevice(i) for i in range(n)]


class TestDeviceSplitSpec:
    def test_parse_grammar(self):
        assert parse_device_split(None) is None
        assert parse_device_split("") is None
        assert parse_device_split("  ") is None
        assert parse_device_split("auto") == {"inf": "auto",
                                              "learn": "rest"}
        assert parse_device_split("inf=2,learn=rest") == {
            "inf": 2, "learn": "rest"
        }
        assert parse_device_split("inf=1,learn=3") == {
            "inf": 1, "learn": 3
        }
        assert parse_device_split("inf=3") == {"inf": 3, "learn": "rest"}

    @pytest.mark.parametrize("bad", [
        "garbage", "inf=x", "inf=0", "learn=2", "inf=1,learn=0",
        "inf=1,learn=q", "inf=1,inf=2", "inf=1,weird=2",
    ])
    def test_parse_rejects(self, bad):
        with pytest.raises(ValueError):
            parse_device_split(bad)

    def test_resolve_auto_fraction(self):
        split = resolve_device_split("auto", _fake_devices(8))
        assert split.n_slices == 2  # 8 // 4
        assert len(split.learner_devices) == 6
        split = resolve_device_split("auto", _fake_devices(2))
        assert split.n_slices == 1  # floor, min 1
        assert len(split.learner_devices) == 1

    def test_resolve_explicit(self):
        split = resolve_device_split("inf=1,learn=rest", _fake_devices(4))
        assert split.n_slices == 1
        assert len(split.learner_devices) == 3
        # Explicit learn=M leaves surplus devices idle.
        split = resolve_device_split("inf=2,learn=2", _fake_devices(8))
        assert [d.id for d in split.inference_devices] == [0, 1]
        assert [d.id for d in split.learner_devices] == [2, 3]

    def test_resolve_rejects_overcommit(self):
        with pytest.raises(ValueError):
            resolve_device_split("inf=4,learn=rest", _fake_devices(4))
        with pytest.raises(ValueError):
            resolve_device_split("inf=3,learn=2", _fake_devices(4))

    def test_single_device_degrades_to_time_shared(self):
        assert resolve_device_split("auto", _fake_devices(1)) is None
        assert (
            resolve_device_split("inf=1,learn=rest", _fake_devices(1))
            is None
        )

    def test_describe_is_json_shaped(self):
        import json

        split = resolve_device_split("inf=2,learn=rest", _fake_devices(4))
        desc = json.loads(json.dumps(split.describe()))
        assert desc["inference_slices"] == 2
        assert desc["learner_devices"] == 2

    def test_slot_hash_static_and_process_stable(self):
        """The actor->slice assignment is a pure function of the slot
        id: identical across DeviceSplit instances (reconnects build
        nothing new) and across processes (splitmix64, not Python's
        salted hash). The literal expectation pins the mapping — a
        hash-function change would silently migrate every deployed
        run's slot tables."""
        a = resolve_device_split("inf=2,learn=rest", _fake_devices(4))
        b = resolve_device_split("inf=2,learn=rest", _fake_devices(4))
        assignment = [a.slice_for_slot(i) for i in range(16)]
        assert assignment == [b.slice_for_slot(i) for i in range(16)]
        assert assignment[:8] == [1, 1, 0, 1, 0, 0, 0, 1]
        # Every slice serves someone (no dead device) at real actor
        # counts.
        assert set(assignment) == {0, 1}

    def test_needs_both_sides(self):
        with pytest.raises(ValueError):
            DeviceSplit("x", (), tuple(_fake_devices(2)))
        with pytest.raises(ValueError):
            DeviceSplit("x", tuple(_fake_devices(2)), ())


# --- multi-device matrix ------------------------------------------------


def _lstm_like_act(ctx, env_outputs, agent_state):
    """A tiny traced act body with the production shape: reads the
    params ctx, advances the [1, B, H] state, returns [1, B] outputs."""
    params, key = ctx
    h = agent_state["h"]
    x = env_outputs["obs"]  # [1, B, D]
    new_h = jnp.tanh(h + x.mean(-1, keepdims=True) * params["w"])
    out = {"action": new_h.sum(-1)[...]}  # [1, B]
    return out, {"h": new_h}


def _make_store(device=None):
    from torchbeast_tpu.serving import PolicySnapshotStore
    from torchbeast_tpu import telemetry

    store = PolicySnapshotStore(1, registry=telemetry.MetricsRegistry())
    params = {"w": jnp.full((4,), 0.5, jnp.float32)}
    if device is not None:
        params = jax.device_put(params, device)
    store.note_update(0)
    store.publish(0, params)
    return store


def _build_serving(split, store, num_slots=8):
    from torchbeast_tpu import telemetry
    from torchbeast_tpu.parallel.sebulba import build_sebulba_serving

    return build_sebulba_serving(
        split,
        store,
        num_slots=num_slots,
        max_batch_size=4,
        timeout_ms=20,
        max_policy_lag=10,
        initial_state={"h": np.zeros((1, 1, 4), np.float32)},
        table_act_fn=_lstm_like_act,
        registry=telemetry.MetricsRegistry(),
    )


def _the_device(x):
    devices = list(x.devices()) if hasattr(x, "devices") else [x.device]
    assert len(devices) == 1
    return devices[0]


@multi_device
class TestSlicePinning:
    def test_slice_tables_and_outputs_pinned(self):
        """Every slice's table lives (and stays) on its own device, a
        full step runs under jax.transfer_guard('disallow') — only
        EXPLICIT transfers on the serving path — and the advanced
        state never appears on another slice's device."""
        devices = jax.devices()
        split = resolve_device_split("inf=2,learn=rest", devices[:3])
        store = _make_store()
        serving = _build_serving(split, store)
        env = {"obs": np.ones((1, 4, 3), np.float32)}
        for stack in serving.stacks:
            table = stack.state_table
            # The steady-state serving path runs fully guarded: the
            # table owns its rng key, so the hooks mint none.
            with jax.transfer_guard("disallow"):
                ctx, _ = stack.hooks.begin_batch()
                out = table.step(
                    np.arange(4, dtype=np.int32),
                    np.ones(4, bool),
                    env,
                    context=ctx,
                )
                fetched = table.fetch(out, 4)
            assert fetched["action"].shape == (1, 4)
            for leaf in jax.tree_util.tree_leaves(table._table):
                assert _the_device(leaf) == stack.device
        # Cross-slice isolation: the two tables occupy DIFFERENT
        # devices (a shared default placement would pass the per-slice
        # check above while time-sharing one chip).
        assert serving.stacks[0].device != serving.stacks[1].device

    def test_slice_tables_draw_different_streams_from_one_seed(self):
        """Each slice's table owns its rng key, seeded rng_seed +
        7919 * (i + 1) as the hooks were: one rng_seed, two slices, two
        streams — and the same build again repeats them."""
        from torchbeast_tpu import telemetry
        from torchbeast_tpu.parallel.sebulba import build_sebulba_serving

        def key_act(ctx, env_outputs, agent_state):
            _params, subkey = ctx
            words = jax.random.key_data(subkey).astype(jnp.uint32)
            b = env_outputs["obs"].shape[1]
            return (
                {"key": jnp.tile(words[None, None, :], (1, b, 1))},
                agent_state,
            )

        devices = jax.devices()
        split = resolve_device_split("inf=2,learn=rest", devices[:3])
        env = {"obs": np.ones((1, 1, 3), np.float32)}

        def streams():
            serving = build_sebulba_serving(
                split, _make_store(), num_slots=4, max_batch_size=4,
                timeout_ms=20, max_policy_lag=10, rng_seed=5,
                initial_state={"h": np.zeros((1, 1, 4), np.float32)},
                table_act_fn=key_act,
                registry=telemetry.MetricsRegistry(),
            )
            out = []
            for stack in serving.stacks:
                params, _ = stack.hooks.begin_batch()
                drawn = []
                for _ in range(4):
                    o = stack.state_table.step(
                        np.zeros(1, np.int32), np.ones(1, bool), env,
                        context=params,
                    )
                    assert _the_device(o["key"]) == stack.device
                    words = stack.state_table.fetch(o, 1)["key"][0, 0]
                    drawn.append(tuple(int(w) for w in words))
                assert len(set(drawn)) == 4
                out.append(drawn)
            return out

        first = streams()
        assert not set(first[0]) & set(first[1])
        assert streams() == first

    def test_sharded_facade_routes_by_slot(self):
        devices = jax.devices()
        split = resolve_device_split("inf=2,learn=rest", devices[:3])
        store = _make_store()
        serving = _build_serving(split, store)
        tables = serving.state_tables
        assert tables.num_slots == 8
        for slot in range(8):
            expected = serving.stacks[
                split.slice_for_slot(slot)
            ].state_table
            assert tables.table_for_slot(slot) is expected
            # Boundary reads come back from the owning slice, shaped
            # like initial_state.
            state = tables.read_slot(slot)
            assert state["h"].shape == (1, 1, 4)
        # reset groups by slice and touches only the owning tables.
        tables.reset(list(range(8)))
        # Poison/rebuild fan out (the supervisor's one-event contract).
        tables.poison()
        assert tables.poisoned
        tables.rebuild()
        assert not tables.poisoned

    def test_router_static_hash_stable_across_reconnects(self):
        """Routing is a pure function of the slot id: the same slot
        lands on the same slice across repeated requests (reconnects
        re-enter compute with the same slot), across router rebuilds,
        and matches the split's published assignment."""
        devices = jax.devices()
        split = resolve_device_split("inf=2,learn=rest", devices[:3])

        class FakeBatcher:
            def __init__(self):
                self.seen = []

            def compute(self, inputs, trace=None):
                self.seen.append(int(inputs["slot"][0, 0]))
                return {"ok": True}

            def size(self):
                return 0

            def is_closed(self):
                return False

        from torchbeast_tpu import telemetry
        from torchbeast_tpu.parallel.sebulba import SliceRouter, SliceStack

        def build_router():
            stacks = [
                SliceStack(i, d, FakeBatcher(), None, None, lambda: None)
                for i, d in enumerate(split.inference_devices)
            ]
            return stacks, SliceRouter(
                split, stacks, registry=telemetry.MetricsRegistry()
            )

        stacks_a, router_a = build_router()
        stacks_b, router_b = build_router()
        for _ in range(3):  # repeated requests == reconnect re-entries
            for slot in range(8):
                req = {"slot": np.full((1, 1), slot, np.int32)}
                router_a.compute(req)
                router_b.compute(req)
        for slot in range(8):
            want = split.slice_for_slot(slot)
            for stacks in (stacks_a, stacks_b):
                for i, stack in enumerate(stacks):
                    if i == want:
                        assert stack.batcher.seen.count(slot) == 3
                    else:
                        assert slot not in stack.batcher.seen

    def test_router_round_robins_stateless(self):
        """Slot-less (stateless-model) requests have no resident state
        to pin; they spread across slices."""
        devices = jax.devices()
        split = resolve_device_split("inf=2,learn=rest", devices[:3])

        from torchbeast_tpu import telemetry
        from torchbeast_tpu.parallel.sebulba import SliceRouter, SliceStack

        class FakeBatcher:
            def __init__(self):
                self.n = 0

            def compute(self, inputs, trace=None):
                self.n += 1
                return {}

            def size(self):
                return 0

            def is_closed(self):
                return False

        stacks = [
            SliceStack(i, d, FakeBatcher(), None, None, lambda: None)
            for i, d in enumerate(split.inference_devices)
        ]
        router = SliceRouter(
            split, stacks, registry=telemetry.MetricsRegistry()
        )
        for _ in range(10):
            router.compute({"env": {}})
        assert stacks[0].batcher.n == 5
        assert stacks[1].batcher.n == 5


@multi_device
class TestSnapshotDeviceToDevice:
    def test_publish_and_latest_on_version_parity(self):
        """The cross-slice publication path: publish on one device,
        place on another — version parity with latest(), leaves
        committed to the target device, values equal to the bf16
        round-trip, and the per-device cache refreshing on republish.
        The whole path runs under jax.transfer_guard('disallow'):
        zero implicit host round-trips."""
        from torchbeast_tpu import telemetry
        from torchbeast_tpu.serving import PolicySnapshotStore

        devices = jax.devices()
        src, dst = devices[0], devices[1]
        store = PolicySnapshotStore(
            1, registry=telemetry.MetricsRegistry()
        )
        params = jax.device_put(
            {"w": jnp.arange(8, dtype=jnp.float32) / 7.0,
             "b": jnp.ones((3,), jnp.bfloat16)},
            src,
        )
        with jax.transfer_guard("disallow"):
            store.note_update(0)
            store.publish(0, params)
            version, placed = store.latest_on(dst)
        assert version == store.latest()[0] == 0
        for leaf in jax.tree_util.tree_leaves(placed):
            assert _the_device(leaf) == dst
        # Values match the bf16 publication round-trip; dtypes restore.
        assert placed["w"].dtype == jnp.float32
        np.testing.assert_array_equal(
            np.asarray(placed["w"]),
            np.asarray(params["w"].astype(jnp.bfloat16)
                       .astype(jnp.float32)),
        )
        # Cache: same version returns the identical placed tree.
        assert store.latest_on(dst)[1] is placed
        # Republish invalidates per-device caches.
        params2 = jax.device_put({"w": params["w"] * 2.0,
                                  "b": params["b"]}, src)
        store.note_update(5)
        with jax.transfer_guard("disallow"):
            store.publish(5, params2)
            version2, placed2 = store.latest_on(dst)
        assert version2 == 5
        assert placed2 is not placed

    def test_hooks_ctx_lands_on_slice_device(self):
        from torchbeast_tpu import telemetry
        from torchbeast_tpu.serving import ReplicaServingHooks

        devices = jax.devices()
        store = _make_store(device=devices[0])
        hooks = ReplicaServingHooks(
            store, max_policy_lag=4, registry=telemetry.MetricsRegistry(),
            device=devices[1], health_key="slice1_lag",
        )
        params, annotate = hooks.begin_batch()
        # The key is minted only on request (a table-less slice's
        # act_fn); it lands on the slice device like the params.
        for leaf in jax.tree_util.tree_leaves(params) + [hooks.next_key()]:
            assert _the_device(leaf) == devices[1]
        out = annotate({"action": np.zeros((1, 3))}, 3)
        np.testing.assert_array_equal(
            out["policy_lag"], np.zeros((1, 3), np.int32)
        )


@multi_device
class TestSplitSuperstepAccounting:
    def test_k1_vs_k2_on_two_device_mesh(self):
        """K=2 superstep over the split's 2-device DP learner mesh ==
        two K=1 dispatches over the same mesh: params and the
        [K]-stacked stats agree (the MLP family is bit-stable under
        scan fusion — the same contract test_learner_superstep pins
        single-device)."""
        from torchbeast_tpu import learner as learner_lib
        from torchbeast_tpu.models import create_model
        from torchbeast_tpu.parallel import (
            create_mesh,
            make_parallel_update_step,
            replicate,
            shard_batch,
        )

        devices = jax.devices()
        mesh = create_mesh(devices=list(devices[1:3]))  # learner devices
        T, B, A, K = 4, 4, 3, 2
        model = create_model("mlp", num_actions=A)

        def make_batch(seed):
            r = np.random.default_rng(seed)
            return {
                "frame": r.integers(
                    0, 255, (T + 1, B, 4, 4, 1), dtype=np.uint8
                ),
                "reward": r.standard_normal((T + 1, B)).astype(np.float32),
                "done": r.random((T + 1, B)) < 0.1,
                "episode_return": np.zeros((T + 1, B), np.float32),
                "episode_step": np.zeros((T + 1, B), np.int32),
                "last_action": r.integers(0, A, (T + 1, B)).astype(np.int32),
                "action": r.integers(0, A, (T + 1, B)).astype(np.int32),
                "policy_logits": r.standard_normal(
                    (T + 1, B, A)
                ).astype(np.float32),
                "baseline": r.standard_normal((T + 1, B)).astype(np.float32),
            }

        batches = [make_batch(i) for i in range(K)]
        hp = learner_lib.HParams(batch_size=B, unroll_length=T)
        optimizer = learner_lib.make_optimizer(hp)
        init = scaffold.init(
            model,
            {"params": jax.random.PRNGKey(0),
             "action": jax.random.PRNGKey(1)},
            batches[0],
            (),
        )

        # K=1 twice.
        step1 = make_parallel_update_step(
            model, optimizer, hp, mesh, donate=False
        )
        params1 = replicate(mesh, init)
        opt1 = optimizer.init(params1)
        stats_seq = []
        for b in batches:
            bs, ss = shard_batch(mesh, b, ())
            params1, opt1, stats = step1(params1, opt1, bs, ss)
            stats_seq.append(jax.device_get(stats))

        # One K=2 superstep over the same mesh.
        step2 = make_parallel_update_step(
            model, optimizer, hp, mesh, donate=False, superstep_k=K
        )
        params2 = replicate(mesh, init)
        opt2 = optimizer.init(params2)
        stacked = {
            k: np.stack([b[k] for b in batches]) for k in batches[0]
        }
        bs, ss = shard_batch(mesh, stacked, (), leading_axes=1)
        params2, opt2, stats2 = step2(params2, opt2, bs, ss)
        stats2 = jax.device_get(stats2)

        for a, b in zip(
            jax.tree_util.tree_leaves(params1),
            jax.tree_util.tree_leaves(params2),
        ):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # [K]-stacked stats row k == the k-th sequential dispatch.
        for key in ("total_loss", "grad_norm"):
            got = np.asarray(stats2[key]).reshape(K)
            want = np.asarray([s[key] for s in stats_seq]).reshape(K)
            np.testing.assert_allclose(got, want, rtol=1e-6)


@multi_device
def test_polybeast_device_split_e2e(tmp_path):
    """The async driver end to end with --device_split inf=1,learn=rest
    on the forced host devices: trains to completion, telemetry carries
    the per-slice gauges + learner.mesh_shape on every line, and the
    snapshot publication really ran."""
    import json
    import os

    from torchbeast_tpu import polybeast, telemetry

    reg = telemetry.get_registry()
    published_before = int(
        reg.counter("serving.snapshots_published").value()
    )
    argv = [
        "--env", "Mock",
        "--num_servers", "2",
        "--batch_size", "2",
        "--unroll_length", "5",
        "--total_steps", "60",
        "--savedir", str(tmp_path),
        "--xpid", "poly-split",
        "--model", "mlp",
        "--use_lstm",
        "--pipes_basename", f"unix:{tmp_path}/pipes",
        "--num_inference_threads", "1",
        "--max_inference_batch_size", "4",
        "--checkpoint_interval_s", "100000",
        "--device_split", "inf=1,learn=2",
        "--num_learner_devices", "2",
    ]
    flags = polybeast.make_parser().parse_args(argv)
    stats = polybeast.train(flags)
    assert stats["step"] >= 60
    assert np.isfinite(stats["total_loss"])
    published = (
        int(reg.counter("serving.snapshots_published").value())
        - published_before
    )
    assert published >= 1  # v0 at minimum
    tpath = os.path.join(str(tmp_path), "poly-split", "telemetry.jsonl")
    lines = [json.loads(line) for line in open(tpath)]
    assert lines
    for line in lines:
        assert line["learner.mesh_shape"] == {"data": 2, "model": 1}
        assert line["device_split"]["inference_slices"] == 1
        assert "inference.slice.0.depth" in line.get("gauges", {})
