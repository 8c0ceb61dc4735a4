"""Native serving plane (ISSUE 16): the C++ SliceRouter/ReplicaRouter
against their Python counterparts.

The parity families:

- routing bit-identity over real transports (unix socket AND shm ring):
  the same slot-framed actor stream through the native pool behind a
  C++ SliceRouter and through the Python pool behind the Python
  SliceRouter produces bit-identical learner batches, and BOTH runs
  land every request on the hash-designated slice (the other slice
  serves nothing);
- per-slice series on native telemetry: NativeTelemetryFolder folds the
  C++ router/batcher counters into the exact `inference.slice.<i>.*`
  schema the Python serving plane emits;
- continuous-batching shed accounting exactness: with the admission
  gate armed and `continuous=True`, every request lands in exactly one
  of served/shed/expired, and the pool's resubmits equal shed+expired;
- replica lag stamping parity: the same snapshot store + hooks behind
  the C++ ReplicaRouter and the Python one stamp bit-identical
  `policy_lag` leaves, and degrade to the central path identically.

Skipped when the extension isn't built (scripts/build_native.sh).
"""

import os
import tempfile
import threading
import time

import numpy as np
import pytest

from torchbeast_tpu.runtime.native import import_native

core = import_native()
pytestmark = pytest.mark.skipif(
    core is None, reason="_tbt_core not built (run scripts/build_native.sh)"
)

T = 4  # unroll length
EPISODE_LEN = 6


# ---------------------------------------------------------------------------
# Routing bit-identity over real transports


class _HostSlotTable:
    """Host-side DeviceStateTable stand-in (same surface the pools use);
    see tests/test_native.py."""

    def __init__(self, num_slots):
        self.num_slots = num_slots
        self.initial_state_host = {"s": np.zeros((1, 1), np.int64)}
        self._values = {}

    @property
    def trash_slot(self):
        return self.num_slots

    def get(self, slot):
        return self._values.get(int(slot), 0)

    def set(self, slot, value):
        self._values[int(slot)] = int(value)

    def reset(self, slots):
        for s in slots:
            self._values[int(s)] = 0

    def read_slot(self, slot):
        return {"s": np.full((1, 1), self.get(slot), np.int64)}


def _serve_slot_batcher(batcher, table):
    """Slice serving thread: CountingEnv dynamics over the slot table."""
    it = iter(batcher)
    while True:
        try:
            batch = next(it)
        except StopIteration:
            return
        inputs = batch.get_inputs()
        slots = np.asarray(inputs["slot"]).reshape(-1)
        advance = np.asarray(inputs["advance"]).reshape(-1)
        done = np.asarray(inputs["env"]["done"])[0].astype(bool)
        prev = np.array([table.get(s) for s in slots], np.int64)
        new = np.where(done, 0, prev) + 1
        for j, slot in enumerate(slots):
            if advance[j]:
                table.set(slot, new[j])
        batch.set_outputs({
            "outputs": {
                "action": np.zeros((1, len(slots)), np.int32),
                "policy_logits": new[None, :, None].astype(np.float32),
                "baseline": new[None].astype(np.float32),
            }
        })


def _py_split(n_slices):
    """A DeviceSplit over opaque placeholder devices: routing only needs
    n_slices and the hash, not real jax devices."""
    from torchbeast_tpu.runtime.placement import DeviceSplit

    return DeviceSplit(
        spec="test",
        inference_devices=tuple(range(n_slices)),
        learner_devices=(n_slices,),
    )


def _collect_sliced_items(pool_kind, address, n_items):
    """One actor in slot mode through TWO slice batchers behind the
    router of `pool_kind`; returns (items, per-slice request counts)."""
    from torchbeast_tpu import nest
    from torchbeast_tpu.telemetry.metrics import MetricsRegistry

    table = _HostSlotTable(num_slots=1)
    if pool_kind == "native":
        learner_queue = core.BatchingQueue(
            batch_dim=1, minimum_batch_size=1, maximum_batch_size=1
        )
        batchers = [
            core.DynamicBatcher(batch_dim=1, timeout_ms=20)
            for _ in range(2)
        ]
        router = core.SliceRouter(slices=batchers)
        pool = core.ActorPool(
            unroll_length=T,
            learner_queue=learner_queue,
            inference_batcher=router,
            env_server_addresses=[address],
            initial_agent_state=table.initial_state_host,
            state_table=table,
        )
        counts = lambda: list(router.telemetry()["requests"])  # noqa: E731
    else:
        from torchbeast_tpu.parallel.sebulba import SliceRouter, SliceStack
        from torchbeast_tpu.runtime.actor_pool import ActorPool
        from torchbeast_tpu.runtime.queues import (
            BatchingQueue,
            DynamicBatcher,
        )

        registry = MetricsRegistry()
        learner_queue = BatchingQueue(
            batch_dim=1, minimum_batch_size=1, maximum_batch_size=1
        )
        batchers = [
            DynamicBatcher(batch_dim=1, timeout_ms=20) for _ in range(2)
        ]
        stacks = [
            SliceStack(i, None, b, None, None, None)
            for i, b in enumerate(batchers)
        ]
        router = SliceRouter(_py_split(2), stacks, registry=registry)
        pool = ActorPool(
            unroll_length=T,
            learner_queue=learner_queue,
            inference_batcher=router,
            env_server_addresses=[address],
            initial_agent_state=table.initial_state_host,
            state_table=table,
        )
        counts = lambda: [  # noqa: E731
            registry.counter(f"inference.slice.{i}.requests").value()
            for i in range(2)
        ]
    servers = [
        threading.Thread(
            target=_serve_slot_batcher, args=(b, table), daemon=True
        )
        for b in batchers
    ]
    for s in servers:
        s.start()
    pool_thread = threading.Thread(target=pool.run, daemon=True)
    pool_thread.start()
    items = []
    it = iter(learner_queue)
    while len(items) < n_items:
        item = next(it)
        items.append(item if not isinstance(item, tuple) else item[0])
    for b in batchers:
        b.close()
    learner_queue.close()
    pool_thread.join(5)
    for s in servers:
        s.join(5)
    flat = [
        [np.asarray(leaf) for leaf in nest.flatten(item)] for item in items
    ]
    return flat, counts()


def _bind_server(kind, tag):
    from torchbeast_tpu.envs import CountingEnv
    from torchbeast_tpu.runtime.env_server import EnvServer

    path = os.path.join(tempfile.mkdtemp(), f"route_{tag}")
    address = f"{kind}:{path}"
    server = EnvServer(
        lambda: CountingEnv(episode_length=EPISODE_LEN), address
    )
    server.start()
    if kind == "unix":
        deadline = time.monotonic() + 10
        while not os.path.exists(path):
            if time.monotonic() > deadline:
                raise TimeoutError("server did not bind")
            time.sleep(0.01)
    else:
        time.sleep(0.3)  # shm attach files appear on first accept
    return server, address


@pytest.mark.parametrize("transport", ["unix", "shm"])
def test_native_routing_bit_identical(transport):
    """Same slot -> same slice -> same reply, either language, over a
    real transport: bit-identical learner batches AND an identical
    all-on-the-hashed-slice request distribution."""
    from torchbeast_tpu.runtime.placement import _mix64

    expected_slice = _mix64(0) % 2  # the single actor serves slot 0
    results = {}
    for kind in ("native", "python"):
        server, address = _bind_server(transport, f"{transport}_{kind}")
        try:
            results[kind] = _collect_sliced_items(kind, address, 5)
        finally:
            server.stop()
    native_items, native_counts = results["native"]
    python_items, python_counts = results["python"]
    # Routing identity: every request on the hash-designated slice.
    assert native_counts[1 - expected_slice] == 0
    assert python_counts[1 - expected_slice] == 0
    assert native_counts[expected_slice] > 0
    assert python_counts[expected_slice] > 0
    # Reply identity: bit-identical learner batches.
    assert len(native_items) == len(python_items)
    for native_item, python_item in zip(native_items, python_items):
        assert len(native_item) == len(python_item)
        for native_leaf, python_leaf in zip(native_item, python_item):
            assert native_leaf.dtype == python_leaf.dtype
            np.testing.assert_array_equal(native_leaf, python_leaf)


def test_slice_router_validation_and_rr():
    with pytest.raises(ValueError):
        core.SliceRouter(slices=[])
    batchers = [core.DynamicBatcher(batch_dim=1) for _ in range(3)]
    router = core.SliceRouter(slices=batchers)
    assert router.n_slices() == 3
    assert router.size() == 0
    assert not router.is_closed()
    router.close()
    assert router.is_closed()


# ---------------------------------------------------------------------------
# Per-slice series on native telemetry


def test_native_per_slice_telemetry_schema():
    """NativeTelemetryFolder folds C++ router/batcher counters into the
    EXACT series the Python serving plane emits: per-slice
    `inference.slice.<i>.requests` counters and `.depth` gauges, the
    replica routing split, and the continuous-batching roll counter."""
    from torchbeast_tpu.runtime.native import NativeTelemetryFolder
    from torchbeast_tpu.telemetry.metrics import MetricsRegistry

    batchers = [core.DynamicBatcher(batch_dim=1) for _ in range(2)]
    router = core.SliceRouter(slices=batchers)
    central = core.DynamicBatcher(batch_dim=1)
    replica = core.DynamicBatcher(batch_dim=1)
    replica_router = core.ReplicaRouter(central=central, replica=replica)

    def one_request(target):
        target.compute({
            "slot": np.zeros((1, 1), np.int32),
            "env": np.zeros((1, 1, 2), np.float32),
        })

    t = threading.Thread(target=one_request, args=(router,), daemon=True)
    t.start()
    # Slot 0 hashes to slice 1 (splitmix64(0) is odd); serve it there.
    batch = next(iter(batchers[1]))
    batch.set_outputs(batch.get_inputs())
    t.join(5)

    registry = MetricsRegistry()
    folder = NativeTelemetryFolder(
        registry,
        slice_batchers=batchers,
        slice_router=router,
        replica_batcher=replica,
        replica_router=replica_router,
    )
    folder.tick()
    assert registry.counter("inference.slice.1.requests").value() == 1
    assert registry.counter("inference.slice.0.requests").value() == 0
    # Depth gauges exist and track batcher.size() (drained -> 0).
    assert registry.gauge("inference.slice.0.depth").value() == 0
    assert registry.gauge("inference.slice.1.depth").value() == 0
    assert registry.counter("serving.replica_requests").value() == 0
    assert registry.counter("serving.central_requests").value() == 0
    assert registry.counter("serving.rolled").value() == 0
    # Delta semantics: a second tick with no new requests credits 0.
    folder.tick()
    assert registry.counter("inference.slice.1.requests").value() == 1
    for b in batchers + [central, replica]:
        b.close()


def test_slice_series_names_match_python_schema():
    """The series the folder creates are EXACTLY the names the Python
    SliceRouter/SebulbaServing register — the ROUTE-PARITY prefix pin,
    checked executably."""
    from torchbeast_tpu.analysis import config as lint_config

    prefix = lint_config.SLICE_SERIES_PREFIX
    assert prefix == "inference.slice."
    from torchbeast_tpu.runtime.native import NativeTelemetryFolder
    from torchbeast_tpu.telemetry.metrics import MetricsRegistry

    batchers = [core.DynamicBatcher(batch_dim=1)]
    router = core.SliceRouter(slices=batchers)
    registry = MetricsRegistry()
    NativeTelemetryFolder(
        registry, slice_batchers=batchers, slice_router=router
    )
    names = set(registry.instruments())
    assert f"{prefix}0.requests" in names
    assert f"{prefix}0.depth" in names
    batchers[0].close()


# ---------------------------------------------------------------------------
# Continuous batching: shed accounting exactness


def _at_gate(batcher):
    """Requests that have met the admission gate so far."""
    tm = batcher.telemetry()
    return tm["admitted"] + tm["shed"]


def test_continuous_shed_accounting_exact():
    """Admission armed + continuous=True: every request lands in exactly
    one of served/shed/expired — client-observed sheds equal the
    batcher's shed+expired, and served+shed+expired covers the total."""
    from torchbeast_tpu.runtime.errors import ShedError

    batcher = core.DynamicBatcher(
        batch_dim=1,
        minimum_batch_size=1,
        maximum_batch_size=4,
        timeout_ms=5,
        shed_max_queue_depth=2,
        continuous=True,
    )
    outcomes = {"served": 0, "shed": 0}
    lock = threading.Lock()

    def client(i):
        try:
            batcher.compute({
                "env": np.full((1, 1, 2), i, np.float32),
            })
            with lock:
                outcomes["served"] += 1
        except ShedError:
            with lock:
                outcomes["shed"] += 1

    n = 64

    def serve():
        it = iter(batcher)
        held = False
        while True:
            try:
                batch = it.__next__()
            except StopIteration:
                return
            if not held:
                # Hold the first batch until every client has met the
                # gate: two more queue behind it, the rest must be shed.
                held = True
                while _at_gate(batcher) < n:
                    time.sleep(0.001)
            batch.set_outputs(batch.get_inputs())

    server = threading.Thread(target=serve, daemon=True)
    server.start()
    clients = [
        threading.Thread(target=client, args=(i,), daemon=True)
        for i in range(n)
    ]
    for c in clients:
        c.start()
    for c in clients:
        c.join(30)
    batcher.close()
    server.join(5)
    tm = batcher.telemetry()
    # Exactness: the typed-shed count the CLIENTS saw is the gate's
    # shed+expired — nothing double-counted, nothing silently dropped.
    assert outcomes["shed"] == tm["shed"] + tm["expired"]
    assert outcomes["served"] + outcomes["shed"] == n
    assert tm["rows"] == outcomes["served"]
    assert tm["admitted"] == tm["rows"] + tm["expired"]
    # The load was engineered to actually shed: while the first batch
    # (at most 4 rows) is held, the gate admits 2 more and no others.
    assert outcomes["shed"] >= n - 4 - 2
    assert tm["rolled"] >= 0  # exposed; exercised in anger by the bench


def test_continuous_rolls_late_arrivals():
    """Directed roll: a request admitted while the serving thread holds
    an under-max batch rides the NEXT dispatch window (rolled counter)
    instead of waiting a full timeout behind a depth bound."""
    batcher = core.DynamicBatcher(
        batch_dim=1,
        minimum_batch_size=2,
        maximum_batch_size=8,
        timeout_ms=2000,
        continuous=True,
    )
    replies = []

    def client(i):
        replies.append(
            batcher.compute({"env": np.full((1, 1), i, np.float32)})
        )

    threads = [
        threading.Thread(target=client, args=(i,), daemon=True)
        for i in range(4)
    ]
    threads[0].start()
    time.sleep(0.05)
    for t in threads[1:]:
        t.start()
    # All four requests complete well inside the 2s window: the batch
    # waits for min=2, then tops up whatever arrived meanwhile.
    batch = next(iter(batcher))
    got = len(batch)
    batch.set_outputs(batch.get_inputs())
    remaining = 4 - got
    while remaining > 0:
        batch = next(iter(batcher))
        remaining -= len(batch)
        batch.set_outputs(batch.get_inputs())
    for t in threads:
        t.join(10)
    assert len(replies) == 4
    tm = batcher.telemetry()
    assert tm["rows"] == 4
    batcher.close()


# ---------------------------------------------------------------------------
# Replica routing: lag stamping parity and degradation


def _serve_with_hooks(batcher, hooks):
    """Replica serving thread: ctx+annotate per batch, exactly like
    runtime.inference.inference_loop's serving_hooks path."""
    it = iter(batcher)
    while True:
        try:
            batch = it.__next__()
        except StopIteration:
            return
        _ctx, annotate = hooks.begin_batch()
        inputs = batch.get_inputs()
        outputs = {
            "action": np.zeros((1, len(batch)), np.int32),
        }
        if annotate is not None:
            annotate(outputs, len(batch))
        batch.set_outputs(outputs)
        _ = inputs


def _serve_plain(batcher):
    it = iter(batcher)
    while True:
        try:
            batch = it.__next__()
        except StopIteration:
            return
        batch.set_outputs({
            "action": np.zeros((1, len(batch)), np.int32),
        })


def _lag_stamp_through(kind, store, hooks, registry):
    """One request through the replica router of `kind`; returns
    (reply, replica_count, central_count)."""
    if kind == "native":
        central = core.DynamicBatcher(batch_dim=1)
        replica = core.DynamicBatcher(batch_dim=1)
        router = core.ReplicaRouter(central=central, replica=replica)
        router.set_serving(hooks.serving_ok())
    else:
        from torchbeast_tpu.runtime.queues import DynamicBatcher
        from torchbeast_tpu.serving import ReplicaRouter

        central = DynamicBatcher(batch_dim=1)
        replica = DynamicBatcher(batch_dim=1)
        router = ReplicaRouter(central, replica, hooks, registry=registry)
    threads = [
        threading.Thread(
            target=_serve_with_hooks, args=(replica, hooks), daemon=True
        ),
        threading.Thread(target=_serve_plain, args=(central,), daemon=True),
    ]
    for t in threads:
        t.start()
    reply = router.compute({"env": np.zeros((1, 1, 2), np.float32)})
    if kind == "native":
        tm = router.telemetry()
        counts = (tm["replica_requests"], tm["central_requests"])
    else:
        counts = (
            registry.counter("serving.replica_requests").value(),
            registry.counter("serving.central_requests").value(),
        )
    central.close()
    replica.close()
    for t in threads:
        t.join(5)
    return reply, counts


@pytest.mark.parametrize("lag", [0, 3])
def test_replica_lag_stamping_parity(lag):
    """The SAME snapshot store + hooks behind both routers: replies
    carry bit-identical policy_lag stamps and both count the request
    on the replica path."""
    from torchbeast_tpu.serving import PolicySnapshotStore
    from torchbeast_tpu.serving.replica import ReplicaServingHooks
    from torchbeast_tpu.telemetry.metrics import MetricsRegistry

    replies = {}
    for kind in ("native", "python"):
        registry = MetricsRegistry()
        store = PolicySnapshotStore(refresh_updates=1, registry=registry)
        store.publish(0, {"w": np.ones((2,), np.float32)})
        for v in range(1, lag + 1):
            store.note_update(v)
        assert store.lag() == lag
        hooks = ReplicaServingHooks(
            store, max_policy_lag=5, batch_dim=1, registry=registry
        )
        reply, (n_replica, n_central) = _lag_stamp_through(
            kind, store, hooks, registry
        )
        assert n_replica == 1 and n_central == 0, kind
        replies[kind] = reply
    native_stamp = np.asarray(replies["native"]["policy_lag"])
    python_stamp = np.asarray(replies["python"]["policy_lag"])
    assert native_stamp.dtype == python_stamp.dtype == np.int32
    np.testing.assert_array_equal(native_stamp, python_stamp)
    assert int(native_stamp.reshape(-1)[0]) == lag


def test_replica_degradation_parity():
    """Lag beyond budget: BOTH routers send the request to the central
    path (the native gate is the serving_ok flag pushed from the same
    hooks that gate the Python router)."""
    from torchbeast_tpu.serving import PolicySnapshotStore
    from torchbeast_tpu.serving.replica import ReplicaServingHooks
    from torchbeast_tpu.telemetry.metrics import MetricsRegistry

    for kind in ("native", "python"):
        registry = MetricsRegistry()
        store = PolicySnapshotStore(refresh_updates=1, registry=registry)
        store.publish(0, {"w": np.ones((2,), np.float32)})
        for v in range(1, 9):
            store.note_update(v)  # lag 8 > budget 5
        hooks = ReplicaServingHooks(
            store, max_policy_lag=5, batch_dim=1, registry=registry
        )
        reply, (n_replica, n_central) = _lag_stamp_through(
            kind, store, hooks, registry
        )
        assert n_replica == 0 and n_central == 1, kind
        # Central replies carry no stamp; the pool normalizes the
        # missing leaf to lag 0 on both runtimes (record_policy_lag).
        assert "policy_lag" not in reply


# ---------------------------------------------------------------------------
# Remote replica tier behind the NATIVE router: proxy_loop bridges a C++
# replica batcher onto a replica host over the wire stack.


def test_proxy_loop_bridges_native_batcher_to_remote():
    from torchbeast_tpu.serving.replica_server import (
        RemoteReplicaBatcher,
        RemoteSnapshotPublisher,
        ReplicaServer,
        proxy_loop,
    )
    from torchbeast_tpu.telemetry.metrics import MetricsRegistry

    def act_fn(params, inputs):
        n = np.asarray(inputs["env"]).shape[1]
        w = float(np.asarray(params["w"]).reshape(-1)[0])
        return {"action": np.full((1, n), int(w), np.int32)}

    path = os.path.join(tempfile.mkdtemp(), "rs_native")
    address = f"unix:{path}"
    server = ReplicaServer(
        act_fn, address, batch_dim=1, timeout_ms=5,
        registry=MetricsRegistry(),
    )
    server.start()
    publisher = RemoteSnapshotPublisher(address, timeout_s=10)
    remote = RemoteReplicaBatcher(address, timeout_s=10)
    central = core.DynamicBatcher(batch_dim=1)
    replica = core.DynamicBatcher(batch_dim=1)
    router = core.ReplicaRouter(central=central, replica=replica)
    proxy = threading.Thread(
        target=proxy_loop, args=(replica, remote), daemon=True
    )
    proxy.start()
    try:
        publisher.publish(0, {"w": np.full((1,), 6.0, np.float32)})
        router.set_serving(True)
        out = router.compute({"env": np.zeros((1, 1, 3), np.float32)})
        assert int(np.asarray(out["action"]).reshape(-1)[0]) == 6
        stamp = np.asarray(out["policy_lag"])
        assert stamp.dtype == np.int32
        assert int(stamp.reshape(-1)[0]) == 0
        tm = router.telemetry()
        assert tm["replica_requests"] == 1 and tm["central_requests"] == 0
    finally:
        central.close()
        replica.close()
        proxy.join(5)
        remote.close()
        publisher.close()
        server.stop()
