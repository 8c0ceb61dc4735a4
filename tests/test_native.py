"""Native (_tbt_core) runtime: same semantic surface as the Python
queues/actor-pool tests, driven through the C extension — plus the
ISSUE 9 parity family: slot framing vs the Python pool (bit-identical
batches), shm transport e2e + crash/reconnect + /dev/shm sweep, the
cross-language wire codec pins (incl. bf16), the raw-item arena intake,
and the telemetry fold. Skipped when the extension isn't built
(scripts/build_native.sh)."""

import multiprocessing as mp
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


class TestNativeBatchingQueue:
    def test_construction_errors(self):
        with pytest.raises(ValueError):
            core.BatchingQueue(minimum_batch_size=0)
        with pytest.raises(ValueError):
            core.BatchingQueue(minimum_batch_size=4, maximum_batch_size=2)

    def test_enqueue_dequeue_roundtrip(self):
        queue = core.BatchingQueue(batch_dim=0, minimum_batch_size=2)
        queue.enqueue({"x": np.full((1, 3), 1.5, np.float32)})
        queue.enqueue({"x": np.full((1, 3), 2.5, np.float32)})
        batch, count = queue.dequeue_many()
        assert count == 2
        assert batch["x"].shape == (2, 3)
        np.testing.assert_array_equal(batch["x"][:, 0], [1.5, 2.5])

    def test_close_semantics(self):
        queue = core.BatchingQueue()
        queue.close()
        with pytest.raises(core.ClosedBatchingQueue):
            queue.enqueue(np.zeros((1, 1)))
        with pytest.raises(RuntimeError):
            queue.close()
        with pytest.raises(StopIteration):
            next(iter(queue))

    def test_validation(self):
        queue = core.BatchingQueue(batch_dim=1)
        with pytest.raises(ValueError):
            queue.enqueue(np.zeros(3))  # too few dims

    def test_iteration_blocks_until_item(self):
        queue = core.BatchingQueue(batch_dim=0, minimum_batch_size=1)
        out = {}

        def consumer():
            out["batch"] = next(iter(queue))

        t = threading.Thread(target=consumer, daemon=True)
        t.start()
        time.sleep(0.05)
        queue.enqueue([np.full((1, 2), 7, np.int64)])
        t.join(5)
        np.testing.assert_array_equal(out["batch"][0], [[7, 7]])

    def test_stress(self):
        queue = core.BatchingQueue(
            batch_dim=0, minimum_batch_size=1, maximum_batch_size=16
        )
        n_producers, items = 8, 100
        got = []
        lock = threading.Lock()

        def producer(p):
            for i in range(items):
                queue.enqueue(np.full((1,), p * items + i, np.int64))

        def consumer():
            while True:
                try:
                    batch, _ = queue.dequeue_many()
                except StopIteration:
                    return
                with lock:
                    got.extend(batch.tolist())

        consumers = [
            threading.Thread(target=consumer, daemon=True) for _ in range(4)
        ]
        producers = [
            threading.Thread(target=producer, args=(p,), daemon=True)
            for p in range(n_producers)
        ]
        for t in consumers + producers:
            t.start()
        for t in producers:
            t.join(30)
        deadline = time.monotonic() + 30
        while queue.size() and time.monotonic() < deadline:
            time.sleep(0.01)
        queue.close()
        for t in consumers:
            t.join(10)
        assert sorted(got) == list(range(n_producers * items))


class TestNativeDynamicBatcher:
    def test_request_response(self):
        batcher = core.DynamicBatcher(batch_dim=0)
        result = {}

        def producer():
            result["out"] = batcher.compute(
                {"x": np.arange(4, dtype=np.float32).reshape(1, 4)}
            )

        t = threading.Thread(target=producer, daemon=True)
        t.start()
        batch = next(iter(batcher))
        inputs = batch.get_inputs()
        assert len(batch) == 1
        batch.set_outputs({"y": inputs["x"] * 10})
        t.join(5)
        np.testing.assert_array_equal(result["out"]["y"], [[0, 10, 20, 30]])

    def test_batched_rows_sliced_back(self):
        batcher = core.DynamicBatcher(batch_dim=0, minimum_batch_size=3)
        outs = {}

        def producer(i):
            outs[i] = batcher.compute(np.full((1, 2), i, np.int64))

        threads = [
            threading.Thread(target=producer, args=(i,), daemon=True)
            for i in range(3)
        ]
        for t in threads:
            t.start()
        time.sleep(0.1)
        batch = next(iter(batcher))
        inputs = batch.get_inputs()
        assert inputs.shape == (3, 2)
        batch.set_outputs(inputs + 100)
        for t in threads:
            t.join(5)
        for i in range(3):
            np.testing.assert_array_equal(outs[i], [[i + 100, i + 100]])

    def test_dropped_batch_breaks_promise(self):
        batcher = core.DynamicBatcher(batch_dim=0)
        caught = {}

        def producer():
            try:
                batcher.compute(np.zeros((1, 1)))
            except core.AsyncError as e:
                caught["err"] = e

        t = threading.Thread(target=producer, daemon=True)
        t.start()
        batch = next(iter(batcher))
        del batch
        t.join(5)
        assert "err" in caught

    def test_close_wakes_producers(self):
        batcher = core.DynamicBatcher(batch_dim=0)
        caught = {}

        def producer():
            try:
                batcher.compute(np.zeros((1, 1)))
            except core.AsyncError as e:
                caught["err"] = e

        t = threading.Thread(target=producer, daemon=True)
        t.start()
        time.sleep(0.1)
        batcher.close()
        t.join(5)
        assert "err" in caught

    def test_set_outputs_twice_raises(self):
        batcher = core.DynamicBatcher(batch_dim=0)
        t = threading.Thread(
            target=lambda: batcher.compute(np.zeros((1, 1))), daemon=True
        )
        t.start()
        batch = next(iter(batcher))
        batch.set_outputs(np.zeros((1, 1)))
        with pytest.raises(RuntimeError):
            batch.set_outputs(np.zeros((1, 1)))
        t.join(5)


def test_conversion_does_not_leak_references():
    """enqueue/dequeue roundtrips must not leak refs to the input arrays
    (reference parity: nest refcount tests, nest/nest_test.py:126-166)."""
    import gc
    import sys

    arr = np.arange(6, dtype=np.float32).reshape(1, 6)
    baseline_rc = sys.getrefcount(arr)

    queue = core.BatchingQueue(batch_dim=0, minimum_batch_size=1)
    for _ in range(10):
        queue.enqueue({"x": arr})
        out, _ = queue.dequeue_many()
        del out
    queue.close()
    del queue
    gc.collect()
    assert sys.getrefcount(arr) == baseline_rc

    # And decoded outputs keep their buffer alive independently.
    queue = core.BatchingQueue(batch_dim=0, minimum_batch_size=1)
    src = np.full((1, 4), 7.0)
    queue.enqueue(src)
    out, _ = queue.dequeue_many()
    del src
    gc.collect()
    np.testing.assert_array_equal(out, [[7.0, 7.0, 7.0, 7.0]])
    queue.close()


EPISODE_LEN = 5
T = 3


def test_native_actor_pool_end_to_end():
    """Full reference architecture: C++ actor loops against a Python env
    server, Python inference thread serving the native batcher, rollouts
    into the native learner queue — with the on-policy invariants held."""
    from torchbeast_tpu.envs import CountingEnv
    from torchbeast_tpu.runtime.env_server import EnvServer

    path = os.path.join(tempfile.mkdtemp(), "native_env")
    address = f"unix:{path}"
    server = EnvServer(
        lambda: CountingEnv(episode_length=EPISODE_LEN), address
    )
    server.start()
    deadline = time.monotonic() + 5
    while not os.path.exists(path):
        if time.monotonic() > deadline:
            raise TimeoutError("server did not bind")
        time.sleep(0.01)

    learner_queue = core.BatchingQueue(
        batch_dim=1, minimum_batch_size=1, maximum_batch_size=1
    )
    batcher = core.DynamicBatcher(batch_dim=1, timeout_ms=20)

    def inference():
        while True:
            try:
                batch = next(iter(batcher))
            except StopIteration:
                return
            inputs = batch.get_inputs()
            done = inputs["env"]["done"]  # [1, B]
            state = np.where(done, 0, inputs["agent_state"]) + 1  # [1, B]
            batch.set_outputs(
                {
                    "outputs": {
                        "action": np.zeros_like(done, np.int32),
                        "policy_logits": state[..., None].astype(np.float32),
                        "baseline": state.astype(np.float32),
                    },
                    "agent_state": state.astype(np.int64),
                }
            )

    inf_thread = threading.Thread(target=inference, daemon=True)
    inf_thread.start()

    pool = core.ActorPool(
        unroll_length=T,
        learner_queue=learner_queue,
        inference_batcher=batcher,
        env_server_addresses=[address],
        initial_agent_state=np.zeros((1, 1), np.int64),
    )
    pool_thread = threading.Thread(target=pool.run, daemon=True)
    pool_thread.start()

    items = []
    it = iter(learner_queue)
    while len(items) < 6:
        items.append(next(it))

    batcher.close()
    learner_queue.close()
    pool_thread.join(5)
    server.stop()

    assert pool.count() >= 6 * T
    prev = None
    for item in items:
        batch = item["batch"]
        initial_state = item["initial_agent_state"]
        assert batch["frame"].shape[:2] == (T + 1, 1)
        if prev is not None:
            for key in batch:
                np.testing.assert_array_equal(
                    batch[key][0], prev[key][-1], err_msg=key
                )
        done0 = batch["done"][0]
        expected = np.where(done0, 0, initial_state[0]) + 1
        np.testing.assert_array_equal(batch["baseline"][1], expected)
        assert (batch["frame"][batch["done"].astype(bool)] == 0).all()
        np.testing.assert_array_equal(
            batch["action"][1:], batch["last_action"][1:]
        )
        prev = batch


# ---------------------------------------------------------------------------
# Cross-language wire codec (ISSUE 9): the C++ encode/decode pinned in
# anger against wire.py — beastlint WIRE-PARITY pins the same contract
# textually; this executes both stacks on the same bytes.


def _norm(v):
    if isinstance(v, dict):
        return {k: _norm(x) for k, x in sorted(v.items())}
    if isinstance(v, (list, tuple)):
        return [_norm(x) for x in v]
    if isinstance(v, np.ndarray):
        return ("array", str(v.dtype), v.shape, v.tobytes())
    return v


def _sorted_keys(v):
    if isinstance(v, dict):
        return {k: _sorted_keys(x) for k, x in sorted(v.items())}
    return v


def _codec_messages():
    rng = np.random.default_rng(7)
    yield {"type": "step", "frame": rng.integers(0, 255, (4, 3), np.uint8),
           "reward": np.asarray(np.float32(0.5)), "done": np.asarray(False),
           "n": 7, "f": 1.5, "s": "hello", "none": None,
           "lst": [1, 2.0, "x", None, True]}
    yield {"scalars": [np.int32(3), np.float64(2.5), np.bool_(True)],
           "empty": np.zeros((0, 5), np.float32),
           "zerod": np.asarray(np.int64(-9))}
    yield {"dtypes": [np.zeros(3, dt) for dt in (
        np.uint8, np.int8, np.int32, np.int64, np.float32, np.float64,
        np.bool_, np.uint16, np.int16, np.uint32, np.uint64, np.float16)]}


def test_wire_codec_cross_language():
    from torchbeast_tpu.runtime import wire

    for msg in _codec_messages():
        # Byte-identical frames for sorted-key dicts (C++ dicts iterate
        # sorted; Python preserves insertion order — the FORMAT is
        # order-insensitive, both decode either ordering).
        smsg = _sorted_keys(msg)
        assert core.wire_encode(smsg) == wire.encode(smsg)
        # Cross-decode both directions.
        assert _norm(core.wire_decode(wire.encode(msg))) == _norm(msg)
        assert _norm(wire.decode(core.wire_encode(msg)[4:])) == _norm(msg)


def test_wire_codec_bf16_roundtrip():
    """bf16 (wire code 12) decodes natively: C++ frame bytes match
    wire.py's and the payload survives both directions bit-exactly."""
    import ml_dtypes

    from torchbeast_tpu.runtime import wire

    bf = np.arange(-6, 6, dtype=ml_dtypes.bfloat16).reshape(3, 4)
    assert core.wire_encode({"x": bf}) == wire.encode({"x": bf})
    for decoded in (core.wire_decode(wire.encode({"x": bf}))["x"],
                    wire.decode(core.wire_encode({"x": bf})[4:])["x"]):
        assert decoded.dtype == np.dtype(ml_dtypes.bfloat16)
        assert decoded.tobytes() == bf.tobytes()


def test_native_queue_carries_bf16():
    """The batching queue moves bf16 payloads (pymodule conversions both
    directions) — what --precision bf16_train rides on natively."""
    import ml_dtypes

    bf16 = np.dtype(ml_dtypes.bfloat16)
    item = {"x": np.arange(8, dtype=bf16).reshape(2, 1, 4)}
    queue = core.BatchingQueue(batch_dim=1, minimum_batch_size=2)
    queue.enqueue(item)
    queue.enqueue({"x": (item["x"] + 1).astype(bf16)})
    batch, count = queue.dequeue_many()
    assert count == 2
    assert batch["x"].dtype == bf16
    assert batch["x"].shape == (2, 2, 4)
    np.testing.assert_array_equal(
        np.asarray(batch["x"][:, 0], np.float32),
        np.asarray(item["x"][:, 0], np.float32),
    )
    queue.close()


# ---------------------------------------------------------------------------
# Raw-item arena intake (--superstep_k native): dequeue_item drains the
# native queue through the SAME BatchArena the Python runtime uses,
# bit-identical to the Python queue path.


def _rollout_item(seed):
    rng = np.random.default_rng(seed)
    return {
        "batch": {
            "frame": rng.integers(0, 255, (6, 1, 4, 4), np.uint8),
            "reward": rng.normal(size=(6, 1)).astype(np.float32),
        },
        "initial_agent_state": rng.normal(size=(1, 1, 3)).astype(np.float32),
    }


def test_native_arena_intake_bit_identical():
    from torchbeast_tpu import nest
    from torchbeast_tpu.runtime.queues import BatchArena, BatchingQueue

    items = [_rollout_item(s) for s in range(4)]
    native_q = core.BatchingQueue(batch_dim=1, minimum_batch_size=2,
                                  maximum_batch_size=2)
    python_q = BatchingQueue(batch_dim=1, minimum_batch_size=2,
                             maximum_batch_size=2)
    for item in items:
        native_q.enqueue(item)
        python_q.enqueue(item)
    stacks = []
    for queue in (native_q, python_q):
        arena = BatchArena(k=2, rows=2, batch_dim=1)
        stacked, release = arena.assemble_from(queue)
        stacks.append([np.asarray(a) for a in nest.flatten(stacked)])
        release()
    assert len(stacks[0]) == len(stacks[1])
    for native_leaf, python_leaf in zip(*stacks):
        assert native_leaf.dtype == python_leaf.dtype
        np.testing.assert_array_equal(native_leaf, python_leaf)
    # Closing the native queue ends assemble_from with StopIteration,
    # exactly like the Python queue (QueueStopped -> StopIteration).
    native_q.close()
    arena = BatchArena(k=2, rows=2, batch_dim=1)
    with pytest.raises(StopIteration):
        arena.assemble_from(native_q)


# ---------------------------------------------------------------------------
# Slot framing: the native pool drives a (host-stand-in) slot table
# through the same {"env", "slot", "advance"} -> {"outputs"} wire
# contract as the Python pool — and produces bit-identical batches.


class _HostSlotTable:
    """Host-side stand-in for runtime.state_table.DeviceStateTable: the
    same reset/read_slot/initial_state_host surface the pools use, with
    state advanced by the serving thread (deterministic, jax-free)."""

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
    """Inference thread body: CountingEnv dynamics over the slot table
    (state = where(done, 0, prev) + 1), replies carry outputs ONLY."""
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


def _collect_slot_items(pool_kind, address, n_items):
    """Run one actor through either pool in slot mode; return the first
    n_items learner items as flat numpy lists."""
    from torchbeast_tpu import nest

    table = _HostSlotTable(num_slots=1)
    if pool_kind == "native":
        learner_queue = core.BatchingQueue(
            batch_dim=1, minimum_batch_size=1, maximum_batch_size=1
        )
        batcher = core.DynamicBatcher(batch_dim=1, timeout_ms=20)
        pool = core.ActorPool(
            unroll_length=T,
            learner_queue=learner_queue,
            inference_batcher=batcher,
            env_server_addresses=[address],
            initial_agent_state=table.initial_state_host,
            state_table=table,
        )
    else:
        from torchbeast_tpu.runtime.actor_pool import ActorPool
        from torchbeast_tpu.runtime.queues import (
            BatchingQueue,
            DynamicBatcher,
        )

        learner_queue = BatchingQueue(
            batch_dim=1, minimum_batch_size=1, maximum_batch_size=1
        )
        batcher = DynamicBatcher(batch_dim=1, timeout_ms=20)
        pool = ActorPool(
            unroll_length=T,
            learner_queue=learner_queue,
            inference_batcher=batcher,
            env_server_addresses=[address],
            initial_agent_state=table.initial_state_host,
            state_table=table,
        )
    serve = threading.Thread(
        target=_serve_slot_batcher, args=(batcher, table), daemon=True
    )
    serve.start()
    pool_thread = threading.Thread(target=pool.run, daemon=True)
    pool_thread.start()
    items = []
    it = iter(learner_queue)
    while len(items) < n_items:
        item = next(it)
        if not isinstance(item, tuple):
            items.append(item)
        else:  # python queue __next__ yields the batch only
            items.append(item[0])
    batcher.close()
    learner_queue.close()
    pool_thread.join(5)
    serve.join(5)
    return [
        [np.asarray(leaf) for leaf in nest.flatten(item)] for item in items
    ]


def test_native_slot_framing_matches_python_pool():
    """Bit-identical learner batches: the same env stream + slot table
    dynamics through the C++ pool and the Python pool. Pins the slot
    framing wire contract (requests {env, slot, advance}, replies
    outputs-only, read_slot at unroll boundaries) end to end."""
    from torchbeast_tpu.envs import CountingEnv
    from torchbeast_tpu.runtime.env_server import EnvServer

    items = {}
    for kind in ("native", "python"):
        path = os.path.join(tempfile.mkdtemp(), f"slot_{kind}")
        server = EnvServer(
            lambda: CountingEnv(episode_length=EPISODE_LEN), f"unix:{path}"
        )
        server.start()
        deadline = time.monotonic() + 10
        while not os.path.exists(path):
            if time.monotonic() > deadline:
                raise TimeoutError("server did not bind")
            time.sleep(0.01)
        try:
            items[kind] = _collect_slot_items(kind, f"unix:{path}", 5)
        finally:
            server.stop()
    assert len(items["native"]) == len(items["python"])
    for native_item, python_item in zip(items["native"], items["python"]):
        assert len(native_item) == len(python_item)
        for native_leaf, python_leaf in zip(native_item, python_item):
            assert native_leaf.dtype == python_leaf.dtype
            np.testing.assert_array_equal(native_leaf, python_leaf)


# ---------------------------------------------------------------------------
# shm transport: the native pool over shared-memory rings served by the
# PYTHON env server (cross-language ring layout in anger), the crash ->
# reconnect contract, and the /dev/shm sweep.


def _start_counting_server_shm(path):
    from torchbeast_tpu.envs import CountingEnv
    from torchbeast_tpu.runtime.env_server import EnvServer

    server = EnvServer(
        lambda: CountingEnv(episode_length=EPISODE_LEN), f"shm:{path}"
    )
    server.start()
    deadline = time.monotonic() + 10
    while not os.path.exists(path):
        if time.monotonic() > deadline:
            raise TimeoutError("server did not bind")
        time.sleep(0.01)
    return server


def _run_native_pool(address, max_reconnects=0, **pool_kwargs):
    learner_queue = core.BatchingQueue(
        batch_dim=1, minimum_batch_size=1, maximum_batch_size=1
    )
    batcher = core.DynamicBatcher(batch_dim=1, timeout_ms=20)

    def inference():
        it = iter(batcher)
        while True:
            try:
                batch = next(it)
            except StopIteration:
                return
            inputs = batch.get_inputs()
            done = inputs["env"]["done"]
            state = np.where(done, 0, inputs["agent_state"]) + 1
            batch.set_outputs({
                "outputs": {
                    "action": np.zeros_like(done, np.int32),
                    "policy_logits": state[..., None].astype(np.float32),
                    "baseline": state.astype(np.float32),
                },
                "agent_state": state.astype(np.int64),
            })

    inf_thread = threading.Thread(target=inference, daemon=True)
    inf_thread.start()
    pool = core.ActorPool(
        unroll_length=T,
        learner_queue=learner_queue,
        inference_batcher=batcher,
        env_server_addresses=[address],
        initial_agent_state=np.zeros((1, 1), np.int64),
        max_reconnects=max_reconnects,
        **pool_kwargs,
    )
    pool_thread = threading.Thread(target=pool.run, daemon=True)
    pool_thread.start()
    return learner_queue, batcher, pool, pool_thread


def test_native_pool_shm_end_to_end():
    """C++ actor loops over shm rings created by the Python env server:
    the cross-language ring layout (header words, wrap/inline markers,
    doorbell bytes) carries real rollouts with the on-policy invariants
    held."""
    path = os.path.join(tempfile.mkdtemp(), "native_shm")
    server = _start_counting_server_shm(path)
    learner_queue, batcher, pool, pool_thread = _run_native_pool(
        f"shm:{path}"
    )
    items = []
    it = iter(learner_queue)
    while len(items) < 5:
        items.append(next(it))
    batcher.close()
    learner_queue.close()
    pool_thread.join(5)
    server.stop()
    assert pool.count() >= 5 * T
    prev = None
    for item in items:
        batch = item["batch"]
        assert batch["frame"].shape[:2] == (T + 1, 1)
        if prev is not None:
            for key in batch:
                np.testing.assert_array_equal(
                    batch[key][0], prev[key][-1], err_msg=key
                )
        assert (batch["frame"][batch["done"].astype(bool)] == 0).all()
        prev = batch
    telemetry = pool.telemetry()
    assert telemetry["env_steps"] == pool.count()
    assert telemetry["bytes_up"] > 0
    assert telemetry["bytes_down"] > 0
    assert telemetry["connects"] == 1
    # Doorbell-wait counters (ISSUE 10): cumulative, recheck wakeups
    # are a subset of armed waits.
    assert telemetry["ring_doorbell_waits"] >= 0
    assert 0 <= telemetry["ring_recheck_wakeups"] <= (
        telemetry["ring_doorbell_waits"]
    )


def _shm_segments():
    if not os.path.isdir("/dev/shm"):
        return set()
    return {n for n in os.listdir("/dev/shm")
            if n.startswith(("psm_", "tbtring_"))}


def _spawn_counting_server_proc(path):
    ctx = mp.get_context("spawn")
    proc = ctx.Process(
        target=_serve_counting_shm_child, args=(path,), daemon=True
    )
    proc.start()
    deadline = time.monotonic() + 30
    while not os.path.exists(path):
        if time.monotonic() > deadline:
            proc.kill()
            raise TimeoutError("spawned server did not bind")
        time.sleep(0.05)
    return proc


def _serve_counting_shm_child(path):
    from torchbeast_tpu.envs import CountingEnv
    from torchbeast_tpu.runtime.env_server import EnvServer

    EnvServer(lambda: CountingEnv(episode_length=5), f"shm:{path}").run()


@pytest.mark.slow
def test_native_shm_crash_reconnect_and_sweep():
    """Crash contract parity with the Python pool: SIGKILL the env
    server mid-ring — the native actor tears down that one connection,
    revives it against the restarted server, and its teardown sweep
    leaves /dev/shm clean (the dead owner never unlinks)."""
    before = _shm_segments()
    path = os.path.join(tempfile.mkdtemp(), "native_shm_crash")
    proc = _spawn_counting_server_proc(path)
    learner_queue, batcher, pool, pool_thread = _run_native_pool(
        f"shm:{path}", max_reconnects=3
    )
    try:
        it = iter(learner_queue)
        next(it)  # at least one rollout through the first connection

        proc.kill()  # SIGKILL: no cleanup, ring abandoned mid-stream
        proc.join(10)
        os.unlink(path)  # dead server's socket file lingers
        proc = _spawn_counting_server_proc(path)

        for _ in range(3):
            next(it)
        assert pool.first_error_message() is None
        assert pool.reconnect_count() >= 1
    finally:
        batcher.close()
        learner_queue.close()
        pool_thread.join(10)
        proc.kill()
        proc.join(10)
    leaked = _shm_segments() - before
    assert leaked == set(), f"leaked /dev/shm segments: {leaked}"


# ---------------------------------------------------------------------------
# Telemetry fold: the C++ counters/stage stamps land in the registry
# under the same series the Python runtime writes.


def test_native_telemetry_fold():
    from torchbeast_tpu.telemetry.metrics import MetricsRegistry
    from torchbeast_tpu.runtime.native import NativeTelemetryFolder

    queue = core.BatchingQueue(batch_dim=0, minimum_batch_size=1)
    batcher = core.DynamicBatcher(batch_dim=0)

    def producer():
        batcher.compute(np.zeros((1, 2), np.float32))

    t = threading.Thread(target=producer, daemon=True)
    t.start()
    batch = next(iter(batcher))
    batch.set_outputs(batch.get_inputs())
    t.join(5)
    queue.enqueue(np.zeros((1, 2), np.float32))
    queue.dequeue_many()

    class FakePool:
        """pool.telemetry() shape incl. the ISSUE 10 ring counters."""

        def __init__(self):
            self.waits = 7
            self.rechecks = 2

        def telemetry(self):
            return {
                "env_steps": 0, "connects": 0, "reconnects": 0,
                "bytes_up": 0, "bytes_down": 0,
                "ring_doorbell_waits": self.waits,
                "ring_recheck_wakeups": self.rechecks,
            }

        def stage_histograms(self):
            """Interval snapshots (reset on read), empty here."""
            return {"actor.env_rtt_s": {
                "buckets": {}, "total": 0.0, "total_sq": 0.0,
                "min": 0.0, "max": 0.0,
            }}

    fake_pool = FakePool()
    registry = MetricsRegistry()
    folder = NativeTelemetryFolder(
        registry, pool=fake_pool, batcher=batcher, queue=queue
    )
    folder.tick()
    assert registry.counter("ring.doorbell_waits").value() == 7
    assert registry.counter("ring.recheck_wakeups").value() == 2
    # Delta semantics: the fold credits increments, not absolutes.
    fake_pool.waits = 10
    assert registry.counter("learner_queue.items_in").value() == 1
    rtt = registry.histogram("actor.request_rtt_s")
    wait = registry.histogram("inference.request_wait_s")
    assert rtt.count == 1 and wait.count == 1
    assert rtt.mean >= wait.mean >= 0.0
    assert registry.histogram("learner_queue.batch_size").count == 1
    # Second tick: interval semantics — the queue/batcher series saw
    # nothing new (no double counting), and the ring counters credit
    # only the delta since the previous tick.
    folder.tick()
    assert registry.counter("learner_queue.items_in").value() == 1
    assert rtt.count == 1
    assert registry.counter("ring.doorbell_waits").value() == 10
    assert registry.counter("ring.recheck_wakeups").value() == 2
    queue.close()
    batcher.close()


# ---------------------------------------------------------------------------
# The wait for the GIL, stamped where _tbt_core takes it (ISSUE 36).

GIL_SITES = {
    "batcher_next", "get_inputs", "set_outputs", "learner_dequeue",
    "slot_hook", "buffer_release", "env_hook", "other",
}


def test_gil_wait_histograms_name_every_site():
    snaps = core.gil_wait_histograms()
    assert set(snaps) == GIL_SITES
    for snap in snaps.values():
        assert set(snap) == {
            "count", "total", "total_sq", "min", "max", "buckets",
        }
    # Reset on read: nothing crossed since.
    assert all(s["count"] == 0 for s in core.gil_wait_histograms().values())


def test_an_uncontended_crossing_waits_microseconds():
    from torchbeast_tpu.telemetry.metrics import bucket_index

    queue = core.BatchingQueue(batch_dim=0, minimum_batch_size=1)
    core.gil_wait_histograms()
    for _ in range(50):
        queue.enqueue(np.zeros((1, 2), np.float32))
        queue.dequeue_many()
    snaps = core.gil_wait_histograms()
    dequeue, enqueue = snaps["learner_dequeue"], snaps["other"]
    assert dequeue["count"] == 50 and enqueue["count"] >= 50
    assert sum(dequeue["buckets"].values()) == 50
    # Some other test's sleeping thread may wake once; most crossings
    # find the lock free.
    assert 0.0 <= dequeue["min"] < 100e-6
    assert dequeue["min"] <= dequeue["total"] / 50 <= dequeue["max"]
    # The registry's own bucket geometry, sample for sample.
    assert bucket_index(dequeue["max"]) == max(dequeue["buckets"])
    assert bucket_index(dequeue["min"]) == min(dequeue["buckets"])
    queue.close()


def test_a_crossing_waits_for_a_thread_that_holds_the_lock():
    """One thread sits in dequeue_many with the GIL dropped until the
    queue's own timeout hands it the short batch; no other thread
    touches Python then but this one, in a pure-Python loop, which
    gives the GIL up only at the interpreter's switch interval. The
    crossing asks for the lock twice: inside the GIL-free call, to let
    go of the numpy buffer the item borrowed (`buffer_release`), and at
    the call's end (`learner_dequeue`). Whichever comes while the loop
    holds the lock waits; the other finds it just handed over."""
    import sys

    queue = core.BatchingQueue(
        batch_dim=0, minimum_batch_size=2, timeout_ms=200
    )
    queue.enqueue(np.zeros((1, 2), np.float32))
    crossed = threading.Event()

    def cross():
        queue.dequeue_many()  # one row of two: back at the timeout
        crossed.set()

    interval = sys.getswitchinterval()
    sys.setswitchinterval(0.02)
    try:
        crosser = threading.Thread(target=cross, daemon=True)
        crosser.start()
        time.sleep(0.05)  # the crosser is inside dequeue_many
        core.gil_wait_histograms()
        deadline = time.monotonic() + 10.0
        spins = 0
        while not crossed.is_set() and time.monotonic() < deadline:
            spins += 1  # holds the GIL but for forced switches
    finally:
        sys.setswitchinterval(interval)
    crosser.join(5)
    assert crossed.is_set()
    snaps = core.gil_wait_histograms()
    assert snaps["learner_dequeue"]["count"] == 1
    assert snaps["buffer_release"]["count"] == 1
    waited = max(snaps[s]["max"] for s in ("learner_dequeue", "buffer_release"))
    assert 1e-3 <= waited < 5.0
    queue.close()


def test_the_folder_folds_gil_waits_and_the_thread_ledger():
    from torchbeast_tpu.runtime.native import NativeTelemetryFolder
    from torchbeast_tpu.telemetry.metrics import MetricsRegistry

    class Ledger:
        folds = 0
        asked = None

        def fold(self, min_interval_s=0.0):
            self.folds += 1
            self.asked = min_interval_s

    queue = core.BatchingQueue(batch_dim=0, minimum_batch_size=1)
    registry, ledger = MetricsRegistry(), Ledger()
    folder = NativeTelemetryFolder(registry, queue=queue, ledger=ledger)
    names = {f"host.gil_wait_s.{site}" for site in GIL_SITES}
    assert names <= set(registry.instruments())
    queue.enqueue(np.zeros((1, 2), np.float32))
    queue.dequeue_many()
    folder.tick()
    assert ledger.folds == 1
    dequeue = registry.histogram("host.gil_wait_s.learner_dequeue")
    assert dequeue.count == 1 and 0.0 <= dequeue.mean < 1.0
    # A site that never drops the lock holds no sample and reads 0.
    held = registry.histogram("host.gil_wait_s.set_outputs").merged()
    assert (held.count, held.total) == (0, 0.0)
    assert ledger.asked == 0.0  # called by name: the account as of now
    # The driver's periodic tick leaves the ledger its period.
    folder.tick(ledger_min_interval_s=30.0)
    assert dequeue.count == 1  # interval semantics: nothing twice
    assert (ledger.folds, ledger.asked) == (2, 30.0)
    # A folder with no native source (the fleet fold alone) leaves the
    # extension's one set of stamps to the folder that has one.
    bare = MetricsRegistry()
    NativeTelemetryFolder(bare).tick()
    assert not [n for n in bare.instruments() if "gil_wait" in n]
    queue.close()


def test_actor_threads_carry_their_name_in_the_kernel():
    """pthread_setname_np where the pool starts its loops: the thread
    ledger's role `actors` goes by this `comm`."""
    from torchbeast_tpu.telemetry.heartbeat import thread_role

    queue = core.BatchingQueue(batch_dim=0, minimum_batch_size=1)
    batcher = core.DynamicBatcher(batch_dim=0)
    pool = core.ActorPool(
        unroll_length=2, learner_queue=queue, inference_batcher=batcher,
        # Nobody listens: the loops dial until the timeout.
        env_server_addresses=[f"unix:{tempfile.mkdtemp()}/none"] * 2,
        initial_agent_state=(), connect_timeout_s=1.0, max_reconnects=0,
    )

    def run():
        try:
            pool.run()
        except RuntimeError:  # WaitForConnected() timed out
            pass

    runner = threading.Thread(target=run, daemon=True)
    runner.start()
    mine = {t.native_id for t in threading.enumerate()}
    deadline = time.monotonic() + 5.0
    named = []
    while len(named) < 2 and time.monotonic() < deadline:
        named = []
        for tid in os.listdir("/proc/self/task"):
            try:
                with open(f"/proc/self/task/{tid}/comm") as f:
                    comm = f.read().strip()
            except OSError:
                continue
            if comm == "tbt-actor":
                named.append(int(tid))
        time.sleep(0.01)
    batcher.close()
    queue.close()
    runner.join(10)
    assert len(named) == 2 and not set(named) & mine
    assert thread_role(None, "tbt-actor") == "actors"


# ---------------------------------------------------------------------------
# Adaptive doorbell recheck (ISSUE 12): the C++ policy pinned through the
# sim binding, and pinned BEHAVIORALLY against the Python policy (beastlint
# ATOMIC-ORDER pins the constants; this pins the walk).


def test_adaptive_recheck_cpp_tighten_and_relax():
    """A forced recheck-heavy window tightens the bound toward the
    floor; quiet windows relax it back to the cap; a mixed window
    inside the hysteresis band holds it."""
    from torchbeast_tpu.runtime import transport as transport_lib

    w = transport_lib._RECHECK_WINDOW
    init = int(transport_lib._WAKE_RECHECK_S * 1000)
    # Every wait ends on the timeout: halve per window down to the floor.
    bounds = core.adaptive_recheck_sim([True] * (4 * w))
    assert bounds[w - 1] == init // 2
    assert bounds[-1] == transport_lib._RECHECK_MIN_MS
    # Quiescent windows double back up to the cap.
    bounds = core.adaptive_recheck_sim([True] * (2 * w) + [False] * (8 * w))
    assert bounds[2 * w - 1] == transport_lib._RECHECK_MIN_MS
    assert bounds[-1] == transport_lib._RECHECK_MAX_MS
    # Inside the hysteresis band (between relax and tighten): hold.
    mixed = [True] * (transport_lib._RECHECK_TIGHTEN - 1)
    mixed += [False] * (w - len(mixed))
    assert core.adaptive_recheck_sim(mixed)[-1] == init


def test_adaptive_recheck_matches_python_policy():
    """Both languages walk IDENTICALLY on the same outcome sequence."""
    from torchbeast_tpu.runtime.transport import AdaptiveRecheck

    rng = np.random.default_rng(3)
    outcomes = [bool(b) for b in rng.integers(0, 2, 512)]
    policy = AdaptiveRecheck()
    py_bounds = []
    for outcome in outcomes:
        policy.record(outcome)
        py_bounds.append(policy.bound_ms)
    assert core.adaptive_recheck_sim(outcomes) == py_bounds


# ---------------------------------------------------------------------------
# Reconnect accounting (ISSUE 12 satellite): reconnect_count() reports
# COMPLETED recoveries, not granted retry attempts — one fault needing
# several dials counts once, on BOTH pools.


def _flaky_step_message(i):
    return {
        "type": "step",
        "frame": np.asarray([i % 250], np.uint8),
        "reward": np.asarray(0.0, np.float32),
        "done": np.asarray(False),
        "episode_step": np.asarray(i, np.int32),
        "episode_return": np.asarray(0.0, np.float32),
        "last_action": np.asarray(0, np.int32),
    }


class _FlakyServer:
    """Unix-socket env stream that (1) serves `serve_steps` steps then
    cuts the stream (the FAULT), (2) closes the next `fail_next`
    accepted connections BEFORE the initial step (failed recovery
    attempts), then (3) serves indefinitely (the completed recovery)."""

    def __init__(self, path, serve_steps=12, fail_next=2):
        import socket

        self._sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        self._sock.bind(path)
        self._sock.listen(8)
        self._serve_steps = serve_steps
        self._fail_next = fail_next
        self._phase = 0
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self):
        while True:
            try:
                conn, _ = self._sock.accept()
            except OSError:
                return  # listener closed: test teardown
            try:
                if self._phase == 0:
                    self._phase = 1
                    self._serve(conn, self._serve_steps)
                elif self._phase == 1 and self._fail_next > 0:
                    self._fail_next -= 1
                else:
                    self._phase = 2
                    self._serve(conn, None)
            except Exception:
                pass  # actor-side teardown cut the stream: expected
            finally:
                conn.close()

    def _serve(self, conn, limit):
        from torchbeast_tpu.runtime import wire

        i = 0
        wire.send_message(conn, _flaky_step_message(i))
        while limit is None or i < limit:
            if wire.recv_message(conn) is None:
                return
            i += 1
            wire.send_message(conn, _flaky_step_message(i))

    def close(self):
        self._sock.close()


def _run_python_pool(address, max_reconnects=0):
    from torchbeast_tpu.runtime.actor_pool import ActorPool
    from torchbeast_tpu.runtime.queues import BatchingQueue, DynamicBatcher

    learner_queue = BatchingQueue(
        batch_dim=1, minimum_batch_size=1, maximum_batch_size=1
    )
    batcher = DynamicBatcher(batch_dim=1, timeout_ms=20)

    def inference():
        it = iter(batcher)
        while True:
            try:
                batch = next(it)
            except StopIteration:
                return
            inputs = batch.get_inputs()
            done = inputs["env"]["done"]
            state = np.where(done, 0, inputs["agent_state"]) + 1
            batch.set_outputs({
                "outputs": {
                    "action": np.zeros_like(done, np.int32),
                    "policy_logits": state[..., None].astype(np.float32),
                    "baseline": state.astype(np.float32),
                },
                "agent_state": state.astype(np.int64),
            })

    threading.Thread(target=inference, daemon=True).start()
    pool = ActorPool(
        unroll_length=T,
        learner_queue=learner_queue,
        inference_batcher=batcher,
        env_server_addresses=[address],
        initial_agent_state=np.zeros((1, 1), np.int64),
        max_reconnects=max_reconnects,
    )
    pool_thread = threading.Thread(target=pool.run, daemon=True)
    pool_thread.start()
    return learner_queue, batcher, pool, pool_thread


@pytest.mark.parametrize("kind", ["native", "python"])
def test_reconnect_counts_completed_recoveries(kind):
    """One stream cut + two failed recovery dials + one successful one
    is ONE fault and ONE recovery: reconnect_count() == 1 on both
    pools (grant-counting would report 3, breaking chaos_run's
    reconnects == injections equality on a flaky re-dial)."""
    path = os.path.join(tempfile.mkdtemp(), f"flaky_{kind}")
    server = _FlakyServer(path, serve_steps=4 * T, fail_next=2)
    runner = _run_native_pool if kind == "native" else _run_python_pool
    learner_queue, batcher, pool, pool_thread = runner(
        f"unix:{path}", max_reconnects=3
    )
    try:
        items = 0
        it = iter(learner_queue)
        # 4 rollouts stream before the cut; needing 7 forces the pool
        # through the flaky recovery (2 dead dials, then success).
        while items < 7:
            next(it)
            items += 1
        assert pool.reconnect_count() == 1
        assert list(pool.errors) == []
        if kind == "native":
            assert pool.telemetry()["reconnects"] == 1
    finally:
        batcher.close()
        learner_queue.close()
        pool_thread.join(10)
        server.close()


# ---------------------------------------------------------------------------
# Native chaos hooks (ISSUE 12 tentpole b): the C++ FaultHooks entry
# points drive the same fault classes the Python FaultingTransport wrap
# does, with the same injected-exact contract.


def test_native_chaos_sever_forces_one_recovery():
    from torchbeast_tpu.envs import CountingEnv
    from torchbeast_tpu.runtime.env_server import EnvServer

    path = os.path.join(tempfile.mkdtemp(), "chaos_sever")
    server = EnvServer(
        lambda: CountingEnv(episode_length=EPISODE_LEN), f"unix:{path}"
    )
    server.start()
    deadline = time.monotonic() + 10
    while not os.path.exists(path):
        if time.monotonic() > deadline:
            raise TimeoutError("server did not bind")
        time.sleep(0.01)
    learner_queue, batcher, pool, pool_thread = _run_native_pool(
        f"unix:{path}", max_reconnects=3, fault_hooks=True
    )
    try:
        it = iter(learner_queue)
        next(it)  # the stream is live
        assert pool.chaos_sever(0) is True
        for _ in range(3):  # the pool recovers and keeps streaming
            next(it)
        assert pool.reconnect_count() == 1
        assert list(pool.errors) == []
        # A delay window on the live stream arms; bogus kinds are loud.
        assert pool.chaos_window(0, "transport_delay", 0.2, 0.001) is True
        with pytest.raises(ValueError):
            pool.chaos_window(0, "transport_teleport")
        # Ring corruption needs an shm transport: False here (retry),
        # exactly like the Python injector's None-ring path.
        assert pool.chaos_corrupt_ring(0, header=True) is False
    finally:
        batcher.close()
        learner_queue.close()
        pool_thread.join(10)
        server.stop()


def test_native_chaos_requires_armed_pool():
    """chaos_* on a pool built without fault_hooks=True fails loudly —
    a miswired driver must not silently abandon every fault."""
    queue = core.BatchingQueue(batch_dim=1, minimum_batch_size=1)
    batcher = core.DynamicBatcher(batch_dim=1)
    pool = core.ActorPool(
        unroll_length=T,
        learner_queue=queue,
        inference_batcher=batcher,
        env_server_addresses=[],
        initial_agent_state={},
    )
    with pytest.raises(ValueError, match="fault_hooks"):
        pool.chaos_sever(0)
    # And an armed pool with no live transport reports "retry".
    armed = core.ActorPool(
        unroll_length=T,
        learner_queue=queue,
        inference_batcher=batcher,
        env_server_addresses=[],
        initial_agent_state={},
        fault_hooks=True,
    )
    assert armed.chaos_sever(0) is False
    assert armed.chaos_window(0, "transport_blackhole", 0.1) is False
    assert armed.chaos_corrupt_ring(0) is False
    queue.close()
    batcher.close()


def test_native_chaos_corrupt_shm_ring_lands():
    """shm ring corruption through the hooks: the stomp observably
    lands (tail-stability contract) and the stream survives — either
    via the WireError -> reconnect path or, in the documented narrow
    window, a reader that already latched the clean header (corruption
    is injected-exact, recovery-probable)."""
    path = os.path.join(tempfile.mkdtemp(), "chaos_ring")
    server = _start_counting_server_shm(path)
    learner_queue, batcher, pool, pool_thread = _run_native_pool(
        f"shm:{path}", max_reconnects=3, fault_hooks=True
    )
    try:
        it = iter(learner_queue)
        next(it)
        injected = False
        deadline = time.monotonic() + 10
        while not injected and time.monotonic() < deadline:
            injected = pool.chaos_corrupt_ring(0, header=True)
            if not injected:
                time.sleep(0.0005)  # ring momentarily empty: retry
        assert injected
        for _ in range(3):  # still streaming (reconnected or unharmed)
            next(it)
        assert list(pool.errors) == []
        assert pool.reconnect_count() in (0, 1)
    finally:
        batcher.close()
        learner_queue.close()
        pool_thread.join(10)
        server.stop()


# ---------------------------------------------------------------------------
# Native graceful degradation, driver-level (ISSUE 12 tentpole a): the
# polybeast HEALTHY/DEGRADED/HALTED machine drives the C++ pool exactly
# like the Python one.


def _poly_flags(tmp_path, **overrides):
    from torchbeast_tpu import polybeast

    argv = [
        "--env", "Mock",
        "--num_servers", "2",
        "--batch_size", "2",
        "--unroll_length", "5",
        "--total_steps", "2000",
        "--savedir", str(tmp_path),
        "--xpid", "native-degrade",
        "--model", "mlp",
        "--pipes_basename", f"unix:{tmp_path}/pipes",
        "--num_inference_threads", "1",
        "--max_inference_batch_size", "4",
        "--checkpoint_interval_s", "100000",
        "--native_runtime",
    ]
    for k, v in overrides.items():
        argv += [f"--{k}"] if v is True else [f"--{k}", str(v)]
    return polybeast.make_parser().parse_args(argv)


@pytest.mark.slow
def test_native_sigkill_above_floor_recovers(tmp_path):
    """A supervised env-server SIGKILL (via a native chaos plan) while
    live actors stay at/above the floor: the server respawns, the
    actor reconnects, the run completes every step, and the recovery
    counters record EXACTLY one respawn + one completed reconnect."""
    import json as json_lib

    from torchbeast_tpu import polybeast

    plan_path = tmp_path / "plan.json"
    plan_path.write_text(json_lib.dumps({
        "seed": 7,
        "faults": [
            {"kind": "env_server_sigkill", "at_step": 400, "target": 0}
        ],
    }))
    flags = _poly_flags(
        tmp_path, xpid="native-above-floor", total_steps="3000",
        min_live_actors="1", chaos_plan=str(plan_path),
    )
    stats = polybeast.train(flags)
    assert stats["step"] >= 3000
    assert stats["health"] in ("HEALTHY", "DEGRADED")
    assert stats["chaos"]["injected"] == {"env_server_sigkill": 1}
    assert stats["server_restarts"] == 1
    assert stats["actor_reconnects"] == 1


@pytest.mark.slow
def test_native_attrition_degrades_above_floor(tmp_path):
    """Kill one of two servers PERMANENTLY (respawn disabled): its
    actor burns the reconnect budget and retires, the run goes (and
    stays — attrition is sticky) DEGRADED, and still completes on the
    surviving actor because live >= --min_live_actors."""
    import json as json_lib

    from torchbeast_tpu import polybeast

    plan_path = tmp_path / "plan.json"
    plan_path.write_text(json_lib.dumps({
        "seed": 7,
        "faults": [
            {"kind": "env_server_sigkill", "at_step": 300, "target": 0}
        ],
    }))
    flags = _poly_flags(
        tmp_path, xpid="native-degraded", total_steps="4000",
        min_live_actors="1", max_server_restarts="0",
        max_actor_reconnects="1", actor_connect_timeout_s="2",
        chaos_plan=str(plan_path),
    )
    stats = polybeast.train(flags)
    assert stats["step"] >= 4000
    assert stats["health"] == "DEGRADED"
    assert any(
        "retired" in reason for _, reason in stats["health_reasons"]
    )


@pytest.mark.slow
def test_native_floor_crossing_halts_cleanly(tmp_path):
    """Kill BOTH servers permanently: both actors retire, live crosses
    the --min_live_actors floor, and the run checkpoints and exits
    CLEANLY with health HALTED (no exception, total_steps unreachable)
    — the native half of the PR 6 floor contract."""
    import json as json_lib

    from torchbeast_tpu import polybeast

    plan_path = tmp_path / "plan.json"
    plan_path.write_text(json_lib.dumps({
        "seed": 7,
        "faults": [
            {"kind": "env_server_sigkill", "at_step": 300, "target": 0},
            {"kind": "env_server_sigkill", "at_step": 300, "target": 1},
        ],
    }))
    flags = _poly_flags(
        tmp_path, xpid="native-halted", total_steps="100000000",
        min_live_actors="1", max_server_restarts="0",
        max_actor_reconnects="1", actor_connect_timeout_s="2",
        chaos_plan=str(plan_path),
    )
    stats = polybeast.train(flags)  # returns instead of raising/hanging
    assert stats["health"] == "HALTED"
    assert any(
        "below --min_live_actors" in reason
        for _, reason in stats["health_reasons"]
    )
    assert (tmp_path / "native-halted" / "model.ckpt").exists()


# ---------------------------------------------------------------------------
# Native request spans (ISSUE 12 tentpole c): sampled C++ stage stamps
# fold into the tracer as the same actor.request.* spans the Python pool
# emits.


def test_native_trace_spans_fold():
    from torchbeast_tpu.runtime.native import NativeTelemetryFolder
    from torchbeast_tpu.telemetry.metrics import MetricsRegistry
    from torchbeast_tpu.telemetry.trace import Tracer

    batcher = core.DynamicBatcher(batch_dim=0, timeout_ms=5)

    def serve():
        it = iter(batcher)
        while True:
            try:
                batch = next(it)
            except StopIteration:
                return
            batch.set_outputs(batch.get_inputs())

    serve_thread = threading.Thread(target=serve, daemon=True)
    serve_thread.start()
    # 1-in-256 sampling: 512 computes guarantee >= 2 recorded spans.
    for _ in range(512):
        batcher.compute(np.zeros((1, 1), np.float32))

    tracer = Tracer()
    folder = NativeTelemetryFolder(
        MetricsRegistry(), batcher=batcher, tracer=tracer
    )
    folder.tick()
    events = [e for e in tracer.events() if e["cat"] == "actor.request"]
    names = {e["name"] for e in events}
    assert {"actor.request",
            "actor.request.batch",
            "actor.request.reply"} <= names
    assert len([e for e in events if e["name"] == "actor.request"]) >= 2
    for e in events:
        assert e["dur"] >= 0
    # Drained: a second tick folds nothing new.
    before = len(tracer.events())
    folder.tick()
    assert len(tracer.events()) == before
    batcher.close()
    serve_thread.join(5)
