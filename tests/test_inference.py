"""inference_loop (runtime/inference.py): bucket padding, row routing,
and the launcher/replier split — the launcher dispatches the next batch
while an earlier reply is still outstanding, a reply goes out the
instant its outputs land (a lone request is answered with nothing
behind it), a failing fetch fails only its batch, and on every exit
(batcher closed, poisoned table) outstanding replies are delivered or
failed and the replier is joined."""

import threading
import time

import numpy as np
import pytest

from torchbeast_tpu import telemetry
from torchbeast_tpu.runtime.errors import StateTablePoisonedError
from torchbeast_tpu.runtime.inference import (
    _HANDOVER_DEPTH,
    bucket_size,
    default_buckets,
    inference_loop,
    pad_advance,
    pad_slots,
    pad_to,
    slice_to,
)
from torchbeast_tpu.runtime.queues import AsyncError, DynamicBatcher

WAIT_S = 10  # every wait in this file is bounded; none should take 1 s


def _act_fn(env_outputs, agent_state, batch_size):
    """Identity-ish act: output = frame * 2, state = state + 1. Batch
    rows keep their values, so routing errors are detectable."""
    assert env_outputs["frame"].shape[1] == batch_size
    return (
        {"action": env_outputs["frame"] * 2},
        {"h": agent_state["h"] + 1},
    )


def _request(i):
    return {
        "env": {"frame": np.full((1, 1, 3), i, np.float32)},
        "agent_state": {"h": np.full((1, 1, 2), 10 * i, np.float32)},
    }


def _slot_request(i):
    return {
        "env": {"frame": np.full((1, 1, 3), i, np.float32)},
        "slot": np.full((1, 1), i, np.int32),
        "advance": np.full((1, 1), True, bool),
    }


class FakeTable:
    """A state table on the host: `step` doubles the frames; `fetch`
    can be held on an event (a device_get that has not landed), made
    to raise for one batch, and `step` made to poison the table."""

    trash_slot = 99

    def __init__(self):
        self.poisoned = False
        self.steps = 0
        self.fetch_gate = None  # threading.Event the fetch waits on
        self.fetch_entered = threading.Event()
        self.fail_fetch_of = set()  # first-row values whose fetch raises
        self.poison_step = None  # the step (0-based) that poisons

    def step(self, slots, advance, env_outputs, context=None):
        if self.steps == self.poison_step:
            self.poisoned = True
            raise RuntimeError("donated buffer consumed")
        self.steps += 1
        return {"action": env_outputs["frame"] * 2}

    def fetch(self, outputs, n):
        self.fetch_entered.set()
        if self.fetch_gate is not None:
            assert self.fetch_gate.wait(WAIT_S)
        if float(outputs["action"][0, 0, 0]) / 2 in self.fail_fetch_of:
            raise RuntimeError("fetch failed")
        return {"action": outputs["action"][:, :n]}


def _batcher(max_batch=8):
    return DynamicBatcher(
        batch_dim=1, minimum_batch_size=1, maximum_batch_size=max_batch,
        timeout_ms=5,
    )


class Serving:
    """One inference_loop on a thread of its own, with its own series
    (a prefix a test) and the error it ended with."""

    def __init__(self, prefix, batcher, act_fn=None, state_table=None,
                 max_batch=8):
        self.prefix, self.batcher, self.error = prefix, batcher, None

        def run():
            try:
                inference_loop(
                    batcher, act_fn, max_batch, state_table=state_table,
                    telemetry_prefix=prefix,
                )
            except BaseException as e:  # noqa: BLE001
                self.error = e

        self.thread = threading.Thread(
            target=run, name=f"serving-{prefix}", daemon=True
        )
        self.thread.start()

    def counter(self, name):
        reg = telemetry.get_registry()
        return reg.counter(f"{self.prefix}.{name}").value()

    def repliers(self):
        return [
            t for t in threading.enumerate()
            if t.name == f"serving-{self.prefix}-replier"
        ]

    def join(self):
        self.thread.join(WAIT_S)
        assert not self.thread.is_alive()
        # The replier's life is the loop's.
        assert self.repliers() == []


class Producers:
    """One thread a request, each blocked in compute() until its reply
    (or its error) arrives."""

    def __init__(self, batcher, make_request):
        self.batcher, self.make_request = batcher, make_request
        self.results, self.errors, self.threads = {}, {}, []

    def send(self, i):
        def run():
            try:
                self.results[i] = self.batcher.compute(self.make_request(i))
            except Exception as e:  # noqa: BLE001
                self.errors[i] = e

        thread = threading.Thread(target=run, daemon=True)
        thread.start()
        self.threads.append(thread)
        return thread

    def join(self):
        for thread in self.threads:
            thread.join(WAIT_S)
        assert not any(t.is_alive() for t in self.threads)


def _wait_for(condition):
    deadline = time.monotonic() + WAIT_S
    while not condition():
        assert time.monotonic() < deadline
        time.sleep(0.001)


@pytest.mark.parametrize("table", [False, True])
def test_rows_route_back_with_several_batches_in_flight(table):
    """16 producers against batches of at most 4 and a replier that
    starts late: batches pile up in the hand-over (the launcher blocks
    when it is full) and every row still reaches its own producer, on
    the table path and the table-less one."""
    batcher = _batcher(max_batch=4)
    fake = FakeTable() if table else None
    gate = threading.Event()
    if table:
        fake.fetch_gate = gate
    serving = Serving(
        f"route_{table}", batcher, None if table else _act_fn,
        state_table=fake, max_batch=4,
    )
    producers = Producers(batcher, _slot_request if table else _request)
    n = 16
    for i in range(n):
        producers.send(i)
    if table:
        # At least four batches exist and the first reply is held:
        # the launcher runs on ahead of it, as far as the hand-over
        # lets it.
        _wait_for(lambda: fake.steps == _HANDOVER_DEPTH + 2)
        assert not producers.results
        gate.set()
    producers.join()
    assert not producers.errors, producers.errors
    assert len(producers.results) == n
    for i, out in producers.results.items():
        np.testing.assert_array_equal(
            out["outputs"]["action"], np.full((1, 1, 3), 2 * i, np.float32)
        )
        if table:
            assert set(out) == {"outputs"}
        else:
            np.testing.assert_array_equal(
                out["agent_state"]["h"],
                np.full((1, 1, 2), 10 * i + 1, np.float32),
            )
    assert serving.counter("rows") == n
    batcher.close()
    serving.join()
    assert serving.error is None


def test_lone_request_is_answered_without_a_second_one():
    """Nothing behind it: the reply must not wait for another batch."""
    batcher = _batcher()
    serving = Serving("lone", batcher, _act_fn)
    producers = Producers(batcher, _request)
    producers.send(3).join(WAIT_S)
    np.testing.assert_array_equal(
        producers.results[3]["outputs"]["action"],
        np.full((1, 1, 3), 6, np.float32),
    )
    assert serving.counter("batches") == 1
    assert serving.counter("overlapped_dispatches") == 0
    batcher.close()
    serving.join()


def test_launcher_dispatches_batch_2_before_reply_1_is_released():
    """With reply 1 held inside fetch (a device_get that has not
    landed), the launcher takes and dispatches batch 2; the counter
    that says the split engages counts that launch."""
    batcher = _batcher()
    table = FakeTable()
    table.fetch_gate = threading.Event()
    serving = Serving("overlap", batcher, state_table=table)
    producers = Producers(batcher, _slot_request)
    producers.send(1)
    assert table.fetch_entered.wait(WAIT_S)  # reply 1 is in the replier
    producers.send(2)
    _wait_for(lambda: table.steps == 2)  # batch 2 dispatched meanwhile
    assert not producers.results
    assert serving.counter("overlapped_dispatches") == 1
    table.fetch_gate.set()
    producers.join()
    assert sorted(producers.results) == [1, 2] and not producers.errors
    assert serving.counter("batches") == 2
    # One of the two launches found a reply outstanding, not both.
    assert serving.counter("overlapped_dispatches") == 1
    batcher.close()
    serving.join()


def test_requests_one_at_a_time_overlap_nothing():
    batcher = _batcher()
    serving = Serving("serial", batcher, state_table=FakeTable())
    producers = Producers(batcher, _slot_request)
    for i in range(5):
        producers.send(i).join(WAIT_S)
        assert i in producers.results
        # set_outputs wakes the producer a moment before the replier
        # marks the entry done; the next launch must not see it.
        time.sleep(0.05)
    assert serving.counter("batches") == 5
    assert serving.counter("overlapped_dispatches") == 0
    batcher.close()
    serving.join()


def test_raising_fetch_fails_only_its_batch():
    batcher = _batcher()
    table = FakeTable()
    table.fail_fetch_of = {2.0}
    serving = Serving("bad_fetch", batcher, state_table=table)
    producers = Producers(batcher, _slot_request)
    for i in (1, 2, 3):
        producers.send(i).join(WAIT_S)
    assert sorted(producers.results) == [1, 3]
    assert isinstance(producers.errors[2], AsyncError)
    assert "fetch failed" in str(producers.errors[2])
    assert serving.thread.is_alive() and len(serving.repliers()) == 1
    batcher.close()
    serving.join()
    assert serving.error is None


def test_poisoned_step_answers_outstanding_replies_then_raises():
    """Batch 1 is dispatched and its reply held; batch 2's step poisons
    the table. Batch 2 fails at once, the loop waits for the replier to
    deliver batch 1, joins it and raises the typed error."""
    batcher = _batcher()
    table = FakeTable()
    table.fetch_gate = threading.Event()
    table.poison_step = 1
    serving = Serving("poison", batcher, state_table=table)
    producers = Producers(batcher, _slot_request)
    first = producers.send(1)
    assert table.fetch_entered.wait(WAIT_S)
    producers.send(2).join(WAIT_S)
    assert isinstance(producers.errors[2], AsyncError)
    # The launcher is in its exit now, waiting on the replier.
    assert first.is_alive() and serving.thread.is_alive()
    table.fetch_gate.set()
    producers.join()
    np.testing.assert_array_equal(
        producers.results[1]["outputs"]["action"],
        np.full((1, 1, 3), 2, np.float32),
    )
    serving.join()
    assert isinstance(serving.error, StateTablePoisonedError)
    batcher.close()


def test_closing_the_batcher_delivers_pending_replies_and_joins():
    batcher = _batcher(max_batch=1)
    table = FakeTable()
    table.fetch_gate = threading.Event()
    serving = Serving("close", batcher, state_table=table, max_batch=1)
    producers = Producers(batcher, _slot_request)
    for i in range(3):
        producers.send(i)
    _wait_for(lambda: table.steps == 3)  # all dispatched, none replied
    batcher.close()
    time.sleep(0.05)
    assert serving.thread.is_alive()  # the loop waits for its replier
    assert not producers.results
    table.fetch_gate.set()
    producers.join()
    assert sorted(producers.results) == [0, 1, 2] and not producers.errors
    serving.join()
    assert serving.error is None


def test_full_hand_over_blocks_the_launcher():
    """The launcher cannot run further ahead than the hand-over holds:
    one batch in the replier's hands, _HANDOVER_DEPTH handed over, one
    dispatched and waiting to be; the rest stay in the batcher."""
    batcher = _batcher(max_batch=1)
    table = FakeTable()
    table.fetch_gate = threading.Event()
    serving = Serving("full", batcher, state_table=table, max_batch=1)
    producers = Producers(batcher, _slot_request)
    n = _HANDOVER_DEPTH + 5
    for i in range(n):
        producers.send(i)
    _wait_for(lambda: table.steps == _HANDOVER_DEPTH + 2)
    time.sleep(0.05)
    assert table.steps == _HANDOVER_DEPTH + 2
    table.fetch_gate.set()
    producers.join()
    assert len(producers.results) == n and not producers.errors
    assert serving.counter("overlapped_dispatches") >= _HANDOVER_DEPTH + 1
    batcher.close()
    serving.join()


def test_hand_over_wait_is_the_batches_behind_a_held_replier():
    """A replier held in flush (a device_get that has not landed) with
    two batches behind it, one handed over and one whose launcher
    blocks on the full hand-over: both wait out the hold, which
    handover_wait_s sees (the entry's stamp is taken before the put)
    and their own reply_s does not. The hold is 30 ms and not the 3 ms
    the replier spends on a batch, to stand clear of a loaded host's
    scheduling."""
    hold_s = 0.03
    batcher = _batcher(max_batch=1)
    table = FakeTable()
    table.fetch_gate = threading.Event()
    serving = Serving("handover", batcher, state_table=table, max_batch=1)
    producers = Producers(batcher, _slot_request)
    producers.send(0)
    assert table.fetch_entered.wait(WAIT_S)  # reply 0 is in the replier
    producers.send(1)
    _wait_for(lambda: table.steps == 2)
    producers.send(2)
    _wait_for(lambda: table.steps == 3)  # both dispatched and stamped
    time.sleep(hold_s)
    table.fetch_gate.set()
    producers.join()
    assert len(producers.results) == 3 and not producers.errors
    reg = telemetry.get_registry()
    # A producer wakes inside set_outputs, a moment before its reply's
    # span closes.
    _wait_for(lambda: reg.histogram("handover.reply_s").count == 3)
    waited = reg.histogram("handover.handover_wait_s").merged()
    replied = reg.histogram("handover.reply_s").merged()
    assert waited.count == replied.count == 3
    # Batches 1 and 2 each waited out the hold; batch 0 found the
    # replier idle.
    assert waited.total >= 2 * hold_s
    assert waited.min < hold_s / 3
    # The hold is inside reply 0 (its fetch) and in no other reply.
    assert replied.max >= hold_s
    assert replied.total - replied.max < hold_s
    batcher.close()
    serving.join()


def test_two_pairs_on_one_batcher_answer_every_request():
    """--num_inference_threads 2: two launcher/replier pairs drain one
    batcher; every producer gets its own rows, over many rounds, with
    the interpreter switching threads as often as it can."""
    import sys

    batcher = _batcher(max_batch=4)
    servings = [
        Serving("pairs", batcher, _act_fn, max_batch=4) for _ in range(2)
    ]
    errors = []

    def producer(i):
        try:
            for step in range(50):
                out = batcher.compute(_request(i + step))
                assert out["outputs"]["action"][0, 0, 0] == 2 * (i + step)
                assert out["agent_state"]["h"][0, 0, 0] == 10 * (i + step) + 1
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [
            threading.Thread(target=producer, args=(i,), daemon=True)
            for i in range(16)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(3 * WAIT_S)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    assert not errors, errors
    assert servings[0].counter("rows") == 16 * 50
    batcher.close()
    for serving in servings:
        serving.thread.join(WAIT_S)
        assert not serving.thread.is_alive() and serving.error is None
    assert servings[0].repliers() == []


class TestBuckets:
    """Edge cases for the power-of-two bucket schedule."""

    def test_default_buckets_exact_power_of_two(self):
        assert default_buckets(8) == [1, 2, 4, 8]
        assert default_buckets(1) == [1]

    def test_default_buckets_non_power_of_two_max(self):
        # The true max batch size caps the schedule even off-power-of-two
        # (a 48-actor run must not pad every full batch up to 64).
        assert default_buckets(48) == [1, 2, 4, 8, 16, 32, 48]

    def test_bucket_size_rounds_up_within_schedule(self):
        buckets = default_buckets(8)
        assert bucket_size(1, buckets) == 1
        assert bucket_size(3, buckets) == 4
        assert bucket_size(8, buckets) == 8

    def test_bucket_size_overflow_raises(self):
        with pytest.raises(ValueError, match="exceeds largest bucket"):
            bucket_size(9, default_buckets(8))


class TestPadSlice:
    """pad_to repeats the LAST row (np.pad mode="edge") — pinned here so
    the module docstring and the code can't drift apart again — and
    slice_to inverts it exactly."""

    def _tree(self, n):
        return {
            "frame": np.arange(n, dtype=np.float32).reshape(1, n, 1) + 1,
            "nested": {"r": np.arange(n, dtype=np.float32)[None] * 10},
        }

    def test_pad_repeats_last_row_not_row_zero(self):
        padded = pad_to(self._tree(3), 8, batch_dim=1)
        assert padded["frame"].shape == (1, 8, 1)
        # Rows 3..7 repeat row 2 (value 3.0) — NOT row 0 (value 1.0).
        np.testing.assert_array_equal(
            padded["frame"][0, :, 0],
            np.asarray([1, 2, 3, 3, 3, 3, 3, 3], np.float32),
        )
        np.testing.assert_array_equal(
            padded["nested"]["r"][0],
            np.asarray([0, 10, 20, 20, 20, 20, 20, 20], np.float32),
        )

    @pytest.mark.parametrize("n,bucket", [(1, 1), (3, 4), (4, 4), (1, 8)])
    def test_pad_slice_round_trip(self, n, bucket):
        """slice_to(pad_to(x)) == x, including the n == bucket identity
        and the n == 1 single-row edge."""
        tree = self._tree(n)
        padded = pad_to(tree, bucket, batch_dim=1)
        for leaf in (padded["frame"], padded["nested"]["r"]):
            assert leaf.shape[1] == bucket
        back = slice_to(padded, n, batch_dim=1)
        np.testing.assert_array_equal(back["frame"], tree["frame"])
        np.testing.assert_array_equal(
            back["nested"]["r"], tree["nested"]["r"]
        )

    @pytest.mark.parametrize("batch_dim", [0, 1, 2])
    @pytest.mark.parametrize("dtype", [np.uint8, np.float32, bool, np.int64])
    def test_pad_matches_numpy_edge_padding(self, batch_dim, dtype):
        """The written-out pad against np.pad(mode="edge"), the
        reference it replaced: equal values, shape and dtype on any
        axis."""
        rng = np.random.default_rng(batch_dim)
        arr = (rng.integers(0, 2, (3, 5, 4, 2)) * 7).astype(dtype)
        width = [(0, 0)] * arr.ndim
        width[batch_dim] = (0, 8 - arr.shape[batch_dim])
        want = np.pad(arr, width, mode="edge")
        got = pad_to({"x": arr}, 8, batch_dim)["x"]
        assert got.dtype == want.dtype and got.shape == want.shape
        np.testing.assert_array_equal(got, want)

    def test_pad_to_exact_size_is_identity_object(self):
        tree = self._tree(4)
        padded = pad_to(tree, 4, batch_dim=1)
        # No copy when nothing pads: the hot path hands the same arrays on.
        assert padded["frame"] is tree["frame"]


class TestSlotPadding:
    """State-table framing helpers: padding must target the trash slot
    with advance=False — an edge-repeated real id would make the padded
    row's scatter race the real row's (last-writer-wins)."""

    def test_pad_slots_uses_trash_not_edge(self):
        padded = pad_slots(np.asarray([3, 5], np.int32), 4, trash_slot=7)
        np.testing.assert_array_equal(
            padded, np.asarray([3, 5, 7, 7], np.int32)
        )

    def test_pad_slots_exact_size_identity(self):
        slots = np.asarray([1, 2], np.int32)
        np.testing.assert_array_equal(
            pad_slots(slots, 2, trash_slot=9), slots
        )

    def test_pad_advance_pads_false(self):
        padded = pad_advance(np.asarray([True, True]), 5)
        np.testing.assert_array_equal(
            padded, np.asarray([True, True, False, False, False])
        )
