"""TPU inference server loop: drain the DynamicBatcher with a jitted,
bucket-padded forward.

The reference's inference threads run the model on whatever batch size the
batcher produced (polybeast_learner.py:269-285) — fine for CUDA, hostile to
XLA, where every distinct batch size is a recompile (SURVEY.md §7 hard part
#1). Here each dynamic batch is padded up to the nearest power-of-two bucket
(the last row repeated — see pad_to), the jitted step runs at that static
shape (one compile per bucket, a handful total), and the outputs are sliced
back to the true size before set_outputs distributes rows to the waiting
actors.

Two state regimes:

- Legacy (state_table=None): requests carry `agent_state`; the loop pads
  it alongside the env nest and the reply materializes the advanced state
  back to the actor — state crosses the host boundary twice per step.
- Device-resident (state_table=DeviceStateTable): requests carry a `slot`
  id and an `advance` flag instead of state; the table's jitted step
  gathers/advances/scatters state entirely on device and the reply holds
  outputs only. Padding rows scatter to the table's trash slot so they
  can never race a real slot's update.

A serving loop is two threads with one role each (inference_loop): the
launcher takes batches and dispatches act programs, the replier it owns
fetches each batch's outputs and answers its actors.
"""

# beastlint: hot-module — every function here sits on the per-batch serving path.

import logging
import queue
import threading
import time
from typing import Any, Callable, List

import numpy as np

from torchbeast_tpu import nest
from torchbeast_tpu import telemetry

log = logging.getLogger(__name__)

# Dispatched batches the launcher may hand over ahead of the replier
# before it blocks: with one in the replier's hands, one handed over and
# one dispatched and waiting to be, the device has a program queued
# behind the one it runs, which is all that running ahead can buy. A
# launcher further ahead than that only cuts the requests that wait
# into smaller batches (a device slower than the launcher, as the CPU
# backend is: every batch costs it a program). Where the host sets the
# pace the hand-over is empty or holds one.
_HANDOVER_DEPTH = 1


def bucket_size(n: int, buckets: List[int]) -> int:
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(f"Batch of {n} exceeds largest bucket {buckets[-1]}")


def default_buckets(max_batch_size: int) -> List[int]:
    out = []
    b = 1
    while b < max_batch_size:
        out.append(b)
        b *= 2
    out.append(max_batch_size)
    return out


def pad_to(tree: Any, size: int, batch_dim: int) -> Any:
    """Pad every leaf to `size` along batch_dim by repeating the edge row
    (valid data, so the padded forward can't produce NaNs that would
    poison batch-norm-style reductions; pad rows are sliced off after).
    Written out, not `np.pad(mode="edge")`: that spends some 30 us of
    Python a leaf whatever its size, in the launcher's prep, which the
    replier waits out for the GIL (tests keep np.pad as the reference)."""

    def pad(arr):
        arr = np.asarray(arr)
        n = arr.shape[batch_dim]
        if n == size:
            return arr
        shape = list(arr.shape)
        shape[batch_dim] = size
        out = np.empty(shape, arr.dtype)
        rows = [slice(None)] * arr.ndim
        rows[batch_dim] = slice(0, n)
        out[tuple(rows)] = arr
        rows[batch_dim] = slice(n - 1, n)
        edge = arr[tuple(rows)]
        rows[batch_dim] = slice(n, size)
        out[tuple(rows)] = edge
        return out

    return nest.map(pad, tree)


def slice_to(tree: Any, size: int, batch_dim: int) -> Any:
    def cut(arr):
        arr = np.asarray(arr)
        sl = [slice(None)] * arr.ndim
        sl[batch_dim] = slice(0, size)
        return arr[tuple(sl)]

    return nest.map(cut, tree)


def pad_slots(slots: np.ndarray, size: int, trash_slot: int) -> np.ndarray:
    """Pad a [n] slot-id vector to `size` with the table's trash slot —
    NOT edge-repeated: a repeated real id would make the padded row's
    scatter race the real row's (duplicate-index scatter is last-writer-
    wins, so the real advance could be silently dropped)."""
    slots = np.asarray(slots).reshape(-1)
    if slots.shape[0] == size:
        return slots
    return np.concatenate(
        [slots, np.full(size - slots.shape[0], trash_slot, slots.dtype)]
    )


def pad_advance(advance: np.ndarray, size: int) -> np.ndarray:
    """Pad a [n] advance mask to `size` with False (padding rows must
    never persist a state advance)."""
    advance = np.asarray(advance, bool).reshape(-1)
    if advance.shape[0] == size:
        return advance
    return np.concatenate(
        [advance, np.zeros(size - advance.shape[0], bool)]
    )


def inference_loop(
    inference_batcher,
    act_fn: Callable,
    max_batch_size: int,
    batch_dim: int = 1,
    lock: threading.Lock = None,
    state_table=None,
    serving_hooks=None,
    throttle_fn: Callable = None,
    telemetry_prefix: str = "inference",
):
    """One serving loop: a launcher/replier pair (polybeast runs
    --num_inference_threads of these per batcher).

    The CALLING thread is the launcher: wait for a batch, prep it (pad
    to its bucket), dispatch the act program (`state_table.step` or
    `act_fn`; asynchronous, returns device arrays) — and nothing else.
    Each dispatched batch goes, in order, over a bounded hand-over
    (`_HANDOVER_DEPTH`; a full one blocks the launcher) to the REPLIER,
    a thread this call starts on entry and owns: fetch the outputs (the
    `device_get` — the one blocking device round trip of a batch), slice
    them to the true size, `annotate`, `set_outputs`. So a launch never
    waits on a `device_get`, and a reply goes out the instant its
    outputs land, whether or not another batch exists. The replier's
    life is this call's: on EVERY exit (batcher closed, an exception,
    a poisoned table) the launcher hands over a stop mark, the replier
    answers what was dispatched before it, and the call joins it before
    it returns or raises — no reply is dropped and no replier outlives
    its loop.

    act_fn(env_outputs, agent_state, batch_size) ->
        (agent_outputs, new_agent_state)   # numpy or device arrays

    With `state_table` (a runtime.state_table.DeviceStateTable), requests
    carry {"env", "slot", "advance"} instead of {"env", "agent_state"}:
    the table's own jitted step (which gets params from its context_fn
    and owns the rng key) gathers/advances/scatters agent state on
    device and `act_fn` is ignored (pass None). Replies then hold
    {"outputs"} only — no state leaf ever crosses the host boundary
    (tests/test_state_table.py pins this with jax.transfer_guard).

    act_fn owns params access and rng threading (see polybeast.py). Pass
    ONE lock shared by every inference thread to serialize model calls
    (the reference's inference lock, polybeast_learner.py:269, 281-283);
    with lock=None calls run concurrently (safe for pure jitted act_fns —
    the device serializes execution anyway).

    A failing act_fn fails only its batch (promises broken with the error
    so producers wake immediately), and so does a failing fetch on the
    replier; the loop continues serving. Exception: a failed STATE-TABLE
    step poisons the table (its buffer is donated into the dispatch, so
    it may already be consumed) — the loop fails the batch, lets the
    replier answer (or fail) the batches dispatched before it, joins it
    and re-raises to end the pair rather than serve garbage.

    `serving_hooks` (serving/replica.ReplicaServingHooks, or anything
    with the same `begin_batch() -> (params, annotate)` shape, plus
    `next_key()` where there is no table) turns this loop into a
    REPLICA serving loop: each batch's params override the state
    table's own context (snapshot params instead of live ones) — or,
    on the legacy path, ride with a key the loop asks the hooks for as
    a 4th act_fn argument (`act_fn(env, state, batch_size, (params,
    key))`) — and `annotate(outputs, n)` stamps the matching policy_lag
    into the reply at flush time, so the lag recorded is the lag of the
    params that actually served.

    `throttle_fn` (resilience/chaos.ChaosController.throttle) is the
    chaos harness's shared-chip stall model: called once per batch
    before dispatch; sleeps while a learner_stall window is active so
    induced overload grows the batcher queue the way a busy chip would.

    `telemetry_prefix` names this loop's instrument series (default
    "inference", today's schema). The Sebulba split runs one loop per
    inference slice with prefix "inference.slice.<i>" so per-slice
    batch/latency series land on every telemetry line instead
    of aggregating into one indistinguishable pile.
    """
    buckets = default_buckets(max_batch_size)

    # Stage attribution for the serving loop: batch-size distribution
    # and four spans. Three tile the launcher's iteration — wait_batch
    # (blocked in the batcher: no request ready), prep (host: inputs,
    # bucket, padding), dispatch (async — the time to hand XLA the
    # program, not device compute) — and reply covers the replier's
    # work on a batch (the device fetch + row slicing + set_outputs
    # actors actually wait on). Each is a histogram `<span>_s` and, on
    # the profiler's clock, `pb:<span>`; resolved once, used every
    # batch. Between dispatch and reply a batch waits in the hand-over
    # (a full one, and a replier still answering the batch before):
    # the histogram handover_wait_s, no span, since the reply span opens
    # right after and names the gap already.
    _reg = telemetry.get_registry()
    _tracer = telemetry.get_tracer()

    def _span(stage):
        return _tracer.span(f"{telemetry_prefix}.{stage}", cat="inference")

    _sp_wait, _sp_prep = _span("wait_batch"), _span("prep")
    _sp_dispatch, _sp_reply = _span("dispatch"), _span("reply")
    # Registered only when a lock exists: a permanently-zero histogram
    # reads as "requests never wait", not "not measured".
    _sp_lock_wait = _span("lock_wait") if lock is not None else None
    _h_batch = _reg.histogram(f"{telemetry_prefix}.batch_size")
    _c_batches = _reg.counter(f"{telemetry_prefix}.batches")
    _c_rows = _reg.counter(f"{telemetry_prefix}.rows")
    # Whether the split engages: the launches made while a reply was
    # still outstanding (handed over or in the replier's hands).
    _c_overlapped = _reg.counter(f"{telemetry_prefix}.overlapped_dispatches")
    _h_handover = _reg.histogram(f"{telemetry_prefix}.handover_wait_s")
    # A Python DynamicBatcher with a telemetry_name already observes
    # inference.batch_size per dequeued batch — observing here too
    # would double-count it. The loop keeps that role only for
    # un-instrumented batchers (the C++ native runtime).
    _observe_sizes = getattr(inference_batcher, "_tm", None) is None

    def flush(entry):
        batch, outputs, new_state, n, annotate = entry
        with _sp_reply:
            try:
                if state_table is not None:
                    # Device-side slice + one explicit device_get; the
                    # reply carries no agent-state leaves.
                    fetched = state_table.fetch(outputs, n)
                    if annotate is not None:
                        fetched = annotate(fetched, n)
                    batch.set_outputs({"outputs": fetched})
                    return
                outputs = nest.map(np.asarray, outputs)
                new_state = nest.map(np.asarray, new_state)
                outputs = slice_to(outputs, n, batch_dim)
                if annotate is not None:
                    outputs = annotate(outputs, n)
                batch.set_outputs(
                    {
                        "outputs": outputs,
                        "agent_state": slice_to(new_state, n, batch_dim),
                    }
                )
            except Exception as e:  # noqa: BLE001
                log.exception("Inference reply failed; continuing")
                batch.fail(e)

    # The hand-over's own count of entries put and not yet marked done
    # IS the number of replies outstanding, so nothing else is shared
    # between the two threads.
    handover = queue.Queue(maxsize=_HANDOVER_DEPTH)

    def reply_loop():
        while True:
            entry = handover.get()
            if entry is None:
                return
            handed_at, dispatched = entry
            _h_handover.observe(time.perf_counter() - handed_at)
            try:
                flush(dispatched)
            finally:
                handover.task_done()

    replier = threading.Thread(
        target=reply_loop,
        name=f"{threading.current_thread().name}-replier",
        daemon=True,
    )
    replier.start()
    batches = iter(inference_batcher)
    try:
        while True:
            # The stall gate runs BEFORE the blocking pull: a stalled
            # chip does not pick work up, so queued requests age toward
            # their deadline and the dequeue-side expiry gate sees the
            # truth. A throttle placed after the pull would grab fresh
            # requests and hold them un-expirable for the whole window.
            if throttle_fn is not None:
                throttle_fn()
            with _sp_wait:
                batch = next(batches, None)
            if batch is None:
                break
            try:
                with _sp_prep:
                    inputs = batch.get_inputs()
                    env_outputs = inputs["env"]
                    n = len(batch)
                    if _observe_sizes:
                        _h_batch.observe(n)
                    _c_batches.inc()
                    _c_rows.inc(n)
                    padded = bucket_size(n, buckets)
                    env_padded = pad_to(env_outputs, padded, batch_dim)
                    # Replica mode: ONE atomic (snapshot ctx, lag
                    # annotation) pick per batch, so the lag stamped
                    # into the reply is the lag of the params this
                    # dispatch actually used.
                    ctx = annotate = None
                    if serving_hooks is not None:
                        ctx, annotate = serving_hooks.begin_batch()
                    if state_table is not None:
                        slots = pad_slots(
                            inputs["slot"], padded, state_table.trash_slot
                        )
                        advance = pad_advance(inputs["advance"], padded)
                    else:
                        state_padded = pad_to(
                            inputs["agent_state"], padded, batch_dim
                        )
                        act_args = (env_padded, state_padded, padded)
                        if serving_hooks is not None:
                            # No table to own the rng chain: the hooks do.
                            act_args += ((ctx, serving_hooks.next_key()),)
                    if handover.unfinished_tasks:
                        _c_overlapped.inc()

                # inference.dispatch_s times ONLY the act dispatch (the
                # host handing XLA the program) — padding is prep and
                # the lock wait has its own span; folding them in would
                # double-count stages and misattribute a lock
                # bottleneck to XLA.
                if state_table is not None:
                    with _sp_dispatch:
                        outputs = state_table.step(
                            slots, advance, env_padded, context=ctx
                        )
                    new_state = None
                elif lock is not None:
                    with _sp_lock_wait:
                        # beastlint: disable=LOCK-DISCIPLINE  the span closes on the acquire and the try/finally release follows at once
                        lock.acquire()
                    try:
                        with _sp_dispatch:
                            outputs, new_state = act_fn(*act_args)
                    finally:
                        lock.release()
                else:
                    with _sp_dispatch:
                        outputs, new_state = act_fn(*act_args)
            except Exception as e:  # noqa: BLE001
                batch.fail(e)
                if state_table is not None and state_table.poisoned:
                    # The donated table buffer may already be consumed;
                    # per-batch retry would serve garbage state. Die
                    # loudly — with the TYPED error, so a supervising
                    # wrapper (resilience.InferenceSupervisor) can
                    # distinguish "rebuild the table and restart me"
                    # from a real serving bug that must stay fatal.
                    from torchbeast_tpu.runtime.errors import (
                        StateTablePoisonedError,
                    )

                    log.exception(
                        "State table poisoned; serving loop exiting"
                    )
                    if isinstance(e, StateTablePoisonedError):
                        raise
                    raise StateTablePoisonedError(
                        f"state table poisoned by: {type(e).__name__}: {e}"
                    ) from e
                log.exception("Inference batch failed; continuing")
                continue
            # Dispatched (async): the reply is the replier's from here.
            handover.put(
                (time.perf_counter(), (batch, outputs, new_state, n, annotate))
            )
    finally:
        # Every exit: the replier answers (or fails) what was
        # dispatched before the stop mark reaches it, then ends.
        handover.put(None)
        replier.join()
