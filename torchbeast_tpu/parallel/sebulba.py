"""Sebulba serving: per-slice pinned inference for the device split.

The Podracer Sebulba architecture (arXiv:2104.06272, PAPERS.md) splits a
pod into dedicated inference slices and a learner mesh. This module owns
the SERVING half for the async driver: given a resolved
`runtime.placement.DeviceSplit` and the learner's `PolicySnapshotStore`,
it builds one serving stack per inference slice —

- a `DynamicBatcher` of its own (telemetry series
  `inference.slice.<i>.*`), so a slow slice backs up its own queue
  instead of head-of-line-blocking the others;
- a `DeviceStateTable` PINNED to the slice device (the table buffer,
  slot ids, and env inputs are all committed there — zero cross-slice
  agent-state traffic, pinned by the transfer-guard test in
  tests/test_sebulba.py);
- `ReplicaServingHooks` pinned to the same device: every batch serves
  from the latest `PolicySnapshotStore` snapshot placed device-to-device
  via `latest_on` (no host round-trip), stamps the true `policy_lag`
  into the reply, and drives the health machine per slice
  (`slice<i>_lag` keys) when lag exceeds --max_policy_lag;
- an `inference_loop` body ready for the InferenceSupervisor.

Routing is the `SliceRouter`: a batcher-shaped facade the actor pool
talks to unchanged. Requests carrying a `slot` id (the device-resident
acting path) route by the split's STATIC hash-by-slot assignment — an
actor's slot lives on one slice for the life of the run, across
reconnects and serving restarts, so slot tables never migrate between
devices. Stateless requests (no slot, nothing resident to migrate)
round-robin for load balance.

Lag semantics under the split: unlike replica serving, there is no
central live-params path to fall back to — the live params live on the
learner mesh, and serving from them would put acting batches back on
learner chips (exactly what the split removes). A slice whose snapshot
exceeds the lag budget therefore keeps serving the NEWEST snapshot it
has while the health machine reports DEGRADED (keyed per slice) until a
fresh publish lands — same stamping, same budget, same recovery
transitions as the replica path.
"""

import logging
import threading
from typing import Any, Callable, List, Optional

import numpy as np

from torchbeast_tpu import telemetry
from torchbeast_tpu.runtime.inference import inference_loop
from torchbeast_tpu.runtime.placement import DeviceSplit

log = logging.getLogger(__name__)


class SliceStack:
    """One inference slice's serving resources."""

    def __init__(self, index: int, device, batcher, state_table, hooks,
                 loop_fn: Callable[[], None]):
        self.index = index
        self.device = device
        self.batcher = batcher
        self.state_table = state_table
        self.hooks = hooks
        self.loop_fn = loop_fn


class ShardedStateTables:
    """The actor-pool / supervisor / chaos view over per-slice tables.

    The pool reads boundary state (`read_slot`) and resets slots on
    (re)connect; the InferenceSupervisor rebuilds on poison; the chaos
    controller pokes `poison()`. Each call routes to (or fans out over)
    the per-slice tables by the split's static slot hash, so callers
    keep the single-table API they had before the split.
    """

    def __init__(self, split: DeviceSplit, tables: List):
        if len(tables) != split.n_slices:
            raise ValueError(
                f"{len(tables)} tables for {split.n_slices} slices"
            )
        self._split = split
        self._tables = list(tables)
        self.num_slots = tables[0].num_slots
        self.initial_state_host = tables[0].initial_state_host

    def table_for_slot(self, slot: int):
        return self._tables[self._split.slice_for_slot(slot)]

    @property
    def trash_slot(self) -> int:
        return self._tables[0].trash_slot

    def read_slot(self, slot: int) -> Any:
        return self.table_for_slot(slot).read_slot(slot)

    def reset(self, slots) -> None:
        # Group by owning slice: one reset dispatch per touched table.
        by_slice = {}
        for slot in np.asarray(slots).reshape(-1):
            by_slice.setdefault(
                self._split.slice_for_slot(int(slot)), []
            ).append(int(slot))
        for idx, group in by_slice.items():
            self._tables[idx].reset(group)

    @property
    def poisoned(self) -> bool:
        """Any slice poisoned: the supervisor rebuilds ALL of them as
        one recovery event (serving threads share one restart
        generation, so per-slice rebuilds would double-count)."""
        return any(t.poisoned for t in self._tables)

    def poison(self) -> None:
        """Chaos hook: one poison event poisons every slice (the
        supervisor's rebuild is all-or-nothing either way)."""
        for t in self._tables:
            t.poison()

    def rebuild(self) -> None:
        for t in self._tables:
            if t.poisoned:
                t.rebuild()


class SliceRouter:
    """Batcher-shaped facade routing actor requests to their slice.

    Shaped like a DynamicBatcher from the actor pool's side
    (compute/size/is_closed), same as serving.ReplicaRouter. Requests
    with a `slot` leaf route by the split's static hash; slot-less
    (stateless-model) requests round-robin — they carry no resident
    state, so there is nothing to keep pinned.
    """

    def __init__(self, split: DeviceSplit, stacks: List[SliceStack],
                 registry=None):
        self._split = split
        self._stacks = stacks
        self._rr_lock = threading.Lock()
        self._rr = 0  # guarded-by: self._rr_lock
        reg = registry if registry is not None else telemetry.get_registry()
        self._c_requests = [
            reg.counter(f"inference.slice.{s.index}.requests")
            for s in stacks
        ]

    def _slice_for(self, inputs) -> int:
        if isinstance(inputs, dict) and "slot" in inputs:
            slot = int(np.asarray(inputs["slot"]).reshape(-1)[0])
            return self._split.slice_for_slot(slot)
        with self._rr_lock:
            self._rr = (self._rr + 1) % len(self._stacks)
            return self._rr

    def compute(self, inputs, trace=None):
        idx = self._slice_for(inputs)
        stack = self._stacks[idx]
        # Per-request lag gate: with a dedicated slice there is no
        # fresher fallback than the newest snapshot, so the return
        # value is advisory — the call's job is driving the health
        # machine's per-slice keyed degradation/recovery transitions.
        if stack.hooks is not None:
            stack.hooks.serving_ok()
        self._c_requests[idx].inc()
        if trace is not None:
            out = stack.batcher.compute(inputs, trace=trace)
        else:
            out = stack.batcher.compute(inputs)
        return out

    def size(self) -> int:
        return sum(s.batcher.size() for s in self._stacks)

    def is_closed(self) -> bool:
        return self._stacks[0].batcher.is_closed()

    def close_all(self) -> None:
        for s in self._stacks:
            try:
                s.batcher.close()
            except RuntimeError:
                pass  # already closed


class SebulbaServing:
    """The assembled serving side of a device split."""

    def __init__(self, split: DeviceSplit, stacks: List[SliceStack],
                 router: SliceRouter,
                 state_tables: Optional[ShardedStateTables]):
        self.split = split
        self.stacks = stacks
        self.router = router
        self.state_tables = state_tables

    @property
    def loop_fns(self) -> List[Callable[[], None]]:
        return [s.loop_fn for s in self.stacks]

    def gauge_tick(self, registry=None) -> Callable[[], None]:
        """A DriverTelemetry tick callback keeping the per-slice depth
        gauges fresh on every exported line."""
        reg = (
            registry if registry is not None else telemetry.get_registry()
        )
        pairs = [
            (reg.gauge(f"inference.slice.{s.index}.depth"), s.batcher)
            for s in self.stacks
        ]

        def tick():
            for gauge, batcher in pairs:
                gauge.set(batcher.size())

        return tick


def slice_gauge_snapshot(registry=None, prefix: str = "inference.slice."):
    """{name: value} of the per-slice serving instruments — the fleet
    heartbeat payload (fleet/coordinator.py `set_gauges_source`): a
    remote host ships its `inference.slice.<i>.*` gauges and counters
    to the lead every heartbeat, where NativeTelemetryFolder re-exports
    them as `host<r>.inference.slice.<i>.*`. Histograms are skipped —
    heartbeats carry scalars, not bucket dicts."""
    reg = registry if registry is not None else telemetry.get_registry()
    out = {}
    for name, inst in reg.instruments().items():
        if not name.startswith(prefix):
            continue
        value = getattr(inst, "value", None)
        if callable(value):  # Counter / Gauge; Histogram has no value()
            out[name] = float(value())
    return out


def build_sebulba_serving(
    split: DeviceSplit,
    store,
    *,
    num_slots: int,
    max_batch_size: int,
    timeout_ms: float,
    max_policy_lag: int,
    rng_seed: int = 0,
    initial_state: Any = None,
    table_act_fn: Optional[Callable] = None,
    legacy_act_fn: Optional[Callable] = None,
    input_filter: Optional[Callable] = None,
    health=None,
    registry=None,
    admission=None,
    throttle_fn: Optional[Callable] = None,
    batch_dim: int = 1,
    batcher_factory: Optional[Callable] = None,
) -> SebulbaServing:
    """Assemble one serving stack per inference slice.

    `batcher_factory(i, name)` overrides per-slice batcher
    construction — the native serving plane (ISSUE 16) passes a
    factory returning C++ `_tbt_core.DynamicBatcher`s so the actor
    pool's C++ SliceRouter fans out without touching Python, while
    the Python serving loops (and the state tables, hooks, and
    telemetry prefixes built here) stay identical.

    `initial_state` + `table_act_fn`: the device-resident path — one
    pinned DeviceStateTable per slice, context (snapshot params)
    provided per batch by the slice's hooks; the table owns its rng
    key. With `initial_state=None` the legacy path serves instead:
    `legacy_act_fn(env, state, batch_size, ctx)` receives `(params,
    key)` from the hooks as its 4th argument (the replica act-path
    shape). Either way slice i draws from the stream seeded
    `rng_seed + 7919 * (i + 1)`, so slices keep distinct streams.

    One shared `admission` controller gates every slice's batcher (the
    serving.* counters aggregate; the depth bound applies per queue).

    Known trade-off: every slice's table allocates the FULL
    `num_slots`+1 rows although the static hash routes only
    ~1/n_slices of the slots to it — slot ids stay GLOBAL, so the
    pool, the facade, and the trash-slot padding all share one id
    space with no remap layer. At recurrent-state sizes (KBs/slot)
    the duplication is noise; if a future model carries MBs of state
    per slot, size tables per owned-slot-count with a
    slice_for_slot-derived row remap (its own change: the remap
    touches every slot-framing consumer).
    """
    from torchbeast_tpu.runtime.queues import DynamicBatcher

    reg = registry if registry is not None else telemetry.get_registry()
    stateful = initial_state is not None
    if stateful and table_act_fn is None:
        raise ValueError("stateful slices need table_act_fn")
    if not stateful and legacy_act_fn is None:
        raise ValueError("stateless slices need legacy_act_fn")

    if stateful:
        import jax

        from torchbeast_tpu.runtime.state_table import DeviceStateTable

    stacks = []
    tables = []
    for i, device in enumerate(split.inference_devices):
        name = f"inference.slice.{i}"
        slice_seed = rng_seed + 7919 * (i + 1)
        if batcher_factory is not None:
            batcher = batcher_factory(i, name)
        else:
            batcher = DynamicBatcher(
                batch_dim=batch_dim,
                minimum_batch_size=1,
                maximum_batch_size=max_batch_size,
                timeout_ms=timeout_ms,
                telemetry_name=name,
                admission=admission,
            )
        hooks = None
        if store is not None:
            from torchbeast_tpu.serving import ReplicaServingHooks

            hooks = ReplicaServingHooks(
                store,
                max_policy_lag=max_policy_lag,
                rng_seed=slice_seed,
                health=health,
                batch_dim=batch_dim,
                registry=reg,
                device=device,
                health_key=f"slice{i}_lag",
            )
        table = None
        if stateful:
            table = DeviceStateTable(
                initial_state,
                num_slots=num_slots,
                act_fn=table_act_fn,
                context_fn=None,  # hooks provide params per batch
                batch_dim=batch_dim,
                input_filter=input_filter,
                device=device,
                rng_key=jax.random.PRNGKey(slice_seed),
            )
            tables.append(table)

        def loop_fn(batcher=batcher, table=table, hooks=hooks, name=name):
            inference_loop(
                batcher,
                None if table is not None else legacy_act_fn,
                max_batch_size,
                batch_dim=batch_dim,
                lock=None,
                state_table=table,
                serving_hooks=hooks,
                throttle_fn=throttle_fn,
                telemetry_prefix=name,
            )

        stacks.append(
            SliceStack(i, device, batcher, table, hooks, loop_fn)
        )

    state_tables = (
        ShardedStateTables(split, tables) if stateful else None
    )
    router = SliceRouter(split, stacks, registry=reg)
    return SebulbaServing(split, stacks, router, state_tables)
