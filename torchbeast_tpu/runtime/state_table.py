"""Device-resident agent-state table for the async acting path.

The legacy inference wiring ships recurrent state with every request:
actors enqueue `{"env", "agent_state"}`, the server pads BOTH, runs the
forward, and materializes the new state back to numpy so each actor can
send it up again next step (runtime/inference.py). For an LSTM that is
two `[L, 1, H]` float32 leaves crossing the host boundary twice per env
step per actor — pure overhead (the Podracer architectures,
arXiv:2104.06272, keep policy state on the accelerator for exactly this
reason).

Here the state lives in a `[.., num_slots+1, ..]`-per-leaf on-device
pytree keyed by slot id (one slot per actor). The jitted step gathers
the batch's states by slot index, runs the bound acting function, and
scatters the advanced states back — all inside ONE dispatch, with the
table buffer donated so the update is in-place in HBM. Per env step the
only host↔device traffic is observations down and actions/logits up;
agent state never crosses (pinned by the transfer-guard test in
tests/test_state_table.py).

One runtime call per act batch: `step` hands its host inputs (slot ids,
advance mask, env leaves) to the jitted program as the numpy arrays
they are — the call itself transfers them — and the acting rng key
lives in the donated carry beside the table, split INSIDE the program.
So a serving loop enters the runtime (and lets go of the GIL) twice a
batch, on a thread each (runtime/inference.py): the launch on its
launcher, `fetch`'s device_get on its replier. The table owns the key
for every caller: contexts carry params only.

Layout/contract notes:

- Slot `num_slots` is a TRASH slot: bucket padding scatters its rows
  there, so padded rows can never race a real slot's update (a masked
  scatter with duplicate indices would be last-writer-wins —
  nondeterministic about whether the real row's advance survives).
- Real slot ids must be unique within a batch. The actor pool
  guarantees this structurally: each actor owns one slot and has at
  most one request in flight.
- `advance=False` rows write their CURRENT state back (a no-op write):
  the actor pool's priming call computes agent outputs without
  persisting the state advance, same as the legacy `advance=False`
  path (reference monobeast.py:145-147).
- Dispatch is serialized under an internal lock because the table
  buffer is donated — a second dispatch against an already-donated
  reference would be a use-after-free. The same lock serializes the
  rng chain (the key rides in the donated carry), so every batch draws
  a distinct subkey with no lock of the key's own. `read_slot`/`reset`
  share the lock; the host fetch in `read_slot` happens OUTSIDE it on a
  fresh (non-donated) gather output, so the inference hot path never
  blocks behind a rollout-boundary fetch.
"""

# beastlint: hot-module — the table dispatch runs once per acting batch.

import threading
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from torchbeast_tpu import nest
from torchbeast_tpu import telemetry


# Canonical re-export: the class lives in runtime/errors.py so the
# jax-free catch sites (actor pool, inference supervisor) can import it
# without pulling this module's jax dependency.
from torchbeast_tpu.runtime.errors import (  # noqa: F401
    StateTablePoisonedError,
)


def _leaves(tree) -> bool:
    return bool(jax.tree_util.tree_leaves(tree))


class DeviceStateTable:
    """On-device `[.., num_slots+1, ..]` agent-state pytree keyed by slot.

    act_fn(ctx, env_outputs, agent_state) -> (outputs, new_agent_state)
        Pure/traceable; runs INSIDE the table's jitted step. `ctx` is
        the pair `(context, subkey)`: `context` is whatever
        `context_fn()` returned (or `step`'s `context=` override; the
        drivers pass params) and `subkey` is this batch's fresh PRNG
        key, split inside the program from the table's own chain. Both
        are traced, so fresh params per call never trigger a recompile.

    Per-bucket static shapes: one compile per (batch bucket) — the
    same compile discipline as the legacy bucket-padded forward.
    """

    def __init__(
        self,
        initial_state: Any,
        num_slots: int,
        act_fn: Callable,
        context_fn: Optional[Callable] = None,
        batch_dim: int = 1,
        input_filter: Optional[Callable] = None,
        device=None,
        rng_key=None,
    ):
        """`device` (optional): pin the table — and every dispatch — to
        one specific jax device. The Sebulba split (runtime/placement.py)
        builds one table per inference slice this way: the initial
        state and the rng key are committed there and the jitted step
        carries that placement (in/out shardings built here, once), so
        the host inputs land on that device inside the call and the
        donated table buffer never leaves it. Context leaves (params)
        are the CALLER's placement responsibility — the slice serving
        hooks place them on the same device (a mixed-device dispatch is
        a jax error, not a silent transfer). None keeps the
        default-device behavior.

        `rng_key` seeds the acting rng chain (default PRNGKey(0)). The
        table never donates the caller's array: the chain starts at
        `fold_in(rng_key, 0)`, and the k-th `rebuild()` restarts it at
        `fold_in(rng_key, k)`, so a rebuilt table does not replay the
        stream."""
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        if not _leaves(initial_state):
            raise ValueError(
                "DeviceStateTable needs a non-empty state pytree; "
                "feed-forward models should use the legacy stateless path"
            )
        self.num_slots = num_slots
        self.batch_dim = batch_dim
        self.device = device
        self._act_fn = act_fn
        self._context_fn = context_fn
        self._input_filter = input_filter
        self._lock = threading.Lock()
        # Pure-host telemetry (spans + dict increments only): adds no
        # device syncs to the acting hot path — pinned by the
        # transfer-guard test in tests/test_telemetry.py. The two
        # parts of step() are children of the serving loop's dispatch
        # span (its launcher's); fetch is the child of its reply span
        # (its replier's, another thread).
        self._tm_dispatches = telemetry.get_registry().counter(
            "state_table.dispatches"
        )
        _tracer = telemetry.get_tracer()
        self._sp_context = _tracer.span("state_table.context")
        self._sp_call = _tracer.span("state_table.call")
        self._sp_fetch = _tracer.span("state_table.fetch")
        self._sp_read_slot = _tracer.span("state_table.read_slot")

        bd = batch_dim
        for leaf in jax.tree_util.tree_leaves(initial_state):
            if np.ndim(leaf) <= bd or np.shape(leaf)[bd] != 1:
                raise ValueError(
                    "initial_state leaves must have size 1 along "
                    f"batch_dim {bd}; got shape {np.shape(leaf)}"
                )
        self._initial = jax.tree_util.tree_map(
            jnp.asarray, initial_state
        )
        if device is not None:
            # Commit the initial state to the pinned device: every
            # derived value (_fresh_table's tile, reset's gather) then
            # computes — and stays — there.
            self._initial = jax.device_put(self._initial, device)
        # Cached host copy: the actor pool hands it to rollouts as the
        # boundary state for freshly-connected actors.
        self.initial_state_host = jax.tree_util.tree_map(
            np.asarray, initial_state
        )
        # +1: the trash slot for bucket-padding rows.
        rows = num_slots + 1

        def expand(leaf):
            reps = [1] * leaf.ndim
            reps[bd] = rows
            return jnp.tile(leaf, reps)

        self._expand = expand
        self._initial_key = (
            jax.random.PRNGKey(0) if rng_key is None else rng_key
        )
        self._rebuilds = 0
        # The donated carry: (table, key), replaced whole by every step.
        self._table = self._fresh_table()
        self._key = self._fresh_key(0)

        def index(slots):
            return (slice(None),) * bd + (slots,)

        def gather(table, slots):
            return jax.tree_util.tree_map(
                lambda leaf: jnp.take(leaf, slots, axis=bd), table
            )

        def scatter(table, slots, values):
            return jax.tree_util.tree_map(
                lambda t, v: t.at[index(slots)].set(v), table, values
            )

        def step(carry, slots, advance, context, env_outputs):
            table, key = carry
            key, subkey = jax.random.split(key)
            state = gather(table, slots)
            outputs, new_state = act_fn(
                (context, subkey), env_outputs, state
            )

            def merge(new, old):
                shape = [1] * new.ndim
                shape[bd] = advance.shape[0]
                return jnp.where(advance.reshape(shape), new, old)

            merged = jax.tree_util.tree_map(merge, new_state, state)
            return (scatter(table, slots, merged), key), outputs

        def reset(table, slots, initial):
            values = jax.tree_util.tree_map(
                lambda leaf: jnp.take(
                    leaf, jnp.zeros_like(slots), axis=bd
                ),
                initial,
            )
            return scatter(table, slots, values)

        placement = {}
        if device is not None:
            pinned = jax.sharding.SingleDeviceSharding(device)
            placement = {"in_shardings": pinned, "out_shardings": pinned}
        self._step_jit = jax.jit(step, donate_argnums=(0,), **placement)
        self._reset_jit = jax.jit(reset, donate_argnums=(0,))
        self._gather_jit = jax.jit(gather)

    def _fresh_table(self):
        """A brand-new [.., num_slots+1, ..] table, every slot at the
        initial state."""
        return jax.tree_util.tree_map(self._expand, self._initial)

    def _fresh_key(self, chain: int):
        """The start of rng chain number `chain`: a new buffer (the
        step donates it), on the pinned device if there is one."""
        key = jax.random.fold_in(self._initial_key, chain)
        if self.device is not None:
            key = jax.device_put(key, self.device)
        return key

    @property
    def trash_slot(self) -> int:
        """Slot id bucket padding scatters to (never read back)."""
        return self.num_slots

    @property
    def poisoned(self) -> bool:
        """True after a table-mutating dispatch failed. The table buffer
        is donated into every step/reset, so a dispatch that raises may
        already have consumed it — continuing would be a use-after-free
        with garbage state. All further calls raise
        StateTablePoisonedError; the serving loop re-raises to kill its
        thread rather than retry per-batch, and the inference
        supervisor (resilience/supervisor.py) owns the recovery:
        `rebuild()` + a thread restart under a bounded budget.

        Read under the table lock (cold path: exception handling and
        supervisor recovery only), so a concurrent poison/rebuild is
        seen whole rather than half-observed (RACE burn-down, ISSUE 7)."""
        with self._lock:
            return self._table is None

    def poison(self) -> None:
        """Chaos/testing hook: put the table into the poisoned state a
        failed donated dispatch produces (resilience/chaos.py's
        `state_table_poison` fault). The dropped buffer is reclaimed by
        XLA once its in-flight uses retire."""
        with self._lock:
            self._table = None

    def rebuild(self) -> None:
        """Recover from poisoning: a fresh table, every actor slot back
        at the initial state. Serving threads may restart immediately
        after. Actors whose request was in the FAILED batch re-prime
        via their batch-retry path (partial rollout discarded, same as
        a reconnect), so their slot state and rollout boundaries
        re-align. Actors with NO request in flight at poison time
        continue their current unroll against a silently-reset slot —
        a bounded mid-unroll state glitch (at most one unroll per
        actor per rebuild), equivalent to the episode-boundary resets
        V-trace already absorbs; pinned acceptable by the chaos
        harness's return-parity check. The rng chain restarts from a
        key no earlier table used (see `rng_key`)."""
        with self._lock:
            self._rebuilds += 1
            self._table = self._fresh_table()
            self._key = self._fresh_key(self._rebuilds)

    def _require_alive(self):
        if self._table is None:
            raise StateTablePoisonedError(
                "DeviceStateTable is poisoned: a prior step/reset failed "
                "after its table buffer was donated; rebuild() it (the "
                "inference supervisor does) before serving again"
            )

    def _put_ids(self, slots):
        return jax.device_put(
            np.asarray(slots, np.int32).reshape(-1), self.device
        )

    def step(self, slots, advance, env_outputs, context=None):
        """One acting dispatch over already-padded inputs: ONE runtime
        call.

        slots: [n] int ids (padding rows = trash_slot), advance: [n]
        bool, env_outputs: env nest padded to n along batch_dim — host
        (numpy) arrays, handed to the jitted step as they are: the call
        transfers them, there is no device_put in front of it, and the
        batch's rng subkey is split inside the program from the key in
        the donated carry. Under `jax.transfer_guard("disallow")` this
        hand-over is the one transfer allowed by name (a
        host-to-device "allow" scoped to the launch); device-to-host
        stays the caller's guard, so a state leaf that crosses still
        raises. Returns the on-device outputs nest (fetch with `fetch`).

        `context` overrides the table's own context_fn for THIS
        dispatch — the replica serving path (serving/replica.py) feeds
        snapshot params through the same jitted step (context leaves
        are traced arguments, so a replica batch never recompiles); the
        state rows gathered/scattered — and the rng chain — are the
        shared table's either way, so state continuity is preserved
        across routing changes.

        `input_filter` (host-side) subsets the env nest to what act_fn
        actually reads: leaves the model ignores would otherwise still
        be transferred every dispatch and fatten the jit signature —
        and a prewarm built from the model schema would compile a
        signature real (unfiltered) traffic misses. The dtype
        normalisation of slots/advance serves the same end: prewarm's
        dummies and live traffic make one signature.
        """
        with self._sp_context:
            if self._input_filter is not None:
                env_outputs = self._input_filter(env_outputs)
            if context is None and self._context_fn is not None:
                context = self._context_fn()
            slots = np.asarray(slots, np.int32).reshape(-1)
            advance = np.asarray(advance, bool).reshape(-1)
        with self._sp_call, self._lock:
            self._require_alive()
            carry, self._table = (self._table, self._key), None
            with jax.transfer_guard_host_to_device("allow"):
                (self._table, self._key), outputs = self._step_jit(
                    carry, slots, advance, context, env_outputs
                )
        self._tm_dispatches.inc()
        return outputs

    def fetch(self, outputs: Any, n: int) -> Any:
        """One explicit device_get of a step's padded outputs, sliced to
        the true batch size on HOST. Host-side slicing is deliberate: a
        device-side cut would either recompile per distinct true n (the
        dynamic batch size takes any value up to max_batch, unlike the
        handful of buckets) or upload fresh index constants per call —
        and the padding overhead fetched here is only the small
        action/logits/baseline rows, not agent state. Transfer-guard-
        clean: the device_get is explicit, the slice is numpy."""
        bd = self.batch_dim

        def cut(arr):
            sl = [slice(None)] * arr.ndim
            sl[bd] = slice(0, n)
            return arr[tuple(sl)]

        with self._sp_fetch:
            return jax.tree_util.tree_map(cut, jax.device_get(outputs))

    def read_slot(self, slot: int) -> Any:
        """Host copy of one slot's state, shaped like `initial_state`
        (size 1 along batch_dim) — the rollout-boundary
        `initial_agent_state` fetch, once per unroll per actor."""
        with self._sp_read_slot:
            ids = self._put_ids([slot])
            with self._lock:
                self._require_alive()
                piece = self._gather_jit(self._table, ids)
            return jax.device_get(piece)

    def reset(self, slots) -> None:
        """Reset `slots` to the initial state (actor connect/reconnect)."""
        ids = self._put_ids(slots)
        with self._lock:
            self._require_alive()
            table, self._table = self._table, None
            self._table = self._reset_jit(table, ids, self._initial)
