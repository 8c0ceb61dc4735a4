"""Device-resident agent-state table (runtime/state_table.py): the
gather -> act -> merge-by-advance -> scatter step, slot isolation
(including the trash slot bucket padding scatters to), reset/read_slot,
the inference_loop integration (slot-framed requests, state-free
replies), and the transfer-guard regression test pinning the tentpole
property: agent state performs ZERO host round trips per env step.

Everything here runs on the CPU backend (conftest forces
JAX_PLATFORMS=cpu) — the CPU device is the "fake device" standing in for
the chip, so tier-1 covers the whole device-resident path without TPU
access.
"""

import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests import family_scaffold as scaffold
from torchbeast_tpu.runtime.inference import (
    inference_loop,
    pad_advance,
    pad_slots,
    pad_to,
)
from torchbeast_tpu.runtime.queues import DynamicBatcher
from torchbeast_tpu.runtime.state_table import DeviceStateTable

H = 2  # state feature width


def _act_fn(ctx, env_outputs, agent_state):
    """outputs = frame + state (so outputs prove WHICH state each row
    gathered), new_state = state + 1 (so persistence is observable)."""
    frame = env_outputs["frame"]  # [1, B, H]
    state = agent_state["h"]  # [1, B, H]
    return {"out": frame + state}, {"h": state + 1}


def make_table(num_slots=4, context_fn=None):
    return DeviceStateTable(
        {"h": np.zeros((1, 1, H), np.float32)},
        num_slots=num_slots,
        act_fn=_act_fn,
        context_fn=context_fn,
        batch_dim=1,
    )


def _mellum2_env(rows):
    return {
        "frame": np.full((1, rows, 4, 4, 1), 7, np.uint8),
        "reward": np.zeros((1, rows), np.float32),
        "done": np.zeros((1, rows), bool),
        "last_action": np.zeros((1, rows), np.int32),
    }


@functools.lru_cache(maxsize=None)
def _mellum2_table():
    """(a toy Mellum2, the scaffold's table of three slots around it),
    made once: a table jits its `act` itself, so a second one would
    trace the family's act step a second time."""
    from torchbeast_tpu.models import Mellum2Net

    model = Mellum2Net(
        num_actions=3, num_layers=4, memory_len=9, d_model=16,
        num_heads=2, kv_heads=1, head_dim=8, sliding_window=4,
        num_experts=4, experts_per_token=2, expert_width=8,
    )
    params = scaffold.init(
        model,
        {"params": jax.random.PRNGKey(0), "action": jax.random.PRNGKey(1)},
        _mellum2_env(1), model.initial_state(1),
    )
    return model, scaffold.state_table(model, params, 3)


def _env(values):
    """env nest for len(values) rows, frame row i == values[i]."""
    v = np.asarray(values, np.float32)
    return {"frame": np.tile(v[None, :, None], (1, 1, H))}


def _step_out(table, slots, advance, env):
    out = table.step(
        np.asarray(slots, np.int32), np.asarray(advance, bool), env
    )
    return np.asarray(jax.device_get(out["out"]))


def slot_state(table, slot):
    return np.asarray(table.read_slot(slot)["h"]).reshape(H)


class TestDeviceStateTable:
    def test_validation(self):
        with pytest.raises(ValueError, match="num_slots"):
            make_table(num_slots=0)
        with pytest.raises(ValueError, match="non-empty"):
            DeviceStateTable(
                {}, num_slots=2, act_fn=_act_fn, batch_dim=1
            )
        with pytest.raises(ValueError, match="size 1 along"):
            DeviceStateTable(
                {"h": np.zeros((1, 3, H), np.float32)},
                num_slots=2,
                act_fn=_act_fn,
                batch_dim=1,
            )

    def test_step_advances_only_requested_slots(self):
        table = make_table()
        # Slots 0 and 2 step (advance), slots 1 and 3 untouched.
        out = _step_out(table, [0, 2], [True, True], _env([10.0, 20.0]))
        # All slots start at state 0: outputs == frames.
        np.testing.assert_array_equal(out[0, 0], np.full(H, 10.0))
        np.testing.assert_array_equal(out[0, 1], np.full(H, 20.0))
        np.testing.assert_array_equal(slot_state(table, 0), np.full(H, 1.0))
        np.testing.assert_array_equal(slot_state(table, 1), np.zeros(H))
        np.testing.assert_array_equal(slot_state(table, 2), np.full(H, 1.0))
        # Second step for slot 0 only: output reflects the advanced state.
        out = _step_out(table, [0], [True], _env([5.0]))
        np.testing.assert_array_equal(out[0, 0], np.full(H, 6.0))
        np.testing.assert_array_equal(slot_state(table, 0), np.full(H, 2.0))

    def test_advance_false_computes_without_persisting(self):
        """The actor pool's priming call: outputs from the CURRENT state,
        state NOT advanced (reference monobeast.py advance=False path)."""
        table = make_table()
        _step_out(table, [1], [True], _env([0.0]))  # slot 1 -> state 1
        out = _step_out(table, [1], [False], _env([7.0]))
        np.testing.assert_array_equal(out[0, 0], np.full(H, 8.0))  # 7 + 1
        np.testing.assert_array_equal(slot_state(table, 1), np.full(H, 1.0))

    def test_input_filter_drops_extra_leaves_without_recompile(self):
        """polybeast's prewarm builds dummy envs from the 4-key model
        schema while real actor traffic carries the full 6-key nest
        (episode stats included). The host-side input_filter must make
        both hit ONE compiled signature — and keep the ignored leaves
        out of the dispatch entirely."""
        traces = []

        def counting_act(ctx, env_outputs, agent_state):
            traces.append(sorted(env_outputs))
            return _act_fn(ctx, env_outputs, agent_state)

        table = DeviceStateTable(
            {"h": np.zeros((1, 1, H), np.float32)},
            num_slots=2,
            act_fn=counting_act,
            batch_dim=1,
            input_filter=lambda env: {"frame": env["frame"]},
        )
        slots = np.asarray([0], np.int32)
        advance = np.ones(1, bool)
        # Prewarm-shaped (model schema only)...
        out1 = table.step(slots, advance, _env([3.0]))
        # ...then wire-shaped (extra leaves the model never reads).
        wire_env = dict(
            _env([4.0]), episode_step=np.zeros((1, 1), np.int32)
        )
        out2 = table.step(slots, advance, wire_env)
        assert traces == [["frame"]]  # one trace; filtered nest only
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(out2["out"]))[0, 0],
            np.full(H, 5.0),  # frame 4 + advanced state 1
        )
        del out1

    def test_failed_step_poisons_table(self):
        """The table buffer is donated into every step dispatch, so a
        step that raises may have consumed it — the table must refuse
        further use (use-after-free would serve garbage state) instead
        of letting the serving loop retry per-batch."""

        def bad_ctx():
            raise RuntimeError("params fetch exploded")

        table = make_table(context_fn=bad_ctx)
        with pytest.raises(RuntimeError, match="params fetch exploded"):
            table.step(
                np.zeros(1, np.int32), np.ones(1, bool), _env([1.0])
            )
        # context_fn runs before the donating dispatch, so the table
        # survives a context failure...
        assert not table.poisoned
        table._context_fn = None

        real_jit = table._step_jit

        def exploding_jit(*args, **kwargs):
            raise RuntimeError("dispatch died")

        table._step_jit = exploding_jit
        with pytest.raises(RuntimeError, match="dispatch died"):
            table.step(
                np.zeros(1, np.int32), np.ones(1, bool), _env([1.0])
            )
        # ...but a failure of the dispatch itself poisons it for every
        # entry point, with a diagnosable error.
        assert table.poisoned
        table._step_jit = real_jit
        for call in (
            lambda: table.step(
                np.zeros(1, np.int32), np.ones(1, bool), _env([1.0])
            ),
            lambda: table.read_slot(0),
            lambda: table.reset([0]),
        ):
            with pytest.raises(RuntimeError, match="poisoned"):
                call()

    def test_trash_slot_padding_never_disturbs_real_slots(self):
        table = make_table(num_slots=2)
        trash = table.trash_slot
        assert trash == 2
        # A padded batch: one real row + three trash rows, advance padded
        # False — exactly what inference_loop builds for bucket padding.
        slots = pad_slots(np.asarray([0]), 4, trash)
        advance = pad_advance(np.asarray([True]), 4)
        out = _step_out(table, slots, advance, _env([1.0, 9.0, 9.0, 9.0]))
        np.testing.assert_array_equal(out[0, 0], np.full(H, 1.0))
        np.testing.assert_array_equal(slot_state(table, 0), np.full(H, 1.0))
        np.testing.assert_array_equal(slot_state(table, 1), np.zeros(H))
        # Even an ADVANCING trash row (duplicate ids, last-writer-wins)
        # only ever writes the trash slot.
        _step_out(
            table,
            np.asarray([trash, trash], np.int32),
            np.asarray([True, True]),
            _env([3.0, 4.0]),
        )
        np.testing.assert_array_equal(slot_state(table, 0), np.full(H, 1.0))
        np.testing.assert_array_equal(slot_state(table, 1), np.zeros(H))

    def test_reset_restores_initial_state(self):
        table = make_table()
        for _ in range(3):
            _step_out(table, [0, 1], [True, True], _env([0.0, 0.0]))
        table.reset([0])
        np.testing.assert_array_equal(slot_state(table, 0), np.zeros(H))
        np.testing.assert_array_equal(slot_state(table, 1), np.full(H, 3.0))

    @pytest.mark.parametrize("via", ["reset", "rebuild"])
    def test_layers_with_caches_of_different_lengths(self, via):
        """A state whose layers differ in their leading size (mellum2:
        window layers 3 slots, the full layer 9; [M, B, heads, D] and
        [M, B], batch on axis 1) through the real family's forward:
        slots step independently, a stepped slot's caches fill from the
        back at each layer's own length, and reset / rebuild bring back
        the empty caches."""
        model, table = _mellum2_table()
        lengths = [m for m, _, _ in model.layer_caches()]
        assert lengths == [3, 3, 3, 9]
        table.reset([0, 1, 2])  # whatever the other id left
        for _ in range(5):
            table.step(
                np.asarray([0, 2], np.int32), np.ones(2, bool),
                _mellum2_env(2),
            )
        table.step(
            np.asarray([2], np.int32), np.ones(1, bool), _mellum2_env(1)
        )

        def valid(slot):
            return [
                np.asarray(layer[2]).reshape(-1)
                for layer in table.read_slot(slot)
            ]

        for layer, length in enumerate(lengths):
            k, v, mask = table.read_slot(0)[layer]
            assert np.shape(k) == np.shape(v) == (length, 1, 1, 8)
            assert np.shape(mask) == (length, 1)
            # Five steps in: a window cache is full, the full layer's
            # holds five of nine, newest last.
            filled = min(5, length)
            np.testing.assert_array_equal(
                valid(0)[layer], [0.0] * (length - filled) + [1.0] * filled
            )
            assert np.any(np.asarray(k)[-1] != 0)
            assert int(valid(2)[layer].sum()) == min(6, length)
            assert not valid(1)[layer].any()  # never stepped
        if via == "reset":
            table.reset([0])
            assert int(valid(2)[3].sum()) == 6  # the others keep theirs
        else:
            table.poison()
            table.rebuild()
            assert not any(v.any() for v in valid(2))
        for layer, length in enumerate(lengths):
            k, _, mask = table.read_slot(0)[layer]
            assert np.shape(k) == (length, 1, 1, 8)
            assert not np.any(k) and not np.any(mask)
        # And it steps on from there.
        table.step(
            np.asarray([0], np.int32), np.ones(1, bool), _mellum2_env(1)
        )
        assert [int(v.sum()) for v in valid(0)] == [1, 1, 1, 1]

    @pytest.mark.parametrize("via", ["reset", "rebuild"])
    def test_an_entry_whose_two_leaves_differ(self, via):
        """A cache entry of unequal leaves (kanana2: a latent [M, B, 1,
        6] and a rope key [M, B, 1, 2] with one validity column), the
        table knowing nothing of either: the slots step apart, both
        leaves roll together, reset and rebuild bring back both at
        their own widths."""
        M = 4
        initial = ((
            np.zeros((M, 1, 1, 6), np.float32),
            np.zeros((M, 1, 1, 2), np.float32),
            np.zeros((M, 1), np.float32),
        ),)

        def act(ctx, env_outputs, agent_state):
            (latent, rope_key, valid), = agent_state
            frame = env_outputs["frame"]  # [1, B, H]
            wide = jnp.broadcast_to(frame[:, :, None, :1], (1,) + latent.shape[1:])
            new = (
                jnp.concatenate([latent[1:], wide]),
                jnp.concatenate([rope_key[1:], wide[..., :2] + 0.5]),
                jnp.concatenate([valid[1:], jnp.ones_like(valid[:1])]),
            )
            return {"out": frame + latent.sum(axis=(0, 2, 3))[None, :, None]}, (new,)

        table = DeviceStateTable(
            initial, num_slots=3, act_fn=act, batch_dim=1
        )
        for value in (1.0, 2.0):
            table.step(
                np.asarray([0, 2], np.int32), np.ones(2, bool),
                _env([value, 10 * value]),
            )
        (latent, rope_key, valid), = table.read_slot(2)
        assert np.shape(latent) == (M, 1, 1, 6)
        assert np.shape(rope_key) == (M, 1, 1, 2)
        np.testing.assert_array_equal(
            np.asarray(latent)[:, 0, 0, 0], [0.0, 0.0, 10.0, 20.0]
        )
        np.testing.assert_array_equal(
            np.asarray(rope_key)[:, 0, 0, 1], [0.0, 0.0, 10.5, 20.5]
        )
        np.testing.assert_array_equal(np.asarray(valid)[:, 0], [0, 0, 1, 1])
        assert not any(np.any(leaf) for leaf in table.read_slot(1)[0])
        # The next step reads what the last one left: 6 x (10 + 20).
        out = _step_out(table, [2], [True], _env([0.0]))
        assert float(out[0, 0, 0]) == 6 * 30.0
        if via == "reset":
            table.reset([2])
            assert np.any(table.read_slot(0)[0][0])
        else:
            table.poison()
            table.rebuild()
            assert not np.any(table.read_slot(0)[0][0])
        (latent, rope_key, valid), = table.read_slot(2)
        assert np.shape(latent) == (M, 1, 1, 6)
        assert np.shape(rope_key) == (M, 1, 1, 2)
        assert not np.any(latent) and not np.any(rope_key)
        assert not np.any(valid)

    @pytest.mark.parametrize("via", ["reset", "rebuild"])
    def test_a_slot_that_holds_a_window_and_a_recurrent_state(self, via):
        """Two kinds of state side by side in one slot (nemotron3: an
        attention layer's rolling window (k, v, valid) and a Mamba
        layer's state [H, B, P, N] with its convolution's tail
        [K - 1, B, C], which are no window and are not rolled; a layer
        that carries nothing has no item), the table knowing nothing of
        either: the window rolls, the state decays and accumulates, the
        tail shifts; reset and rebuild bring every leaf back as zeros
        of its own shape."""
        M = 4
        initial = (
            (
                np.zeros((M, 1, 1, 2), np.float32),
                np.zeros((M, 1, 1, 2), np.float32),
                np.zeros((M, 1), np.float32),
            ),
            (
                np.zeros((3, 1, 2, 5), np.float32),
                np.zeros((3, 1, 6), np.float32),
            ),
        )

        def act(ctx, env_outputs, agent_state):
            (k, v, valid), (h, tail) = agent_state
            frame = env_outputs["frame"]  # [1, B, H]
            value = frame[0, :, 0]  # [B]
            wide = jnp.broadcast_to(frame[:, :, None, :1], (1,) + k.shape[1:])
            window = (
                jnp.concatenate([k[1:], wide]),
                jnp.concatenate([v[1:], 2 * wide]),
                jnp.concatenate([valid[1:], jnp.ones_like(valid[:1])]),
            )
            carried = (
                0.5 * h + value[None, :, None, None],
                jnp.concatenate([
                    tail[1:],
                    jnp.broadcast_to(value[None, :, None], (1,) + tail.shape[1:]),
                ]),
            )
            out = frame + (h.sum(axis=(0, 2, 3)) + tail[-1].sum(axis=-1))[
                None, :, None
            ]
            return {"out": out}, (window, carried)

        table = DeviceStateTable(
            initial, num_slots=3, act_fn=act, batch_dim=1
        )
        for value in (1.0, 2.0):
            table.step(
                np.asarray([0, 2], np.int32), np.ones(2, bool),
                _env([value, 10 * value]),
            )
        (k, v, valid), (h, tail) = table.read_slot(2)
        assert [np.shape(leaf) for leaf in (k, v, valid, h, tail)] == [
            (M, 1, 1, 2), (M, 1, 1, 2), (M, 1), (3, 1, 2, 5), (3, 1, 6),
        ]
        np.testing.assert_array_equal(
            np.asarray(k)[:, 0, 0, 0], [0.0, 0.0, 10.0, 20.0]
        )
        np.testing.assert_array_equal(np.asarray(valid)[:, 0], [0, 0, 1, 1])
        # No roll: 0.5 x 10 + 20 everywhere; the tail's last two inputs.
        np.testing.assert_array_equal(np.asarray(h), np.full_like(h, 25.0))
        np.testing.assert_array_equal(
            np.asarray(tail)[:, 0, 0], [0.0, 10.0, 20.0]
        )
        assert not any(
            np.any(leaf) for item in table.read_slot(1) for leaf in item
        )
        # The next step reads both: 30 of state x 25 + 6 of tail x 20.
        out = _step_out(table, [2], [True], _env([0.0]))
        assert float(out[0, 0, 0]) == 30 * 25.0 + 6 * 20.0
        if via == "reset":
            table.reset([2])
            assert all(
                np.any(leaf) for item in table.read_slot(0) for leaf in item
            )
        else:
            table.poison()
            table.rebuild()
            assert not any(
                np.any(leaf) for item in table.read_slot(0) for leaf in item
            )
        held = table.read_slot(2)
        assert [[np.shape(leaf) for leaf in item] for item in held] == [
            [np.shape(leaf) for leaf in item] for item in initial
        ]
        assert not any(np.any(leaf) for item in held for leaf in item)
        # And it steps on from zeros.
        out = _step_out(table, [2], [True], _env([3.0]))
        assert float(out[0, 0, 0]) == 3.0

    def test_read_slot_shape_matches_initial_state(self):
        table = make_table()
        piece = table.read_slot(3)
        assert np.shape(piece["h"]) == (1, 1, H)

    def test_context_fn_threads_fresh_ctx_without_recompile(self):
        calls = []

        def context_fn():
            calls.append(None)
            return jnp.float32(len(calls))

        def act_with_ctx(ctx, env_outputs, agent_state):
            context, _subkey = ctx
            return (
                {"out": env_outputs["frame"] + context},
                {"h": agent_state["h"]},
            )

        table = DeviceStateTable(
            {"h": np.zeros((1, 1, H), np.float32)},
            num_slots=2,
            act_fn=act_with_ctx,
            context_fn=context_fn,
            batch_dim=1,
        )
        out1 = np.asarray(
            jax.device_get(
                table.step(
                    np.asarray([0], np.int32),
                    np.asarray([True]),
                    _env([0.0]),
                )["out"]
            )
        )
        out2 = np.asarray(
            jax.device_get(
                table.step(
                    np.asarray([0], np.int32),
                    np.asarray([True]),
                    _env([0.0]),
                )["out"]
            )
        )
        # ctx is traced, not baked in: the second call sees ctx=2.
        np.testing.assert_array_equal(out1[0, 0], np.full(H, 1.0))
        np.testing.assert_array_equal(out2[0, 0], np.full(H, 2.0))


def _key_act(ctx, env_outputs, agent_state):
    """Outputs carry the batch's subkey ([1, B, 2] raw key words), so a
    test can read which key each dispatch drew."""
    _context, subkey = ctx
    b = env_outputs["frame"].shape[1]
    words = jax.random.key_data(subkey).astype(jnp.uint32)
    return (
        {"key": jnp.tile(words[None, None, :], (1, b, 1))},
        {"h": agent_state["h"] + 1},
    )


def _key_table(rng_key=None, num_slots=2):
    return DeviceStateTable(
        {"h": np.zeros((1, 1, H), np.float32)},
        num_slots=num_slots,
        act_fn=_key_act,
        batch_dim=1,
        rng_key=rng_key,
    )


def _drawn_key(table):
    out = table.step(
        np.zeros(1, np.int32), np.ones(1, bool), _env([0.0])
    )
    return tuple(int(w) for w in table.fetch(out, 1)["key"][0, 0])


class TestOneRuntimeCall:
    def test_step_enters_the_runtime_once(self, monkeypatch):
        """The one-call contract, counted: after warm-up a step makes
        no host-side rng split and no device_put — its only runtime
        entry is the launch of the jitted step (one per step); the
        reply's device_get lives in fetch."""
        table = make_table()
        slots, advance = np.asarray([0, 1], np.int32), np.ones(2, bool)
        env = _env([1.0, 2.0])
        table.step(slots, advance, env)  # warm-up: trace + compile

        counts = {"put": 0, "split": 0, "launch": 0}

        def counting(name, fn):
            def wrapper(*args, **kwargs):
                counts[name] += 1
                return fn(*args, **kwargs)
            return wrapper

        monkeypatch.setattr(
            jax, "device_put", counting("put", jax.device_put)
        )
        monkeypatch.setattr(
            jax.random, "split", counting("split", jax.random.split)
        )
        table._step_jit = counting("launch", table._step_jit)
        for _ in range(5):
            out = table.step(slots, advance, env)
        assert counts == {"put": 0, "split": 0, "launch": 5}
        # Still the same program: five more advances on slots 0 and 1.
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(out["out"]))[0, 0],
            np.full(H, 1.0 + 5.0),
        )

    def test_prewarm_and_traffic_share_one_signature(self):
        """slots/advance arrive as whatever ints/bools the caller has
        (prewarm: np.full(.., int32); the pool: int64 / uint8 views);
        the host-side dtype normalisation makes them one jit
        signature, so nothing compiles after prewarm."""
        table = make_table()
        env = _env([1.0, 2.0])
        table.step(np.full(2, table.trash_slot, np.int32),
                   np.zeros(2, bool), env)
        table.step(np.asarray([0, 1], np.int64),
                   np.asarray([1, 0], np.uint8), env)
        table.step([1, 0], [True, False], env)
        assert table._step_jit._cache_size() == 1

    def test_consecutive_steps_draw_different_subkeys(self):
        table = _key_table()
        keys = [_drawn_key(table) for _ in range(8)]
        assert len(set(keys)) == 8
        # One chain, deterministic in the seed key: a second table
        # built from the same key draws the same stream...
        again = _key_table()
        assert [_drawn_key(again) for _ in range(8)] == keys
        # ...and another seed key another one.
        other = _key_table(rng_key=jax.random.PRNGKey(1))
        assert not set(keys) & {_drawn_key(other) for _ in range(8)}

    def test_two_threads_never_see_a_repeated_subkey(self):
        """The table lock serialises the launches, so the key in the
        donated carry needs no lock of its own: 200 steps from two
        threads draw 200 distinct subkeys."""
        import sys

        table = _key_table()
        _drawn_key(table)  # compile outside the race
        seen, errors = [[], []], []

        def worker(i):
            try:
                for _ in range(100):
                    seen[i].append(_drawn_key(table))
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        threads = [
            threading.Thread(target=worker, args=(i,)) for i in range(2)
        ]
        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
        finally:
            sys.setswitchinterval(interval)
        assert not any(t.is_alive() for t in threads)
        assert not errors, errors
        assert len(seen[0]) == len(seen[1]) == 100
        assert len(set(seen[0]) | set(seen[1])) == 200

    def test_rebuild_does_not_replay_the_stream(self):
        table = _key_table()
        first = [_drawn_key(table) for _ in range(6)]
        table.poison()
        assert table.poisoned
        table.rebuild()
        second = [_drawn_key(table) for _ in range(6)]
        assert not set(first) & set(second)
        # A second cycle starts a third stream, not the second again.
        table.poison()
        table.rebuild()
        third = [_drawn_key(table) for _ in range(6)]
        assert not (set(first) | set(second)) & set(third)

    def test_callers_key_is_never_donated(self):
        """The chain starts at fold_in(rng_key, 0), a fresh buffer: the
        caller's own array (polybeast's state["rng"], which its
        table-less act path still splits) survives every step."""
        mine = jax.random.PRNGKey(7)
        table = _key_table(rng_key=mine)
        for _ in range(3):
            _drawn_key(table)
        assert not mine.is_deleted()
        np.testing.assert_array_equal(
            np.asarray(mine), np.asarray(jax.random.PRNGKey(7))
        )


class TestInferenceLoopIntegration:
    def test_slot_framed_requests_route_and_replies_carry_no_state(self):
        table = make_table(num_slots=8)
        batcher = DynamicBatcher(
            batch_dim=1, minimum_batch_size=1, maximum_batch_size=8,
            timeout_ms=5,
        )
        server = threading.Thread(
            target=inference_loop,
            args=(batcher, None, 8),
            kwargs={"state_table": table},
            daemon=True,
        )
        server.start()

        results, errors = {}, []

        def producer(i):
            try:
                for _ in range(3):  # 3 advancing steps per slot
                    out = batcher.compute(
                        {
                            "env": {
                                "frame": np.full((1, 1, H), float(i),
                                                 np.float32)
                            },
                            "slot": np.full((1, 1), i, np.int32),
                            "advance": np.full((1, 1), True, bool),
                        }
                    )
                results[i] = out
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        threads = [
            threading.Thread(target=producer, args=(i,)) for i in range(8)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert not errors, errors
        assert len(results) == 8
        for i, out in results.items():
            # Reply framing: outputs only — no agent-state leaves.
            assert set(out.keys()) == {"outputs"}
            # Third step saw state 2: out = frame + 2.
            np.testing.assert_array_equal(
                np.asarray(out["outputs"]["out"]),
                np.full((1, 1, H), float(i) + 2.0, np.float32),
            )
            np.testing.assert_array_equal(
                slot_state(table, i), np.full(H, 3.0)
            )
        batcher.close()
        server.join(timeout=10)
        assert not server.is_alive()


class TestTransferGuard:
    def test_state_never_crosses_host_boundary_per_step(self):
        """The tentpole regression test: a full padded unroll of table
        steps under jax.transfer_guard("disallow") — only the hand-over
        of observations/ids to the jitted step, allowed BY NAME inside
        DeviceStateTable.step (a host-to-device "allow" scoped to the
        launch; there is no explicit put), and the EXPLICIT device_get
        of outputs (fetch) cross; device-to-host stays "disallow", and
        any agent-state leaf crossing the boundary outside that launch
        would be an implicit transfer and raise (next test)."""
        table = make_table(num_slots=4)
        # Warm the compile caches outside the guard (compilation itself
        # may transfer constants; the guarded property is the per-step
        # hot path, not the one-time compile).
        slots = pad_slots(np.asarray([0, 1]), 4, table.trash_slot)
        advance = pad_advance(np.asarray([True, True]), 4)
        env = pad_to(_env([1.0, 2.0]), 4, batch_dim=1)
        out = table.step(slots, advance, env)
        table.fetch(out, 2)
        table.read_slot(0)

        with jax.transfer_guard("disallow"):
            for t in range(5):  # one unroll's worth of acting steps
                out = table.step(slots, advance, env)
                fetched = table.fetch(out, 2)
            # Rollout-boundary state read: one explicit fetch per unroll.
            boundary = table.read_slot(0)
        # Warmup advanced slot 0 once; guarded steps 1..5 saw states
        # 1..5, so the last output is frame + 5 and the boundary state 6.
        np.testing.assert_array_equal(
            np.asarray(fetched["out"])[0, 0], np.full(H, 1.0 + 5.0)
        )
        np.testing.assert_array_equal(
            np.asarray(boundary["h"]).reshape(H), np.full(H, 6.0)
        )

    def test_guard_still_fails_a_state_leaf_that_crosses(self):
        """step's "allow" ends with the launch: under the same guard a
        state leaf that arrives from the host (the legacy path's shape:
        state shipped with the request) still raises, before and after
        a table step, and step itself leaves the guard as it found
        it."""
        table = make_table(num_slots=2)
        slots, advance = np.asarray([0], np.int32), np.ones(1, bool)
        env = _env([1.0])
        legacy = jax.jit(lambda state: state + 1)
        legacy(jnp.zeros((1, 1, H)))  # compile outside the guard
        table.step(slots, advance, env)
        host_state = np.zeros((1, 1, H), np.float32)
        with jax.transfer_guard("disallow"):
            with pytest.raises(Exception, match="host-to-device"):
                legacy(host_state)
            table.step(slots, advance, env)
            with pytest.raises(Exception, match="host-to-device"):
                legacy(host_state)

    @pytest.mark.parametrize("leak", [False, True])
    def test_guard_holds_on_the_launcher_and_the_replier(self, leak):
        """A serving loop is two threads and `jax.transfer_guard(...)`
        is per thread, so the guard is set process-wide here (a thread
        that set none of its own reads the config's value). A whole
        loop's traffic under "disallow": the launcher's hand-over is
        the one allowed by name inside step, the replier's device_get
        is explicit, and nothing else crosses. `leak` is the control
        that the guard does reach the replier: a fetch that also ships
        a host state leaf to the device fails its batch there."""
        table = make_table(num_slots=4)
        legacy = jax.jit(lambda state: state + 1)
        legacy(jnp.zeros((1, 1, H)))  # compile outside the guard
        host_state = np.zeros((1, 1, H), np.float32)
        fetch = table.fetch
        fetch_threads = set()

        def watched_fetch(outputs, n):
            fetch_threads.add(threading.current_thread().name)
            if leak:
                legacy(host_state)
            return fetch(outputs, n)

        table.fetch = watched_fetch
        request = {
            "env": {"frame": np.full((1, 1, H), 1.0, np.float32)},
            "slot": np.full((1, 1), 0, np.int32),
            "advance": np.full((1, 1), True, bool),
        }
        slots, advance = np.asarray([0], np.int32), np.ones(1, bool)
        table.fetch(table.step(slots, advance, request["env"]), 1)  # compile
        fetch_threads.clear()

        batcher = DynamicBatcher(
            batch_dim=1, minimum_batch_size=1, maximum_batch_size=1,
            timeout_ms=5,
        )
        server = threading.Thread(
            target=inference_loop, args=(batcher, None, 1),
            kwargs={"state_table": table}, name="guarded", daemon=True,
        )
        before = jax.config.jax_transfer_guard
        jax.config.update("jax_transfer_guard", "disallow")
        try:
            server.start()
            if leak:
                with pytest.raises(Exception, match="host-to-device"):
                    batcher.compute(request)
            else:
                for step in range(1, 6):
                    out = batcher.compute(request)
                    np.testing.assert_array_equal(
                        np.asarray(out["outputs"]["out"]),
                        np.full((1, 1, H), 1.0 + step, np.float32),
                    )
            batcher.close()
            server.join(timeout=10)
        finally:
            jax.config.update("jax_transfer_guard", before)
        assert not server.is_alive()
        assert fetch_threads == {"guarded-replier"}

    def test_pipelined_unroll_state_stays_on_device(self):
        """Lag-1 collector variant of the guard test: a device-side
        policy's recurrent state flows device -> device across a whole
        collect() with implicit transfers disallowed; only the action
        fetch and the end-of-unroll bulk fetch cross, explicitly."""
        from torchbeast_tpu.envs import CountingEnv
        from torchbeast_tpu.envs.vec import SerialEnvPool
        from torchbeast_tpu.rollout import PipelinedRolloutCollector
        from torchbeast_tpu.types import AgentOutput

        B = 2

        @jax.jit
        def policy_step(done, state):
            state = jnp.where(done, 0, state) + 1
            out = AgentOutput(
                action=jnp.zeros(done.shape, jnp.int32),
                policy_logits=state.astype(jnp.float32)[..., None],
                baseline=state.astype(jnp.float32),
            )
            return out, state

        def policy(env_output, agent_state):
            done = jax.device_put(np.asarray(env_output["done"]))
            out, state = policy_step(done, agent_state)
            assert isinstance(state, jax.Array)  # never left the device
            return out, state

        pool = SerialEnvPool(
            [lambda: CountingEnv(episode_length=5) for _ in range(B)]
        )
        state0 = jax.device_put(np.zeros(B, np.int64))
        # Warm the compile outside the guard.
        policy_step(jnp.zeros(B, bool), state0)

        collector = PipelinedRolloutCollector(
            pool, policy, state0, unroll_length=3
        )
        with jax.transfer_guard("disallow"):
            for _ in range(3):
                batch, initial_state = collector.collect()
        assert isinstance(initial_state, jax.Array)
        # Invariants still hold under the guard (spot check: the policy
        # writes its post-increment state into baseline).
        done0 = batch["done"][0]
        expected = np.where(done0, 0, np.asarray(initial_state)) + 1
        np.testing.assert_array_equal(batch["baseline"][1], expected)
