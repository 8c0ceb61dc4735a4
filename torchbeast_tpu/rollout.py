"""Rollout collection with the reference's on-policy bookkeeping invariants.

This replaces the reference's per-actor unroll loop (monobeast.py:128-191 and
the C++ ActorPool hot loop, actorpool.cc:408-450) with a single vectorized
collector: one batched policy call per env step for all B envs at once —
the TPU-friendly formulation (one big `[1, B]` forward instead of B tiny
ones).

Invariants preserved exactly (these are what the reference's agent-state
integration test pins down, SURVEY.md §4):
- **Overlap-by-one**: slot 0 of rollout k+1 == slot T of rollout k (both env
  and agent sides).
- **Pairing**: the agent output stored at slot i was computed from the env
  output at slot i-1 (slot 0's agent output is never consumed by the
  learner, which time-shifts it away).
- **Agent-state carry**: `initial_agent_state` returned with a rollout is
  the recurrent state entering the rollout's first policy call; state is
  carried across rollouts and reset inside the model wherever done is set.

Two schedules over the SAME data flow:

- `RolloutCollector` (synchronous): materializes every policy result on
  host before stepping envs — the full AgentOutput (and, with a naive
  policy fn, the recurrent state) crosses the host boundary every step.
- `PipelinedRolloutCollector` (lag-1): per env step, ONLY the action is
  fetched (one small explicit device_get); policy logits/baseline stay on
  device and the host materializes tick t-1's results while the envs step
  tick t (the pool's step_async/step_wait window), one dispatch behind
  the device. Agent state never crosses at all: it flows device →
  device between policy calls, and the learner consumes the on-device
  `initial_agent_state` directly (tests/test_state_table.py pins the
  zero-host-round-trips property with jax.transfer_guard). Batches are
  BIT-IDENTICAL to the synchronous collector's — the lag is in when the
  host *retrieves* results, never in what the policy saw — so every
  invariant above holds unchanged (test_rollout.py runs both).
"""

from typing import Any, Callable, Dict, List, Tuple

import jax
import numpy as np

from torchbeast_tpu.types import AgentOutput

# policy(env_output [B,...] dict, agent_state) -> (AgentOutput [B,...], state)
PolicyFn = Callable[[Dict[str, np.ndarray], Any], Tuple[AgentOutput, Any]]


def _build_batch(
    env_steps: List[Dict[str, np.ndarray]], agent_steps: List[AgentOutput]
) -> Dict[str, np.ndarray]:
    """Stack T+1 env dicts + host AgentOutputs into the [T+1, B] batch."""
    batch = {
        k: np.stack([s[k] for s in env_steps], axis=0) for k in env_steps[0]
    }
    batch["action"] = np.stack([np.asarray(a.action) for a in agent_steps])
    batch["policy_logits"] = np.stack(
        [np.asarray(a.policy_logits) for a in agent_steps]
    )
    batch["baseline"] = np.stack(
        [np.asarray(a.baseline) for a in agent_steps]
    )
    return batch


class RolloutCollector:
    def __init__(self, pool, policy: PolicyFn, initial_agent_state, unroll_length: int):
        self._pool = pool
        self._policy = policy
        self._unroll_length = unroll_length
        self._agent_state = initial_agent_state

        self._pending_env = pool.initial()
        # Prime the boundary agent output; the state advance is discarded —
        # the first in-rollout policy call re-consumes this env output with
        # the state advancing for real (reference monobeast.py:145-147).
        self._pending_agent, _ = policy(self._pending_env, self._agent_state)

    def collect(self) -> Tuple[Dict[str, np.ndarray], Any]:
        """Run one unroll; return (batch [T+1, B, ...], initial_agent_state).

        The batch dict carries both env fields (frame, reward, done,
        episode_return, episode_step, last_action) and behavior-agent fields
        (action, policy_logits, baseline).
        """
        T = self._unroll_length
        initial_agent_state = self._agent_state

        env_steps = [self._pending_env]
        agent_steps = [self._pending_agent]
        for _ in range(T):
            agent_out, self._agent_state = self._policy(
                self._pending_env, self._agent_state
            )
            self._pending_env = self._pool.step(np.asarray(agent_out.action))
            env_steps.append(self._pending_env)
            agent_steps.append(agent_out)
        self._pending_agent = agent_steps[-1]

        return _build_batch(env_steps, agent_steps), initial_agent_state


class PipelinedRolloutCollector:
    """Lag-1 pipelined collector (see module docstring).

    Per tick: dispatch the policy call, fetch ONLY its action (explicit
    device_get), hand the actions to the pool's async send phase, then —
    while the env workers step — materialize the PREVIOUS tick's full
    AgentOutput. The device result for tick t reaches the host at tick
    t+1 (or in the single batched end-of-unroll fetch for the last tick):
    host retrieval runs exactly one dispatch behind.

    The policy must return its AgentOutput/state WITHOUT materializing
    them (no device_get inside — monobeast wires this with
    `pipelined=True`). Pools without step_async (e.g. a bare object with
    only step()) degrade to the synchronous phase order, same results.
    """

    def __init__(self, pool, policy: PolicyFn, initial_agent_state,
                 unroll_length: int):
        self._pool = pool
        self._policy = policy
        self._unroll_length = unroll_length
        self._agent_state = initial_agent_state
        self._split_step = hasattr(pool, "step_async")

        self._pending_env = pool.initial()
        # Same priming contract as the sync collector; kept on device —
        # it is materialized lazily by the first collect()'s bulk fetch.
        self._pending_agent, _ = policy(self._pending_env, self._agent_state)

    def collect(self) -> Tuple[Dict[str, np.ndarray], Any]:
        """One unroll; identical contract/results to RolloutCollector.

        `initial_agent_state` is returned as-is (on device when the
        policy keeps it there) — the learner consumes it without a host
        round trip.
        """
        T = self._unroll_length
        initial_agent_state = self._agent_state

        env_steps = [self._pending_env]
        # Mixed host/device AgentOutputs; device entries are materialized
        # one tick behind (or in the final bulk fetch).
        agent_steps: List[AgentOutput] = [self._pending_agent]
        for _ in range(T):
            agent_out, self._agent_state = self._policy(
                self._pending_env, self._agent_state
            )
            # The action is the only per-step device→host fetch on this
            # path (explicit: np.asarray would be an implicit transfer
            # under jax.transfer_guard).
            action = np.asarray(jax.device_get(agent_out.action))
            if self._split_step:
                self._pool.step_async(action)
                # Lag-1 window: envs are stepping; materialize the
                # previous tick's outputs behind them.
                agent_steps[-1] = jax.device_get(agent_steps[-1])
                self._pending_env = self._pool.step_wait()
            else:
                self._pending_env = self._pool.step(action)
            env_steps.append(self._pending_env)
            agent_steps.append(agent_out)

        # One batched fetch for whatever is still on device (always the
        # last tick; every tick when the pool had no split step phase).
        agent_steps = jax.device_get(agent_steps)
        self._pending_agent = agent_steps[-1]

        return _build_batch(env_steps, agent_steps), initial_agent_state
