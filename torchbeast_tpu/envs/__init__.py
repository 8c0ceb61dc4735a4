"""Environment construction.

`create_env(name, ...)` mirrors the reference's `create_env(flags)`
(monobeast.py:638-646, polybeast_env.py:49-58): "Mock"/"Counting" build the
dependency-free test envs, "Catch"/"Memory" the dependency-free LEARNABLE
tasks (Memory requires a recurrent core — see MemoryChainEnv); anything
else is treated as a gymnasium Atari id and gets the DeepMind
preprocessing stack.
"""

from torchbeast_tpu.envs.environment import Environment  # noqa: F401
from torchbeast_tpu.envs.mock import (  # noqa: F401
    CatchEnv,
    CountingEnv,
    MemoryChainEnv,
    MockEnv,
    parse_memory_id,
)


def num_actions_of(env) -> int:
    """Discrete action count of a raw env (our minimal protocol's
    `num_actions` attribute, or a gym(nasium) `action_space.n`)."""
    if hasattr(env, "num_actions"):
        return int(env.num_actions)
    return int(env.action_space.n)


def create_env(name: str, seed=None, **kwargs):
    """`seed=None` (default) keeps the historical behavior: stochastic
    envs draw OS entropy per instance so parallel actors decorrelate.
    A seed makes the instance's draw stream deterministic — the driver
    layer derives per-actor seeds from `--env_seed` so runs reproduce
    while actors STAY decorrelated (seed + actor index)."""
    if name == "Mock":
        return MockEnv(**kwargs)  # deterministic; nothing to seed
    if name == "Counting":
        return CountingEnv(**kwargs)  # deterministic; nothing to seed
    if name == "Catch":
        return CatchEnv(seed=seed, **kwargs)
    # Parameterized corridor ids: "Memory" (default length) or
    # "Memory-L41" (cue 40 steps before the query) — id-encoded like
    # gym's "-v4"-style suffixes so every driver reads them from the
    # one --env flag (parse shared with the jittable twin).
    memory_length = parse_memory_id(name)
    if memory_length is not None:
        return MemoryChainEnv(length=memory_length, seed=seed, **kwargs)
    from torchbeast_tpu.envs.atari import create_atari_env

    return create_atari_env(name, seed=seed, **kwargs)


def probe_env(name: str):
    """One throwaway env instance -> (num_actions, frame shape, frame
    dtype): what a driver needs of the env to build its model."""
    probe = create_env(name)
    n = num_actions_of(probe)
    frame = Environment(probe).initial()["frame"]
    if hasattr(probe, "close"):
        probe.close()
    return int(n), frame.shape, frame.dtype
