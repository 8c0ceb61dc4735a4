"""Capability probes that are False somewhere this suite runs.

Every probe of JAX-version skew that used to live here (top-level
`jax.shard_map`, `check_vma`, Mosaic's stop_gradient rule, a sound SPMD
partitioner for dense TP) is True on the one installation there is
(jax 0.9.0), so those probes left together with their skipifs. The
ring-attention family's probe — NamedSharding taking a bare-string spec
— is False on jax 0.9.0 and named a real gap; ops/attention.py now
builds PartitionSpecs, so that family runs unconditionally too.

What remains depends on how the process was started, not on the JAX
version.
"""


def has_multi_device_cpu(n: int = 2) -> bool:
    """Whether this process sees >= n jax devices. tests/conftest.py
    forces `--xla_force_host_platform_device_count=8` before jax
    initializes; a caller that overrode XLA_FLAGS with a smaller count
    sees fewer, and the Sebulba device-split suites
    (tests/test_sebulba.py) SKIP visibly instead of failing."""
    import jax

    return len(jax.devices()) >= n
