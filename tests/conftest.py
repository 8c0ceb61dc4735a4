"""Test environment: the CPU backend with 8 virtual devices, so every
sharding/mesh test runs without an accelerator (SURVEY.md §4
implication; `__graft_entry__.dryrun_multichip` uses the same
mechanism). The variables are set before jax is imported — JAX reads
them at import, and nothing here touches a backend.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

from torchbeast_tpu.utils.xla_cache import use_compile_cache  # noqa: E402

# Persistent compilation cache: repeat suite runs skip XLA recompiles.
use_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
